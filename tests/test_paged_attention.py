"""Pallas fused paged-attention kernel (ops/paged_attention.py).

The load-bearing claims, each against the gather path as parity oracle:

- **Kernel parity**: the in-kernel block-table walk matches the
  gather-then-dense oracle to float tolerance across every serving shape
  — dense and GQA head grouping, sliding window, scalar AND [B]-vector
  offsets, S_in=1 decode and the K+1 spec-verify shape, fetch widths 1/2/4
  (the key tile's blocks where the kernel walks a slot's live blocks itself)
  — and the fused int8 dequant path matches the gather-quant oracle.
- **Engine token bit-parity**: an ``attn_impl='pallas'`` engine (running
  the interpreter-mode kernel on CPU) emits tokens BIT-equal to the
  contiguous-cache ``generate()`` golden and to the gather engine, with
  ``decode_signatures == 1`` — speculative verify and the int8 pool
  included.
- **Memory evidence** (via the Telemetry AOT hook): the gather arm's
  compiled decode program materializes the O(max_blocks*bs) gathered-view
  buffer; the pallas arm's program never allocates that shape.
- **Hot-loop lint**: ``gather_kv`` is never called while the pallas
  engine traces its programs — the gather survives only as the parity
  oracle.

Budget: ONE module-scope bundle (a single GQA+sliding-window family,
spec_k=2) holds the golden, the pallas+gather engine pair, and the int8
engine — every test reuses the same handful of compiled programs.  The
32k long-context serving proof is slow-tier.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistpackage_tpu.models import generate, init_gpt_params, llama_config
from torchdistpackage_tpu.ops.paged_attention import (
    call_walk,
    chunk_tile,
    decode_walk,
    fetched_block,
    modeled_attend_temp_bytes,
    paged_decode_attention,
    resolve_attn_impl,
)
from torchdistpackage_tpu.serving import Request, ServingEngine, paged_attention

# One family covering GQA (kv_heads < nheads) AND sliding-window masking;
# spec_k=2 makes the decode program the K+1 verify shape.
CFG = llama_config(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=32,
                   kv_heads=2, ffn_hidden=48, dtype=jnp.float32,
                   sliding_window=6)
PROMPT, NEW = 5, 6


def _run_staggered(eng, prompts):
    """The engine's real regime: request B admitted while A decodes."""
    r0 = eng.submit(Request(prompts[0].tolist(), NEW))
    eng.step()
    eng.step()
    r1 = eng.submit(Request(prompts[1].tolist(), NEW))
    eng.run_until_idle(max_ticks=500)
    return [np.asarray(eng.finished[r]["tokens"]) for r in (r0, r1)]


@pytest.fixture(scope="module")
def bundle():
    """Module-scope bundle: golden, pallas+gather engine pair (with
    Telemetry capturing the compiled decode program via the AOT hook),
    int8 pallas engine, and the gather_kv trace-time call counts."""
    import torchdistpackage_tpu.serving.paged_cache as pc
    from torchdistpackage_tpu.obs import Telemetry

    calls = {"n": 0}
    real_gather_kv = pc.gather_kv

    def counting_gather_kv(*a, **kw):
        calls["n"] += 1
        return real_gather_kv(*a, **kw)

    pc.gather_kv = counting_gather_kv
    try:
        params = init_gpt_params(jax.random.PRNGKey(0), CFG)
        prompts = np.stack([
            np.asarray(jax.random.randint(
                jax.random.PRNGKey(10 + i), (PROMPT,), 0, CFG.vocab_size))
            for i in range(2)
        ]).astype(np.int32)
        want = np.asarray(jax.jit(
            lambda p, t: generate(p, t, CFG, max_new_tokens=NEW)
        )(params, jnp.asarray(prompts)))

        out = {"cfg": CFG, "params": params, "prompts": prompts,
               "want": want, "tel": {}, "eng": {}, "tokens": {},
               "gather_calls": {}}
        # narrow tables (max_ctx=16 at block_size=8 -> 3-wide) keep the
        # interpreter's work small: compile cost, not coverage.  Three slots
        # for two requests: the decode walk's two tile halves, ``[2, Hkv,
        # mb * bs, hd]`` where a tile covers the table, must not LOOK like
        # two slots' gathered view ``[B, Hkv, mb * bs, hd]``
        ekw = dict(num_slots=3, block_size=8, chunk=4, max_ctx=16)
        # pallas arm runs spec_k=2 so its decode program IS the K+1
        # verify shape; the gather oracle runs the ordinary S_in=1 decode
        # (both gather programs' gathered view looks the same)
        for impl, k in (("pallas", 2), ("gather", 0)):
            calls["n"] = 0
            tel = Telemetry(run=f"paged-{impl}", poll_memory=False)
            eng = ServingEngine(params, CFG, spec_k=k, attn_impl=impl,
                                telemetry=tel, **ekw)
            out["tokens"][impl] = _run_staggered(eng, prompts)
            out["gather_calls"][impl] = calls["n"]
            out["tel"][impl], out["eng"][impl] = tel, eng
        calls["n"] = 0
        q8 = ServingEngine(params, CFG, attn_impl="pallas", kv_quant=True,
                           **ekw)
        rids = [q8.submit(Request(p.tolist(), NEW)) for p in prompts]
        q8.run_until_idle(max_ticks=500)
        out["gather_calls"]["int8_pallas"] = calls["n"]
        out["tokens"]["int8_pallas"] = [
            np.asarray(q8.finished[r]["tokens"]) for r in rids]
        out["eng"]["int8_pallas"] = q8
        yield out
    finally:
        pc.gather_kv = real_gather_kv


# ------------------------------------------------------- kernel-level parity


def _rand_pool(nb, hkv, bs, hd, seed):
    kp = jax.random.normal(jax.random.PRNGKey(seed), (nb, hkv, bs, hd),
                           jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(seed + 1), (nb, hkv, bs, hd),
                           jnp.float32)
    return kp, vp


def _pools_for(int8, shape, rs):
    """(k, v) pools of ``shape`` [..., nb, hkv, bs, hd]: fp, or int8 pairs."""
    def side():
        if int8:
            return (jnp.asarray(rs.randint(-127, 128, shape), jnp.int8),
                    jnp.asarray(rs.uniform(1e-3, 2e-2, shape[:-1]),
                                jnp.float32))
        return jnp.asarray(rs.standard_normal(shape), jnp.float32)
    return side(), side()


def test_kernel_matches_gather_oracle():
    """Dense + GQA x {decode, K+1 verify} x {causal, sliding window} x
    fetch widths 1/2/4, vector offsets — all within float tolerance of the
    gather-then-dense oracle (eager interpreter, no compiles)."""
    B, hkv, bs, hd, mb = 2, 2, 4, 8, 5  # mb % fw != 0: remainder covered
    nb = 1 + B * mb
    kp, vp = _rand_pool(nb, hkv, bs, hd, 1)
    tables = jnp.asarray(
        np.random.RandomState(0).permutation(np.arange(1, nb))
        .reshape(B, mb), jnp.int32)
    offs = jnp.asarray([9, 14], jnp.int32)
    # masking semantics at fetch_width=1, then fetch_width=4 (mb=5: the
    # remainder step) once on the hardest combination — each axis covered
    # without the full cross product (eager interpreter calls are slow)
    cases = [(g, s, w, 1) for g in (1, 2) for s in (1, 3)
             for w in (None, 6)] + [(2, 3, 6, 4), (2, 1, None, 4)]
    for groups, s_in, window, fw in cases:
        H = hkv * groups
        q = jax.random.normal(
            jax.random.PRNGKey(groups * 10 + s_in), (B, H, s_in, hd),
            jnp.float32)
        want = paged_attention(q, kp, vp, offs, tables=tables,
                               window=window)
        got = paged_decode_attention(q, kp, vp, tables, offs,
                                     window=window, fetch_width=fw)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-6,
            err_msg=f"G={groups} S={s_in} w={window} fw={fw}")


def test_kernel_scalar_offset_matches_vector():
    """A scalar offset is the constant-vector case, bitwise."""
    B, hkv, bs, hd, mb, nb = 2, 2, 4, 8, 4, 12
    kp, vp = _rand_pool(nb, hkv, bs, hd, 3)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, 4, 1, hd), jnp.float32)
    a = paged_decode_attention(q, kp, vp, tables, 7)
    b = paged_decode_attention(q, kp, vp, tables,
                               jnp.asarray([7, 7], jnp.int32))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and both agree with the oracle at the scalar offset
    want = paged_attention(q, kp, vp, 7, tables=tables)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), atol=2e-6)


def test_kernel_int8_fused_dequant():
    """The fused int8 path — (q8, scale) block pairs dequantized
    in-register — matches the gather-quant oracle (which materializes the
    f32 gathered view) to float tolerance, for k AND v scales."""
    B, hkv, bs, hd, mb, nb = 2, 2, 4, 8, 5, 12
    rs = np.random.RandomState(7)
    k8 = jnp.asarray(rs.randint(-127, 128, (nb, hkv, bs, hd)), jnp.int8)
    v8 = jnp.asarray(rs.randint(-127, 128, (nb, hkv, bs, hd)), jnp.int8)
    ks = jnp.asarray(rs.uniform(1e-3, 2e-2, (nb, hkv, bs)), jnp.float32)
    vs = jnp.asarray(rs.uniform(1e-3, 2e-2, (nb, hkv, bs)), jnp.float32)
    tables = jnp.asarray(rs.permutation(np.arange(1, nb))[:B * mb]
                         .reshape(B, mb), jnp.int32)
    offs = jnp.asarray([11, 6], jnp.int32)
    for s_in in (1, 3):
        q = jax.random.normal(jax.random.PRNGKey(s_in), (B, 4, s_in, hd),
                              jnp.float32)
        want = paged_attention(q, (k8, ks), (v8, vs), offs, tables=tables)
        got = paged_decode_attention(q, (k8, ks), (v8, vs), tables, offs)
        assert got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)


#: (groups, S_in, window, int8): dense / GQA, decode / K+1 verify / chunk,
#: causal / sliding window, fp pool / int8 pair
STACKED_CASES = {
    "dense-decode": (1, 1, None, False),
    "gqa-verify": (2, 3, None, False),
    "gqa-window-chunk": (2, 8, 6, False),
    "dense-window-decode": (1, 1, 6, False),
    "int8-decode": (2, 1, None, True),
    "int8-verify-window": (2, 3, 6, True),
}


@pytest.mark.parametrize("case", sorted(STACKED_CASES))
def test_kernel_stacked_pool_matches_per_layer(case):
    """The whole pool ``[L, nb, Hkv, bs, hd]`` and a layer index (a python
    int, and a traced scalar as the layer scan hands it over) give BITWISE
    what the per-layer call gives on ``pool[layer]``, for every layer: the
    index map's leading coordinate is all that changed.  And the gather
    oracle reaches the same layer through ``[layer, tables]``."""
    groups, s_in, window, int8 = STACKED_CASES[case]
    L, B, hkv, bs, hd, mb = 3, 2, 2, 4, 8, 5
    nb = 1 + B * mb
    rs = np.random.RandomState(11)
    kp, vp = _pools_for(int8, (L, nb, hkv, bs, hd), rs)
    tables = jnp.asarray(rs.permutation(np.arange(1, nb)).reshape(B, mb),
                         jnp.int32)
    offs = jnp.asarray([9, 12 - s_in], jnp.int32)
    q = jnp.asarray(rs.standard_normal((B, hkv * groups, s_in, hd)),
                    jnp.float32)
    one = lambda c, i: jax.tree.map(lambda a: a[i], c)
    traced = jax.jit(lambda q, kp, vp, li: paged_decode_attention(
        q, kp, vp, tables, offs, layer=li, window=window))
    for li in range(L):
        want = paged_decode_attention(q, one(kp, li), one(vp, li), tables,
                                      offs, window=window)
        got = paged_decode_attention(q, kp, vp, tables, offs, layer=li,
                                     window=window)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(traced(q, kp, vp, jnp.int32(li))), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(paged_attention(q, kp, vp, offs, tables=tables,
                                       window=window, layer=li)),
            np.asarray(paged_attention(q, one(kp, li), one(vp, li), offs,
                                       tables=tables, window=window)))


#: (q heads, KV heads, table columns) of the serving cells at toy size:
#: Mistral's 32 / 8 and Nemotron's 32 / 2 over six columns, ZAYA1's 8 / 2 over
#: twenty.  Few rows a head, so a program carries all of a slot's KV heads and
#: walks the slot's live blocks itself, a key tile of ``T`` blocks at a time.
DECODE_GEOMETRIES = {"gqa8-4": (8, 4, 6), "gqa8-2": (8, 2, 6),
                     "gqa8-2x20": (8, 2, 20)}


def _decode_case(geom, T, s_in, rs, bs=4, hd=8):
    """Slots whose live blocks number 1, ``T``, ``T`` + 1 and all of the
    table, their rows' last position the last but one of the last block."""
    H, hkv, mb = DECODE_GEOMETRIES[geom]
    lives = (1, T, min(T + 1, mb), mb)
    B, nb = len(lives), 1 + len(lives) * mb
    tables = jnp.asarray(rs.permutation(np.arange(1, nb)).reshape(B, mb),
                         jnp.int32)
    offs = jnp.asarray([n * bs - s_in - 1 for n in lives], jnp.int32)
    q = jnp.asarray(rs.standard_normal((B, H, s_in, hd)), jnp.float32)
    return lives, nb, tables, offs, q


@pytest.mark.parametrize("s_in", (1, 3))
@pytest.mark.parametrize("fw", (1, 2, 3, 4, 6))
@pytest.mark.parametrize("geom", sorted(DECODE_GEOMETRIES))
def test_decode_shape_matches_gather_oracle(geom, fw, s_in):
    """The decode shape (the in-kernel walk, ``hb`` > 1 KV heads a program)
    at the cells' table widths, every tile width ``T`` = ``fw`` that divides,
    covers or straddles them, slots whose live blocks number 1, exactly
    ``T``, ``T`` + 1 and ``mb``: decode and the K+1 verify rows, with and
    without the window, the int8 pool (which keeps the grid's walk,
    ``fetch_width`` its blocks a step), and the stacked pool under a traced
    layer, all within float tolerance of the gather oracle."""
    H, hkv, mb = DECODE_GEOMETRIES[geom]
    bs, hd, L = 4, 8, 2
    rows = -(-(H // hkv) * s_in // 8) * 8
    assert decode_walk(hkv, rows, mb, fw, bs, bs * hd * 4) == (hkv, mb)
    assert decode_walk(hkv, rows, mb, fw, bs, bs * hd, True) == (hkv, 0)
    rs = np.random.RandomState(fw * 10 + s_in)
    _lives, nb, tables, offs, q = _decode_case(geom, fw, s_in, rs)
    traced = jax.jit(lambda q, kp, vp, li, window: paged_decode_attention(
        q, kp, vp, tables, offs, layer=li, window=window, fetch_width=fw),
        static_argnums=4)
    # (int8 pool, window, traced layer of the stacked pool)
    for int8, window, layer in ((False, None, None), (False, 6, 1),
                                (True, 6 if s_in == 1 else None, None)):
        shape = (nb, hkv, bs, hd) if layer is None else (L, nb, hkv, bs, hd)
        kp, vp = _pools_for(int8, shape, rs)
        want = paged_attention(q, kp, vp, offs, tables=tables, window=window,
                               layer=layer)
        if layer is None:
            got = paged_decode_attention(q, kp, vp, tables, offs,
                                         window=window, fetch_width=fw)
        else:
            got = traced(q, kp, vp, jnp.int32(layer), window)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-6,
            err_msg=f"int8={int8} window={window} layer={layer}")


@pytest.mark.parametrize("s_in", (1, 3))
@pytest.mark.parametrize("T", (None, 1, 3, 8))
def test_decode_walk_reads_nothing_a_slot_does_not_own(T, s_in):
    """NaN in every pool block that no live column of the call's tables
    names (the NULL block behind the dead columns among them) and in the
    rows of each slot's last block behind its last position: the output is
    BITWISE the clean pool's.  A dead block inside a live key tile is never
    copied, its stale keys are masked and its values zeroed, so nothing of
    it reaches the output, not even as 0 x NaN."""
    geom, bs, hd, L = "gqa8-2x20", 4, 8, 2
    rs = np.random.RandomState(s_in)
    lives, nb, tables, offs, q = _decode_case(geom, T or 5, s_in, rs, bs, hd)
    hkv, mb = DECODE_GEOMETRIES[geom][1:]
    kp, vp = (np.array(a) for a in _pools_for(False, (L, nb, hkv, bs, hd), rs))
    dirty = [kp.copy(), vp.copy()]
    owned = np.zeros(nb, bool)
    tab = np.asarray(tables)
    for b, n in enumerate(lives):
        owned[tab[b, :n]] = True
        for pool in dirty:  # the last block's rows nobody wrote yet
            pool[:, tab[b, n - 1], :, (int(offs[b]) + s_in) % bs:] = np.nan
    tables = jnp.asarray(np.where(
        np.arange(mb)[None] < np.asarray(lives)[:, None], tab, 0), jnp.int32)
    for pool in dirty:
        pool[:, ~owned] = np.nan
    run = lambda k, v: np.asarray(paged_decode_attention(
        q, jnp.asarray(k), jnp.asarray(v), tables, offs, layer=1,
        fetch_width=T))
    want = run(kp, vp)
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(run(*dirty), want)


#: (KV heads, query heads, S_in, table columns, block size, head dim,
#: itemsize, int8) -> (hb, T): every shape the cells and the smoke test hand
#: the kernel, at their real sizes.
WALK_SHAPES = {
    "mistral7b.decode": ((8, 32, 1, 6, 128, 128, 2, False), (8, 6)),
    "mistral7b.decode-chunk": ((8, 32, 256, 6, 128, 128, 2, False), (1, 0)),
    "nemotron3s.decode": ((2, 32, 1, 6, 128, 128, 2, False), (2, 6)),
    "nemotron3s.decode-chunk": ((2, 32, 128, 6, 128, 128, 2, False), (1, 0)),
    "zaya1.reason": ((2, 8, 1, 20, 128, 128, 2, False), (2, 10)),
    "zaya1.reason-chunk": ((2, 8, 256, 20, 128, 128, 2, False), (1, 0)),
    "zaya1.reason-verify": ((2, 8, 3, 21, 128, 128, 2, False), (2, 10)),
    "chip_smoke": ((16, 16, 1, 16, 128, 128, 2, False), (16, 4)),
    "32k-engine": ((2, 4, 1, 64, 512, 8, 4, False), (2, 2)),
    "32k-mistral": ((8, 32, 1, 256, 128, 128, 2, False), (8, 8)),
    "int8-decode": ((8, 32, 1, 6, 128, 128, 1, True), (8, 0)),
    "one-column": ((8, 32, 1, 1, 128, 128, 2, False), (8, 1)),
}


@pytest.mark.parametrize("name", sorted(WALK_SHAPES))
def test_walk_follows_the_shape(name):
    """``decode_walk`` is the one place that says how a call walks: ``hb``
    KV heads a program and a key tile of ``T`` blocks for the small shapes,
    the grid's walk (``T`` = 0) for a chunk's rows and an int8 pool."""
    (hkv, H, s_in, mb, bs, hd, itemsize, int8), want = WALK_SHAPES[name]
    rows = -(-(H // hkv) * s_in // 8) * 8
    assert decode_walk(hkv, rows, mb, min(6, mb), bs, bs * hd * itemsize,
                       int8) == want


#: (window, int8, tile blocks): a prefill chunk's rows (2 query heads a KV
#: head x 72 = 144 rows a program: the grid's walk) over a table of 12
#: columns.  A window that binds (the walk starts at it), one as wide as the
#: table (a mask that takes nothing out), none; the tile the kernel takes on
#: its own (None: all 12 columns, one step) and tiles that divide, straddle
#: and cover the table; the int8 pool's scale rows side by side.
CHUNK_TILES = {
    "none-own": (None, False, None), "none-2": (None, False, 2),
    "none-5": (None, False, 5), "none-12": (None, False, 12),
    "binds-own": (20, False, None), "binds-3": (20, False, 3),
    "binds-5": (20, False, 5), "wide-4": (96, False, 4),
    "wide-own": (96, False, None), "int8-own": (None, True, None),
    "int8-3": (None, True, 3), "int8-binds-4": (20, True, 4),
}


def _chunk_case(rs, s_in=72, groups=2, hkv=2, bs=8, hd=16, mb=12):
    """Slots whose chunk ends in the table's first block, mid-table and at
    its end: live blocks from 1 short of a tile to all of the table."""
    offs = np.asarray([0, 3, 17, 24], np.int32)
    offs = np.minimum(offs, mb * bs - s_in)
    B, nb = len(offs), 1 + len(offs) * mb
    tables = rs.permutation(np.arange(1, nb)).reshape(B, mb).astype(np.int32)
    q = jnp.asarray(rs.standard_normal((B, hkv * groups, s_in, hd)),
                    jnp.float32)
    return offs, nb, tables, q


@pytest.mark.parametrize("case", sorted(CHUNK_TILES))
def test_chunk_tile_matches_gather_oracle(case):
    """The grid's walk with a step's blocks as ONE key tile against the
    gathered oracle: every slot's last tile holds dead sub-blocks (its chunk
    ends before the tile does), a window's walk starts mid-table."""
    window, int8, fw = CHUNK_TILES[case]
    rs = np.random.RandomState(len(case))
    hkv, bs, hd, L = 2, 8, 16, 2
    offs, nb, tables, q = _chunk_case(rs)
    kp, vp = _pools_for(int8, (L, nb, hkv, bs, hd), rs)
    want = paged_attention(q, kp, vp, jnp.asarray(offs), tables=tables,
                           window=window, layer=1)
    got = paged_decode_attention(q, kp, vp, jnp.asarray(tables),
                                 jnp.asarray(offs), layer=1, window=window,
                                 fetch_width=fw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("window", (None, 20))
@pytest.mark.parametrize("fw", (None, 3, 5, 8))
def test_chunk_tile_reads_nothing_a_slot_does_not_own(fw, window):
    """The twin of the decode walk's test for the grid's walk: NaN in every
    pool block that no live column of the call's tables names (the NULL
    block that a never-live operand holds among them, and every block behind
    a window) and in the rows of each slot's last block behind its last
    position: the output is finite and BITWISE the clean pool's.  A dead
    sub-block of a live tile is masked out of the scores and zeroed as
    values, so nothing of it reaches the output, not even as 0 x NaN."""
    rs = np.random.RandomState(7)
    hkv, bs, hd, L, s_in, mb = 2, 8, 16, 2, 72, 12
    offs, nb, tab, q = _chunk_case(rs)
    kp, vp = (np.array(a) for a in _pools_for(False, (L, nb, hkv, bs, hd), rs))
    dirty = [kp.copy(), vp.copy()]
    owned = np.zeros(nb, bool)
    tables = np.zeros_like(tab)
    for b, off in enumerate(offs):
        lo = max(off - window + 1, 0) // bs if window else 0
        hi1 = (off + s_in - 1) // bs
        tables[b, lo:hi1 + 1] = tab[b, lo:hi1 + 1]
        owned[tab[b, lo:hi1 + 1]] = True
        for pool in dirty:  # the last block's rows nobody wrote yet
            pool[:, tab[b, hi1], :, (off + s_in - 1) % bs + 1:] = np.nan
    for pool in dirty:
        pool[:, ~owned] = np.nan
    run = lambda k, v: np.asarray(paged_decode_attention(
        q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables),
        jnp.asarray(offs), layer=1, window=window, fetch_width=fw))
    want = run(kp, vp)
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(run(*dirty), want)
    oracle = paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                             jnp.asarray(offs), tables=tab, window=window,
                             layer=1)
    np.testing.assert_allclose(want, np.asarray(oracle), rtol=2e-5, atol=2e-6)


#: (hb x rows, walked columns, block size) -> blocks of one key tile, at the
#: cells' real sizes: as many columns as 1,024 keys and 24 MB of scores take,
#: in equal steps.
CHUNK_TILE_SHAPES = {
    "mistral7b.decode": ((1024, 6, 128), 6),
    "zaya1.reason": ((1024, 20, 128), 7),
    "nemotron3s.decode": ((2048, 6, 128), 6),
    "trinitymini.mixedlen-window": ((4096, 21, 128), 7),
    "trinitymini.mixedlen-global": ((4096, 112, 128), 8),
    "int8-decode": ((64, 6, 128), 6),
    "1024-rows-21-columns": ((1024, 21, 128), 7),
    "8192-rows": ((8192, 21, 128), 4),
    "a-block-of-1024-keys": ((2048, 6, 1024), 1),
    "toy": ((144, 7, 8), 7),
}


@pytest.mark.parametrize("name", sorted(CHUNK_TILE_SHAPES))
def test_chunk_tile_follows_the_shape(name):
    """``chunk_tile`` says from the shape alone how many blocks the grid's
    walk makes one key tile; ``call_walk`` hands it on as the chunk's ``fw``
    and leaves a caller's own ``fetch_width`` and the decode shape alone."""
    (rows, cols, bs), want = CHUNK_TILE_SHAPES[name]
    assert chunk_tile(1, rows, cols, bs) == want
    got = call_walk(rows, 2, cols, bs, bs * 128 * 2)
    assert got == ((rows, want, 1, 0) if rows > 128 else
                   (rows, got[1], 2, min(cols, 1280 // bs)))
    assert call_walk(rows, 2, cols, bs, bs * 128 * 2, fetch_width=2)[1] == min(
        2, cols)


def _exp_shapes(s_in, fw, *, groups, bs, mb, hkv=2, hd=128, window=None):
    """Shapes of every ``exp`` over a plane of scores (more than one key) in
    the body of the chunk's kernel, from the call's jaxpr alone."""
    S = jax.ShapeDtypeStruct
    pool = S((1 + mb, hkv, bs, hd), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v, t, o: paged_decode_attention(
        q, k, v, t, o, fetch_width=fw, window=window))(
            S((1, hkv * groups, s_in, hd), jnp.bfloat16), pool, pool,
            S((1, mb), jnp.int32), S((1,), jnp.int32))
    found = []

    def walk(j, inside):
        for e in j.eqns:
            if inside and e.primitive.name == "exp" and (
                    e.outvars[0].aval.shape[-1] > 1):
                found.append(tuple(e.outvars[0].aval.shape))
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub, inside or e.primitive.name == "pallas_call")

    walk(jaxpr.jaxpr, False)
    return found


@pytest.mark.parametrize("groups,s_in,bs,mb,window,want", [
    (4, 256, 128, 6, None, (1, 1024, 768)),
    (16, 128, 128, 6, None, (1, 2048, 768)),
    (4, 256, 128, 20, None, (1, 1024, 896)),
    (8, 512, 128, 112, 2048, (1, 4096, 896)),
    (8, 512, 128, 112, None, (1, 4096, 1024)),
    (16, 128, 1024, 6, None, (1, 2048, 1024))],
    ids=["mistral7b", "nemotron3s", "zaya1", "trinity-window",
         "trinity-global", "a-block-a-step"])
def test_a_chunk_step_is_one_softmax_step(groups, s_in, bs, mb, window, want):
    """A grid step of the chunk's kernel holds ONE ``exp`` over ``[rows,
    tile keys]`` at the cells' shapes (one mask, one max, one rescale of the
    accumulator for all the blocks it fetched), and where the shape fits
    nothing wider than a block, the step a block it always had."""
    assert _exp_shapes(s_in, None, groups=groups, bs=bs, mb=mb,
                       window=window) == [want]
    if bs == 1024:
        assert want[-1] == bs
    # a caller's own width is one tile too
    assert _exp_shapes(s_in, 3, groups=groups, bs=bs, mb=mb,
                       window=window) == [want[:2] + (3 * bs,)]


def _pallas_grid(s_in, fw, *, B=2, H=8, hkv=4, bs=4, hd=8, mb=6):
    """(grid, block shapes of every operand and the output) of the call's
    ``pallas_call``, read from its jaxpr."""
    S = jax.ShapeDtypeStruct
    pool = S((1 + B * mb, hkv, bs, hd), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, t, o: paged_decode_attention(
        q, k, v, t, o, fetch_width=fw))(
            S((B, H, s_in, hd), jnp.float32), pool, pool,
            S((B, mb), jnp.int32), S((B,), jnp.int32))

    def calls(jaxpr):  # the decode walk's call sits inside its own jit
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    blocks = [tuple(d.block_size for d in bm.block_shape)
              for bm in gm.block_mappings]
    return call.params["name"], gm.grid, blocks


@pytest.mark.parametrize("fw", (1, 2, 4))
def test_chunk_keeps_one_head_a_step(fw):
    """``S_in`` = a chunk yields ``hb`` = 1 and ``paged_chunk``'s grid, block
    shapes and operand count as they were before a program carried several
    heads (``(slot, kv-head, kv-step)``, a ``(1, 1, 1, bs, hd)`` block a
    sub-block and side); the decode shape at the same geometry takes all
    four heads in one program a slot, its pools handed over whole (they
    stay in HBM and the kernel copies what is live)."""
    B, H, hkv, bs, hd, mb, chunk = 2, 8, 4, 4, 8, 6, 128
    rows = (H // hkv) * chunk  # 256: past the one 128-row tile of a program
    name, grid, blocks = _pallas_grid(chunk, fw)
    assert name == "paged_chunk"
    assert grid == (B, hkv, -(-mb // fw))
    assert blocks == ([(1, 1, rows, hd)] + 2 * fw * [(1, 1, 1, bs, hd)]
                      + [(1, 1, rows, hd)])
    name, grid, blocks = _pallas_grid(1, fw)
    assert name == "paged_decode"
    assert grid == (B, 1)
    assert blocks == ([(1, hkv, 8, hd)] + 2 * [(1, 1 + B * mb, hkv, bs, hd)]
                      + [(1, hkv, 8, hd)])


#: unequal widths (keys 24 wide transposed in the pool, values 16), GQA 8 /
#: 2 or 8 / 4 over a table of six blocks of 8: name -> (KV heads, rows a
#: slot, window, a sink?).  Four query rows a head or fewer: the in-kernel
#: walk; 4 x 40 rows a head: the grid's walk (``shape_walk``'s ``T``)
WIDE_CASES = {
    "window-sink-decode": (4, 1, 8, True),
    "window-sink-verify": (4, 3, 8, True),
    "window-sink-chunk": (2, 40, 8, True),
    "global-decode": (2, 1, None, False),
    "global-chunk": (2, 40, None, False),
    "global-sink-chunk": (2, 40, None, True),
}


@pytest.mark.parametrize("case", sorted(WIDE_CASES))
def test_unequal_widths_and_a_sink_match_the_gathered_oracle(case):
    """Both walks on a pool whose K leaf lies transposed (``[.., 24, bs]``
    beside V's ``[.., bs, 16]``), with a sink a query head in the softmax's
    denominator and a window of ONE block, against the gathered oracle and
    against dense attention spelled out (the sink one more column that is
    dropped): slots at their own depths, the first inside its first block."""
    from torchdistpackage_tpu.ops.paged_attention import shape_walk
    from torchdistpackage_tpu.serving.paged_cache import paged_write

    hkv, s_in, window, sunk = WIDE_CASES[case]
    B, H, hd, hv, bs, mb = 3, 8, 24, 16, 8, 6
    *_, T = shape_walk(H // hkv, s_in, hkv, mb, bs, bs * 20 * 4, window)
    assert bool(T) == (s_in <= 3), (case, T)
    rs = np.random.RandomState(7)
    tables = jnp.asarray(1 + rs.permutation(B * mb).reshape(B, mb), jnp.int32)
    kk = jnp.asarray(rs.randn(B, hkv, mb * bs, hd), jnp.float32)
    vv = jnp.asarray(rs.randn(B, hkv, mb * bs, hv), jnp.float32)
    zero = jnp.zeros(B, jnp.int32)
    kp = paged_write(jnp.full((2, 1 + B * mb, hkv, hd, bs), jnp.nan),
                     kk, zero, tables=tables, layer=1, transposed=True)
    vp = paged_write(jnp.full((2, 1 + B * mb, hkv, bs, hv), jnp.nan),
                     vv, zero, tables=tables, layer=1)
    q = jnp.asarray(rs.randn(B, H, s_in, hd), jnp.float32)
    sink = jnp.asarray(rs.randn(H) + 1.0, jnp.float32) if sunk else None
    offs = jnp.asarray([3, 8, 48 - s_in], jnp.int32)
    got = {impl: np.asarray(paged_attention(
        q, kp, vp, offs, tables=tables, window=window, impl=impl, layer=1,
        sink=sink)) for impl in ("gather", "pallas")}
    assert got["pallas"].shape == (B, H, s_in, hv)
    qpos = np.asarray(offs)[:, None] + np.arange(s_in)
    kpos = np.arange(mb * bs)
    want = np.zeros((B, H, s_in, hv))
    for b in range(B):
        for h in range(H):
            sc = np.asarray(q[b, h]) @ np.asarray(kk[b, h * hkv // H]).T
            keep = kpos[None] <= qpos[b][:, None]
            if window:
                keep &= kpos[None] > qpos[b][:, None] - window
            sc = np.where(keep, sc / np.sqrt(hd), -np.inf)
            if sunk:
                sc = np.concatenate(
                    [sc, np.full((s_in, 1), float(sink[h]))], -1)
            e = np.exp(sc - sc.max(-1, keepdims=True))
            pr = (e / e.sum(-1, keepdims=True))[:, :mb * bs]
            want[b, h] = pr @ np.asarray(vv[b, h * hkv // H])
    for impl in got:
        np.testing.assert_allclose(got[impl], want, atol=3e-6, err_msg=impl)


@pytest.mark.parametrize("fw", (1, 2, 3, 4, 6))
def test_fetch_rule_asks_for_live_blocks_only(fw):
    """The mechanism's counter, without a chip: walk the grid in its order
    and issue a copy wherever an operand's index (``fetched_block``: the
    index map's own rule) differs from the one it holds, as the pipeline
    does.  Every (slot, head group) then fetches exactly its live blocks,
    once each (K and V share the map), and the constant block is fetched
    only by an operand that was live in the slot before and is never live
    in this one."""
    bs, mb = 4, 6
    lives = np.asarray([1, 3, 6, 2, 2, 5, 6, 1])
    B = len(lives)
    tables = 1 + np.random.RandomState(fw).permutation(B * mb).reshape(B, mb)
    for s_in, groups in ((1, 1), (3, 2), (1, 4)):
        offs = lives * bs - s_in - 1
        held, got, const = [None] * fw, {}, []
        for b in range(B):
            for h in range(groups):
                for j in range(-(-mb // fw)):
                    for i in range(fw):
                        idx = tuple(int(x) for x in fetched_block(
                            tables, offs, b, h, j, i, S_in=s_in, bs=bs, fw=fw))
                        if idx == held[i]:
                            continue
                        held[i] = idx
                        if idx == (0, 0):
                            const.append((b, i))
                        else:
                            assert idx[1] == h
                            got.setdefault((b, h), []).append(idx[0])
        for b in range(B):
            for h in range(groups):
                assert sorted(got[b, h]) == sorted(tables[b, :lives[b]])
        assert const == [(b, i) for b in range(B) for i in range(fw)
                         if i >= lives[b] and (b == 0 or i < lives[b - 1])]


def test_resolve_attn_impl():
    """'auto' resolves per backend (gather on CPU — the interpreter kernel
    is a correctness story, not a speed story); junk is rejected."""
    assert resolve_attn_impl("auto") == "gather"  # CPU container
    assert resolve_attn_impl(None) == "gather"
    assert resolve_attn_impl("pallas") == "pallas"
    assert resolve_attn_impl("gather") == "gather"
    with pytest.raises(ValueError, match="attn_impl"):
        resolve_attn_impl("cuda")
    with pytest.raises(ValueError, match="attn_impl"):
        ServingEngine(None, CFG, attn_impl="nope")


# ---------------------------------------------------- engine token parity


def test_pallas_engine_token_bit_parity(bundle):
    """The pallas engine (spec_k=2 — the decode program IS the K+1 verify
    shape) emits tokens BIT-equal to contiguous ``generate()`` and to the
    gather engine, at one decode signature per arm."""
    for impl in ("pallas", "gather"):
        for row, got in enumerate(bundle["tokens"][impl]):
            np.testing.assert_array_equal(
                got, bundle["want"][row],
                err_msg=f"{impl} engine diverged from generate()")
        s = bundle["eng"][impl].serving_summary()
        assert s["decode_signatures"] == 1
        assert s["prefill_signatures"] == 1
        assert s["attn_impl"] == impl
        assert s["requests"]["completed"] == 2


def test_pallas_engine_int8_pool_parity(bundle):
    """The int8 pool through the FUSED dequant path: token-identical to
    the fp golden at these seeds (the established quantized-KV bar —
    test_serving.py's gather-quant golden makes the same claim)."""
    for row, got in enumerate(bundle["tokens"]["int8_pallas"]):
        np.testing.assert_array_equal(
            got, bundle["want"][row],
            err_msg="int8 pallas decode diverged beyond quant tolerance")
    s = bundle["eng"]["int8_pallas"].serving_summary()
    assert s["decode_signatures"] == 1 and s["attn_impl"] == "pallas"


# ----------------------------------------------------- memory-ledger evidence


def test_compiled_decode_drops_gathered_temp(bundle):
    """Via the Telemetry AOT hook (the compiled decode executable captured
    at first dispatch — no second compile): the gather arm's program
    materializes the O(max_blocks*bs) gathered-view buffer ([B, Hkv,
    max_blocks*bs, hd] or its [B, mb, Hkv, bs, hd] precursor); the pallas
    arm's program contains NO buffer of either shape — per-step attention
    traffic is block-bounded, which is what opens 32k contexts."""
    from torchdistpackage_tpu.obs.mem_ledger import static_ledger

    def views(impl):
        eng = bundle["eng"][impl]
        B, hkv, hd = eng.num_slots, 2, 8
        mb, bs = eng.max_blocks, eng.block_size
        return (f"f32[{B},{hkv},{mb * bs},{hd}]",
                f"[{B},{mb},{hkv},{bs},{hd}]")

    texts = {}
    for impl in ("pallas", "gather"):
        comps = bundle["tel"][impl].compiled_programs()
        assert comps, f"{impl}: Telemetry captured no compiled signature"
        # the hook's static ledger parses the same executable
        assert static_ledger(comps[0]) is not None
        texts[impl] = "\n".join(c.as_text() for c in comps)
    assert any(v in texts["gather"] for v in views("gather")), (
        "gather arm lost its gathered view? shapes under test are stale")
    assert not any(v in texts["pallas"] for v in views("pallas")), (
        "pallas decode program still allocates the gathered-view temp")


# --------------------------------------------------------------- hot-loop lint


def test_gather_kv_not_called_from_pallas_hot_loop(bundle):
    """Repo-lint: with ``attn_impl='pallas'`` the engine's traced programs
    never call ``gather_kv`` (counted at trace time — compiled steps make
    no python calls); the gather arm does (it IS the gather), and the
    engine source never references gather_kv directly (it survives only
    in paged_cache's oracle branch and audit-free paths)."""
    import inspect

    import torchdistpackage_tpu.serving.engine as engine_mod

    assert bundle["gather_calls"]["pallas"] == 0, (
        "pallas engine still gathers in the hot loop")
    assert bundle["gather_calls"]["int8_pallas"] == 0
    assert bundle["gather_calls"]["gather"] > 0  # the counter works
    assert "gather_kv" not in inspect.getsource(engine_mod)


# ------------------------------------------------------- 32k long context


@pytest.mark.slow
def test_32k_long_context_serving():
    """The bounded-VMEM payoff: a 32k-context engine on the pallas path
    serves a long prompt through chunked prefill over paged KV and
    decodes, at one signature per phase — while the modeled per-step
    footprint verdict (MemoryModel-style shape math against
    ``headroom_verdict``) says the gather path's gathered view would NOT
    fit the same budget.  docs/long_context.md has the composition."""
    from torchdistpackage_tpu.obs.mem_ledger import headroom_verdict
    from torchdistpackage_tpu.serving import pool_bytes

    cfg = llama_config(vocab_size=64, dim=32, nheads=4, nlayers=1,
                       max_seq=32768, kv_heads=2, ffn_hidden=48,
                       dtype=jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, num_slots=1, block_size=512,
                        chunk=512, max_ctx=32768, attn_impl="pallas")
    assert eng.max_blocks == 64
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2048,), 0, cfg.vocab_size), np.int32)
    rid = eng.submit(Request(prompt.tolist(), 4))
    eng.run_until_idle(max_ticks=100)
    f = eng.finished[rid]
    assert f["reason"] == "max_tokens" and f["new_tokens"] == 4
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1

    # modeled per-decode-step footprint: pool + attention working set
    pool = pool_bytes(eng.cache)
    hd = cfg.block.head_dim
    common = dict(batch=1, kv_heads=2, max_blocks=eng.max_blocks,
                  block_size=eng.block_size, head_dim=hd, itemsize=4)
    gather_ws = modeled_attend_temp_bytes("gather", **common)
    pallas_ws = modeled_attend_temp_bytes("pallas", groups=2, **common)
    assert pallas_ws < gather_ws / 10  # block-bounded vs context-bounded
    capacity = pool + gather_ws // 2
    assert headroom_verdict(pool + gather_ws, capacity)["verdict"] == "oom_risk"
    assert headroom_verdict(pool + pallas_ws, capacity)["verdict"] == "ok"
