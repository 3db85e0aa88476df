"""The hybrid family's windowed shape (models/hybrid.py kinds ``W`` and ``*``
side by side, the output gate, the head norms, a norm behind every mixer, a
scaled embedding: the ``afmoe`` architecture) at toy widths on the CPU: TWO
block pools in one model, the window layers' table handed on block by block,
both paged walks starting at the window, and chunked prefill + decode through
``ServingEngine`` against the plain reference's full forward
(benchmarks/reference/afmoe.py, which imports nothing of the program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import afmoe as family
from benchmarks.reference import afmoe as ref
from benchmarks.weights_afmoe import make_weights
from torchdistpackage_tpu.models import init_hybrid_params
from torchdistpackage_tpu.ops import paged_attention as PA
from torchdistpackage_tpu.parallel.moe import moe_serve_forward
from torchdistpackage_tpu.serving import (
    Request, ServingEngine, copy_blocks, expected_pool_bytes, init_paged_kv,
    pool_bytes)
from torchdistpackage_tpu.serving.paged_cache import (
    paged_attention, window_bytes, window_reach)

#: an ``afmoe`` configuration file in small: two periods of three window
#: blocks to one global block, one leading dense block, 8 experts routed top-2,
#: 4 held (the second of two shares), window 16
TOY = {
    "name": "toy-afmoe", "family": "afmoe", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "num_hidden_layers": 8, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
                   + ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 16, "num_experts": 4, "published": {"num_experts": 8},
    "deployment_share": {"first_expert": 4}, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "moe_intermediate_size": 32,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "n_group": 1, "topk_group": 1, "mup_enabled": True, "hidden_act": "silu",
    "tie_word_embeddings": False, "rope_scaling": None, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "vocab_size": 211, "max_position_embeddings": 512,
}
F32 = jnp.float32
MAX_CTX = 96


@pytest.fixture(scope="module")
def toy():
    """(Shape, the program's config in float32, float32 weights)."""
    s = family.shape(TOY, MAX_CTX)
    cfg = dataclasses.replace(family.program_config(TOY, MAX_CTX), dtype=F32)
    params = jax.tree.map(lambda a: a.astype(F32), make_weights(s, 7))
    return s, cfg, params


def test_pattern_two_pools_and_parameter_counts(toy):
    s, cfg, params = toy
    assert s.pattern == cfg.pattern == "WDWEWE*EWEWEWE*E"
    assert (cfg.nlayers, cfg.kv_layers, cfg.window_layers,
            cfg.state_layers) == (16, 2, 6, 0)
    assert cfg.window == 16 and cfg.embed_scale == 8.0
    # two pools of ONE block shape, each with blocks and a NULL of its own
    pool = init_paged_kv(cfg, 7, 8, window_blocks=5)
    assert pool["k"].shape == (2, 7, 2, 8, 16)
    assert pool["win"]["v"].shape == (6, 5, 2, 8, 16)
    assert window_bytes(pool) == 2 * 6 * 5 * 2 * 8 * 16 * 4
    assert pool_bytes(pool) == expected_pool_bytes(
        cfg, 7, 8, window_blocks=5) == (2 * 7 + 6 * 5) * 2 * 2 * 8 * 16 * 4
    for kw, why in (({"quantized": True}, "int8"),
                    ({"axis_size": 2}, "tensor-parallel")):
        with pytest.raises(NotImplementedError, match=why):
            init_paged_kv(cfg, 7, 8, window_blocks=5, **kw)
    with pytest.raises(ValueError, match="window_blocks"):
        init_paged_kv(cfg, 7, 8)
    # copy-on-write ids name the pool that keeps everything; the window
    # pool comes back as it went in
    noisy = jax.tree.map(lambda a: jax.random.normal(
        jax.random.PRNGKey(1), a.shape), pool)
    out = copy_blocks(noisy, jnp.asarray([2, 0]), jnp.asarray([5, 0]))
    np.testing.assert_array_equal(out["k"][:, 5], noisy["k"][:, 2])
    np.testing.assert_array_equal(out["win"]["k"], noisy["win"]["k"])
    # the family's count is the tree's, and so is the program's own init
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    assert count(params) == family.num_params(s)
    # the program's own init: the same tree less the optional leaves
    own = init_hybrid_params(jax.random.PRNGKey(0), cfg)
    optional = {"wg", "q_norm", "k_norm", "post_norm"}
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, {
        **params, "layers": [{k: v for k, v in lp.items()
                              if k not in optional}
                             for lp in params["layers"]]})
    for bad, kw, why in (("WE", {}, "'\\*' layer beside it"),
                         ("W*", {"window": 0}, "window > 0"),
                         ("W*L", {}, "one kind of block pool"),
                         ("W*X", {}, "pattern")):
        with pytest.raises(ValueError, match=why):
            dataclasses.replace(cfg, pattern=bad, **kw)


@pytest.mark.parametrize("window,chunk,bs,want", [
    (2048, 512, 128, 20), (16, 8, 8, 3), (16, 32, 8, 6), (16, 1, 8, None),
    (10, 6, 4, None)])
def test_window_reach_counts_a_calls_columns(window, chunk, bs, want):
    """``(window + chunk) / block``, both in whole blocks (asked of them),
    against a count by hand: every chunk offset and every decode position."""
    if want is None:
        with pytest.raises(ValueError, match="whole blocks"):
            window_reach(window, chunk, bs)
        return
    assert window_reach(window, chunk, bs) == want
    cols = lambda off, n: (off + n - 1) // bs - max(off - window + 1, 0) // bs + 1
    assert want == max([cols(o, chunk) for o in range(0, 40 * chunk, chunk)]
                       + [cols(t, 1) for t in range(40 * bs)])


# ------------------------------------------------------------ the two walks


def _pools(B, mb, bs, hkv, hd, L=2):
    """Seeded pools whose NULL block and whose every block BEHIND a slot's
    window are poisoned: a walk that fetches and multiplies them shows."""
    nb = 1 + B * mb
    kp = jax.random.normal(jax.random.PRNGKey(0), (L, nb, hkv, bs, hd), F32)
    vp = jax.random.normal(jax.random.PRNGKey(1), (L, nb, hkv, bs, hd), F32)
    tables = 1 + np.random.RandomState(2).permutation(B * mb).reshape(B, mb)
    return kp, vp, tables.astype(np.int32)


@pytest.mark.parametrize("s_in,groups,fw", [
    (1, 2, None), (1, 1, 2), (3, 2, None), (24, 2, 2), (24, 2, 3), (40, 1, 4),
    (72, 4, 3), (72, 2, None), (72, 2, 6), (72, 4, None)],
    ids=["decode", "decode-tile2", "verify", "chunk-fw2", "chunk-fw3",
         "chunk-wide", "chunk-split", "chunk-own-tile", "chunk-tile6",
         "chunk-split-own-tile"])
def test_both_walks_start_at_the_window(s_in, groups, fw, monkeypatch):
    """Offsets before, at and far past the window, decode and chunk rows:
    the kernel against the gathered oracle, with every table column that
    lies wholly behind the first row's window sent to a block of NaN (a
    handed-on column reads as NULL): nothing of it is fetched into a score
    or a value.  The windowed call carries its own kernel name."""
    bs, mb, hkv, hd, window = 8, 12, 2, 16, 20
    if groups == 4:   # 288 rows a KV head: two programs of 144 share its K, V
        monkeypatch.setattr(PA, "_PROGRAM_ROWS", 150)
        assert PA.head_split(groups, s_in) == 2
    offs = np.asarray([0, 5, 19, 20, 37, 50, 96 - s_in], np.int32)
    offs = np.minimum(offs, mb * bs - s_in)
    B = len(offs)
    kp, vp, tables = _pools(B, mb, bs, hkv, hd)
    q = jax.random.normal(jax.random.PRNGKey(3), (B, hkv * groups, s_in, hd),
                          F32)
    want = paged_attention(q, kp, vp, jnp.asarray(offs), tables=tables,
                           window=window, impl="gather", layer=1)
    # poison: NULL, and every block wholly behind a slot's first window
    behind = np.maximum(offs - window + 1, 0) // bs
    dead = np.concatenate([[0]] + [tables[b, :behind[b]] for b in range(B)])
    cut = tables.copy()
    for b in range(B):
        cut[b, :behind[b]] = 0
    kp, vp = (a.at[:, dead].set(jnp.nan) for a in (kp, vp))
    got = PA.paged_decode_attention(
        q, kp, vp, jnp.asarray(cut), jnp.asarray(offs), layer=1,
        window=window, fetch_width=fw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # some slot skipped much of its table (a chunk of 72 rows fills it)
    assert behind.max() >= (4 if s_in < 72 else 0)


def test_kernel_names_and_grid_follow_the_window():
    """A window that can lie short of the table: ``swa_decode`` /
    ``swa_chunk``, the chunk's grid as long as a window's columns and no
    longer; none, or one as wide as the table: today's names and grid."""
    bs, mb, hkv, hd, B = 8, 12, 2, 16, 2
    kp, vp, tables = _pools(B, mb, bs, hkv, hd)
    offs = jnp.asarray([3, 60], jnp.int32)

    def calls(s_in, window):
        q = jnp.zeros((B, 2 * hkv, s_in, hd), F32)
        jaxpr = jax.make_jaxpr(lambda *a: PA.paged_decode_attention(
            *a, layer=0, window=window, fetch_width=2))(
                q, kp, vp, jnp.asarray(tables), offs)
        found = []

        def walk(j):
            for e in j.eqns:
                if e.primitive.name == "pallas_call":
                    found.append((e.params["name"],
                                  tuple(e.params["grid_mapping"].grid)))
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        return found

    assert calls(1, None) == [("paged_decode", (B, 1))]
    assert calls(1, 20) == [("swa_decode", (B, 1))]
    assert calls(1, mb * bs) == [("paged_decode", (B, 1))]
    assert calls(72, None) == [("paged_chunk", (B, hkv, 6))]
    # 72 rows' windows of 20 reach over (20 + 72 - 3) // 8 + 2 = 13 > 12
    assert calls(72, 20) == [("swa_chunk", (B, hkv, 6))]
    # 66 rows' windows of 4 reach over (4 + 66 - 3) // 8 + 2 = 10 columns
    assert calls(66, 4) == [("swa_chunk", (B, hkv, 5))]
    # few enough rows for the in-kernel walk: no grid over columns at all
    assert calls(24, 20) == [("swa_chunk", (B, 1))]
    assert PA.window_columns(2048, 512, 128) == 21
    assert PA.window_columns(2048, 1, 128) == 17


@pytest.mark.parametrize("groups,s_in,want,tile", [
    (16, 128, 1, 6), (4, 256, 1, 7), (8, 1, 1, None), (8, 512, 1, 7),
    (4, 1024, 1, 7), (8, 1024, 2, 7), (8, 2048, 4, 8), (3, 2000, 3, 8)],
    ids=["nemotron3s", "mistral7b", "decode", "trinity", "four-long-heads",
         "four-heads-a-program", "two-heads-a-program", "odd-heads"])
def test_a_heads_rows_stay_one_program_up_to_what_always_compiled(
        groups, s_in, want, tile):
    """Up to 4,096 rows a KV head a call is one program (every cell's
    shape); past it, the fewest programs of whole query heads that come
    under 4,096 rows.  The estimator counts the same, the float32 scores of
    a key tile among it (``tile``: what the shape takes of a window's 18-32
    columns, in equal steps of at most 1,024 keys)."""
    assert PA.head_split(groups, s_in) == want
    rows = groups * s_in // want
    assert rows <= 4096 and (want == 1 or groups * s_in // (want - 1) > 4096
                             or groups % (want - 1))
    geo = dict(batch=2, kv_heads=2, max_blocks=32, block_size=128,
               head_dim=128, itemsize=2)
    if groups * s_in > 128:   # the grid's walk: a tile's blocks a side, twice
        for fw in (6, None):  # a caller's tile, the shape's
            assert PA.modeled_attend_temp_bytes(
                "pallas", s_in=s_in, groups=groups, window=2048,
                fetch_width=fw, **geo) == 2 * 2 * want * (
                    2 * rows * 128 * 2 + 2 * 2 * (fw or tile) * 128 * 128 * 2
                    + 4 * rows * (fw or tile) * 128)


def test_the_index_map_asks_for_no_block_behind_the_window():
    """Walk the chunk's grid as the pipeline does: with a window, operand i
    fetches the live columns from ``first_column`` on and nothing else."""
    bs, mb, fw, s_in, window = 4, 16, 3, 6, 10
    offs = np.asarray([0, 7, 30, 41, 58], np.int32)
    B = len(offs)
    tables = 1 + np.random.RandomState(0).permutation(B * mb).reshape(B, mb)
    steps = -(-min(mb, PA.window_columns(window, s_in, bs)) // fw)
    held, got = [None] * fw, {}
    for b in range(B):
        for j in range(steps):
            for i in range(fw):
                idx = tuple(int(x) for x in PA.fetched_block(
                    tables, offs, b, 0, j, i, S_in=s_in, bs=bs, fw=fw,
                    window=window))
                if idx != held[i]:
                    held[i] = idx
                    if idx != (0, 0):
                        got.setdefault(b, []).append(idx[0])
    for b in range(B):
        lo = max(offs[b] - window + 1, 0) // bs
        hi = (offs[b] + s_in - 1) // bs
        assert sorted(got[b]) == sorted(tables[b, lo:hi + 1]), b


# ---------------------------------------------------------------- the layers


def test_the_two_shares_add_up_to_the_uncut_layer(toy):
    """Experts 0-3 and 4-7, each share's routed part with the shared expert
    counted once, add up to the uncut reference's expert layer; attention is
    every share's whole."""
    s, cfg, params = toy
    p = params["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 64), F32)
    full_s = dataclasses.replace(s, held_first=0, held=8)
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    every = {"w1": jax.random.normal(k1, (8, 64, 64), F32) / 8,
             "w2": jax.random.normal(k2, (8, 32, 64), F32) / 6}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.moe({**p, "experts": every}, x[b], full_s)[0]
                          for b in range(2)])
        shared = jnp.stack([ref.dense_mlp(p["shared"], x[b])
                            for b in range(2)])
        total = 0.0
        for first in (0, 4):
            mcfg = dataclasses.replace(cfg, moe_held=(first, 4)).moe
            share = {**p, "experts": jax.tree.map(
                lambda w: w[first:first + 4], every)}
            y, m = moe_serve_forward(share, x, mcfg, return_metrics=True)
            total = total + (y - shared)
            assert m["gate_idx"].shape == (2, 9, 2)
    np.testing.assert_allclose(total + shared, want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------- the engine


def _served_gap(s, params, finished):
    worst = 0.0
    for f in finished:
        toks = np.asarray(f["tokens"])
        p = len(toks) - f["new_tokens"]
        logits = np.asarray(ref.forward_logits(params, toks[:-1], s))[p - 1:]
        served = logits[np.arange(len(toks) - p), toks[p:]]
        worst = max(worst, float((logits.max(-1) - served).max()))
    return worst


#: (prompt, new tokens): one never past the window of 16, one that crosses
#: it while it decodes, one past it in its first chunk of 32 and handing
#: blocks on in every chunk of 8, and more requests than slots
REQUESTS = ((5, 6), (11, 30), (50, 12), (33, 40), (8, 3), (70, 26))


def _serve(toy, chunk=8, requests=REQUESTS, poison=False, **kw):
    _, cfg, params = toy
    rng = np.random.RandomState(0)
    kw.setdefault("attn_impl", "gather")
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(params, cfg, num_slots=3, block_size=8,
                            chunk=chunk, max_ctx=MAX_CTX, **kw)
        if poison:   # whatever a block held before it is handed on or given
            eng.cache = jax.tree.map(lambda a: jnp.full_like(a, 1e4),
                                     eng.cache)
        for p, n in requests:
            eng.submit(Request(tokens=rng.randint(0, 211, p).tolist(),
                               max_new_tokens=n))
        eng.run_until_idle()
    return eng


@pytest.fixture(scope="module")
def served(toy):
    return _serve(toy, record_routing=True, run_ahead=True, poison=True)


def test_engine_prefill_and_decode_equal_the_reference_forward(toy, served):
    """Logits, not tokens: every served token's logit in the reference's
    full forward, across a window crossing and block after block handed on,
    from a pool that held 1e4 everywhere (a stale key would show), with
    ``run_ahead`` and ``record_routing`` on."""
    s, _, params = toy
    assert len(served.finished) == len(REQUESTS)
    assert served.audit(heal=False)["ok"]
    assert served.stats["blocks_handed_on"] > 20
    with jax.default_matmul_precision("highest"):
        gap = _served_gap(s, params, served.finished.values())
        for f in served.finished.values():
            toks = np.asarray(f["tokens"])
            assert f["routing"].shape == (len(toks) - 1, 7, 2)
            own = ref.forward_following(params, toks[:-1], s)
            np.testing.assert_array_equal(
                np.sort(f["routing"], -1), np.sort(own["routing"], -1))
    assert gap <= 1e-4, gap


def test_a_moved_chunk_boundary_and_the_kernel_path_change_nothing(
        toy, served):
    """Chunks of 32 (a first chunk that is past the window before it ends,
    six blocks a slot where chunks of 8 hold three) and the kernels in
    interpret mode with no ``run_ahead``: the same tokens, request for
    request."""
    wide = _serve(toy, chunk=32)
    assert (served.window_reach, wide.window_reach) == (3, 6)
    assert wide.window_blocks == 1 + 3 * 6
    from torchdistpackage_tpu.utils.profiling import spans
    spans.clear()
    kernel = _serve(toy, attn_impl="pallas", requests=REQUESTS[:3])
    (walk,) = [r[5] for r in spans.snapshot()
               if r[2] == "tdp:engine.init.pool"]
    # a chunk of 8 under the toy's heads: one tile over the table's columns
    # in a global layer, over a window's reach in a window layer
    assert walk["chunk_tile_keys"] == kernel.max_blocks * 8
    assert walk["window_chunk_tile_keys"] == PA.window_columns(16, 8, 8) * 8
    for rid, f in served.finished.items():
        np.testing.assert_array_equal(wide.finished[rid]["tokens"],
                                      f["tokens"])
        if rid in kernel.finished:
            np.testing.assert_array_equal(kernel.finished[rid]["tokens"],
                                          f["tokens"])


def test_a_window_at_least_the_context_is_every_layer_global_and_rotated(toy):
    """``window`` past ``max_ctx``: nothing is ever handed on, and the
    engine serves the logits of the reference with every ``W`` layer read as
    global AND rotated."""
    s, cfg, params = toy
    wide = (s, dataclasses.replace(cfg, window=4 * MAX_CTX), params)
    eng = _serve(wide, requests=REQUESTS[:4])
    assert eng.stats["blocks_handed_on"] == 0 and eng.audit(heal=False)["ok"]
    with jax.default_matmul_precision("highest"):
        gap = _served_gap(dataclasses.replace(s, window=None), params,
                          eng.finished.values())
        narrow = _served_gap(s, params, eng.finished.values())
    assert gap <= 1e-4 < narrow, (gap, narrow)


def test_preempt_and_readmit_across_a_window(toy):
    """A higher priority takes the slot of a request that has already
    handed blocks on; the victim is replayed from its prompt and both pools'
    audits stay clean throughout."""
    s, cfg, params = toy
    rng = np.random.RandomState(3)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(params, cfg, num_slots=1, block_size=8, chunk=8,
                            max_ctx=MAX_CTX, attn_impl="gather")
        eng.submit(Request(tokens=rng.randint(0, 211, 40).tolist(),
                           max_new_tokens=20))
        while eng.stats["blocks_handed_on"] < 4:
            eng.step()
        eng.submit(Request(tokens=rng.randint(0, 211, 30).tolist(),
                           max_new_tokens=8, priority=5))
        while eng.queue or eng.n_busy:
            eng.step()
            assert eng.audit(heal=False)["ok"]
        assert eng.stats["preempted"] == 1 and len(eng.finished) == 2
        gap = _served_gap(s, params, eng.finished.values())
    assert gap <= 1e-4, gap
    # after the queue drained: both allocators hold nothing
    assert eng._allocs[0].in_use == eng._walloc.in_use == 0
    assert not eng._wtables.any()


def test_the_audit_sees_the_window_pool(toy):
    _, cfg, params = toy
    eng = ServingEngine(params, cfg, num_slots=2, block_size=8, chunk=8,
                        max_ctx=MAX_CTX, attn_impl="gather")
    eng.submit(Request(tokens=list(range(20)), max_new_tokens=4))
    eng.step()
    assert eng.audit(heal=False)["ok"]
    eng._wtables[0, 1] = 0                      # a column lost its block
    bad = eng.audit(heal=False)
    assert [v["kind"] for v in bad["violations"]] == ["table_mismatch"]
    assert bad["violations"][0]["pool"] == "window"
    eng.audit(heal=True)                        # requeued: replayed clean
    eng.run_until_idle()
    assert eng.audit(heal=False)["ok"] and len(eng.finished) == 1
    assert eng._walloc.in_use == 0


def test_spans_counters_and_summary_say_both_pools(toy, served):
    _, cfg, _ = toy
    summ = served.serving_summary()
    assert summ["prefill_signatures"] == summ["decode_signatures"] == 1
    kv = summ["kv_pool"]
    assert kv["pool_bytes"] == kv["pool_bytes_expected"] == pool_bytes(
        served.cache)
    win = kv["window"]
    assert win["num_blocks"] == served.window_blocks == 1 + 3 * 3
    assert win["blocks_per_slot"] == 3 and win["window"] == 16
    assert win["pool_bytes"] == window_bytes(served.cache) > 0
    assert 0 < win["mean_utilization"] <= win["peak_utilization"] <= 1.0
    assert win["blocks_handed_on"] == served.stats["blocks_handed_on"]
    ticks = list(served.tick_records)
    assert sum(t["blocks_handed_on"] for t in ticks) == win["blocks_handed_on"]
    # a prefill call and a decode call a tick, three slots of 16 each at most
    assert all(t["window_positions"] <= 2 * 3 * 16 for t in ticks)
    from torchdistpackage_tpu.utils.profiling import spans
    recs = spans.snapshot()
    pools = [r[5] for r in recs if r[2] == "tdp:engine.init.pool"
             and r[5].get("window_blocks") == 10]   # three slots' engines
    assert pools and pools[-1]["bytes"] == pool_bytes(served.cache)
    assert pools[-1]["window_bytes"] == window_bytes(served.cache)
    calls = [r[5] for r in recs if r[2] in (
        "tdp:engine.decode", "tdp:engine.prefill") and "window_positions" in r[5]]
    assert calls and all(
        0 < c["window_positions"] <= c["live_tokens"] for c in calls)
    assert any(c["window_positions"] < c["live_tokens"] for c in calls)
    assert any(c["blocks_handed_on"] for c in calls)
    # a prefill row attends min(window, position + 1) keys in a window layer
    chunks = [c for c in calls if "window_pairs" in c]
    assert chunks and all(
        c["tokens"] <= c["window_pairs"] <= c["live_pairs"] for c in chunks)
    assert any(c["window_pairs"] < c["live_pairs"] for c in chunks)
    fetched = [r[5] for r in recs if r[2] == "tdp:engine.fetch"
               and "experts_touched" in r[5]]
    assert fetched and all(0 < f["experts_touched"] <= 2 * 4 * 3 * 4
                           for f in fetched)
    assert any(r[2] == "tdp:engine.handon" for r in recs)


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "prefix_cache"), ({"spec_k": 2}, "spec_k"),
    ({"kv_quant": True}, "kv_quant"),
    ({"cp_axis": "context", "mesh": "m"}, "cp_axis"),
    ({"mesh": "m"}, "a mesh")])
def test_each_refusal_names_its_reason(toy, kw, what):
    _, cfg, _ = toy
    with pytest.raises(NotImplementedError,
                       match=f"{what}.* with a window pool"):
        ServingEngine(None, cfg, **kw)


@pytest.mark.parametrize("call", ["drain", "resume", "export_slot",
                                  "import_slot"])
def test_a_window_pools_requests_do_not_leave_the_engine(toy, call):
    _, cfg, params = toy
    eng = ServingEngine(params, cfg, num_slots=1, block_size=8, chunk=8,
                        max_ctx=MAX_CTX, attn_impl="gather")
    args = {"drain": (), "resume": ({},), "export_slot": (0,),
            "import_slot": ({},)}[call]
    with pytest.raises(NotImplementedError, match="with a window pool"):
        getattr(eng, call)(*args)
