"""Serving subsystem tests: paged KV cache + continuous-batching engine.

The load-bearing claim is BIT PARITY: the paged block-pool cache attends
through gathered block tables, yet (fp cache) every token the engine emits
must equal the contiguous-cache ``generate()`` batch — for the dense GPT,
GQA/llama, sliding-window, and MoE families, single-device and on a tp_dp
mesh.  Everything else (admission, chunked prefill, retirement, per-slot
sampling, compile-once) rides the same tiny per-family bundles so the
whole file costs a handful of compiled programs, not one per test.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import NamedSharding

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.models import (
    GPTConfig,
    generate,
    gpt_moe_param_specs,
    gpt_param_specs,
    init_gpt_moe_params,
    init_gpt_params,
    llama_config,
)
from torchdistpackage_tpu.obs.events import EventLog, set_default_event_log
from torchdistpackage_tpu.serving.engine import (
    PREFILL_WIDTH_EXPERTS, _filtered_logits, _slot_sample)
from torchdistpackage_tpu.serving import (
    BlockAllocator,
    NULL_BLOCK,
    Request,
    ServingEngine,
    init_paged_kv,
)
from torchdistpackage_tpu.utils import spans

# One tiny config per family the acceptance bar names.  nlayers=2 keeps
# compiles cheap; max_seq=32 keeps block tables narrow.
CFGS = {
    "dense": GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2,
                       max_seq=32),
    "gqa": llama_config(vocab_size=64, dim=32, nheads=4, nlayers=2,
                        max_seq=32, kv_heads=2, ffn_hidden=48,
                        dtype=jnp.float32),
    "sliding": llama_config(vocab_size=64, dim=32, nheads=4, nlayers=2,
                            max_seq=32, kv_heads=2, ffn_hidden=48,
                            dtype=jnp.float32, sliding_window=6),
    "moe": GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=32,
                     moe_experts=4, moe_top_k=2, moe_every=2,
                     moe_capacity_factor=2.0),  # = E/top_k: no drops
}
FAMILIES = list(CFGS)
PROMPT, NEW = 5, 6  # chunk=4 < PROMPT: prefill genuinely chunks (2 slices)


def _init(name):
    cfg = CFGS[name]
    init = init_gpt_moe_params if cfg.moe_experts else init_gpt_params
    return init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def bundles():
    """Lazily-built per-family bundle: params, a 2-slot engine, the two
    staggered prompts, and the contiguous-cache ``generate()`` golden.
    Module-scoped so every test reuses the SAME compiled engine steps."""
    cache = {}

    def get(name):
        if name in cache:
            return cache[name]
        cfg = CFGS[name]
        params = _init(name)
        prompts = np.stack([
            np.asarray(jax.random.randint(
                jax.random.PRNGKey(10 + i), (PROMPT,), 0, cfg.vocab_size))
            for i in range(2)
        ]).astype(np.int32)
        want = np.asarray(jax.jit(
            lambda p, t: generate(p, t, cfg, max_new_tokens=NEW)
        )(params, jnp.asarray(prompts)))
        eng = ServingEngine(params, cfg, num_slots=2, block_size=4, chunk=4)
        cache[name] = {"cfg": cfg, "params": params, "prompts": prompts,
                       "want": want, "eng": eng}
        return cache[name]

    return get


@pytest.fixture()
def event_log():
    log = EventLog()
    set_default_event_log(log)
    yield log
    set_default_event_log(None)


def _drain(eng, max_ticks=500):
    eng.run_until_idle(max_ticks=max_ticks)


# --------------------------------------------------------------- allocator


def test_block_allocator():
    a = BlockAllocator(8)  # block 0 reserved
    assert a.n_usable == 7 and a.n_free == 7 and a.in_use == 0
    got = a.alloc(3)
    assert got is not None and len(got) == 3
    assert NULL_BLOCK not in got  # the NULL block is never handed out
    assert a.in_use == 3 and a.peak_in_use == 3
    assert a.alloc(5) is None  # over-ask: nothing partially allocated
    assert a.n_free == 4
    rest = a.alloc(4)
    assert a.n_free == 0 and a.utilization() == 1.0 and a.peak_in_use == 7
    a.free(got)
    assert a.n_free == 3 and a.peak_in_use == 7  # peak sticks
    with pytest.raises(ValueError):
        a.free([got[0]])  # double free
    with pytest.raises(ValueError):
        a.free([NULL_BLOCK])
    # LIFO reuse: the most recently freed block comes back first
    assert a.alloc(1) == [got[-1]]
    a.free(rest)
    with pytest.raises(ValueError):
        BlockAllocator(1)  # no room for the NULL block


def test_init_paged_kv_guards():
    cfg = CFGS["gqa"]
    with pytest.raises(ValueError, match="num_blocks"):
        init_paged_kv(cfg, 1, 4)
    with pytest.raises(ValueError, match="divisible"):
        init_paged_kv(cfg, 4, 4, axis_size=3)
    pool = init_paged_kv(cfg, 4, 4, quantized=True)
    q8, scale = pool["k"]
    assert q8.dtype == jnp.int8 and q8.shape == (2, 4, 2, 4, 8)
    assert scale.shape == q8.shape[:-1]


# ------------------------------------------------- paged parity (tentpole)


@pytest.mark.parametrize(
    "family",
    # moe demoted to slow (PR-19 budget payback): the staggered
    # admission regime is family-independent and held fast-tier by the
    # dense/gqa/sliding rows; the moe family keeps its own fast-tier
    # holder in test_moe_engine_token_bit_parity below
    [pytest.param("moe", marks=pytest.mark.slow)]
    + [f for f in FAMILIES if f != "moe"])
def test_paged_parity_staggered(bundles, family):
    """Bit parity under the engine's real regime: request B is admitted
    while request A is already decoding (mixed prefill/decode ticks,
    different block tables, per-slot offsets) — and every emitted token
    still equals the contiguous-cache ``generate()`` row."""
    b = bundles(family)
    eng = b["eng"]
    eng.reset_metrics()
    r0 = eng.submit(Request(b["prompts"][0].tolist(), NEW))
    eng.step()  # A: first prefill slice
    eng.step()  # A: final slice + first token (TTFT)
    r1 = eng.submit(Request(b["prompts"][1].tolist(), NEW))
    _drain(eng)
    for rid, row in ((r0, 0), (r1, 1)):
        f = eng.finished[rid]
        assert f["reason"] == "max_tokens" and f["new_tokens"] == NEW
        np.testing.assert_array_equal(
            f["tokens"], b["want"][row],
            err_msg=f"{family}: paged decode diverged from generate()")
    # compile-once evidence: however the ticks interleaved, exactly one
    # signature per phase
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    # retirement returned every block to the pool
    assert all(a.n_free == a.n_usable for a in eng._allocs)
    assert s["requests"]["completed"] == 2
    assert s["ttft_s"] and s["tpot_s"]


def test_moe_engine_token_bit_parity(bundles):
    """Tier-1's holder of the MoE family in the engine (the [moe] rows
    around it are slow-tier): the ragged no-drop serving path emits tokens
    BIT-equal to contiguous ``generate()`` under staggered admission, one
    decode signature."""
    b = bundles("moe")
    eng = b["eng"]
    eng.reset_metrics()
    r0 = eng.submit(Request(b["prompts"][0].tolist(), NEW))
    eng.step()
    eng.step()
    r1 = eng.submit(Request(b["prompts"][1].tolist(), NEW))
    _drain(eng)
    for rid, row in ((r0, 0), (r1, 1)):
        np.testing.assert_array_equal(
            eng.finished[rid]["tokens"], b["want"][row],
            err_msg="moe: engine diverged from generate()")
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1
    assert s["requests"]["completed"] == 2


def test_moe_engine_summary_block(bundles):
    """serving_summary()['moe'] is the live expert-load block the
    router's load index consumes: real per-expert routed-token counts,
    normalized entropy, no drops at cf=E/top_k — and it validates."""
    from torchdistpackage_tpu.obs.report import _validate_serving

    b = bundles("moe")
    eng, cfg = b["eng"], b["cfg"]
    eng.reset_metrics()
    for p in b["prompts"]:
        eng.submit(Request(p.tolist(), NEW))
    _drain(eng)
    s = eng.serving_summary()
    assert _validate_serving(s) == []
    moe = s["moe"]
    assert "dispatch" not in moe  # one serving path: nothing to name
    assert moe["num_experts"] == cfg.moe_experts
    assert len(moe["expert_tokens"]) == cfg.moe_experts
    assert sum(moe["expert_tokens"]) > 0  # stats actually flowed
    assert moe["imbalance"] >= 0.0
    assert 0.0 <= moe["load_entropy"] <= 1.0
    assert moe["dropped_token_rate"] == 0.0  # cf = E/top_k: no drops
    assert eng.moe_imbalance() == pytest.approx(moe["imbalance"])


@pytest.mark.parametrize(
    "family",
    # dense demoted to slow (PR-12 budget payback): the mesh/table
    # plumbing it exercises is family-independent and held fast-tier by
    # the gqa/sliding/moe rows; dense single-device parity stays fast-tier
    # above, and the pallas-vs-gather engine pair in
    # test_paged_attention.py re-proves the dense-attention math per PR.
    # moe joins it (PR-19 payback): the mesh/table plumbing is held by
    # the fast gqa/sliding rows; the moe family's single-device engine
    # parity is test_moe_engine_token_bit_parity below
    [pytest.param(f, marks=pytest.mark.slow) for f in ("dense", "moe")]
    + [f for f in FAMILIES if f not in ("dense", "moe")])
def test_tp_dp_paged_parity(bundles, family, devices8):
    """The same goldens on a tensor=2 x data=2 mesh: KV heads + vocab
    shard over 'tensor' exactly as training, slots + block pool split over
    'data' — four requests, two per data group, all bit-equal to the
    serial ``generate()``."""
    b = bundles(family)
    cfg = b["cfg"]
    tpc.setup_process_groups(
        [("data", 2), ("tensor", 2)], devices=devices8[:4])
    mesh = tpc.get_view()
    spec_fn = gpt_moe_param_specs if cfg.moe_experts else gpt_param_specs
    specs = spec_fn(cfg, tp_axis="tensor")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        b["params"], specs)
    eng = ServingEngine(sharded, cfg, num_slots=4, block_size=4, chunk=4,
                        mesh=mesh, axis="tensor", dp_axis="data")
    assert eng.dp == 2 and eng.slots_per_group == 2
    prompts = np.concatenate([b["prompts"], b["prompts"][::-1]])
    rids = [eng.submit(Request(p.tolist(), NEW)) for p in prompts]
    _drain(eng)
    want = np.concatenate([b["want"], b["want"][::-1]])
    for rid, row in zip(rids, range(4)):
        np.testing.assert_array_equal(
            eng.finished[rid]["tokens"], want[row],
            err_msg=f"{family}: tp_dp paged decode diverged")
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1


# ------------------------------------------------------- engine lifecycle


def test_chunked_prefill_never_stalls_decode(bundles, event_log):
    """A long prompt admitted mid-decode advances one chunk per tick while
    the in-flight request keeps decoding EVERY tick (the whole point of
    chunked prefill)."""
    b = bundles("dense")
    eng = b["eng"]
    eng._ev = event_log  # the module-scoped engine captured the old default
    eng.reset_metrics()
    eng.submit(Request(b["prompts"][0].tolist(), NEW))
    eng.step()
    eng.step()  # slot 0 now decoding
    long_prompt = np.tile(b["prompts"][1], 4)[:17]  # 5 chunks of 4
    eng.submit(Request(long_prompt.tolist(), 2))
    decoded_during_prefill = 0
    for _ in range(4):  # the long prefill occupies >= 4 more ticks
        out = eng.step()
        if eng._slots[1].state == "prefill":
            decoded_during_prefill += out["decode_slots"]
    assert decoded_during_prefill >= 2, (
        "in-flight decode stalled while the long prompt prefilled")
    _drain(eng)
    chunks = event_log.of_kind("prefill_chunk")
    assert len(chunks) >= 5
    # lifecycle events carry the request story
    admitted = event_log.of_kind("request_admitted")
    retired = event_log.of_kind("request_retired")
    assert len(admitted) == 2 and len(retired) == 2
    assert {e["reason"] for e in retired} == {"max_tokens"}
    assert all(e["ttft_s"] is not None for e in retired)


def test_eos_and_queue_backpressure(bundles):
    b = bundles("dense")
    eng = b["eng"]
    eng.reset_metrics()
    first_tok = int(b["want"][0, PROMPT])  # greedy first generated token
    rid = eng.submit(Request(b["prompts"][0].tolist(), NEW,
                             eos_id=first_tok))
    # 3 requests into 2 slots: the third queues until a slot frees
    others = [eng.submit(Request(b["prompts"][1].tolist(), 3))
              for _ in range(2)]
    eng.step()
    assert len(eng.queue) == 1  # back-pressure: no slot for request 3 yet
    _drain(eng)
    f = eng.finished[rid]
    assert f["reason"] == "eos" and f["new_tokens"] == 1
    np.testing.assert_array_equal(
        f["tokens"], np.concatenate([b["prompts"][0], [first_tok]]))
    for r in others:
        assert eng.finished[r]["reason"] == "max_tokens"
    assert eng.n_busy == 0 and len(eng.queue) == 0


def test_per_slot_sampling_isolated_and_reproducible(bundles):
    """A sampled request must not perturb its greedy neighbor (per-slot
    keys/params), and the same seed must replay the same tokens."""
    b = bundles("dense")
    eng = b["eng"]

    def serve_pair(seed):
        eng.reset_metrics()
        g = eng.submit(Request(b["prompts"][0].tolist(), NEW))
        s = eng.submit(Request(b["prompts"][1].tolist(), NEW,
                               temperature=1.0, top_k=16, top_p=0.9,
                               seed=seed))
        _drain(eng)
        return (eng.finished[g]["tokens"], eng.finished[s]["tokens"])

    greedy_a, sampled_a = serve_pair(7)
    greedy_b, sampled_b = serve_pair(7)
    _, sampled_c = serve_pair(8)
    # greedy row: bit-equal to generate() despite the sampled neighbor
    np.testing.assert_array_equal(greedy_a, b["want"][0])
    np.testing.assert_array_equal(greedy_b, b["want"][0])
    np.testing.assert_array_equal(sampled_a, sampled_b)  # seed replays
    assert not np.array_equal(sampled_a, sampled_c)  # seed matters
    assert np.all(sampled_a[PROMPT:] < b["cfg"].vocab_size)


# ----------------------------- the sampler's cost follows what its rows ask


def _straight_sample(logits, keys, temperature, top_k, top_p):
    """`_slot_sample` as it was before its ``cond``: every row through the
    filter chain and the draw, the greedy rows' results thrown away."""
    x = logits.astype(jnp.float32)
    greedy = jnp.argmax(x, axis=-1).astype(jnp.int32)
    xs = _filtered_logits(x, temperature, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(keys, xs).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _sampler_inputs(temps):
    n, V = len(temps), 64
    logits = (4.0 * jax.random.normal(jax.random.PRNGKey(3), (n, V))).astype(
        jnp.bfloat16)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
    return (logits, keys, jnp.asarray(temps, jnp.float32),
            jnp.asarray([V, 16, V, 5][:n], jnp.int32),
            jnp.asarray([1.0, 0.9, 0.5, 1.0][:n], jnp.float32))


@pytest.mark.parametrize("temps", [(0.0, 0.0, 0.0, 0.0), (0.0, 0.8, 0.0, 0.0),
                                   (0.7, 0.0, 1.3, 0.0), (0.9, 0.9, 0.9, 0.9)],
                         ids=["all_greedy", "one_sampling", "half", "all"])
def test_slot_sample_returns_the_straight_line_samplers_tokens(temps):
    """Whatever the mix of rows: the tokens of the body that filtered and
    drew for every row.  A greedy row is the f32 argmax, always."""
    args = _sampler_inputs(temps)
    got = np.asarray(_slot_sample(*args))
    np.testing.assert_array_equal(got, np.asarray(_straight_sample(*args)))
    greedy = np.argmax(np.asarray(args[0].astype(jnp.float32)), axis=-1)
    rows = np.asarray(temps) <= 0.0
    np.testing.assert_array_equal(got[rows], greedy[rows])
    assert got.dtype == np.int32


def _prims(jaxpr, skip=()):
    """Every primitive of ``jaxpr`` and of what its equations carry
    (pjit bodies, branches), bar the equations named in ``skip``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in skip:
            continue
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _prims(sub, skip)


def test_slot_sample_sorts_only_inside_its_cond():
    """The sort (and the draw) are one branch of the function's one
    ``cond``; the other branch and everything outside it hold neither, so
    an all-greedy call executes no sort."""
    jaxpr = jax.make_jaxpr(_slot_sample)(*_sampler_inputs((0.0,) * 4)).jaxpr
    outside = list(_prims(jaxpr, skip=("cond",)))
    assert "argmax" in outside and "sort" not in outside
    assert not any("random" in p or "threefry" in p for p in outside)
    (cond,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    greedy, draw = (list(_prims(b.jaxpr)) for b in cond.params["branches"])
    assert "sort" in draw and "sort" not in greedy  # index 0: the false branch
    assert not greedy, greedy  # hands the argmax through, computes nothing
    # the [B, V] operand of the cond is the logits as the model left them
    # (bf16 here), not an f32 copy: see the comment in `_slot_sample`
    wide = [v.aval.dtype for v in cond.invars if v.aval.shape == (4, 64)]
    assert wide == [jnp.bfloat16], wide


def _sampled_rows(kind):
    return [r[5]["sampled_rows"] for r in spans.snapshot()
            if r[2] == "tdp:engine." + kind]


SAMPLING = dict(temperature=1.0, top_k=16, top_p=0.9)


def _discipline(b, run_ahead):
    """The bundle's engine as it is constructed (``run_ahead``), or a serial
    one of the same geometry, built once a module."""
    if run_ahead:
        assert b["eng"].run_ahead
        return b["eng"]
    if "serial" not in b:
        b["serial"] = ServingEngine(b["params"], b["cfg"], num_slots=2,
                                    block_size=4, chunk=4, run_ahead=False)
    return b["serial"]


DISCIPLINES = pytest.mark.parametrize("run_ahead", [False, True],
                                      ids=["serial", "run_ahead"])


@DISCIPLINES
def test_one_sampling_row_among_greedy_rows(bundles, run_ahead):
    """A batch with ONE sampling row: its tokens are those it draws when
    served alone from the same seed, its greedy neighbour's are the
    all-greedy run's, and ``sampled_rows`` on the spans says which calls
    took the sampler's drawing branch: 0 on the all-greedy run's.  With
    ``run_ahead`` the host sees a retirement one decode call later: the
    sampling slot sits the call after its last out, its temperature still
    among the call's (no call is built for a slot that sits out alone)."""
    b = bundles("dense")
    eng = _discipline(b, run_ahead)
    lag = int(run_ahead)

    def serve(*reqs):
        eng.reset_metrics()
        spans.clear()
        rids = [eng.submit(r) for r in reqs]
        _drain(eng)
        counts = _sampled_rows("prefill"), _sampled_rows("decode")
        return [eng.finished[r]["tokens"] for r in rids], counts

    p0, p1 = (p.tolist() for p in b["prompts"])
    (g, s), (pre, dec) = serve(Request(p0, NEW + 3),
                               Request(p1, NEW, seed=7, **SAMPLING))
    # the greedy request outlives the sampling one: the last calls are
    # all-greedy again
    assert pre == [1, 1] and dec[:NEW - 1 + lag] == [1] * (NEW - 1 + lag)
    assert dec[NEW - 1 + lag:] == [0] * (3 - lag)
    (alone,), (pre, dec) = serve(Request(p1, NEW, seed=7, **SAMPLING))
    assert pre == [1, 1] and dec == [1] * (NEW - 1)
    np.testing.assert_array_equal(s, alone)
    (g0, g1), (pre, dec) = serve(Request(p0, NEW + 3), Request(p1, NEW))
    assert pre == [0, 0] and set(dec) == {0} and len(dec) == NEW + 2
    np.testing.assert_array_equal(g, g0)
    np.testing.assert_array_equal(g0[:PROMPT + NEW], b["want"][0])
    np.testing.assert_array_equal(g1, b["want"][1])
    assert not np.array_equal(s, g1)  # the draw was a draw


@DISCIPLINES
def test_a_late_samplers_draws_do_not_depend_on_the_ticks_before(bundles,
                                                                 run_ahead):
    """The key stream: a request that starts sampling after N all-greedy
    ticks draws what it draws when its neighbour sampled throughout, and
    what it draws alone.  And a greedy slot's key advances on every call
    it is in, though no call of its run drew from it (with ``run_ahead``
    the host holds the key of the last call it has BOOKED, one behind)."""
    b = bundles("dense")
    eng = _discipline(b, run_ahead)
    p0, p1 = (p.tolist() for p in b["prompts"])
    N = 4

    def late(**neighbour):
        eng.reset_metrics()
        spans.clear()
        eng.submit(Request(p0, 20, seed=3, **neighbour))
        for _ in range(N):
            eng.step()
        before = _sampled_rows("prefill") + _sampled_rows("decode")
        neighbour_at_n = eng._keys[0].copy(), len(eng._slots[0].generated)
        rid = eng.submit(Request(p1, NEW, seed=7, **SAMPLING))
        _drain(eng)
        return eng.finished[rid]["tokens"], before, neighbour_at_n

    after_greedy, before, (key, emitted) = late()
    assert before == [0] * len(before) and len(before) >= N
    # one split for every token the slot emitted (the last prefill slice's,
    # then a decode call's each), all of them on the greedy branch
    want = jax.random.PRNGKey(3)
    for _ in range(emitted):
        want = jax.random.split(want, 2)[0]
    assert emitted == N - 1 - int(run_ahead)
    np.testing.assert_array_equal(key, np.asarray(want))
    after_sampling, before, _ = late(**SAMPLING)
    assert before == [1] * len(before)
    np.testing.assert_array_equal(after_greedy, after_sampling)
    eng.reset_metrics()
    rid = eng.submit(Request(p1, NEW, seed=7, **SAMPLING))
    _drain(eng)
    np.testing.assert_array_equal(after_greedy, eng.finished[rid]["tokens"])


def test_submit_guards(bundles):
    b = bundles("dense")
    eng = b["eng"]
    with pytest.raises(ValueError, match="max_ctx"):
        eng.submit(Request([1] * 30, 10))  # > max_ctx=32
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request([1], 0)
    with pytest.raises(ValueError, match="temperature"):
        Request([1], 1, temperature=-0.5)
    with pytest.raises(ValueError, match="empty"):
        Request([], 1)
    with pytest.raises(ValueError, match="need a mesh"):
        ServingEngine(b["params"], b["cfg"], axis="tensor")
    # the serving MoE path is one path: the option went with the fused
    # kernel (PR 28)
    with pytest.raises(TypeError, match="moe_dispatch"):
        ServingEngine(b["params"], b["cfg"], moe_dispatch="gather")
    import dataclasses
    cp = dataclasses.replace(b["cfg"], attn_impl="ring")
    # training-side ring/Ulysses still refuses — serving-side CP is the
    # engine's own cp_axis= (ring paged prefill, tests/test_cp_prefill.py)
    with pytest.raises(NotImplementedError, match="cp_axis"):
        ServingEngine(b["params"], cp)


# ------------------------------------------------- int8 KV-quant coverage


@pytest.mark.slow
def test_kv_quant_sliding_window_decode():
    """Satellite: the _kv_quant cache path vs the fp cache, on the
    sliding-window family (window masking composes with the per-vector
    scales — previously untested).  At these seeds the int8 cache keeps
    greedy decode token-identical; prefill logits stay within quant
    tolerance.

    Slow tier (PR-19 budget payback): fast-tier holders are
    test_paged_parity_staggered[sliding] (window masking under the
    engine) and test_generate.py::test_int8_kv_cache_decode (the quant
    cache math itself)."""
    from torchdistpackage_tpu.models.generate import (
        _full_logits, forward_cached, init_kv_cache)

    cfg = CFGS["sliding"]
    params = _init("sliding")
    prompt = jax.random.randint(
        jax.random.PRNGKey(3), (2, 10), 0, cfg.vocab_size)  # > window=6
    want = jax.jit(
        lambda p, t: generate(p, t, cfg, max_new_tokens=NEW))(params, prompt)
    got = jax.jit(
        lambda p, t: generate(p, t, cfg, max_new_tokens=NEW, kv_quant=True)
    )(params, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # logits tolerance: one cached prefill, fp vs int8 cache
    cache_f = init_kv_cache(cfg, 2, 12)
    cache_q = init_kv_cache(cfg, 2, 12, quantized=True)
    _, lf = forward_cached(params, prompt, cfg, cache_f, 0)
    _, lq = forward_cached(params, prompt, cfg, cache_q, 0)
    rel = float(jnp.linalg.norm(lq - lf) / jnp.linalg.norm(lf))
    assert rel < 0.02, rel


@pytest.mark.slow
def test_kv_quant_paged_engine_parity(bundles):
    """The engine's quantized block pool (paged_write runs the same
    _kv_quant per-vector scheme) serves the sliding-window family
    token-identically to the fp golden at these seeds.

    Slow-tier since PR 12 (budget payback): the fast-tier version of this
    claim now rides test_paged_attention.py's int8 PALLAS engine golden —
    same family, same quantized pool and paged_write path, through the
    fused-dequant kernel that is the TPU default — with the gather-quant
    attend math still fast-tier as the kernel test's oracle."""
    b = bundles("sliding")
    eng = ServingEngine(b["params"], b["cfg"], num_slots=2, block_size=4,
                        chunk=4, kv_quant=True)
    rids = [eng.submit(Request(p.tolist(), NEW)) for p in b["prompts"]]
    _drain(eng)
    for rid, row in zip(rids, range(2)):
        np.testing.assert_array_equal(
            eng.finished[rid]["tokens"], b["want"][row],
            err_msg="int8 paged decode diverged beyond quant tolerance")


def test_paged_write_quant_bit_parity():
    """paged_write on a quantized pool must store BIT-identical (q8,
    scale) payloads to _kv_quant of the raw values — the scatter cannot
    perturb the quantization."""
    from torchdistpackage_tpu.models.generate import _kv_quant
    from torchdistpackage_tpu.serving import gather_kv, paged_write

    rng = jax.random.PRNGKey(0)
    val = jax.random.normal(rng, (1, 2, 6, 8), jnp.float32)  # B,Hkv,S,hd
    pool = (jnp.zeros((4, 2, 4, 8), jnp.int8), jnp.ones((4, 2, 4), jnp.float32))
    tables = jnp.asarray([[1, 2, 3]], jnp.int32)
    pool = paged_write(pool, val, jnp.asarray([0]), tables=tables)
    g8, gs = gather_kv(pool, tables)
    want_q, want_s = _kv_quant(val.transpose(0, 2, 1, 3))  # [B,S,Hkv,hd]
    np.testing.assert_array_equal(
        np.asarray(g8[0, :, :6]), np.asarray(want_q[0].transpose(1, 0, 2)))
    np.testing.assert_array_equal(
        np.asarray(gs[0, :, :6]), np.asarray(want_s[0].T))


# ------------------------------------------------ compact prefill batches


def _full_width_calls(eng):
    """The prefill call as wide as the decode batch, built from the same
    ``_step_fn``: tokens ``[num_slots, chunk]``, every slot's own sampling
    row and key, the tables of slots that are not prefilling masked to the
    NULL block.  Stands in for ``eng._prefill_calls``: what every row the
    compact batch leaves out would have computed, nobody read."""
    def calls(pre):
        B, C = eng.num_slots, eng.chunk
        _, tables = eng._masked("prefill")
        tokens = np.zeros((B, C), np.int32)
        offsets = np.zeros(B, np.int32)
        last_idx = np.zeros(B, np.int32)
        for i in pre:
            s = eng._slots[i]
            sl = s.prompt[s.off:s.off + C]
            tokens[i, :len(sl)] = sl
            offsets[i] = s.off
            last_idx[i] = min(len(s.prompt) - 1 - s.off, C - 1)
        out = eng._step_fn(eng.params, eng.cache, tokens, tables, offsets,
                           last_idx, eng._samp(), eng._keys)
        eng.cache = out[0]
        # what _prefill_calls hands back: the calls' fetch
        return lambda: (np.asarray(out[1]), np.asarray(out[2]), {})

    return calls


SLOTS4 = 4


@pytest.fixture(scope="module")
def compact_pairs():
    """Per family and width: an engine whose prefill calls carry ``width``
    of its 4 slots, and one whose prefill is the full-width reference call;
    compiled once a module."""
    cache = {}

    def get(name, width):
        if (name, width) not in cache:
            cfg, params = CFGS[name], _init(name)
            eng, ref = (ServingEngine(params, cfg, num_slots=SLOTS4,
                                      block_size=4, chunk=4) for _ in "ab")
            eng.prefill_width = width
            ref._prefill_calls = _full_width_calls(ref)
            cache[name, width] = (cfg, eng, ref)
        return cache[name, width]

    return get


def _prompt(cfg, seed, n):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size)).tolist()


def _serve_wave(eng, cfg, n_prefilling):
    """``n_prefilling`` prompts admitted in ONE tick while the other slots
    decode; returns every request's tokens and the attrs of the wave tick's
    ``tdp:engine.prefill`` span."""
    eng.reset_metrics()
    early = [eng.submit(Request(_prompt(cfg, 40 + i, 3 + i), NEW + 4))
             for i in range(SLOTS4 - n_prefilling)]
    for _ in range(2 if early else 0):
        eng.step()  # the early ones are decoding now
    wave = [eng.submit(Request(_prompt(cfg, 50 + i, 2 + 2 * i), NEW))
            for i in range(n_prefilling)]
    spans.clear()
    eng.step()
    pre = [r[5] for r in spans.snapshot() if r[2] == "tdp:engine.prefill"]
    _drain(eng)  # (the reference call opens no span)
    return [eng.finished[r]["tokens"] for r in early + wave], pre


def _serve_staggered(eng, cfg):
    """Prompts longer than a chunk (3, 2 and 3 slices) admitted on
    successive ticks: the prefilling set changes every tick."""
    eng.reset_metrics()
    rids = []
    for i, n in enumerate((9, 6, 11)):
        rids.append(eng.submit(Request(_prompt(cfg, 60 + i, n), NEW)))
        eng.step()
    _drain(eng)
    return [eng.finished[r]["tokens"] for r in rids], []


# width 1: a call never mixes a slot's row with padding; width 2: with 1 or
# 3 slots prefilling the last call carries one of each (the MoE family's
# padding rows are routed like any other, so this is its case)
@pytest.mark.parametrize("family,width",
                         [("dense", 1), ("dense", 2), ("moe", 2)])
@pytest.mark.parametrize("scenario", [1, 3, SLOTS4, "staggered"])
def test_compact_prefill_matches_full_width_call(compact_pairs, family, width,
                                                 scenario):
    """The compact prefill batch drops only rows whose output nobody read:
    greedy tokens equal, token for token, those of the full-width call
    through the same ``_step_fn``, with 1, 3 and all slots prefilling in a
    tick and with multi-chunk prompts admitted on different ticks."""
    cfg, eng, ref = compact_pairs(family, width)
    serve = (_serve_staggered if scenario == "staggered"
             else lambda e, c: _serve_wave(e, c, scenario))
    got, wave_spans = serve(eng, cfg)
    want, _ = serve(ref, cfg)
    assert len(got) == len(want) >= 3
    for g, w in zip(got, want):
        np.testing.assert_array_equal(
            g, w, err_msg=f"{family} width {width} {scenario}")
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    assert s["prefill_calls"] >= s["prefill_chunks"] > 0
    assert len(wave_spans) == (scenario != "staggered")
    for attrs in wave_spans:
        calls = -(-scenario // width)
        assert attrs["calls"] == calls
        assert attrs["rows"] == calls * width * eng.chunk
        assert attrs["tokens"] == sum(
            min(eng.chunk, 2 + 2 * i) for i in range(scenario))
    if family == "moe":
        assert sum(s["moe"]["expert_tokens"]) > 0  # absorbed once a call


def test_compact_prefill_unequal_dp_groups(bundles, devices8):
    """Under ``dp_axis`` the compact batch is ``[dp * W, chunk]``, group
    g's rows at ``g*W``: three prompts land two in group 0 and one in group
    1, so the fuller group sets the number of calls and the other's row is
    padding in the later ones.  Tokens equal the serial ``generate()``."""
    b = bundles("gqa")
    cfg = b["cfg"]
    tpc.setup_process_groups(
        [("data", 2), ("tensor", 2)], devices=devices8[:4])
    mesh = tpc.get_view()
    sharded = jax.tree.map(
        lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)),
        b["params"], gpt_param_specs(cfg, tp_axis="tensor"))
    eng = ServingEngine(sharded, cfg, num_slots=4, block_size=4, chunk=4,
                        mesh=mesh, axis="tensor", dp_axis="data")
    assert eng.prefill_width <= eng.slots_per_group == 2
    eng.prefill_width = W = 1
    rows = (0, 1, 0)
    rids = [eng.submit(Request(b["prompts"][r].tolist(), NEW)) for r in rows]
    spans.clear()
    eng.step()
    assert [s.state for s in eng._slots] == ["prefill"] * 3 + ["free"]
    (pre,) = [r for r in spans.snapshot() if r[2] == "tdp:engine.prefill"]
    calls = -(-2 // W)  # group 0 holds two of the three
    assert pre[5]["calls"] == calls
    assert pre[5]["rows"] == calls * 2 * W * eng.chunk
    assert pre[5]["tokens"] == 3 * eng.chunk
    _drain(eng)
    for rid, r in zip(rids, rows):
        np.testing.assert_array_equal(
            eng.finished[rid]["tokens"], b["want"][r],
            err_msg="compact prefill under dp_axis diverged")
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    assert (s["prefill_chunks"], s["prefill_calls"]) == (2, 2 * calls)


# -------------------------------------- the default width, family by family

SLOTS8 = 8


def _hybrid_toy(mod):
    """A hybrid family at toy width in float32, from the TOY configuration
    of the family's own test file: (the program's config, weights)."""
    s = mod.family.shape(mod.TOY, 64)
    cfg = dataclasses.replace(mod.family.program_config(mod.TOY, 64),
                              dtype=jnp.float32)
    return cfg, jax.tree.map(lambda a: a.astype(jnp.float32),
                             mod.make_weights(s, 7))


#: every family the engine serves: dense GQA, routed experts, and the state
#: model's four shapes (recurrent state + held experts, latent attention +
#: held gated experts, convolved attention with a tail a slot, recurrent
#: state with a dense MLP and no expert anywhere)
SERVED = {"gqa": None, "moe": None, "state": "test_hybrid",
          "latent": "test_sarvam_mla", "cca": "test_zaya",
          "ssm": "test_granite_hybrid"}
#: the width each family's engine gives itself: 2 where the model has
#: expert layers, 1 where it has none
WIDTH = {"gqa": 1, "moe": 2, "state": 2, "latent": 2, "cca": 2, "ssm": 1}


@pytest.fixture(scope="module")
def width_pairs():
    """Per served family: an engine of 8 slots whose prefill calls have the
    DEFAULT width, and the full-width reference, one call that carries all
    8 slots; compiled once a module."""
    cache = {}

    def get(name):
        if name not in cache:
            if SERVED[name] is None:
                cfg, params, kw = CFGS[name], _init(name), dict(
                    block_size=4, chunk=4)
            else:
                cfg, params = _hybrid_toy(importlib.import_module(SERVED[name]))
                kw = dict(block_size=8, chunk=8, max_ctx=64,
                          attn_impl="gather")
            eng, ref = (ServingEngine(params, cfg, num_slots=SLOTS8, **kw)
                        for _ in "ab")
            assert eng.prefill_width == WIDTH[name] < SLOTS8
            ref.prefill_width = SLOTS8
            cache[name] = (cfg, eng, ref)
        return cache[name]

    return get


def _wave_len(i, C):
    """Prompt i of a first wave: 2 tokens up to just under two chunks."""
    return 2 + (i * (2 * C - 3)) // 7


def _serve_first_wave(eng, cfg):
    """A first wave: as many prompts as slots, of one and of two chunks,
    admitted in ONE tick.  Steps until nothing prefills, checks that every
    slot then decodes over a clean pool, and returns every request's tokens
    and each prefilling tick's ``tdp:engine.prefill`` attrs."""
    eng.reset_metrics()
    C = eng.chunk
    rids = [eng.submit(Request(_prompt(cfg, 70 + i, _wave_len(i, C)), NEW + 3))
            for i in range(SLOTS8)]
    spans.clear()
    eng.step()
    eng.step()
    assert [s.state for s in eng._slots] == ["decode"] * SLOTS8
    assert eng.audit(heal=False)["ok"]
    pre = [r[5] for r in spans.snapshot() if r[2] == "tdp:engine.prefill"]
    _drain(eng)
    assert eng.audit(heal=False)["ok"]
    return [eng.finished[r]["tokens"] for r in rids], pre


def _serve_one_by_one(eng, cfg):
    """The steady case: prompts of one to three chunks admitted on
    successive ticks, so a call carries one or two slots and padding."""
    eng.reset_metrics()
    C, rids = eng.chunk, []
    for i, n in enumerate((2 * C + 1, C, C + 3, 3, 3 * C - 1)):
        rids.append(eng.submit(Request(_prompt(cfg, 80 + i, n), NEW)))
        eng.step()
    _drain(eng)
    return [eng.finished[r]["tokens"] for r in rids], []


@pytest.mark.parametrize("family", list(SERVED))
@pytest.mark.parametrize("scenario", ["first_wave", "one_by_one"])
def test_default_width_matches_full_width_call(width_pairs, family, scenario):
    """At the width the engine gave itself every served family's greedy
    tokens equal, token for token, those of an engine whose one prefill
    call carries every slot: a first wave of ``num_slots`` prompts
    (``ceil(n / W)`` calls a tick, then every slot decoding, the pool's
    audit clean) and prompts admitted one at a time."""
    cfg, eng, ref = width_pairs(family)
    serve = _serve_first_wave if scenario == "first_wave" else _serve_one_by_one
    got, wave = serve(eng, cfg)
    want, full = serve(ref, cfg)
    assert len(got) == len(want) >= 5
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w, err_msg=f"{family} {scenario}")
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    if scenario == "first_wave":
        W, C = eng.prefill_width, eng.chunk
        two_chunks = sum(_wave_len(i, C) > C for i in range(SLOTS8))
        assert [a["calls"] for a in wave] == [
            -(-SLOTS8 // W), -(-two_chunks // W)]
        assert [a["rows"] for a in wave] == [a["calls"] * W * C for a in wave]
        assert [a["calls"] for a in full] == [1, 1]
        assert s["prefill_calls"] == sum(a["calls"] for a in wave)


@pytest.mark.parametrize("family", list(SERVED))
def test_the_engine_takes_its_prefill_width_from_its_models_layers(
        width_pairs, family):
    """The width is the engine's own choice at construction, from one thing
    it can see of the model it was handed: 2 with expert layers, 1 without
    (a dense model, a state model under a dense MLP).  Never more than a dp
    group's slots, and ONE prefill signature whatever arrives in a tick."""
    cfg, eng, _ = width_pairs(family)
    assert bool(cfg.moe_experts) == (WIDTH[family] == 2)
    assert eng.prefill_width == WIDTH[family] <= eng.slots_per_group
    eng.reset_metrics()
    C = eng.chunk
    rids = [eng.submit(Request(_prompt(cfg, 60 + i, n), NEW))
            for i, n in enumerate((3, C + 2, 2 * C))]   # three in one tick
    spans.clear()
    eng.step()
    (pre,) = [r[5] for r in spans.snapshot() if r[2] == "tdp:engine.prefill"]
    assert pre["calls"] == -(-3 // WIDTH[family])
    assert pre["rows"] == pre["calls"] * WIDTH[family] * C
    eng.submit(Request(_prompt(cfg, 66, 4), NEW))   # one alone, steady
    _drain(eng)
    assert all(eng.finished[r]["new_tokens"] == NEW for r in rids)
    s = eng.serving_summary()
    assert s["prefill_signatures"] == s["decode_signatures"] == 1


def test_the_prefill_width_never_exceeds_a_groups_slots():
    """An engine of ONE slot carries one slot's rows whatever its model."""
    cfg, params = CFGS["moe"], _init("moe")
    assert cfg.moe_experts and PREFILL_WIDTH_EXPERTS > 1
    eng = ServingEngine(params, cfg, num_slots=1, block_size=4, chunk=4)
    assert eng.prefill_width == eng.slots_per_group == 1


def test_a_ticks_prefill_calls_are_dispatched_back_to_back(width_pairs,
                                                           monkeypatch):
    """A tick with n > W prefilling slots makes ``ceil(n / W)`` dispatches
    of the one signature and the host asks for no result (no
    ``block_until_ready``, no read of a call's output) until the last is
    dispatched: the pool is donated and chained call to call, so a queued
    call holds its small inputs only."""
    cfg, eng, _ = width_pairs("gqa")
    eng.reset_metrics()
    order = []

    class Out:
        """A call's output that notes when the host reads it."""

        def __init__(self, value, call):
            self.value, self.call = value, call

        def __array__(self, *a, **kw):
            order.append(("read", self.call))
            return np.asarray(self.value)

    real = eng._dispatch

    def dispatch(fn, args):
        out = real(fn, args)
        if args[0].shape[1] == 1:  # the decode call
            return out
        call = sum(1 for kind, _ in order if kind == "call")
        order.append(("call", call))
        return tuple(Out(o, call) for o in out)

    monkeypatch.setattr(eng, "_dispatch", dispatch)
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: order.append(("wait", None)) or x)
    n = SLOTS8 - 1
    rids = [eng.submit(Request(_prompt(cfg, 90 + i, 2 + i % 3), NEW))
            for i in range(n)]
    eng.step()
    calls = -(-n // eng.prefill_width)
    assert calls >= 2
    assert order[:calls] == [("call", c) for c in range(calls)]
    assert {kind for kind, _ in order[calls:]} == {"read"}
    assert {c for _, c in order[calls:]} == set(range(calls))
    monkeypatch.undo()
    _drain(eng)
    assert all(eng.finished[r]["new_tokens"] == NEW for r in rids)
    assert eng.serving_summary()["prefill_signatures"] == 1


# ----------------------------------------------------------------- report


def test_serving_summary_validates(bundles):
    """The engine's summary is exactly the RUNREPORT ``serving`` section:
    it must pass the validator, and the validator must actually bite."""
    from torchdistpackage_tpu.obs.report import _validate_serving

    b = bundles("dense")
    eng = b["eng"]
    eng.reset_metrics()
    for p in b["prompts"]:
        eng.submit(Request(p.tolist(), NEW))
    _drain(eng)
    s = eng.serving_summary()
    assert _validate_serving(s) == []
    assert s["tokens_per_sec"] > 0
    assert 0.0 < s["slot_occupancy"]["mean"] <= 1.0
    assert 0.0 < s["kv_pool"]["mean_utilization"] <= 1.0
    assert 0.0 < s["kv_pool"]["peak_utilization"] <= 1.0
    assert s["decode_batch_mean"] > 0

    # the validator rejects broken sections
    assert _validate_serving("nope")
    bad = dict(s, tokens_per_sec=-1.0)
    assert any("tokens_per_sec" in e for e in _validate_serving(bad))
    bad = dict(s, slot_occupancy={"mean": 1.5})
    assert any("slot_occupancy" in e for e in _validate_serving(bad))
    bad = dict(s, ttft_s={})
    assert any("ttft_s" in e for e in _validate_serving(bad))
