"""Serving fast path: refcounted prefix cache + copy-on-write blocks +
static-k speculative decoding (PR 10).

The load-bearing claims, asserted against goldens / the event timeline:

- a warm shared-prefix admission maps resident blocks instead of
  re-prefilling (prefill ticks drop, ``prefix_hit`` event) and still
  emits tokens BIT-equal to the cold ``generate()`` golden — including
  the whole-prompt-cached case, which copy-on-writes its last block
  (``block_cow``), and with TWO concurrent writers COWing the same
  source block;
- sharing never breaks block conservation: retire/preempt/cancel on a
  shared block decrement rather than free (the co-owner keeps decoding
  bit-exactly), the refcount-aware audit passes every tick — including
  under the PR-9 ``table_corrupt`` / ``alloc_exhaust`` chaos faults —
  and refcount-0 cached blocks are evicted LRU only under pressure
  (``cache_evict``);
- temp-0 speculative decode is token-bitwise-identical to
  non-speculative decode (the dense engine here; GQA + sliding-window
  via per-family bundles), the hot loop stays at ONE decode signature
  (the verify program at fixed k), and a drained speculative in-flight
  request resumes to exact temp-0 parity;
- ``estimate_ttft`` subtracts already-resident prefill chunks (warm vs
  cold queue), so the PR-9 deadline gate does not shed warm traffic.

Everything dense rides ONE module-scope engine (3 slots, 10 usable
blocks, ``prefix_cache=True, spec_k=2``); the family matrix adds two
lazily-built bundles — a handful of compiled programs for the file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistpackage_tpu.models import (
    GPTConfig,
    generate,
    init_gpt_params,
    llama_config,
)
from torchdistpackage_tpu.obs.events import EventLog, set_default_event_log
from torchdistpackage_tpu.obs.report import _validate_serving
from torchdistpackage_tpu.resilience import ChaosMonkey, Fault
from torchdistpackage_tpu.serving import BlockAllocator, Request, ServingEngine
from torchdistpackage_tpu.serving.engine import FREE
from torchdistpackage_tpu.serving.paged_cache import chain_block_hashes

CFG = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=32)
BS, CHUNK, K = 4, 4, 2
NEW = 6
P8 = 8                      # two FULL blocks: the whole-prompt/COW case
USABLE = 10                 # need/req = ceil((8+6+2)/4) = 4 with spec slack

FAMILY_CFGS = {
    "gqa": llama_config(vocab_size=64, dim=32, nheads=4, nlayers=2,
                        max_seq=32, kv_heads=2, ffn_hidden=48,
                        dtype=jnp.float32),
    "sliding": llama_config(vocab_size=64, dim=32, nheads=4, nlayers=2,
                            max_seq=32, kv_heads=2, ffn_hidden=48,
                            dtype=jnp.float32, sliding_window=6),
}


def _prompt(seed, n=P8, cfg=CFG):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size)).astype(np.int32)


@pytest.fixture(scope="module")
def fp():
    """Shared params, the P8 golden, and ONE prefix+spec engine."""
    params = init_gpt_params(jax.random.PRNGKey(0), CFG)
    gold = jax.jit(lambda p, t: generate(p, t, CFG, max_new_tokens=NEW))

    def want(prompt):
        return np.asarray(gold(params, jnp.asarray(prompt)[None]))[0]

    eng = ServingEngine(params, CFG, num_slots=3, block_size=BS,
                        chunk=CHUNK, num_blocks=USABLE + 1,
                        prefix_cache=True, spec_k=K)
    return {"params": params, "eng": eng, "want": want}


@pytest.fixture()
def event_log(fp):
    log = EventLog()
    set_default_event_log(log)
    fp["eng"]._ev = log
    yield log
    set_default_event_log(None)


def _fresh(eng):
    """Between tests: no live work, and every block either free or
    CACHED (prefix retention is deliberate cross-test state; a leaked
    refcount is not)."""
    assert eng.n_busy == 0 and not eng.queue, "previous test leaked state"
    for a in eng._allocs:
        assert a.in_use == 0, "previous test leaked block refcounts"
        assert a.n_free + a.n_cached == a.n_usable, "blocks went missing"
    eng.reset_metrics()
    eng.chaos = None
    eng._draining = False
    eng._tick_ewma = None
    eng._ttft_bias = None  # calibration is measurement state, like the EWMA
    eng._inject.clear()
    return eng


def _run_audited(eng):
    while eng.queue or eng.n_busy:
        eng.step()
        rep = eng.audit(heal=False)
        assert rep["ok"], (eng._tick, rep["violations"])
        assert eng._tick < 300


# ------------------------------------------------------- allocator unit


def test_allocator_refcounts_share_cache_evict():
    a = BlockAllocator(8)
    got = a.alloc(3)
    a.register(got[0], "h0")
    a.register(got[1], "h1")
    assert a.match(["h0", "h1"]) == got[:2]
    assert a.match(["h0", "hX", "h1"]) == got[:1]  # longest PREFIX only

    # share bumps the refcount: two frees to release; audit wants the
    # reference count to EQUAL the refcount (legal sharing), and flags
    # a mismatch as `shared`
    a.share(got[0])
    assert a.audit([got, [got[0]]])["ok"]
    rep = a.audit([got])  # one reference, refcount 2
    assert not rep["ok"] and rep["shared"] == [got[0]]
    a.free([got[0]])
    assert a.in_use == 3  # still owned once
    assert a.audit([got])["ok"]

    # release: registered blocks go to the cached LRU, not the free list
    a.free(got)
    assert a.in_use == 0 and a.n_cached == 2
    assert a.n_free + a.n_cached == a.n_usable
    assert a.audit([])["ok"]  # conservation counts cached blocks

    # a cached block revives via share (off the LRU, refcount 1)
    a.share(got[1])
    assert a.in_use == 1 and a.n_cached == 1
    a.free([got[1]])

    # eviction ONLY under pressure, LRU first, hashes dropped
    rest = a.alloc(a.n_free)
    assert a.n_cached == 2 and a.cache_evictions == 0
    more = a.alloc(1)  # free list empty: evicts the LRU cached block
    assert more is not None and a.cache_evictions == 1
    assert a.pop_evicted() == [got[0]]
    assert a.match(["h0"]) == []  # the prefix is gone with the block
    assert a.match(["h1"]) == [got[1]]
    a.free(rest + more)
    # reclaim purges refcounts, cache membership, and registrations
    healed = a.reclaim(list(range(1, 8)))
    assert a.n_free == a.n_usable and a.n_cached == 0 and a.in_use == 0
    assert a.match(["h1"]) == [] and healed
    with pytest.raises(ValueError):
        a.share(got[0])  # non-resident


def test_warm_admission_logits_bitwise(fp):
    """Acceptance bar, at the paged-forward level: a chunk computed
    against a SHARED prefix block (mapped into a different table row)
    produces logits BIT-identical to the same chunk in the cold run —
    sharing is pure table indirection, zero numerics."""
    from torchdistpackage_tpu.serving import init_paged_kv
    from torchdistpackage_tpu.serving.paged_cache import paged_forward

    params = fp["params"]
    prompt = _prompt(35)  # 8 tokens = 2 chunks of 4
    pool = init_paged_kv(CFG, 8, BS)
    step = jax.jit(lambda c, t, tab, off: paged_forward(
        params, t, CFG, c, tab, off, last_idx=jnp.asarray([BS - 1])))
    cold_tab = jnp.asarray([[1, 2, 0]], jnp.int32)
    t0 = jnp.asarray(prompt[:BS])[None]
    t1 = jnp.asarray(prompt[BS:])[None]
    pool, _ = step(pool, t0, cold_tab, jnp.asarray([0], jnp.int32))
    pool, cold_logits = step(pool, t1, cold_tab, jnp.asarray([BS], jnp.int32))
    # warm: block 1 (the shared prefix) mapped into a DIFFERENT table;
    # the second chunk writes into a fresh block and attends through it
    warm_tab = jnp.asarray([[1, 3, 0]], jnp.int32)
    pool, warm_logits = step(pool, t1, warm_tab, jnp.asarray([BS], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(cold_logits), np.asarray(warm_logits),
        err_msg="shared-prefix chunk logits drifted from the cold run")


# ---------------------------------------------- warm admission + estimate


def test_warm_prefix_hit_parity_and_prefill_savings(fp, event_log):
    eng = _fresh(fp["eng"])
    base = _prompt(40)
    warm = np.concatenate([base[:BS], _prompt(41, 3)])  # shares block 0
    cold_want, warm_want = fp["want"](base), fp["want"](warm)

    r0 = eng.submit(Request(base.tolist(), NEW))
    _run_audited(eng)
    cold_chunks = eng.stats["prefill_chunks"]
    np.testing.assert_array_equal(eng.finished[r0]["tokens"], cold_want)
    assert eng.stats["prefix_hits"] == 0  # nothing resident yet

    eng.reset_metrics()
    r1 = eng.submit(Request(warm.tolist(), NEW))
    _run_audited(eng)
    np.testing.assert_array_equal(
        eng.finished[r1]["tokens"], warm_want,
        err_msg="warm prefix admission diverged from its cold run")
    hits = event_log.of_kind("prefix_hit")
    assert len(hits) == 1 and hits[0]["cached_tokens"] == BS
    assert not hits[0]["cow"]
    # prefill ticks saved ∝ hit: 7-token remainder = 2 chunks vs 2 for 8
    assert eng.stats["prefill_chunks"] < cold_chunks
    s = eng.serving_summary()
    assert s["prefix_hit_rate"] == pytest.approx(BS / len(warm))
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    assert _validate_serving(s) == []
    # the validator bites on out-of-range fast-path rates
    assert any("prefix_hit_rate" in e for e in _validate_serving(
        dict(s, prefix_hit_rate=2.0)))
    assert any("spec" in e for e in _validate_serving(
        dict(s, spec={"drafted": 1, "accepted": 2})))


def test_estimate_ttft_warm_vs_cold_queue(fp):
    """Satellite: admission estimates subtract already-resident prefill
    chunks, so warm shared-prefix traffic is not spuriously shed."""
    eng = _fresh(fp["eng"])
    warm_prompt = _prompt(40)  # resident from the previous test
    cold_prompt = _prompt(44)
    eng._tick_ewma = 0.01
    # cold: 2 chunks of 4; warm: both blocks resident, COW-capped to 1
    # recomputed token = 1 chunk
    assert eng.estimate_ttft(P8, tokens=cold_prompt.tolist()) == \
        pytest.approx(0.02)
    assert eng.estimate_ttft(P8, tokens=warm_prompt.tolist()) == \
        pytest.approx(0.01)
    # queued work ahead is costed at its WARM price too
    req = Request(warm_prompt.tolist(), NEW)
    import dataclasses
    req = dataclasses.replace(req, rid=0)
    eng._seq[0] = 0
    eng.queue.append((req, 0.0))
    assert eng.estimate_ttft(P8, tokens=cold_prompt.tolist()) == \
        pytest.approx(0.03)  # 2 cold + 1 warm queued
    eng.queue.clear()
    del eng._seq[0]


def test_estimate_ttft_calibration_converges_and_warm_stays(fp):
    """Satellite (PR 11): the TTFT calibration loop.  Feed a
    deliberately skewed sequence — the engine's measured TTFT is
    consistently 2x its raw (ticks x EWMA) estimate — and the bias EWMA
    must converge to the true factor (tracking actual/RAW, not
    actual/corrected, which would converge to sqrt(2)); estimate_ttft
    then predicts the skewed truth.  A warm-cache prediction resolved at
    its true (warm) cost must leave the converged bias put — warm
    traffic is cheaper because fewer chunks run, not because the clock
    model is wrong, so it must not be 'corrected'."""
    eng = _fresh(fp["eng"])
    eng._tick_ewma = 0.01
    cold = _prompt(44)              # nothing resident: 2 chunks raw
    for i in range(40):
        est = eng.estimate_ttft(P8, tokens=cold.tolist())
        raw = est / (eng._ttft_bias if eng._ttft_bias is not None else 1.0)
        assert raw == pytest.approx(0.02)
        eng._ttft_pred[9000 + i] = {"est": est, "raw": raw}
        eng._resolve_ttft(9000 + i, actual=0.04, priority=0)
    assert eng._ttft_bias == pytest.approx(2.0, rel=0.02)
    assert eng.estimate_ttft(P8, tokens=cold.tolist()) == \
        pytest.approx(0.04, rel=0.02)

    # warm prompt (resident from the earlier module tests): 1 chunk raw,
    # biased to 0.02 — and resolving it at exactly that cost holds the
    # bias (extends the PR-10 warm/cold queue evidence into calibration)
    warm = _prompt(40)
    est_w = eng.estimate_ttft(P8, tokens=warm.tolist())
    assert est_w == pytest.approx(0.02, rel=0.02)
    eng._ttft_pred[9999] = {"est": est_w, "raw": est_w / eng._ttft_bias}
    eng._resolve_ttft(9999, actual=est_w, priority=2)
    assert eng._ttft_bias == pytest.approx(2.0, rel=0.05)

    cal = eng.serving_summary()["slo"]["calibration"]
    assert cal["n"] == 41 and cal["pending"] == 0
    assert cal["bias"] == pytest.approx(2.0, rel=0.05)
    # the warm prediction was spot-on: zero relative error at its class
    assert cal["priorities"]["2"]["rel_err_p50"] == pytest.approx(
        0.0, abs=1e-9)
    # the skewed class's error shrinks as the bias converges: the median
    # (late, converged) error is far below the first prediction's 50%
    assert cal["priorities"]["0"]["rel_err_p50"] < 0.05
    assert _validate_serving(eng.serving_summary()) == []
    eng._ttft_bias = None  # leave no calibration state for later tests


# --------------------------------------------------- COW + shared safety


def test_cow_whole_prompt_cached_concurrent_writers(fp, event_log):
    """Two requests whose WHOLE prompt is resident admitted the same
    tick: each COWs the same source block into its own copy, writes its
    recomputed last token there, and decodes bit-identically to the cold
    golden — the concurrent-writer case block sharing must survive."""
    eng = _fresh(fp["eng"])
    prompt = _prompt(50)
    want = fp["want"](prompt)
    r0 = eng.submit(Request(prompt.tolist(), NEW))
    _run_audited(eng)
    np.testing.assert_array_equal(eng.finished[r0]["tokens"], want)

    eng.reset_metrics()
    r1 = eng.submit(Request(prompt.tolist(), NEW))
    r2 = eng.submit(Request(prompt.tolist(), NEW))
    eng.step()
    cows = event_log.of_kind("block_cow")
    assert len(cows) == 2, "both whole-prompt hits must COW"
    assert cows[0]["src_block"] == cows[1]["src_block"]
    assert cows[0]["dst_block"] != cows[1]["dst_block"]
    _run_audited(eng)
    for r in (r1, r2):
        np.testing.assert_array_equal(
            eng.finished[r]["tokens"], want,
            err_msg="COW writer diverged from the cold golden")
    s = eng.serving_summary()
    assert s["prefix_cache"]["cow_copies"] == 2
    assert s["prefix_cache"]["cow_signatures"] == 1  # one compiled copy
    assert s["decode_signatures"] == 1
    hits = event_log.of_kind("prefix_hit")
    assert len(hits) == 2 and all(h["cow"] for h in hits)


def test_preempt_on_shared_blocks_never_frees_coowner(fp, event_log):
    """A preempted (and a cancelled) sharer must DECREMENT, not free:
    the co-owner keeps decoding on the shared blocks bit-exactly."""
    eng = _fresh(fp["eng"])
    prompt = _prompt(60)
    want = fp["want"](prompt)
    a = eng.submit(Request(prompt.tolist(), NEW))
    _run_audited(eng)  # A completes; blocks cached + registered
    eng.reset_metrics()

    a2 = eng.submit(Request(prompt.tolist(), NEW))          # COW + share
    b = eng.submit(Request(prompt.tolist(), NEW))           # shares too
    eng.step()
    shared_counts = [v for v in eng._allocs[0]._ref.values() if v > 1]
    assert shared_counts, "expected refcount > 1 on the shared prefix"

    # a high-priority request that cannot fit evicts the most recent
    # same-priority sharer; the survivor's blocks must stay live
    hi = eng.submit(Request(_prompt(61).tolist(), NEW, priority=5))
    _run_audited(eng)
    pre = event_log.of_kind("request_preempted")
    assert len(pre) == 1 and pre[0]["by_rid"] == hi
    for rid in (a2, b):
        f = eng.finished[rid]
        assert f["reason"] == "max_tokens"
        np.testing.assert_array_equal(
            f["tokens"], want,
            err_msg="sharer diverged after its co-owner was preempted")
    np.testing.assert_array_equal(
        eng.finished[hi]["tokens"], fp["want"](_prompt(61)))
    assert eng.serving_summary()["requests"]["preempted"] == 1

    # cancel a sharer mid-flight: same decrement discipline
    eng.reset_metrics()
    c1 = eng.submit(Request(prompt.tolist(), NEW))
    c2 = eng.submit(Request(prompt.tolist(), NEW))
    # ONE step: the whole prompt is a cache hit, so prefill is one chunk,
    # and spec_k=2 retires a NEW-token request on the second tick.  Cancel
    # while both provably hold slots on the same blocks, so the decrement
    # path is what runs.
    eng.step()
    live = {s.rid for s in eng._slots if s.state != FREE}
    assert {c1, c2} <= live, "the sharers must be in flight at the cancel"
    assert any(v > 1 for v in eng._allocs[0]._ref.values())
    assert eng.cancel(c1) is True
    assert eng.finished[c1]["reason"] == "cancelled"
    rep = eng.audit(heal=False)
    assert rep["ok"], rep["violations"]
    _run_audited(eng)
    np.testing.assert_array_equal(eng.finished[c2]["tokens"], want)
    assert _kinds_count(event_log, "request_cancelled") == 1


def _kinds_count(log, kind):
    return sum(1 for e in log.as_list() if e["kind"] == kind)


def test_cache_eviction_only_under_pressure(fp, event_log):
    """Refcount-0 cached blocks are retained until the free list cannot
    cover a fresh allocation, then evicted LRU with a ``cache_evict``
    event — block conservation holds throughout."""
    eng = _fresh(fp["eng"])
    alloc = eng._allocs[0]
    # fill the cache with distinct retired prefixes
    seeds = (70, 71, 72)
    for s in seeds:
        eng.submit(Request(_prompt(s).tolist(), 1))
    _run_audited(eng)
    assert alloc.n_cached > 0
    evictions_before = eng.stats["cache_evictions"]
    # two cold requests need 8 fresh blocks; free+cached covers them only
    # by evicting
    assert alloc.n_free < 8 <= alloc.n_free + alloc.n_cached
    r = [eng.submit(Request(_prompt(80 + i).tolist(), NEW))
         for i in range(2)]
    _run_audited(eng)
    for i, rid in enumerate(r):
        np.testing.assert_array_equal(
            eng.finished[rid]["tokens"], fp["want"](_prompt(80 + i)))
    assert eng.stats["cache_evictions"] > evictions_before
    assert event_log.of_kind("cache_evict")
    # the evicted prefix is findable no more
    oldest = chain_block_hashes(_prompt(seeds[0]), BS)
    assert alloc.match(oldest) == []


# ----------------------------------------------------- chaos w/ refcounts


@pytest.mark.parametrize("fault", ["table_corrupt", "alloc_exhaust"])
def test_chaos_faults_green_with_refcounts(fp, event_log, fault):
    """Satellite: the PR-9 chaos faults stay green on a prefix+spec
    engine — the refcount-aware audit heals, only the poisoned request
    replays, co-batched output is bit-identical, one decode signature."""
    eng = _fresh(fp["eng"])
    p0, p1 = _prompt(90), _prompt(91)
    kw = {"slot": 1} if fault == "table_corrupt" else {}
    eng.chaos = ChaosMonkey(faults=[Fault(fault, step=4, **kw)], seed=0)
    rids = [eng.submit(Request(p.tolist(), NEW)) for p in (p0, p1)]
    _run_audited(eng)
    eng.chaos = None
    for rid, p in zip(rids, (p0, p1)):
        np.testing.assert_array_equal(
            eng.finished[rid]["tokens"], fp["want"](p),
            err_msg=f"{fault}: tokens diverged under refcounted sharing")
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1
    assert s["faults"]["healed"] == s["faults"]["detected"] >= 1
    kinds = {e["kind"] for e in event_log.as_list()}
    assert {"engine_fault_detected", "engine_recovered"} <= kinds


# ------------------------------------------------ speculative decode claims


def test_spec_drain_resume_exact_parity(fp, event_log, tmp_path):
    """A speculative in-flight request drained mid-decode resumes to
    exact temp-0 token parity (the descriptor's emitted list IS the
    accepted-draft state; replay rides chunked prefill + the warm
    prefix cache)."""
    eng = _fresh(fp["eng"])
    prompt = _prompt(95)
    want = fp["want"](prompt)
    g = eng.submit(Request(prompt.tolist(), NEW))
    smp = eng.submit(Request(_prompt(96).tolist(), NEW, temperature=1.0,
                             top_k=16, seed=7))
    while not any(s.state == "decode" and s.generated
                  for s in eng._slots):
        eng.step()
    path = str(tmp_path / "spec_drain.json")
    payload = eng.drain(persist_path=path)
    assert eng.n_busy == 0 and payload["n"] == 2
    assert _kinds_count(event_log, "engine_drained") == 1

    eng._draining = False
    rids = eng.resume(path)
    _run_audited(eng)
    f = eng.finished[rids[0]]
    np.testing.assert_array_equal(
        f["tokens"], want,
        err_msg="speculative drain/resume broke temp-0 parity")
    assert f["new_tokens"] == NEW
    smp_f = eng.finished[rids[1]]
    assert smp_f["new_tokens"] == NEW
    assert np.all(smp_f["tokens"] < CFG.vocab_size)
    s = eng.serving_summary()
    assert s["requests"]["resumed"] == 2
    assert s["decode_signatures"] == 1


def test_spec_sampled_deterministic_replay(fp):
    """Sampled speculative decode draws from the slot's own key stream:
    same seed replays the same tokens, different seeds differ, every
    token is in-vocab (residual rejection sampling never leaves the
    filtered support)."""
    eng = _fresh(fp["eng"])
    prompt = _prompt(97)

    def run(seed):
        rid = eng.submit(Request(prompt.tolist(), NEW, temperature=1.0,
                                 top_k=16, top_p=0.9, seed=seed))
        _run_audited(eng)
        return eng.finished[rid]["tokens"]

    a, b, c = run(3), run(3), run(4)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(a[P8:] < CFG.vocab_size)
    assert eng.serving_summary()["decode_signatures"] == 1


def test_lifecycle_trace_preempt_drain_resume(fp, event_log, tmp_path):
    """Acceptance (PR 11): a preempted-then-resumed SPECULATIVE request's
    full lifecycle reconstructs from the trace alone — every phase span
    present and ordered (queued → prefill → decode/verify ticks →
    preempted → queued → drained, then the resumed instance through to
    retirement), flow-linked across the drain→resume restart — and the
    whole traced path adds zero compiled programs
    (``decode_signatures == 1``)."""
    from torchdistpackage_tpu.obs.trace import build_trace, validate_trace
    from torchdistpackage_tpu.serving import (
        assemble_request_timelines,
        lifecycle_phases,
        request_trace_events,
        validate_request_record,
    )

    eng = _fresh(fp["eng"])
    pa, pv, ph = _prompt(120), _prompt(121), _prompt(122)
    a = eng.submit(Request(pa.tolist(), NEW))
    v = eng.submit(Request(pv.tolist(), NEW))

    def _decoding(rid, tokens=1):
        return any(s.rid == rid and s.state == "decode"
                   and len(s.generated) >= tokens for s in eng._slots)

    # past their first verify call: a prompt's last slice leaves the slot in
    # DECODE with ONE token, and its first decode step is the next tick's
    while not (_decoding(a, 2) and _decoding(v, 2)):
        eng.step()
        assert eng._tick < 100
    # v (most recently admitted at equal priority) is the preemption
    # victim; the freed blocks cover hi, v waits in the queue
    hi = eng.submit(Request(ph.tolist(), NEW, priority=5))
    while not _decoding(hi):
        eng.step()
        assert eng._tick < 100
    assert any(r.rid == v for r, _t in eng.queue), "victim not requeued"

    path = str(tmp_path / "obs_drain.json")
    payload = eng.drain(persist_path=path)
    assert payload["n"] == 3
    eng._draining = False
    rids = eng.resume(path)
    _run_audited(eng)
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    assert _validate_serving(s) == []

    events = event_log.as_list()
    records = assemble_request_timelines(events)
    for rec in records:
        assert validate_request_record(rec) == [], rec
    by_uid = {r["uid"]: r for r in records}
    (vrec,) = [r for r in records if r["rid"] == v and r["terminal"] ==
               "drained"]

    # every phase span present and ORDERED: the preempted speculative
    # request's walk, reconstructed purely from the timeline
    assert lifecycle_phases(vrec) == [
        "queued", "admitted", "prefill", "decode", "preempted", "queued",
        "drained"]
    names = [sp["name"] for sp in vrec["spans"]]
    assert names == ["queued", "prefill", "decode", "queued"]
    for s0, s1 in zip(vrec["spans"], vrec["spans"][1:]):
        assert s1["t0"] >= s0["t1"] - 1e-9, "phase spans out of order"
    # per-tick children: chunked prefill and the SPECULATIVE verify ticks
    child_kinds = {c["name"] for c in vrec["ticks"]}
    assert {"prefill_chunk", "verify_tick"} <= child_kinds

    # flow-linked across drain -> resume: the drained instance names the
    # instance that continues it, and the continuation retires cleanly
    assert vrec["resumed_to"] is not None
    rrec = by_uid[vrec["resumed_to"]]
    assert rrec["resumed_from"] == vrec["uid"]
    assert lifecycle_phases(rrec) == [
        "queued", "admitted", "prefill", "decode", "retired"]
    assert rrec["spans"][0]["t0"] >= vrec["spans"][-1]["t1"] - 1e-9
    # the resumed request replayed to the unpreempted golden
    np.testing.assert_array_equal(
        eng.finished[rrec["rid"]]["tokens"], fp["want"](pv),
        err_msg="preempt+drain+resume broke the token stream")
    # the other two drained instances resumed and retired too
    assert len(rids) == 3 and all(
        eng.finished[r]["reason"] == "max_tokens" for r in rids)

    # and it all renders as a loadable Perfetto trace with the requeue
    # and resume flow arrows connecting the journey
    trace = build_trace([], events=events)
    assert validate_trace(trace) == []
    flows = [e for e in trace["traceEvents"] if e.get("cat") == "flow"]
    names = {e["name"] for e in flows}
    assert "resume" in names, "drain->resume flow arrow missing"
    req_events = request_trace_events(events)
    starts = [e for e in req_events if e["ph"] == "s"]
    ends = [e for e in req_events if e["ph"] == "f"]
    assert starts and len(starts) == len(ends)
    for sev in starts:
        (fev,) = [e for e in ends if e["id"] == sev["id"]]
        assert fev["ts"] >= sev["ts"], "flow arrow points backwards"


@pytest.mark.parametrize(
    "family",
    # slow tier (PR-19 budget payback): each param compiles a fresh
    # engine pair.  Fast-tier holders: the dense shared-engine spec
    # tests above (test_spec_drain_resume_exact_parity,
    # test_spec_sampled_deterministic_replay) prove the speculative
    # verify/rollback machinery, and test_serving.py's staggered matrix
    # proves the gqa/sliding attention variants under paged decode.
    [pytest.param(f, marks=pytest.mark.slow) for f in ("gqa", "sliding")])
def test_spec_family_parity(family):
    """Acceptance matrix: temp-0 speculative paged decode bit-equals
    non-speculative ``generate()`` for the GQA and sliding-window
    families too (dense is covered by the shared-engine tests)."""
    cfg = FAMILY_CFGS[family]
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    prompts = np.stack([_prompt(10 + i, 5, cfg) for i in range(2)])
    want = np.asarray(jax.jit(
        lambda p, t: generate(p, t, cfg, max_new_tokens=NEW)
    )(params, jnp.asarray(prompts)))
    eng = ServingEngine(params, cfg, num_slots=2, block_size=BS,
                        chunk=CHUNK, prefix_cache=True, spec_k=K)
    r0 = eng.submit(Request(prompts[0].tolist(), NEW))
    eng.step()
    eng.step()  # slot 0 decoding when slot 1 admits: staggered offsets
    r1 = eng.submit(Request(prompts[1].tolist(), NEW))
    _run_audited(eng)
    for rid, row in ((r0, 0), (r1, 1)):
        np.testing.assert_array_equal(
            eng.finished[rid]["tokens"], want[row],
            err_msg=f"{family}: speculative decode diverged from generate()")
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    assert 0.0 <= s["spec_accept_rate"] <= 1.0
    assert _validate_serving(s) == []
