"""Serving under stress: priorities/preemption, deadlines/shedding,
chaos-hardened recovery, preemption-safe drain (PR 9).

The load-bearing claims, each asserted against goldens or the event
timeline rather than prints:

- preemption unblocks a waiting high-priority request, and the evicted
  request's eventual tokens BIT-equal its unpreempted run (discard +
  prompt replay is deterministic);
- admission sheds with a structured verdict instead of queueing without
  bound, and deadlines expire queued requests that can no longer be
  served in time;
- under every injected engine fault (slot stall, allocator exhaustion,
  corrupted block table, NaN/garbage logit row) the engine retires ONLY
  the poisoned request, the block-conservation audit passes every tick,
  co-batched requests decode bit-identically to a fault-free run, and
  the hot loop stays at one decode signature;
- drain -> persist -> resume replays temp-0 requests to exact token
  parity (``tools/parity_diff`` gates it) and continues sampled key
  streams exactly.

Everything shares ONE module-scope engine (3 slots, a deliberately
undersized 8-usable-block pool so exhaustion/preemption are reachable)
plus one "restarted" engine for resume — a handful of compiled programs
for the whole file (the tier-1 budget discipline)."""

import json

import jax
import numpy as np
import pytest

from torchdistpackage_tpu.models import GPTConfig, generate, init_gpt_params
from torchdistpackage_tpu.obs.events import EventLog, set_default_event_log
from torchdistpackage_tpu.obs.report import SERVING_VERDICTS, _validate_serving
from torchdistpackage_tpu.resilience import ChaosMonkey, Fault, Watchdog
from torchdistpackage_tpu.serving import (BlockAllocator, Request,
                                           ServingEngine, StubDeviceStep)

CFG = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=32)
PROMPT, NEW = 5, 6          # chunk=4 < PROMPT: prefill genuinely chunks
NEED = 3                    # ceil((5 + 6) / block_size=4) blocks/request
SLOTS, USABLE = 3, 8        # 3 full requests (9 blocks) CANNOT coexist


def _mk_engine(params, **kw):
    return ServingEngine(params, CFG, num_slots=SLOTS, block_size=4,
                         chunk=4, num_blocks=USABLE + 1, **kw)


@pytest.fixture(scope="module")
def stress():
    """Shared params, 3 prompts, the ``generate()`` goldens, one engine,
    and one 'restarted' engine (identical shapes) for resume."""
    params = init_gpt_params(jax.random.PRNGKey(0), CFG)
    prompts = np.stack([
        np.asarray(jax.random.randint(
            jax.random.PRNGKey(20 + i), (PROMPT,), 0, CFG.vocab_size))
        for i in range(3)
    ]).astype(np.int32)
    want = np.asarray(jax.jit(
        lambda p, t: generate(p, t, CFG, max_new_tokens=NEW)
    )(params, prompts))
    return {"params": params, "prompts": prompts, "want": want,
            "eng": _mk_engine(params), "eng2": _mk_engine(params)}


@pytest.fixture()
def event_log(stress):
    log = EventLog()
    set_default_event_log(log)
    stress["eng"]._ev = log
    stress["eng2"]._ev = log
    yield log
    set_default_event_log(None)


@pytest.fixture()
def stub_log():
    """Event log for compile-free StubDeviceStep tests — does NOT touch
    the module-scope ``stress`` fixture, so a stub-only test never pays
    for the compiled engines."""
    log = EventLog()
    set_default_event_log(log)
    yield log
    set_default_event_log(None)


def _fresh(eng):
    """Reset the shared engine between tests; a leaked slot/queue entry
    would silently couple tests, so fail loudly instead of scrubbing."""
    assert eng.n_busy == 0 and not eng.queue, "previous test leaked state"
    assert all(a.n_free == a.n_usable for a in eng._allocs), (
        "previous test leaked blocks")
    eng.reset_metrics()
    eng.max_queue = None
    eng.chaos = None
    eng.watchdog = None
    eng._draining = False
    eng._tick_ewma = None
    eng._ttft_bias = None  # calibration is measurement state, like the EWMA
    eng._inject.clear()
    return eng


def _kinds(log):
    return [e["kind"] for e in log.as_list()]


# ------------------------------------------------------ allocator audit


def test_allocator_audit_and_reclaim():
    a = BlockAllocator(9)
    assert a.audit([])["ok"]
    s0 = a.alloc(3)
    s1 = a.alloc(2)
    assert a.audit([s0, s1])["ok"]

    # leak: a live block no slot references
    rep = a.audit([s0, s1[:1]])
    assert not rep["ok"] and rep["orphaned"] == [s1[1]]
    # use-after-free: a slot referencing a freed block
    a.free([s1[1]])
    rep = a.audit([s0, s1])
    assert not rep["ok"] and rep["unknown"] == [s1[1]]
    # double ownership
    rep = a.audit([s0, s0[:1]])
    assert not rep["ok"] and rep["shared"] == [s0[0]]

    # reclaim heals whatever state the blocks are in: double-reclaim and
    # reclaiming a free block are no-ops, conservation is restored
    healed = a.reclaim(s0 + s1)
    assert healed == s0 + s1[:1]  # s1[1] already free
    rep = a.audit([])
    assert rep["ok"] and rep["conserved"]
    assert a.n_free == a.n_usable and a.in_use == 0
    assert a.reclaim(s0) == []  # idempotent

    # fragmentation shuffle: interleaved alloc/free keeps all-or-nothing
    # refusal and conservation exact whatever order blocks come back in
    xs = [a.alloc(2) for _ in range(4)]  # pool exhausted
    assert a.alloc(1) is None
    a.free(xs[0]); a.free(xs[2])  # noqa: E702 — scattered holes
    assert a.alloc(5) is None     # 4 free, all-or-nothing refuses 5
    got = a.alloc(4)
    assert sorted(got) == sorted(xs[0] + xs[2])
    a.free(got); a.free(xs[1]); a.free(xs[3])  # noqa: E702
    assert a.audit([])["ok"]


# ------------------------------------- exhaustion, back-pressure, preemption


def test_exhaustion_backpressure_then_preemption(stub_log):
    """Back-pressure and preemption POLICY (PR-17: compile-free on
    StubDeviceStep — admission, the all-or-nothing allocator, priority
    eviction, and replay are host code; the chaos matrix below keeps
    the real-engine compile evidence).  The preempted request's replay
    still bit-equals its unpreempted run: the stub's token rule is
    deterministic in (last token, position), so a replay that dropped
    or doubled a token would diverge."""
    event_log = stub_log
    eng = _mk_engine(None, device_step=StubDeviceStep())
    rng = np.random.RandomState(5)
    p = rng.randint(0, CFG.vocab_size, size=(3, PROMPT)).astype(np.int32)

    def solo(tokens):
        e = _mk_engine(None, device_step=StubDeviceStep())
        r = e.submit(Request(tokens, NEW))
        e.run_until_idle()
        return e.finished[r]["tokens"]

    want = [solo(p[i].tolist()) for i in range(3)]
    low = [eng.submit(Request(p[i].tolist(), NEW)) for i in range(2)]
    eng.step()
    assert eng.n_busy == 2 and eng._allocs[0].n_free == USABLE - 2 * NEED

    # a third same-priority request: a slot is FREE but the pool can only
    # cover 2 of its 3 blocks -> all-or-nothing refusal = back-pressure,
    # and equal priority NEVER preempts
    low2 = eng.submit(Request(p[2].tolist(), NEW))
    eng.step()
    assert len(eng.queue) == 1 and eng.stats["preempted"] == 0
    assert eng._allocs[0].alloc(NEED) is None  # nothing partially allocated
    assert eng.audit(heal=False)["ok"]

    # a high-priority request evicts the LOWEST-priority running slot
    # (most recently admitted among equals) and is admitted the same tick
    hi = eng.submit(Request(p[2].tolist(), NEW, priority=5))
    out = eng.step()
    assert out["admitted"] >= 1
    assert any(s.rid == hi for s in eng._slots if s.state != "free")
    pre = [e for e in event_log.as_list() if e["kind"] == "request_preempted"]
    assert len(pre) == 1 and pre[0]["rid"] == low[1]
    assert pre[0]["by_rid"] == hi and pre[0]["by_priority"] == 5
    assert eng.stats["preempted"] == 1
    # the victim went back to the queue, not to /dev/null
    assert {r.rid for r, _ in eng.queue} == {low[1], low2}

    eng.run_until_idle()
    # every request completed, and the PREEMPTED one replayed to the exact
    # tokens of its never-preempted golden
    for rid, row in ((low[0], 0), (low[1], 1), (low2, 2), (hi, 2)):
        f = eng.finished[rid]
        assert f["reason"] == "max_tokens" and f["new_tokens"] == NEW
        np.testing.assert_array_equal(
            f["tokens"], want[row],
            err_msg=f"rid {rid} diverged after preemption/replay")
    s = eng.serving_summary()
    assert s["verdict"] == "degraded"  # preempted, nothing shed
    assert s["requests"]["preempted"] == 1 and s["requests"]["shed"] == 0
    assert set(s["priorities"]) == {"0", "5"}
    assert s["priorities"]["5"]["completed"] == 1
    assert s["priorities"]["0"]["ttft_s"]["p99"] >= 0
    # compile evidence lives with the real engines (chaos matrix below);
    # here the stub just confirms both program kinds were exercised
    assert eng.device_step.calls["decode"] > 0
    # the stub is handed (and charges its modelled seconds for) the compact
    # prefill batches the real step would compute, one call each
    assert eng.device_step.calls["prefill"] == s["prefill_calls"] > 0
    assert _validate_serving(s) == []


# ----------------------------------------- deadlines, shedding, cancellation


def test_estimate_ttft_model(stub_log):
    """Admission-model POLICY (PR-19 budget payback: pure host
    arithmetic, rides StubDeviceStep)."""
    eng = _mk_engine(None, device_step=StubDeviceStep())
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, CFG.vocab_size, size=PROMPT).tolist()
    assert eng.estimate_ttft(PROMPT) is None  # unmeasured: admit everything
    eng._tick_ewma = 0.01
    assert eng.estimate_ttft(PROMPT) == pytest.approx(0.02)  # 2 chunks
    # queue work ahead counts
    eng.queue.append((Request(prompt, NEW, rid=0), 0.0))
    eng._seq[0] = 0
    assert eng.estimate_ttft(PROMPT) == pytest.approx(0.04)
    eng.queue.clear()


def test_deadline_shed_expire_and_bounded_queue(stub_log):
    """Deadline/shed/bounded-queue POLICY (PR-19 budget payback:
    admission decisions are host code, so this rides StubDeviceStep —
    the chaos matrix below keeps the real-engine compile evidence)."""
    event_log = stub_log
    eng = _mk_engine(None, device_step=StubDeviceStep())
    rng = np.random.RandomState(6)
    p = rng.randint(0, CFG.vocab_size, size=(3, PROMPT)).astype(np.int32)
    eng._tick_ewma = 0.01  # pretend-measured tick so the model is armed

    ok = eng.submit(Request(p[0].tolist(), NEW, deadline_s=10.0))
    assert ok not in eng.rejected  # est ~0.02s, plenty of budget

    shed = eng.submit(Request(p[1].tolist(), NEW, deadline_s=1e-4))
    assert shed in eng.rejected
    v = eng.rejected[shed]
    assert v["reason"] == "deadline_unmeetable" and v["est_ttft_s"] > 1e-4

    # bounded queue: one spot, already taken
    eng.max_queue = 1
    full = eng.submit(Request(p[2].tolist(), NEW))
    assert eng.rejected[full]["reason"] == "queue_full"
    eng.max_queue = None

    # expiry: admitted with a live deadline, then the clock runs out while
    # still queued (simulated by aging the submit stamp — no sleeps)
    exp = eng.submit(Request(p[2].tolist(), NEW, deadline_s=5.0))
    assert exp not in eng.rejected
    eng.queue = [(r, t - 100.0 if r.rid == exp else t) for r, t in eng.queue]
    eng.step()
    assert eng.rejected[exp]["reason"] == "expired"
    kinds = _kinds(event_log)
    assert kinds.count("request_shed") == 2 and "request_expired" in kinds

    eng.run_until_idle()
    assert eng.finished[ok]["reason"] == "max_tokens"
    s = eng.serving_summary()
    assert s["verdict"] == "overloaded"
    assert s["requests"]["shed"] == 2 and s["requests"]["expired"] == 1
    assert _validate_serving(s) == []
    # the validator bites on a bogus verdict
    assert any("verdict" in e for e in _validate_serving(
        dict(s, verdict="on fire")))
    assert "on fire" not in SERVING_VERDICTS


def test_cancel_queued_and_inflight(stub_log):
    """Cancellation POLICY (PR-19 budget payback: same-tick retirement
    and block return are host code, so this rides StubDeviceStep; the
    completed survivor's tokens still check against a stub-solo
    golden)."""
    event_log = stub_log
    eng = _mk_engine(None, device_step=StubDeviceStep())
    rng = np.random.RandomState(7)
    p = rng.randint(0, CFG.vocab_size, size=(3, PROMPT)).astype(np.int32)

    def solo(tokens):
        e = _mk_engine(None, device_step=StubDeviceStep())
        r = e.submit(Request(tokens, NEW))
        e.run_until_idle()
        return e.finished[r]["tokens"]

    want1 = solo(p[1].tolist())
    rids = [eng.submit(Request(p[i % 3].tolist(), NEW)) for i in range(3)]
    eng.step()  # 2 admitted, third queued (pool back-pressure)
    assert len(eng.queue) == 1

    assert eng.cancel(rids[2]) is True  # queued: removed without service
    assert eng.finished[rids[2]]["reason"] == "cancelled"
    assert eng.finished[rids[2]]["new_tokens"] == 0

    eng.step(); eng.step()  # noqa: E702 — rid0 decoding now
    in_use_before = eng._allocs[0].in_use
    assert eng.cancel(rids[0]) is True  # in-flight: blocks freed SAME tick
    assert eng._allocs[0].in_use == in_use_before - NEED
    f = eng.finished[rids[0]]
    assert f["reason"] == "cancelled" and 0 < f["new_tokens"] < NEW
    assert eng.audit(heal=False)["ok"]
    assert eng.cancel(99_999) is False

    eng.run_until_idle()
    np.testing.assert_array_equal(eng.finished[rids[1]]["tokens"], want1)
    s = eng.serving_summary()
    assert s["requests"]["cancelled"] == 2
    # cancellation is user-initiated, not degradation
    assert s["verdict"] == "healthy"
    assert s["requests"]["completed"] == 1
    assert _validate_serving(s) == []
    assert _kinds(event_log).count("request_cancelled") == 2


def test_first_token_retirement_mid_prefill_conserves_blocks(stress):
    """The leak suspect the allocator audit was built to catch: a request
    that retires ON its first sampled token (max_new=1, final prefill
    slice) while a co-batched slot is still mid-prefill.  Conservation
    must hold on every tick and the freed blocks must be reusable
    immediately."""
    eng = _fresh(stress["eng"])
    p = stress["prompts"]
    one = eng.submit(Request(p[0].tolist(), 1))       # retires at TTFT
    slow = eng.submit(Request(p[1].tolist(), NEW))    # keeps prefilling
    free0 = eng._allocs[0].n_free
    while eng.n_busy or eng.queue:
        eng.step()
        assert eng.audit(heal=False)["ok"], eng._tick
    assert eng.finished[one]["new_tokens"] == 1
    np.testing.assert_array_equal(
        eng.finished[one]["tokens"][:PROMPT + 1],
        stress["want"][0][:PROMPT + 1])
    np.testing.assert_array_equal(
        eng.finished[slow]["tokens"], stress["want"][1])
    assert eng._allocs[0].n_free == free0  # captured pre-admission: all back
    assert eng.serving_summary()["faults"]["detected"] == 0


# ------------------------------------------------------------ chaos matrix


def _serve_pair_with(eng, stress, chaos=None, watchdog=None):
    """Submit prompts[0]+[1] greedy, run to idle asserting the
    conservation audit green after EVERY tick (the in-step audit heals at
    tick start, so a post-tick heal=False pass must always be clean);
    return the two token arrays."""
    eng.chaos = chaos
    eng.watchdog = watchdog
    rids = [eng.submit(Request(stress["prompts"][i].tolist(), NEW))
            for i in range(2)]
    while eng.queue or eng.n_busy:
        eng.step()
        rep = eng.audit(heal=False)
        assert rep["ok"], (eng._tick, rep["violations"])
        assert eng._tick < 300
    eng.chaos = None
    eng.watchdog = None
    return [eng.finished[r]["tokens"] for r in rids]


@pytest.mark.parametrize("fault", [
    "nan_logits", "table_corrupt", "alloc_exhaust", "slot_stall"])
def test_chaos_matrix(stress, event_log, fault):
    """The acceptance matrix: under each injected engine fault the engine
    retires only the poisoned request, the conservation audit passes
    every tick, co-batched requests decode bit-identically to the
    fault-free goldens, and the hot loop never recompiles."""
    eng = _fresh(stress["eng"])
    # tick 4: both requests are mid-decode (prefill = ticks 1-2)
    kw = {"slot": 1} if fault in ("nan_logits", "table_corrupt") else {}
    if fault == "slot_stall":
        kw["duration_s"] = 0.25
    chaos = ChaosMonkey(faults=[Fault(fault, step=4, **kw)], seed=0)
    dog = (Watchdog(timeout_s=0.08, poll_s=0.02).start()
           if fault == "slot_stall" else None)

    toks = _serve_pair_with(eng, stress, chaos=chaos, watchdog=dog)
    audit_ok = eng.audit(heal=False)
    if dog is not None:
        dog.stop()

    assert chaos.fired_count == 1, "declared fault did not fire"
    # co-batched bit-identity: BOTH requests (the poisoned one replays)
    for got, row in zip(toks, range(2)):
        np.testing.assert_array_equal(
            got, stress["want"][row],
            err_msg=f"{fault}: tokens diverged from the fault-free run")
    assert audit_ok["ok"], audit_ok["violations"]
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    assert s["requests"]["completed"] == 2
    assert all(a.n_free == a.n_usable for a in eng._allocs)

    kinds = _kinds(event_log)
    assert "fault_injected" in kinds
    if fault == "slot_stall":
        # a wedged tick is the watchdog's problem, not the scheduler's
        assert "hang_suspected" in kinds
        assert s["verdict"] == "healthy" and s["faults"]["detected"] == 0
        return
    assert "engine_fault_detected" in kinds and "engine_recovered" in kinds
    assert s["verdict"] == "degraded"
    assert s["faults"]["detected"] >= 1
    assert s["faults"]["healed"] == s["faults"]["detected"]
    if fault == "nan_logits":
        ev = [e for e in event_log.as_list()
              if e["kind"] == "engine_fault_detected"]
        assert ev[0]["fault"] == "invalid_token" and ev[0]["slot"] == 1
    if fault == "table_corrupt":
        ev = [e for e in event_log.as_list()
              if e["kind"] == "engine_recovered"]
        assert len(ev[0]["requeued_rids"]) == 1  # ONLY the poisoned slot
    if fault == "alloc_exhaust":
        ev = [e for e in event_log.as_list()
              if e["kind"] == "engine_recovered"]
        assert ev[0]["blocks_reclaimed"] >= 1  # the leak came back


# ------------------------------------------------------- drain and resume


def test_drain_resume_exact_parity(stress, event_log, tmp_path, capsys):
    eng = _fresh(stress["eng"])
    eng2 = _fresh(stress["eng2"])
    p = stress["prompts"]

    # arm A: uninterrupted — one greedy, one sampled (its own key stream)
    g = eng.submit(Request(p[0].tolist(), NEW))
    smp = eng.submit(Request(p[1].tolist(), NEW, temperature=1.0, top_k=16,
                             seed=7))
    eng.run_until_idle()
    want_g = eng.finished[g]["tokens"]
    want_s = eng.finished[smp]["tokens"]
    np.testing.assert_array_equal(want_g, stress["want"][0])

    # arm B: same requests, drained MID-DECODE, persisted, resumed in a
    # "restarted" engine
    eng.reset_metrics()
    eng.submit(Request(p[0].tolist(), NEW))
    eng.submit(Request(p[1].tolist(), NEW, temperature=1.0, top_k=16,
                       seed=7))

    def _mid_decode():
        busy = [s for s in eng._slots if s.state != "free"]
        return len(busy) == 2 and all(
            s.state == "decode" and 2 <= len(s.generated) < NEW for s in busy)

    while not _mid_decode():
        eng.step()
    assert eng.n_busy == 2
    path = str(tmp_path / "drain.json")
    payload = eng.drain(persist_path=path)
    assert eng.n_busy == 0 and not eng.queue
    assert eng.audit(heal=False)["ok"]
    assert all(a.n_free == a.n_usable for a in eng._allocs)
    assert payload["n"] == 2 and len(payload["requests"]) == 2
    assert all(len(d["emitted"]) >= 2 for d in payload["requests"])
    assert _kinds(event_log).count("engine_drained") == 1
    # a draining engine sheds instead of admitting
    late = eng.submit(Request(p[2].tolist(), NEW))
    assert eng.rejected[late]["reason"] == "draining"
    eng._draining = False

    rids = eng2.resume(path)
    assert len(rids) == 2 and not eng2.rejected
    eng2.run_until_idle()
    for rid, want in zip(rids, (want_g, want_s)):
        f = eng2.finished[rid]
        np.testing.assert_array_equal(
            f["tokens"], want,
            err_msg="drain/resume broke the token stream")
        assert f["prompt_len"] == PROMPT  # original, not prompt+prefix
        assert f["new_tokens"] == NEW and f["resumed"]
    s2 = eng2.serving_summary()
    assert s2["requests"]["resumed"] == 2
    assert s2["decode_signatures"] == 1  # resume is not a new signature

    # temp-0 exact parity, gated the way the acceptance bar names: two
    # per-token JSONL streams through the tools/parity_diff CLI
    from torchdistpackage_tpu.tools.parity_diff import main as parity_main

    a_path, b_path = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    for path_t, toks in ((a_path, want_g[PROMPT:]),
                         (b_path, eng2.finished[rids[0]]["tokens"][PROMPT:])):
        path_t.write_text("\n".join(
            json.dumps({"step": i, "token": int(t)})
            for i, t in enumerate(toks)))
    rc = parity_main([str(a_path), str(b_path), "--key", "token",
                      "--label-a", "uninterrupted", "--label-b", "resumed"])
    out = capsys.readouterr().out
    assert rc == 0 and '"verdict": "exact"' in out

    # verify-before-restore: rotted bytes are refused, not half-parsed
    from torchdistpackage_tpu.resilience import CheckpointCorruptError

    raw = bytearray((tmp_path / "drain.json").read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    (tmp_path / "drain.json").write_bytes(bytes(raw))
    with pytest.raises(CheckpointCorruptError):
        eng2.resume(path)


def test_run_until_idle_drains_on_stop(stress, event_log, tmp_path):
    """The GracefulShutdown contract: a stop flag mid-loop turns
    run_until_idle into a drain instead of finishing the work."""

    class _Stop:
        requested = False

    eng = _fresh(stress["eng"])
    stop = _Stop()
    rid = eng.submit(Request(stress["prompts"][0].tolist(), NEW))
    eng.step()
    stop.requested = True
    path = str(tmp_path / "sigterm_drain.json")
    eng.run_until_idle(stop=stop, persist_path=path)
    assert eng.n_busy == 0 and rid not in eng.finished
    assert _kinds(event_log).count("engine_drained") == 1

    eng2 = _fresh(stress["eng2"])
    (rid2,) = eng2.resume(path)
    eng2.run_until_idle()
    np.testing.assert_array_equal(
        eng2.finished[rid2]["tokens"], stress["want"][0])
    eng._draining = False
