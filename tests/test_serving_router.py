"""Multi-replica serving router (PR 15): prefix-affinity routing,
prefill/decode disaggregation with cross-replica KV migration, KV-free
rebalance/evacuation, and the validated fleet roll-up.

The load-bearing claims, asserted against goldens / the event timeline:

- ``migrate_blocks`` moves exactly the named blocks between two pools —
  bit-exact for fp and int8 pools, bounded-error for the int8 WIRE format
  on an fp pool — and NULL lanes stay harmless;
- affinity routing sends warm traffic to the replica whose prefix cache
  owns it (``request_routed`` evidence), and a shedding replica falls
  through to the next-best;
- a prefill→decode handoff produces token streams BIT-identical (fp
  pool, temp-0 — and the sampled key stream continues exactly) to the
  same request served end-to-end on one engine, with the prefill replica
  never dispatching its decode program and the decode replica never
  prefilling; the cross-allocator audit passes every tick; a warm
  handoff ships only the unshared tail blocks;
- rebalance and chaos-kill evacuation move requests by exact-parity
  drain descriptors (PR-9): tokens equal the unfaulted golden;
- ``Router.summary()`` validates through ``_validate_router`` and the
  validator bites on corrupted roll-ups.

Budget discipline: ONE module-scope engine pair (identical shapes ⇒
reused compiled entries) + the stacked ``generate()`` golden serve every
test; routers are host-only wrappers built per test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistpackage_tpu.models import GPTConfig, generate, init_gpt_params
from torchdistpackage_tpu.obs.comm_model import AxisCost, CommModel
from torchdistpackage_tpu.obs.events import EventLog, set_default_event_log
from torchdistpackage_tpu.obs.report import _validate_router
from torchdistpackage_tpu.resilience import ChaosMonkey, Fault
from torchdistpackage_tpu.serving import (
    ChunkedWireTransport,
    Request,
    Router,
    ServingEngine,
    StubDeviceStep,
    assemble_fleet_request_timelines,
    init_paged_kv,
    migrate_blocks,
    migration_wire_bytes,
)

CFG = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=64)
PROMPT, NEW = 9, 6   # chunk=4 < PROMPT: prefill genuinely chunks
BS = 4               # block size


@pytest.fixture(scope="module")
def fleet():
    """Shared params, 4 prompts, stacked ``generate()`` goldens, and ONE
    engine pair — identical shapes, so the pair costs one set of
    compiled programs; every test builds its (host-only) Router on top."""
    params = init_gpt_params(jax.random.PRNGKey(0), CFG)
    prompts = np.stack([
        np.asarray(jax.random.randint(
            jax.random.PRNGKey(20 + i), (PROMPT,), 0, CFG.vocab_size))
        for i in range(4)
    ]).astype(np.int32)
    want = np.asarray(jax.jit(
        lambda p, t: generate(p, t, CFG, max_new_tokens=NEW)
    )(params, prompts))

    def mk():
        return ServingEngine(params, CFG, num_slots=3, block_size=BS,
                             chunk=4, prefix_cache=True)

    return {"params": params, "prompts": prompts, "want": want,
            "a": mk(), "b": mk()}


@pytest.fixture()
def event_log(fleet):
    log = EventLog()
    set_default_event_log(log)
    fleet["a"]._ev = log
    fleet["b"]._ev = log
    yield log
    set_default_event_log(None)


@pytest.fixture()
def stub_log():
    """Event log for the stub-engine policy tests — deliberately does
    NOT touch the ``fleet`` fixture, so a stub-only test never pays the
    compiled pair's setup."""
    log = EventLog()
    set_default_event_log(log)
    yield log
    set_default_event_log(None)


def _fresh(eng):
    """Reset one shared engine between tests — loud on leaked state."""
    assert eng.n_busy == 0 and not eng.queue, "previous test leaked state"
    for a in eng._allocs:
        assert a.in_use == 0, "previous test leaked blocks"
        # registered prefixes may be cached; reclaim them so each test
        # starts cold (affinity tests warm their own replicas)
        a.reclaim(list(range(1, a.num_blocks)))
    assert all(a.n_free == a.n_usable for a in eng._allocs)
    eng.reset_metrics()
    eng.max_queue = None
    eng.chaos = None
    eng.watchdog = None
    eng.hold_decode = False
    eng._draining = False
    eng._tick_ewma = None
    eng._ttft_bias = None
    eng._inject.clear()
    return eng


def _pair(fleet):
    return _fresh(fleet["a"]), _fresh(fleet["b"])


def _run_audited(router, max_ticks=300):
    """Drain the fleet asserting the cross-allocator audit green after
    EVERY tick (each engine's own in-step audit heals at tick start, so
    a post-tick heal-free pass must always be clean)."""
    ticks = 0
    while router.has_work():
        router.step()
        rep = router.audit()
        assert rep["ok"], (ticks, rep["violations"])
        ticks += 1
        assert ticks < max_ticks
    return ticks


def _kinds(log):
    return [e["kind"] for e in log.as_list()]


# -------------------------------------------------------- migrate_blocks unit


def test_migrate_blocks_unit():
    """The cross-pool copy primitive, no engines: named blocks move
    bit-exactly between fp pools and int8 pools (pairs ship verbatim);
    the int8 WIRE format on an fp pool lands within quantization error;
    NULL pad lanes never touch live dst blocks."""
    src = init_paged_kv(CFG, 8, BS)
    dst = init_paged_kv(CFG, 8, BS)
    key = jax.random.PRNGKey(1)
    src = jax.tree.map(
        lambda a: jax.random.normal(key, a.shape, a.dtype), src)
    dst_mark = jax.tree.map(lambda a: a.at[:, 5].set(7.0), dst)

    lanes = np.zeros(4, np.int32)
    lanes_src, lanes_dst = lanes.copy(), lanes.copy()
    lanes_src[:2] = [2, 3]
    lanes_dst[:2] = [4, 6]
    out = migrate_blocks(src, dst_mark, lanes_src, lanes_dst)
    np.testing.assert_array_equal(out["k"][:, 4], src["k"][:, 2])
    np.testing.assert_array_equal(out["v"][:, 6], src["v"][:, 3])
    # untouched dst blocks survive; pad lanes only wrote the NULL block
    np.testing.assert_array_equal(out["k"][:, 5], dst_mark["k"][:, 5])

    # int8 wire format on an fp pool: per-vector quantization error only
    outc = migrate_blocks(src, dst, lanes_src, lanes_dst, compress=True)
    got = np.asarray(outc["k"][:, 4], np.float32)
    ref = np.asarray(src["k"][:, 2], np.float32)
    amax = np.abs(ref).max(axis=-1, keepdims=True)
    assert np.all(np.abs(got - ref) <= amax / 127.0 + 1e-7)
    # and the wire-bytes model prices the trade: int8+scale < fp32 payload
    assert migration_wire_bytes(CFG, 2, BS, compressed=True) < \
        migration_wire_bytes(CFG, 2, BS)

    # quantized pools ARE the wire format: pairs copy bit-exactly,
    # compress flag changes nothing
    srcq = init_paged_kv(CFG, 8, BS, quantized=True)
    srcq = jax.tree.map(
        lambda a: (jax.random.randint(key, a.shape, -5, 5).astype(a.dtype)
                   if a.dtype == jnp.int8 else
                   jax.random.uniform(key, a.shape, a.dtype)), srcq)
    dstq = init_paged_kv(CFG, 8, BS, quantized=True)
    for flag in (False, True):
        outq = migrate_blocks(srcq, dstq, lanes_src, lanes_dst,
                              compress=flag)
        np.testing.assert_array_equal(outq["k"][0][:, 4], srcq["k"][0][:, 2])
        np.testing.assert_array_equal(outq["k"][1][:, 4], srcq["k"][1][:, 2])


# ----------------------------------------------------- routing and fallback


def test_affinity_routing_and_shed_fallback(stub_log):
    """Routing POLICY (PR-17: compile-free on StubDeviceStep — every
    decision here is host code; the bit-parity claims stay with the
    real-engine handoff/rebalance tests below).  Warm traffic routes to
    its prefix owner by affinity, a shedding replica falls through to
    the next-best, and the token streams still match a solo engine's
    (the router never corrupts what it routes)."""
    rng = np.random.RandomState(3)
    p = rng.randint(0, CFG.vocab_size, size=(3, PROMPT)).astype(np.int32)

    def mk():
        return ServingEngine(None, CFG, num_slots=3, block_size=BS,
                             chunk=4, prefix_cache=True,
                             device_step=StubDeviceStep())

    def solo(tokens):
        e = mk()
        r = e.submit(Request(tokens, NEW))
        e.run_until_idle()
        return e.finished[r]["tokens"]

    want = [solo(p[i].tolist()) for i in range(2)]
    event_log = stub_log
    a, b = mk(), mk()
    router = Router([a, b])
    # warm each replica with a different prefix (through the router, so
    # the registration happens exactly as production traffic would)
    wa = router.submit(Request(p[0].tolist(), 2))
    router.run_until_idle()
    where_a = router.finished[wa]["replica"]
    wb_req = Request(p[1].tolist(), 2)
    # force the second warmup onto the OTHER replica: mark the first busy
    router.alive[where_a] = False
    wb = router.submit(wb_req)
    router.run_until_idle()
    router.alive[where_a] = True
    other = router.finished[wb]["replica"]
    assert other != where_a
    router.reset_metrics()

    # warm traffic routes to its prefix owner, by affinity not by index
    ra = router.submit(Request(p[0].tolist(), NEW))
    rb = router.submit(Request(p[1].tolist(), NEW))
    routed = {e["rid"]: e for e in event_log.as_list()
              if e["kind"] == "request_routed"}
    assert routed[ra]["replica"] == where_a
    assert routed[ra]["affinity_tokens"] > 0
    assert routed[rb]["replica"] == other
    assert routed[rb]["affinity_tokens"] > 0
    router.run_until_idle()
    np.testing.assert_array_equal(router.finished[ra]["tokens"], want[0])
    np.testing.assert_array_equal(router.finished[rb]["tokens"], want[1])
    s = router.summary()
    assert s["fleet"]["affinity"]["hit_rate"] == 1.0
    assert _validate_router(s) == []

    # shed fallback: the affinity-preferred replica refuses (queue full)
    # and the request lands on the next-best instead of dying
    pref = router.replicas[where_a]
    pref.max_queue = 1
    pref.queue = [(Request(p[2].tolist(), NEW, rid=900), 0.0)]
    pref._seq[900] = 900
    rc = router.submit(Request(p[0].tolist(), NEW))  # affinity says pref
    ev = [e for e in event_log.as_list()
          if e["kind"] == "request_routed" and e["rid"] == rc]
    assert ev and ev[0]["replica"] == other and ev[0]["fallback_rank"] > 0
    assert rc not in router.rejected
    pref.queue.clear()
    pref.max_queue = None
    router.run_until_idle()
    np.testing.assert_array_equal(router.finished[rc]["tokens"], want[0])


# --------------------------------------------- disaggregated handoff parity


def test_prefill_decode_handoff_bit_parity(fleet, event_log):
    """The acceptance claim: a prefill→decode handoff via migrate_blocks
    produces token streams bit-identical (fp pool, temp-0) to the same
    request served end-to-end on one engine — and the sampled key stream
    continues exactly.  The prefill replica never dispatches its decode
    program, the decode replica never prefills, the cross-allocator
    audit is green every tick, decode_signatures stays 1 per replica."""
    a, b = _pair(fleet)
    p = fleet["prompts"]
    # mono golden for the SAMPLED request: engine b end-to-end, then reset
    smp_req = dict(tokens=p[3].tolist(), max_new_tokens=NEW,
                   temperature=1.0, top_k=16, seed=7)
    rid0 = b.submit(Request(**smp_req))
    b.run_until_idle()
    want_sampled = b.finished[rid0]["tokens"]
    _fresh(b)

    router = Router([a, b], roles=["prefill", "decode"])
    rids = [router.submit(Request(p[i].tolist(), NEW)) for i in range(3)]
    rs = router.submit(Request(**smp_req))
    _run_audited(router)

    for rid, row in zip(rids, range(3)):
        f = router.finished[rid]
        np.testing.assert_array_equal(
            f["tokens"], fleet["want"][row],
            err_msg="handoff broke temp-0 bit parity")
        assert f["replica"] == 1  # finished on the decode tier
    np.testing.assert_array_equal(
        router.finished[rs]["tokens"], want_sampled,
        err_msg="handoff broke the sampled key stream")

    # strict tier separation + compile-once per replica
    assert a.stats["decode_steps"] == 0 and a.stats["prefill_chunks"] > 0
    assert b.stats["prefill_chunks"] == 0 and b.stats["decode_steps"] > 0
    sa, sb = a.serving_summary(), b.serving_summary()
    assert sa["decode_signatures"] == 0 and sa["prefill_signatures"] == 1
    assert sb["decode_signatures"] == 1 and sb["prefill_signatures"] == 0
    assert sa["requests"]["migrated_out"] == 4
    assert sb["requests"]["migrated_in"] == 4

    s = router.summary()
    mig = s["fleet"]["migrations"]
    assert mig["handoffs"] == 4 and mig["blocks"] > 0 and mig["bytes"] > 0
    assert mig["signatures"] == 1  # one compiled pair program
    assert _validate_router(s) == []
    kinds = _kinds(event_log)
    assert "blocks_migrated" in kinds and "request_migrated" in kinds

    # PR-17 acceptance on the REAL-engine path: each migrated request
    # reconstructs from the event timeline alone as ONE cross-replica
    # journey (prefill hop on 0, decode hop on 1), with the
    # decode_signatures==1 evidence above still standing
    fleet_tl = assemble_fleet_request_timelines(event_log.as_list())
    by_rid = {j["rid"]: j for j in fleet_tl["journeys"]}
    for rid in rids + [rs]:
        assert [h["replica"] for h in by_rid[rid]["hops"]] == [0, 1]
        assert by_rid[rid]["outcome"] == "retired"
        assert by_rid[rid]["migrations"][0]["bytes"] > 0


@pytest.mark.parametrize("wire", ["loopback", "chunked_wire", "bounced"])
def test_exported_pool_is_good_until_the_source_steps(fleet, event_log,
                                                      monkeypatch, wire):
    """``export_slot`` hands out the source engine's OWN pool buffer, which
    the source's next device call donates.  So: export from A, import and
    deliver into B through the transport, THEN step A (the handle dies),
    then B: B's tokens equal the unmigrated run's.  ``bounced``: the
    destination refuses the import, the request goes back into A through
    the same-replica lane copy (source pool == destination pool), and
    still ends bit-equal after its second, real handoff."""
    a, b = _pair(fleet)
    p = fleet["prompts"]
    router = Router(
        [a, b], roles=["prefill", "decode"],
        transport=ChunkedWireTransport() if wire == "chunked_wire" else None)
    moved = router.submit(Request(p[0].tolist(), NEW))
    while not a.decode_slots():
        a.step()
    (src_rid, _slot), = a.decode_slots()
    exported = a.cache["k"]  # what export_slot will hand out
    if wire == "bounced":
        monkeypatch.setattr(b, "import_slot", lambda desc: None)
    assert router._handoff(0, src_rid)
    monkeypatch.undo()
    assert not exported.is_deleted()  # a handoff makes no call on A's pool
    assert (a.n_busy, b.n_busy) == ((1, 0) if wire == "bounced" else (0, 1))
    assert router.audit()["ok"]

    other = router.submit(Request(p[1].tolist(), NEW))  # lands on A
    a.step()  # A's next device call: the exported handle is consumed
    if wire != "bounced":  # (a bounce already replaced A's pool by a copy)
        assert exported.is_deleted()
    _run_audited(router)
    for rid, row in ((moved, 0), (other, 1)):
        np.testing.assert_array_equal(
            router.finished[rid]["tokens"], fleet["want"][row],
            err_msg=f"{wire}: the migrated KV was not what A had written")
        assert router.finished[rid]["replica"] == 1


def test_warm_handoff_ships_only_the_tail(fleet, event_log):
    """Affinity on the migration leg: the first handoff of a prefix
    migrates and REGISTERS its full blocks on the decode replica, so the
    second same-prefix handoff shares them on arrival and migrates only
    the unshared tail — fewer wire bytes, same bit-exact tokens."""
    a, b = _pair(fleet)
    p = fleet["prompts"]
    router = Router([a, b], roles=["prefill", "decode"])
    shared = p[0].tolist()[:8]  # two FULL blocks
    reqs = [shared + [1], shared + [2]]
    want = np.asarray(jax.jit(
        lambda pr, t: generate(pr, t, CFG, max_new_tokens=NEW)
    )(fleet["params"], np.asarray(reqs, np.int32)))

    r1 = router.submit(Request(reqs[0], NEW))
    router.run_until_idle()
    r2 = router.submit(Request(reqs[1], NEW))
    router.run_until_idle()
    np.testing.assert_array_equal(router.finished[r1]["tokens"], want[0])
    np.testing.assert_array_equal(router.finished[r2]["tokens"], want[1])

    migs = [e for e in event_log.as_list() if e["kind"] == "blocks_migrated"]
    assert len(migs) == 2
    first, second = migs
    assert first["n_shared"] == 0
    assert second["n_shared"] == 2          # both full prefix blocks shared
    assert second["n_blocks"] < first["n_blocks"]
    assert second["bytes"] < first["bytes"]
    # prefill side also went warm: its second prefill rode its own cache
    assert a.stats["prefix_hits"] >= 1


# ------------------------------------------------- rebalance and evacuation


def test_rebalance_policy_and_parity_stub(stub_log):
    """Rebalance POLICY on StubDeviceStep (PR-19 budget payback: the
    fast-tier holder for ``test_rebalance_moves_queue_with_exact_parity``
    below, now ``slow``): a watermark-deep queue spills to the idle
    peer via exact-parity descriptors — the stub's deterministic token
    rule still diverges on any drop/replay bug."""
    rng = np.random.RandomState(11)
    p0 = rng.randint(0, CFG.vocab_size, size=PROMPT).astype(np.int32)

    def mk():
        return ServingEngine(None, CFG, num_slots=3, block_size=BS,
                             chunk=4, prefix_cache=True,
                             device_step=StubDeviceStep())

    shared = p0.tolist()[:8]
    reqs = [shared + [i] for i in range(6)]

    def solo(tokens):
        e = mk()
        r = e.submit(Request(tokens, NEW))
        e.run_until_idle()
        return e.finished[r]["tokens"]

    want = [solo(r) for r in reqs]
    router = Router([mk(), mk()], rebalance_every=1, rebalance_watermark=1)
    w = router.submit(Request(p0.tolist(), 2))  # pin affinity to one side
    router.run_until_idle()
    pinned = router.finished[w]["replica"]
    router.reset_metrics()

    rids = [router.submit(Request(r, NEW)) for r in reqs]
    routed = [e for e in stub_log.as_list()
              if e["kind"] == "request_routed"]
    assert all(e["replica"] == pinned for e in routed[-6:])
    _run_audited(router)
    s = router.summary()
    assert s["fleet"]["rebalances"] >= 1
    assert s["fleet"]["rebalanced_requests"] >= 1
    assert router.replicas[1 - pinned].stats["generated_tokens"] > 0
    moved = [e for e in stub_log.as_list()
             if e["kind"] == "request_migrated" and e["mode"] == "rebalance"]
    assert moved and all(e["src_replica"] == pinned for e in moved)
    for rid, row in zip(rids, range(6)):
        np.testing.assert_array_equal(
            router.finished[rid]["tokens"], want[row],
            err_msg="rebalance broke replay parity")
    assert _validate_router(s) == []


@pytest.mark.slow
def test_rebalance_moves_queue_with_exact_parity(fleet, event_log):
    """Real-engine rebalance parity (slow tier; fast holder:
    ``test_rebalance_policy_and_parity_stub``)."""
    a, b = _pair(fleet)
    p = fleet["prompts"]
    router = Router([a, b], rebalance_every=1, rebalance_watermark=1)
    # pin affinity to ONE replica: warm it with the shared prefix
    w = router.submit(Request(p[0].tolist(), 2))
    router.run_until_idle()
    pinned = router.finished[w]["replica"]
    router.reset_metrics()

    shared = p[0].tolist()[:8]
    reqs = [shared + [i] for i in range(6)]
    want = np.asarray(jax.jit(
        lambda pr, t: generate(pr, t, CFG, max_new_tokens=NEW)
    )(fleet["params"], np.asarray(reqs, np.int32)))
    rids = [router.submit(Request(r, NEW)) for r in reqs]
    routed = [e for e in event_log.as_list() if e["kind"] == "request_routed"]
    assert all(e["replica"] == pinned for e in routed[-6:])  # all piled on

    _run_audited(router)
    s = router.summary()
    assert s["fleet"]["rebalances"] >= 1
    assert s["fleet"]["rebalanced_requests"] >= 1
    other_eng = router.replicas[1 - pinned]
    assert other_eng.stats["generated_tokens"] > 0  # work actually moved
    moved = [e for e in event_log.as_list()
             if e["kind"] == "request_migrated" and e["mode"] == "rebalance"]
    assert moved and all(e["src_replica"] == pinned for e in moved)
    for rid, row in zip(rids, range(6)):
        np.testing.assert_array_equal(
            router.finished[rid]["tokens"], want[row],
            err_msg="rebalance broke replay parity")
    assert _validate_router(s) == []


def test_replica_kill_mid_decode_evacuates_to_survivor(fleet, event_log):
    """The chaos satellite: an ENGINE_FAULT_KINDS fault fires on one
    replica mid-decode; the router's evacuate-on-fault policy drains it
    (queue + in-flight → exact-parity descriptors), takes it out of
    rotation, and resumes everything on the survivor — temp-0 token
    streams BIT-equal the unfaulted goldens, audit green on both
    allocators every tick."""
    a, b = _pair(fleet)
    p = fleet["prompts"]
    a.chaos = ChaosMonkey(
        faults=[Fault("table_corrupt", step=4, slot=0)], seed=0)
    router = Router([a, b], evacuate_on_fault=True)
    # both requests land on replica 0: replica 1 plays dead at submit
    router.alive[1] = False
    rids = [router.submit(Request(p[i].tolist(), NEW)) for i in range(2)]
    router.alive[1] = True
    ticks = _run_audited(router)
    assert a.chaos.fired_count == 1, "declared fault did not fire"
    assert not router.alive[0] and router.alive[1]

    for rid, row in zip(rids, range(2)):
        f = router.finished[rid]
        np.testing.assert_array_equal(
            f["tokens"], fleet["want"][row],
            err_msg="evacuation broke token parity")
        assert f["replica"] == 1
    kinds = _kinds(event_log)
    assert "replica_degraded" in kinds
    ev = [e for e in event_log.as_list() if e["kind"] == "request_migrated"]
    assert ev and all(e["mode"] == "evacuation" for e in ev)
    s = router.summary()
    assert s["fleet"]["verdict"] == "degraded"
    assert s["fleet"]["n_alive"] == 1
    assert s["fleet"]["evacuations"] == 1
    assert s["replicas"][1]["decode_signatures"] == 1
    assert _validate_router(s) == [], _validate_router(s)
    assert ticks < 300
    a.chaos = None


# ------------------------------------------------ pricing and the validator


def test_dcn_migration_pricing_and_int8_wire(stub_log):
    """The comm-model loop on the migration leg (PR-19 budget payback:
    pricing is host POLICY, so this rides StubDeviceStep; the int8
    wire's bounded-error parity on real arrays stays with
    ``test_migrate_blocks_unit`` above): a zone-crossing handoff is
    priced through ``predict_compressed`` on the calibrated DCN axis and
    ships the int8 wire format iff the model approves; an
    alpha-dominated leg REFUSES and stays exact."""
    event_log = stub_log
    rng = np.random.RandomState(13)
    p = rng.randint(0, CFG.vocab_size, size=(2, PROMPT)).astype(np.int32)

    def mk():
        return ServingEngine(None, CFG, num_slots=3, block_size=BS,
                             chunk=4, prefix_cache=True,
                             device_step=StubDeviceStep())

    def solo(tokens):
        e = mk()
        r = e.submit(Request(tokens, NEW))
        e.run_until_idle()
        return e.finished[r]["tokens"]

    model = CommModel(
        axis_costs={"dcn": AxisCost(1e-3, 1e9, "calibrated")},
        compressed_axis_costs={"dcn": AxisCost(1e-3, 1e9, "calibrated")})
    router = Router([mk(), mk()], roles=["prefill", "decode"],
                    zones=["east", "west"], comm_model=model)
    rid = router.submit(Request(p[0].tolist(), NEW))
    _run_audited(router)
    ev = [e for e in event_log.as_list() if e["kind"] == "blocks_migrated"][-1]
    assert ev["dcn"] and ev["compressed"]
    assert ev["basis"] == "calibrated-int8"
    assert ev["pred_compressed_s"] < ev["pred_exact_s"]
    fp_bytes = migration_wire_bytes(CFG, ev["n_blocks"], BS)
    assert ev["bytes"] == migration_wire_bytes(
        CFG, ev["n_blocks"], BS, compressed=True) < fp_bytes
    assert router.finished[rid]["new_tokens"] == NEW  # served to completion
    assert router.summary()["fleet"]["migrations"]["compressed"] == 1

    # alpha-dominated leg: quartered bytes can't pay for themselves ->
    # the model REFUSES and the wire stays exact
    slow = CommModel(
        axis_costs={"dcn": AxisCost(1.0, float("inf"), "calibrated")},
        compressed_axis_costs={"dcn": AxisCost(1.0, float("inf"),
                                               "calibrated")})
    router = Router([mk(), mk()], roles=["prefill", "decode"],
                    zones=["east", "west"], comm_model=slow)
    rid = router.submit(Request(p[1].tolist(), NEW))
    _run_audited(router)
    ev = [e for e in event_log.as_list() if e["kind"] == "blocks_migrated"][-1]
    assert ev["dcn"] and not ev["compressed"]
    np.testing.assert_array_equal(  # exact wire => parity intact
        router.finished[rid]["tokens"], solo(p[1].tolist()))


def test_router_summary_validator_bites(stub_log):
    """Validator logic is pure host code (PR-19 budget payback: rides
    StubDeviceStep, never pays the compiled fleet fixture)."""
    import copy

    rng = np.random.RandomState(17)
    prompt = rng.randint(0, CFG.vocab_size, size=PROMPT).tolist()

    def mk():
        return ServingEngine(None, CFG, num_slots=3, block_size=BS,
                             chunk=4, prefix_cache=True,
                             device_step=StubDeviceStep())

    router = Router([mk(), mk()])
    rid = router.submit(Request(prompt, NEW))
    router.run_until_idle()
    assert router.finished[rid]["new_tokens"] == NEW
    s = router.summary()
    assert _validate_router(s) == []
    assert _validate_router(None) == []  # optional section

    bad = copy.deepcopy(s)
    bad["fleet"]["goodput_tok_s"] = 1e9  # > sum of replica rates
    assert any("goodput" in e for e in _validate_router(bad))
    bad = copy.deepcopy(s)
    bad["fleet"]["affinity"]["hit_rate"] = 1.5
    assert any("hit_rate" in e for e in _validate_router(bad))
    bad = copy.deepcopy(s)
    bad["fleet"]["verdicts"] = ["healthy"]  # mislengthed
    assert any("verdicts" in e for e in _validate_router(bad))
    bad = copy.deepcopy(s)
    bad["replicas"][0]["verdict"] = "on fire"
    assert _validate_router(bad)  # replica section re-validated
    bad = copy.deepcopy(s)
    del bad["fleet"]["migrations"]
    assert any("migrations" in e for e in _validate_router(bad))

    # and the section round-trips the full report validator + renderers
    from torchdistpackage_tpu.obs import Telemetry
    from torchdistpackage_tpu.obs.report import (
        render_markdown, render_summary_line, validate_runreport)

    tel = Telemetry(run="router-test", poll_memory=False)
    tel.record_router(s)
    report = tel.finalize(write=False, print_summary=False)
    assert validate_runreport(report) == []
    assert "Router fleet" in render_markdown(report)
    assert "fleet=" in render_summary_line(report)
    bad_report = copy.deepcopy(report)
    bad_report["router"]["fleet"]["verdicts"] = ["healthy"]
    assert any("router" in e for e in validate_runreport(bad_report))
