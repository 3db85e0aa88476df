"""Fleet observability (PR 17): the router decision ledger, cross-replica
trace stitching, the validated FLEETREPORT extensions, and the trace
replay harness — all on :class:`StubDeviceStep` engines, so this module
compiles NOTHING (the seam is the point: the policy surface is host
code; tests/test_serving_router.py keeps the real-engine bit-parity
coverage, including ``decode_signatures == 1`` on traced paths).

The load-bearing claims:

- every placement the Router makes is attributable after the fact: one
  ``route_decision`` per submit carrying the ranked candidate table it
  chose from, ``handoff_decision``/``rebalance_decision`` for every
  cross-replica move, counts reconciling EXACTLY with ``Router.stats``;
- ``Router.alive`` flips land ``replica_up``/``replica_down`` (with
  reason/role/zone) on the timeline — the autoscaler's switch is
  auditable;
- a request that prefills on replica A and decodes on replica B
  reconstructs from the event timeline ALONE as one ordered journey and
  one flow-linked Perfetto track (the PR-11 acceptance idiom, now
  cross-replica), with the migration leg priced in bytes;
- the FLEETREPORT ``slo``/``balance`` sections validate, render, and
  the validator bites on contradictions (a "balanced" verdict under a
  degraded fleet);
- ``tools/trace_replay.py`` pushes 10^5 synthetic requests through the
  REAL Router on stubbed engines inside the slow-tier budget, and the
  result is schema-valid with complete ledger attribution (the 10^3
  tier-1 twin keeps the harness honest between slow runs).
"""

import json

import numpy as np
import pytest

from torchdistpackage_tpu.models import GPTConfig
from torchdistpackage_tpu.obs.events import (
    EVENT_KINDS,
    EventLog,
    set_default_event_log,
)
from torchdistpackage_tpu.obs.report import _validate_router
from torchdistpackage_tpu.serving import (
    ROUTER_EVENT_KINDS,
    Request,
    Router,
    ServingEngine,
    StubDeviceStep,
    assemble_fleet_request_timelines,
    fleet_trace_events,
    serving_trace_events,
)

CFG = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=64)
BS = 4


def _engine(**kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", BS)
    kw.setdefault("chunk", 4)
    kw.setdefault("prefix_cache", True)
    return ServingEngine(None, CFG, device_step=StubDeviceStep(), **kw)


def _prompt(seed, n=9):
    rng = np.random.RandomState(seed)
    return rng.randint(0, CFG.vocab_size, size=n).tolist()


@pytest.fixture()
def event_log():
    log = EventLog()
    set_default_event_log(log)
    yield log
    set_default_event_log(None)


def _drain(router, max_ticks=500):
    ticks = 0
    while router.has_work():
        router.step()
        ticks += 1
        assert ticks < max_ticks
    return ticks


# ------------------------------------------------------------ decision ledger


def test_decision_ledger_attributes_every_placement(event_log):
    """One ``route_decision`` per submit, carrying the ranked candidate
    table (affinity/ETA/load per replica) the choice was made from;
    ledger counts reconcile exactly with ``Router.stats`` — no placement
    happens off the books."""
    router = Router([_engine(), _engine()])
    rids = [router.submit(Request(_prompt(i), max_new_tokens=4,
                                  temperature=0.0))
            for i in range(8)]
    _drain(router)

    decisions = event_log.of_kind("route_decision")
    assert len(decisions) == len(rids)
    assert [d["rid"] for d in decisions] == rids
    routed = [d for d in decisions if d["outcome"] == "routed"]
    assert len(routed) == router.stats["routed"]
    for d in routed:
        # the inputs that drove the choice ride the record
        assert d["chosen"] in (0, 1) and d["n_alive"] == 2
        for cand in d["candidates"]:
            assert {"replica", "role", "affinity_tokens",
                    "est_ttft_s", "load"} <= set(cand)
        # and the placement event agrees with the decision
        placed = [e for e in event_log.of_kind("request_routed")
                  if e["rid"] == d["rid"]]
        assert len(placed) == 1 and placed[0]["replica"] == d["chosen"]
    # full-history sanity: every ledger kind seen here is registered
    assert {e["kind"] for e in event_log.as_list()} <= EVENT_KINDS


def test_shed_decision_carries_reason_and_fallthrough(event_log):
    """A fleet-wide shed is a ``route_decision`` with outcome ``shed``,
    the refusing candidates in ``fallthrough``, and the last structured
    verdict's reason — the unplaceable request is attributable too."""
    router = Router([_engine(max_queue=1)])
    rids = [router.submit(Request(_prompt(40 + i), max_new_tokens=4,
                                  temperature=0.0))
            for i in range(8)]
    _drain(router)
    shed = [d for d in event_log.of_kind("route_decision")
            if d["outcome"] == "shed"]
    assert shed, "bounded queue never refused — workload too small"
    assert len(shed) == router.stats["router_shed"]
    for d in shed:
        assert d["reason"] and d["fallthrough"]
        assert d["rid"] in router.rejected
    assert sum(1 for r in rids if r in router.rejected) == len(shed)


def test_replica_up_down_events_on_timeline(event_log):
    """The autoscaler's switch: ``set_alive`` flips emit
    ``replica_up``/``replica_down`` with reason/role/zone/n_alive (no-op
    on an already-matching bit), evacuation lands its ``replica_down``
    with the evacuation reason, and routing honours the dead set on the
    very next submit."""
    router = Router([_engine(), _engine()], zones=["a", "b"])
    router.set_alive(1, False, reason="manual")
    router.set_alive(1, False, reason="manual")  # no-op, no second event
    down = event_log.of_kind("replica_down")
    assert len(down) == 1
    assert down[0] == dict(down[0], replica=1, reason="manual",
                           role="both", zone="b", n_alive=1)

    rid = router.submit(Request(_prompt(1), max_new_tokens=3,
                                temperature=0.0))
    d = event_log.of_kind("route_decision")[-1]
    assert d["rid"] == rid and d["chosen"] == 0 and d["n_alive"] == 1

    router.set_alive(1, True, reason="scale_up")
    up = event_log.of_kind("replica_up")
    assert len(up) == 1 and up[0]["reason"] == "scale_up"
    assert up[0]["n_alive"] == 2

    # the fault path: evacuate() takes the replica out via the same
    # switch, so the ledger shows WHY it left rotation
    _drain(router)
    router.submit(Request(_prompt(2), max_new_tokens=3, temperature=0.0))
    router.evacuate(0, reason="faults_detected")
    down = event_log.of_kind("replica_down")
    assert len(down) == 2
    assert down[1]["replica"] == 0
    assert down[1]["reason"] == "faults_detected"
    _drain(router)


# ------------------------------------------------- cross-replica trace stitch


def test_cross_replica_journey_reconstructs_from_trace_alone(event_log):
    """The PR-11 acceptance idiom, cross-replica: a request that
    prefills on replica 0 (prefill tier), migrates, and decodes on
    replica 1 reconstructs from the event timeline ALONE — one journey,
    ordered hops, the full lifecycle sequence across both engines, the
    routing + handoff decisions that placed it, and the migration leg
    priced in bytes."""
    router = Router([_engine(), _engine()], roles=["prefill", "decode"])
    rid = router.submit(Request(_prompt(7), max_new_tokens=4,
                                temperature=0.0))
    _drain(router)
    assert rid in router.finished

    fleet = assemble_fleet_request_timelines(event_log.as_list())
    (j,) = [j for j in fleet["journeys"] if j["rid"] == rid]
    assert [h["replica"] for h in j["hops"]] == [0, 1]
    assert j["sequence"] == [
        "@replica0", "queued", "admitted", "prefill", "exported",
        "@replica1", "imported", "decode", "retired"]
    assert j["outcome"] == "retired"
    kinds = [(d["kind"], d.get("outcome")) for d in j["decisions"]]
    assert ("route_decision", "routed") in kinds
    assert ("handoff_decision", "handoff") in kinds
    (mig,) = j["migrations"]
    assert mig["src_replica"] == 0 and mig["dst_replica"] == 1
    assert mig["bytes"] > 0 and mig["n_blocks"] >= 1


def test_cross_replica_flow_arrows_in_perfetto_trace(event_log):
    """The rendered trace is ONE flow-linked track: a ``route-`` arrow
    from the router lane (pid 99) to the placement and a ``mig-`` arrow
    from the replica-0 instance to the replica-1 instance carrying the
    priced bytes; ``serving_trace_events`` auto-dispatches replica-tagged
    timelines to the fleet renderer."""
    router = Router([_engine(), _engine()], roles=["prefill", "decode"])
    rid = router.submit(Request(_prompt(7), max_new_tokens=4,
                                temperature=0.0))
    _drain(router)

    events = event_log.as_list()
    trace = fleet_trace_events(events)
    assert trace == serving_trace_events(events)  # the dispatch seam

    flows = [e for e in trace if e.get("ph") in ("s", "f")]
    route = [e for e in flows if e["id"] == f"route-{rid}"]
    assert {(e["ph"], e["pid"]) for e in route} == {("s", 99), ("f", 100)}
    mig = [e for e in flows if e["id"].startswith(f"mig-{rid}-")]
    assert {(e["ph"], e["pid"]) for e in mig} == {("s", 100), ("f", 101)}
    (s,) = [e for e in mig if e["ph"] == "s"]
    (f,) = [e for e in mig if e["ph"] == "f"]
    assert s["ts"] <= f["ts"]                     # Perfetto binds s -> f
    assert s["args"]["bytes"] > 0 and s["args"]["via"] == "prefill_handoff"
    # both engine instances exist as request tracks on their own
    # replica pids (async b/e spans, cat "request")
    tracks = {(e["pid"], e["name"]) for e in trace
              if e.get("ph") == "b" and e.get("cat") == "request"}
    assert (100, f"req{rid}") in tracks
    assert (101, f"req{rid}") in tracks


# ----------------------------------------------------- FLEETREPORT extensions


def _mixed_fleet_summary(event_log):
    router = Router([_engine(), _engine()])
    for i in range(10):
        router.submit(Request(
            _prompt(i), max_new_tokens=4, temperature=0.0,
            priority=i % 2, deadline_s=None if i % 3 else 5.0))
    _drain(router)
    return router.summary()


def test_fleetreport_slo_and_balance_sections_validate(event_log):
    """``Router.summary()['fleet']`` carries per-priority/per-replica
    SLO attainment and a cited balance verdict; the whole roll-up passes
    ``_validate_router`` and renders in the .md + summary line."""
    from torchdistpackage_tpu.obs.report import (
        render_markdown,
        render_summary_line,
    )

    s = _mixed_fleet_summary(event_log)
    assert _validate_router(s) == []
    fleet = s["fleet"]
    assert fleet["verdict"] != "unknown"
    assert fleet["slo"]["attainment"] == 1.0      # generous deadlines met
    assert set(fleet["slo"]["priorities"]) == {"0", "1"}
    assert len(fleet["slo"]["per_replica"]) == 2
    bal = fleet["balance"]
    assert bal["verdict"] == "balanced" and bal["basis"]
    assert bal["imbalance_index"] >= 1.0

    report = {"run": "t", "steps": 1, "backend": "cpu", "chip": "none",
              "n_devices": 1, "n_processes": 1, "wall_time_s": 0.1,
              "router": s}
    md = render_markdown(report)
    assert "fleet SLO attainment: **100%**" in md
    assert "- load balance: **balanced**" in md
    assert "| SLO att |" in md
    line = render_summary_line(report)
    assert "att 100%" in line and "BALANCE=" not in line  # balanced is quiet


def test_fleetreport_validator_bites_on_contradiction(event_log):
    """The new checks bite: a ``balanced`` verdict under a non-healthy
    fleet verdict is a contradiction, an unknown balance verdict and a
    missing basis are schema errors, per-replica SLO rows must cover the
    fleet."""
    s = _mixed_fleet_summary(event_log)

    bad = json.loads(json.dumps(s))
    bad["fleet"]["verdict"] = "degraded"
    assert any("contradicts" in e for e in _validate_router(bad))

    bad = json.loads(json.dumps(s))
    bad["fleet"]["balance"]["verdict"] = "wobbly"
    assert any("balance" in e for e in _validate_router(bad))

    bad = json.loads(json.dumps(s))
    bad["fleet"]["balance"]["basis"] = ""
    assert any("basis" in e or "evidence" in e
               for e in _validate_router(bad))

    bad = json.loads(json.dumps(s))
    bad["fleet"]["slo"]["per_replica"] = []
    assert _validate_router(bad)


# ----------------------------------------------------------- stub device step


def test_stub_handoff_preserves_token_stream(event_log):
    """The migration lane works on the stub exactly as on devices: the
    same greedy request served end-to-end on one stub engine and split
    prefill->migrate->decode across a stub pair produces IDENTICAL
    tokens (the stub's token rule depends on position + last token, so
    any drop or replay across the handoff would diverge the stream)."""
    solo = _engine()
    solo_rid = solo.submit(Request(_prompt(3), max_new_tokens=5,
                                   temperature=0.0))
    solo.run_until_idle()
    want = solo.finished[solo_rid]["tokens"]

    router = Router([_engine(), _engine()], roles=["prefill", "decode"])
    rid = router.submit(Request(_prompt(3), max_new_tokens=5,
                                temperature=0.0))
    _drain(router)
    got = router.finished[rid]["tokens"]
    np.testing.assert_array_equal(got, want)
    # and it really crossed replicas
    assert router.stats["handoffs"] == 1
    # compile-free by construction: the stub never built a jax program
    assert solo.serving_summary()["decode_signatures"] in (0, 1)


# ----------------------------------------------------------------- replay CLI


def test_trace_replay_small_run_is_valid_and_attributable(tmp_path,
                                                          capsys):
    """The tier-1 twin of the 10^5 acceptance run: a 10^3-request replay
    through the real Router on stub engines completes in-process,
    produces a schema-valid FLEETREPORT with a non-``unknown`` verdict,
    reconciles the decision ledger exactly, and the CLI emits the
    metric JSON line + writes report/ledger/trace artifacts."""
    from torchdistpackage_tpu.tools.trace_replay import main

    report = tmp_path / "FLEETREPORT.json"
    ledger = tmp_path / "ledger.jsonl"
    trace = tmp_path / "trace.json"
    rc = main(["--n-requests", "1000", "--num-slots", "8",
               "--diurnal-period", "256",
               "--report", str(report), "--ledger", str(ledger),
               "--trace", str(trace)])
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    (rec,) = [r for r in lines if r.get("metric") == "trace-replay"]
    assert rec["report_valid"] and rec["attribution_complete"]
    assert rec["fleet_verdict"] != "unknown"
    assert rec["n_requests"] == 1000
    assert {"fleet_goodput_tok_s", "fleet_slo_attainment",
            "migration_count", "migration_bytes"} <= set(rec)

    # --report follows the RUNREPORT convention: JSON at the path,
    # rendered markdown at the sibling .md
    rep = json.loads(report.read_text())
    assert rep["router"]["fleet"]["goodput_tok_s"] > 0
    assert rep["counters"]["attribution"]["complete"]
    assert "## Router fleet" in (tmp_path / "FLEETREPORT.md").read_text()
    led = [json.loads(ln) for ln in ledger.read_text().splitlines()]
    assert {r["kind"] for r in led} <= ROUTER_EVENT_KINDS
    assert sum(r["kind"] == "route_decision" for r in led) == 1000
    tr = json.loads(trace.read_text())
    pids = {e.get("pid") for e in tr["traceEvents"]}
    assert 99 in pids and 100 in pids


def test_trace_replay_mixed_traffic_no_starvation():
    """The PR-20 mixed-traffic probe, compile-free on stub engines: long
    documents injected into a short-request stream flow through the
    Router without starving the short class — short p99 latency (ticks)
    stays BELOW the long class's p50, every injected document completes,
    and the ledger attribution still reconciles (long submissions are
    route decisions like any other)."""
    from torchdistpackage_tpu.tools.trace_replay import run_replay

    out = run_replay(n_requests=160, n_replicas=3, num_slots=8, seed=3,
                     long_docs=3, long_doc_len=384, curve_every=64)
    assert out["validation_errors"] == []
    assert out["attribution"]["complete"]
    mt = out["mixed_traffic"]
    assert mt["long_docs"] == 3 and mt["long"]["n"] == 3
    assert mt["short"]["n"] + mt["long"]["n"] <= out["submitted"]
    assert mt["short"]["n"] > 100
    # the starvation claim: a 384-token document takes ~24 prefill
    # chunks through the prefill tier, yet the short class's tail
    # latency stays below even the MEDIAN long-document latency
    assert mt["short"]["p99_wait_ticks"] < mt["long"]["p50_wait_ticks"]
    # and the long class is not being silently deprioritized to death
    assert mt["long"]["p99_wait_ticks"] < out["ticks"]


@pytest.mark.slow
def test_trace_replay_100k_acceptance(capsys):
    """The acceptance run: 10^5 requests through the real Router +
    StubDeviceStep fleet on CPU, inside the slow-tier budget, schema
    valid, non-``unknown`` verdict, every placement attributable."""
    from torchdistpackage_tpu.tools.trace_replay import run_replay

    out = run_replay(n_requests=100_000)
    out.pop("events")
    assert out["submitted"] == 100_000
    assert out["validation_errors"] == []
    assert out["attribution"]["complete"], out["attribution"]
    fleet = out["summary"]["fleet"]
    assert fleet["verdict"] != "unknown"
    assert fleet["balance"]["verdict"] in ("balanced", "skewed", "degraded")
    assert fleet["goodput_tok_s"] > 0
    assert out["attribution"]["ledger_route_decisions"] == 100_000
    # the diurnal peak really exercised the cross-replica machinery
    assert out["attribution"]["handoffs"] > 0


def test_trace_replay_autoscale_chaos_twin(tmp_path, capsys):
    """Tier-1 twin of the PR-19 elastic acceptance run: a 10^3-request
    ``--autoscale --chaos --ab`` replay with provisioned spares.  Both
    arms share a config hash (same offered load, same fleet, only the
    controller differs), the autoscaled arm's attainment is strictly
    better, every non-hold ``scale_decision`` reconciles with the
    controller's action count, the transport fault plan fired, and the
    curves landed in the report."""
    from torchdistpackage_tpu.tools.trace_replay import main

    report = tmp_path / "FLEETREPORT.json"
    ledger = tmp_path / "ledger.jsonl"
    rc = main(["--n-requests", "1000", "--num-slots", "8",
               "--replicas", "3", "--spares", "1",
               "--diurnal-period", "256", "--curve-every", "64",
               "--eval-every", "16", "--cooldown", "48",
               "--queue-high", "1.0",
               "--autoscale", "--chaos", "--ab",
               "--report", str(report), "--ledger", str(ledger)])
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    (rec,) = [r for r in lines if r.get("metric") == "trace-replay"]
    (ab,) = [r for r in lines if r.get("metric") == "trace-replay-ab"]
    # the fleet counters ride the metric line
    assert {"autoscale_actions", "migration_retry_count",
            "transport_fallback_count"} <= set(rec)
    assert rec["report_valid"] and rec["attribution_complete"]
    assert rec["autoscale_actions"] >= 1
    assert rec["migration_retry_count"] >= 1
    # A/B at equal config hash: elasticity must WIN on attainment
    assert ab["config_hash_match"], ab
    assert ab["baseline_valid"], ab
    assert ab["win"] and ab["attainment_delta"] > 0, ab

    rep = json.loads(report.read_text())
    asc = rep["counters"]["autoscale"]
    assert asc["verdict"] in ("elastic", "thrashing"), asc
    att = rep["counters"]["attribution"]
    assert att["scale_actions"] == att["ledger_scale_actions"] >= 1
    curves = rep["counters"]["curves"]
    assert len(curves["tick"]) >= 2
    assert len(curves["attainment"]) == len(curves["tick"])
    assert len(curves["n_alive"]) == len(curves["tick"])
    # the fleet really flexed: replica count moved during the run
    assert len(set(curves["n_alive"])) >= 2, curves["n_alive"]
    assert rep["counters"]["chaos"]["fired"] >= 1
    # ledger JSONL stays inside the router lane, scale decisions on it
    led = [json.loads(ln) for ln in ledger.read_text().splitlines()]
    assert {r["kind"] for r in led} <= ROUTER_EVENT_KINDS
    assert any(r["kind"] == "scale_decision" for r in led)


@pytest.mark.slow
def test_trace_replay_100k_elastic_chaos_acceptance():
    """The PR-19 acceptance run: 10^5 requests with autoscaling, parked
    spares, and a seeded transport-fault plan (death included) — the
    report validates, attribution (scale decisions included) reconciles
    exactly, and attainment strictly beats the autoscaling-disabled arm
    at the SAME config hash."""
    from torchdistpackage_tpu.tools.trace_replay import run_replay

    kw = dict(n_requests=100_000, n_replicas=4, n_spares=2, chaos=True,
              chaos_faults=24,
              autoscale_kw={"eval_every": 64, "cooldown": 192,
                            "queue_high": 4.0})
    on = run_replay(autoscale=True, **kw)
    on.pop("events")
    off = run_replay(autoscale=False, **kw)
    off.pop("events")
    assert on["config_hash"] == off["config_hash"]
    for out in (on, off):
        assert out["submitted"] == 100_000
        assert out["validation_errors"] == []
        assert out["attribution"]["complete"], out["attribution"]
    assert on["attribution"]["scale_actions"] >= 1
    assert on["attribution"]["ledger_scale_actions"] == (
        on["attribution"]["scale_actions"])
    att_on = on["summary"]["fleet"]["attainment"]
    att_off = off["summary"]["fleet"]["attainment"]
    assert att_on > att_off, (att_on, att_off)
    assert len(on["curves"]["tick"]) >= 10
    assert len(set(on["curves"]["n_alive"])) >= 2
