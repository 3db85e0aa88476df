"""Serving observability (PR 11), host-side half: request-lifecycle
assembly from synthetic event streams, Perfetto rendering (flow tracks,
tick phase lanes, counter tracks), the ``serving_metrics`` live-export
schema through the real exporter sinks, the RUNREPORT ``serving.slo``
validation ranges, and the markdown rendering.

Everything here processes plain dicts — NO engine, NO compiled program,
zero tier-1 compile budget.  The engine-integrated half (calibration
convergence, the preempt→drain→resume lifecycle reconstructed from a
real run) rides the module-scope engine in test_serving_fastpath.py."""

import json

from torchdistpackage_tpu.obs.exporters import (
    JsonlSink,
    PrometheusTextfileSink,
)
from torchdistpackage_tpu.obs.report import (
    _validate_serving,
    render_markdown,
    render_summary_line,
)
from torchdistpackage_tpu.obs.trace import chrome_trace_events, validate_trace
from torchdistpackage_tpu.serving.tracing import (
    REQUEST_PHASES,
    SERVING_METRICS_SCHEMA,
    TICK_PHASES,
    TICK_TIDS,
    assemble_request_timelines,
    lifecycle_phases,
    phase_table,
    request_trace_events,
    serving_metrics_record,
    serving_trace_events,
    tick_trace_events,
    validate_request_record,
)


def _ev(kind, t, **fields):
    return {"type": "event", "kind": kind, "t_wall": t, "t_mono": t,
            "process": 0, **fields}


#: where each phase's span lies inside a synthetic tick, as shares of its
#: length: measured starts and ends with gaps between them (the host's own
#: work, e.g. building the call's arrays between ``sched`` and ``prefill``)
_TICK_LAYOUT = {"audit": (0.0, 0.1), "sched": (0.1, 0.2),
                "prefill": (0.25, 0.45), "draft": (0.45, 0.5),
                "decode": (0.5, 0.85), "fetch": (0.85, 0.95)}


def _tick(n, t0, t1, *, prefill=(), decode=(), spec=False, queue=0,
          busy=0, **extra):
    dur = t1 - t0
    spans = [[f"tdp:engine.{name}", t0 + a * dur, t0 + b * dur]
             for name, (a, b) in _TICK_LAYOUT.items()]
    phases = {name: (b - a) * dur for name, (a, b) in _TICK_LAYOUT.items()}
    phases["host"] = dur - sum(phases.values())
    return _ev("engine_tick", t1, tick=n, t_start=t0, tick_s=dur,
               phases=phases, spans=spans, queue_depth=queue, busy=busy,
               admitted=extra.pop("admitted", 0), expired=0,
               prefill_slots=len(prefill), decode_slots=len(decode),
               batch_util=len(decode) / 4, pool_util=0.5,
               emitted_tokens=len(decode), prefix_hit_rate=0.5,
               spec_accept_rate=0.25, spec=spec,
               prefill_rids=list(prefill), decode_rids=list(decode),
               **extra)


def _synthetic_stream():
    """One request's full life, hand-written: submit -> admit -> two
    prefill chunks -> two verify ticks -> preempt -> requeue -> re-admit
    -> decode -> drain; then a second engine resumes it (rid reused!) and
    retires it.  Plus a shed request for the terminal coverage."""
    ev = [
        _ev("request_submitted", 1.0, rid=0, prompt_len=8,
            max_new_tokens=6, priority=0, deadline_s=None),
        _ev("request_submitted", 1.1, rid=1, prompt_len=8,
            max_new_tokens=6, priority=0, deadline_s=1e-4),
        _ev("request_shed", 1.2, rid=1, reason="deadline_unmeetable",
            priority=0),
        _ev("request_admitted", 2.0, rid=0, slot=0, prompt_len=8,
            queue_wait_s=1.0),
        _tick(1, 2.0, 2.5, prefill=[0], admitted=1),
        _tick(2, 2.5, 3.0, prefill=[0]),
        _tick(3, 3.0, 3.5, decode=[0], spec=True, busy=1),
        _tick(4, 3.5, 4.0, decode=[0], spec=True, busy=1),
        _ev("request_preempted", 4.2, rid=0, slot=0, priority=0,
            by_rid=7, by_priority=5),
        _ev("request_admitted", 5.0, rid=0, slot=1, prompt_len=8,
            queue_wait_s=0.8),
        _tick(5, 5.0, 5.5, prefill=[0], admitted=1),
        _tick(6, 5.5, 6.0, decode=[0], spec=True, busy=1),
        _ev("engine_drained", 6.5, n_inflight=1, n_queued=0,
            persisted=False),
        # the restarted engine: rid 0 again — a NEW instance
        _ev("request_submitted", 7.0, rid=0, prompt_len=12,
            max_new_tokens=4, priority=0, deadline_s=None),
        _ev("request_resumed", 7.01, rid=0, orig_rid=0, emitted_tokens=2,
            shed=False),
        _ev("request_admitted", 7.1, rid=0, slot=0, prompt_len=12,
            queue_wait_s=0.1),
        _tick(7, 7.1, 7.6, prefill=[0], admitted=1),
        _tick(8, 7.6, 8.0, decode=[0], spec=True, busy=1),
        _ev("request_retired", 8.2, rid=0, slot=0, reason="max_tokens",
            new_tokens=6, priority=0, ttft_s=0.6),
    ]
    return ev


def test_assemble_lifecycle_preempt_and_resume_links():
    records = assemble_request_timelines(_synthetic_stream())
    assert len(records) == 3  # two rid-0 instances + the shed rid 1
    for rec in records:
        assert validate_request_record(rec) == [], rec
    first, shed, second = records
    assert first["uid"] == "0.0" and second["uid"] == "0.1"
    assert lifecycle_phases(first) == [
        "queued", "admitted", "prefill", "decode", "preempted", "queued",
        "admitted", "prefill", "decode", "drained"]
    assert first["terminal"] == "drained" and first["preemptions"] == 1
    assert lifecycle_phases(shed) == ["queued", "shed"]
    assert lifecycle_phases(second) == [
        "queued", "admitted", "prefill", "decode", "retired"]
    # the drain->resume link is bidirectional and instance-exact
    assert first["resumed_to"] == "0.1"
    assert second["resumed_from"] == "0.0"
    # spec ticks render as verify ticks; spans use the phase vocabulary
    assert {c["name"] for c in first["ticks"]} == {"prefill_chunk",
                                                   "verify_tick"}
    assert all(sp["name"] in REQUEST_PHASES for sp in first["spans"])
    # spans are time-ordered and contiguous-or-later
    ts = [sp["t0"] for sp in first["spans"]]
    assert ts == sorted(ts)


def test_request_trace_events_flows_and_validity():
    events = _synthetic_stream()
    out = request_trace_events(events)
    assert validate_trace({"traceEvents": out}) == []
    # async begin/end pairs balance per id
    for uid in ("0.0", "0.1"):
        b = [e for e in out if e["ph"] == "b" and e["id"] == uid]
        e_ = [e for e in out if e["ph"] == "e" and e["id"] == uid]
        assert len(b) == len(e_) > 0
    flows = [e for e in out if e.get("cat") == "flow"]
    names = {e["name"] for e in flows}
    assert names == {"requeue", "resume"}  # preempt->re-admit AND restart
    for s in (e for e in flows if e["ph"] == "s"):
        (f,) = [e for e in flows if e["ph"] == "f" and e["id"] == s["id"]]
        assert f["ts"] >= s["ts"]
    # instants carry the marks
    marks = {e["name"] for e in out if e["ph"] == "n"}
    assert {"admitted", "preempted", "drained"} <= marks


def test_tick_trace_events_phase_lanes_and_counters():
    events = _synthetic_stream()
    out = tick_trace_events(events)
    assert validate_trace({"traceEvents": out}) == []
    xs = [e for e in out if e["ph"] == "X"]
    # one lane a measured phase; ``host`` is the remainder and has neither
    # a start nor an end, so no lane and no lane name
    lanes = {t for p, t in TICK_TIDS.items() if p != "host"}
    assert {e["tid"] for e in xs} == lanes
    assert {e["tid"] for e in out if e["ph"] == "M"} == lanes
    # the lanes are MEASURED: each span sits where the event's ``spans``
    # put it, so the gap between sched's end and prefill's start (5% of the
    # tick) is there to read, not closed up from the tick's start
    t0 = min(e["t_start"] for e in events if e["kind"] == "engine_tick")
    tick1 = {e["name"]: e for e in xs if e["args"]["tick"] == 1}
    (ev1,) = [e for e in events if e.get("tick") == 1]
    for name, s0, s1 in ev1["spans"]:
        lane = tick1[name.rpartition(".")[2]]
        assert abs(lane["ts"] - (s0 - t0) * 1e6) < 0.01
        assert abs(lane["dur"] - (s1 - s0) * 1e6) < 0.01
    gap = tick1["prefill"]["ts"] - (tick1["sched"]["ts"] + tick1["sched"]["dur"])
    assert abs(gap - 0.05 * ev1["tick_s"] * 1e6) < 0.01
    counters = {e["name"] for e in out if e["ph"] == "C"}
    assert {"serving_queue_depth", "serving_slots", "serving_utilization",
            "serving_rates"} <= counters
    # negative timestamps would make Perfetto refuse the file
    assert all(e.get("ts", 0) >= 0 for e in out if e["ph"] != "M")
    # a record without ``spans`` (a file from before they were measured)
    # draws nothing for the lanes and keeps its counters
    old = [{k: v for k, v in e.items() if k != "spans"} for e in events]
    drawn = tick_trace_events(old)
    assert not [e for e in drawn if e["ph"] == "X"]
    assert len([e for e in drawn if e["ph"] == "C"]) == len(
        [e for e in out if e["ph"] == "C"])


def test_chrome_trace_events_appends_serving_and_elides_tick_instants():
    events = _synthetic_stream()
    out = chrome_trace_events([], events=events)
    assert validate_trace({"traceEvents": out}) == []
    cats = {e.get("cat") for e in out}
    assert {"request", "tick", "flow"} <= cats
    # engine_tick events are NOT duplicated as instant pins
    assert not any(e["ph"] == "i" and e["name"] == "engine_tick"
                   for e in out)
    # and the t0 anchor respects t_start: nothing lands negative
    assert all(e["ts"] >= 0 for e in out if e["ph"] != "M")
    assert serving_trace_events([]) == []


def test_serving_metrics_record_through_real_sinks(tmp_path):
    rec = {"tick": 3, "tick_s": 0.5, "phases": {"audit": 0.1, "decode": 0.3},
           "queue_depth": 2, "busy": 3, "prefill_slots": 1,
           "decode_slots": 2, "batch_util": 0.5, "pool_util": 0.7,
           "admitted": 1, "expired": 0, "emitted_tokens": 2,
           "prefix_hit_rate": 0.9, "spec_accept_rate": 0.3}
    flat = serving_metrics_record(rec)
    assert flat["schema"] == SERVING_METRICS_SCHEMA
    assert flat["type"] == "serving_metrics"
    assert flat["busy_slots"] == 3 and flat["phase_decode_s"] == 0.3
    assert set(f"phase_{p}_s" for p in TICK_PHASES) <= set(flat)

    prom = PrometheusTextfileSink(str(tmp_path / "m.prom"),
                                  prefix="tdp_serving", run="t")
    prom.write(flat)
    body = (tmp_path / "m.prom").read_text()
    assert "tdp_serving_queue_depth" in body
    assert "tdp_serving_phase_decode_s" in body
    assert 'run="t"' in body

    jl = JsonlSink(str(tmp_path / "m.jsonl"))
    jl.write(flat)
    jl.close()
    line = json.loads((tmp_path / "m.jsonl").read_text())
    assert line["schema"] == SERVING_METRICS_SCHEMA


def test_phase_table_renders():
    table = phase_table(_synthetic_stream())
    assert table.splitlines()[0].startswith("tick phase breakdown (8 ticks")
    for name in TICK_PHASES:
        assert any(ln.strip().startswith(name) for ln in table.splitlines())
    assert phase_table([]) == "tick phase breakdown: no engine_tick records"
    # under the phases, the time lost to stalls: the stream's two pauses
    # between ticks (4.0 -> 5.0 s preempted, 6.0 -> 7.1 s restarting)
    assert table.splitlines()[-2:] == [
        "  stalls: 2 slow ticks of 8 lost 2.100 s",
        "    in fetch (the device) 0.000 s, anywhere else (the host) 2.100 s"]
    # tick 3's fetch takes 0.6 s longer
    slow = [dict(e) for e in _synthetic_stream()]
    ticks = [e for e in slow if e.get("kind") == "engine_tick"]
    ticks[2]["spans"] = [[n, a, b + (0.6 if n.endswith(".fetch") else 0.0)]
                         for n, a, b in ticks[2]["spans"]]
    ticks[2]["tick_s"] += 0.6
    for e in ticks[3:]:
        e["t_start"] += 0.6
        e["spans"] = [[n, a + 0.6, b + 0.6] for n, a, b in e["spans"]]
    assert phase_table(slow).splitlines()[-2:] == [
        "  stalls: 3 slow ticks of 8 lost 2.700 s",
        "    in fetch (the device) 0.600 s, anywhere else (the host) 2.100 s"]


# ----------------------------------------------- serving.slo validation


def _summary():
    """A minimal well-formed serving summary with the PR-11 fields."""
    return {
        "requests": {"completed": 3, "queued": 0, "in_flight": 0,
                     "shed": 1, "expired": 0, "cancelled": 0,
                     "preempted": 0, "resumed": 0},
        "tokens_per_sec": 100.0,
        "generated_tokens": 30,
        "ttft_s": {"p50": 0.01, "p95": 0.02, "p99": 0.03},
        "tpot_s": {"p50": 0.001, "p95": 0.002, "p99": 0.003},
        "slot_occupancy": {"mean": 0.5},
        "kv_pool": {"mean_utilization": 0.5},
        "verdict": "overloaded",
        "verdict_basis": "demand refused: shed=1, expired=0",
        "verdict_evidence": {"shed": 1, "expired": 0},
        "slo": {
            "goodput_tokens": 20,
            "goodput_tok_s": 80.0,
            "attainment": 0.75,
            "priorities": {"0": {"completed": 3, "met": 3, "missed": 0,
                                 "shed": 1, "expired": 0,
                                 "goodput_tokens": 20,
                                 "attainment": 0.75}},
            "calibration": {"n": 3, "bias": 1.2, "pending": 0,
                            "priorities": {"0": {"n": 3,
                                                 "rel_err_p50": 0.1,
                                                 "rel_err_p95": 0.4}}},
        },
    }


def test_validate_serving_slo_ranges_bite():
    s = _summary()
    assert _validate_serving(s) == []
    # goodput cannot exceed the aggregate rate (same span, subset tokens)
    bad = _summary()
    bad["slo"]["goodput_tok_s"] = 150.0
    assert any("goodput" in e for e in _validate_serving(bad))
    # attainment is a fraction
    bad = _summary()
    bad["slo"]["attainment"] = 1.5
    assert any("attainment" in e for e in _validate_serving(bad))
    # met + missed must equal completed
    bad = _summary()
    bad["slo"]["priorities"]["0"]["met"] = 1
    assert any("met+missed" in e for e in _validate_serving(bad))
    # calibration bias must be positive, errors non-negative
    bad = _summary()
    bad["slo"]["calibration"]["bias"] = 0.0
    assert any("bias" in e for e in _validate_serving(bad))
    bad = _summary()
    bad["slo"]["calibration"]["priorities"]["0"]["rel_err_p50"] = -0.1
    assert any("rel_err" in e for e in _validate_serving(bad))


def test_validate_serving_verdict_cites_consistent_evidence():
    s = _summary()
    # a verdict contradicting its own counters fails validation
    bad = dict(s, verdict="healthy")
    assert any("contradicts" in e for e in _validate_serving(bad))
    bad = dict(s, verdict="degraded")
    assert any("contradicts" in e for e in _validate_serving(bad))
    # an empty basis fails
    bad = dict(s, verdict_basis="")
    assert any("verdict_basis" in e for e in _validate_serving(bad))
    # consistent degraded summary passes
    ok = _summary()
    ok["requests"]["shed"] = 0
    ok["slo"]["priorities"]["0"]["shed"] = 0
    ok["requests"]["preempted"] = 2
    ok["verdict"] = "degraded"
    ok["verdict_basis"] = "served by degrading: preempted=2"
    assert _validate_serving(ok) == []


def test_render_markdown_slo_table_and_tick_elision():
    report = {
        "schema": "tdp-runreport/v1", "run": "t", "backend": "cpu",
        "n_devices": 1, "n_processes": 1, "steps": 1,
        "step_time_s": {"n": 0}, "spans_mean_s": {}, "throughput": {},
        "mfu": {}, "memory": {}, "numerics": {}, "compile": {},
        "hosts": {"n_hosts": 1, "per_host": []}, "comm": {},
        "counters": {},
        "events": [_ev("run_start", 0.0, run="t"),
                   _tick(1, 1.0, 1.5, decode=[0], busy=1)],
        "serving": {
            **_summary(),
            "tick_accounting": {"ticks": 8, "mean_tick_s": 0.5,
                                "phases_mean_s": {"decode": 0.2,
                                                  "audit": 0.01}},
        },
    }
    md = render_markdown(report)
    assert "| priority | completed | met | missed | shed " in md
    assert "SLO goodput" in md and "TTFT calibration" in md
    assert "tick accounting: 8 ticks" in md
    assert "demand refused" in md  # the verdict cites its basis
    assert "engine_tick` record(s) elided" in md
    line = render_summary_line(report)
    assert "goodput=80.0tok/s(att 75%)" in line
