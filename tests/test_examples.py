"""CI smoke for every example script: each runs end-to-end on the 8-device
CPU sim in a subprocess (examples configure their own platform via
TDP_CPU_SIM, so they must NOT inherit this test process's JAX).  The analogue
of the reference treating its examples/ as the de-facto test suite
(SURVEY.md §4) — but actually wired into CI.

obs-integrated examples additionally get TDP_RUNREPORT pointed at a temp
file and must leave a schema-valid ``RUNREPORT.json`` behind — the driver
artifacts are self-reporting, not just exit-code-0."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = pathlib.Path(__file__).resolve().parent.parent
EXAMPLES = sorted(
    p.name
    for pat in ("train_*.py", "serve_*.py")
    for p in (REPO / "examples").glob(pat)
)

# Examples wired through obs.Telemetry: each must produce a valid
# RUNREPORT.json under the CI runner.  Per-example extra assertions probe
# the counters the example exists to report; ``comm`` names the ledger
# dimension the example's parallelism must show bytes for, and those
# examples also get TDP_TRACE pointed at a temp file that must come back
# as a valid Perfetto-loadable Chrome trace.
OBS_EXAMPLES = {
    "train_llama.py": {},
    # ``numerics`` probes the PR-7 section: train_tp_dp fuses
    # numerics_stats into its compiled step (healthy run: timeline + dtype
    # ledger, zero alerts); train_resilient's chaos NaN spike must appear
    # as a numerics_alert BEFORE the rollback event on the timeline
    # ``autoplan`` probes the PR-13 section: train_tp_dp's planner phase
    # plans the layout from the three cost models, proves the chosen plan
    # trains, and records the validated section + plan_selected event
    "train_tp_dp.py": {"comm": "dp", "memory": True, "numerics": "healthy",
                       "autoplan": True},
    "train_pipeline.py": {"counter": "pipeline", "field": "bubble_fraction"},
    "train_interleaved_pipeline.py": {
        "counter": "pipeline", "field": "bubble_fraction"},
    # zero-bubble A/B (PR 14): the report's pipeline section must carry
    # the validated zb-vs-1f1b bubble pair (validate_runreport enforces
    # zb strictly below the 1f1b reference) and the schedule-build events
    "train_zb_pipeline.py": {
        "counter": "pipeline", "field": "bubble_fraction", "zb": True},
    # ``autoplan`` additionally probes the PR-18 MoE planner phase: the
    # ep-arm enumeration, the chosen plan's GSPMD training proof, and the
    # validated section riding the same RUNREPORT
    "train_moe.py": {"counter": "moe", "field": "imbalance", "comm": "moe",
                     "autoplan": True},
    # overlap-audited examples (PR 3): GSPMD FSDP's param all-gathers and
    # the ZeRO owner-scatter both ledger onto the data axis.  ``memory``
    # probes the PR-6 mem-ledger section; for the FSDP example the probe
    # additionally demands SHARDED leaf evidence (resident < global) —
    # ZeRO-3 proven from the compiled program's own input layouts
    "train_fsdp_offload.py": {"comm": "dp", "memory": "sharded"},
    "train_zero_ema_ckpt.py": {"comm": "dp"},
    # self-healing loop (PR 4): chaos NaN spike -> rollback -> recovered;
    # the report must carry the resilience verdict AND the fault/rollback
    # events on its timeline
    "train_resilient.py": {"comm": "dp", "resilience": "recovered",
                           "numerics": "alert_before_rollback"},
    # continuous-batching engine (PR 5): the report must carry the serving
    # section (TTFT/TPOT, tokens/s, occupancy, pool) with the compile-once
    # evidence, plus the request lifecycle events.  "stress" (PR 9) adds
    # the per-priority percentiles + verdict and the SIGTERM drain demo's
    # engine_drained event
    "serve_gpt.py": {"serving": "stress"},
    # context-parallel long-context tier (PR 20): the serving section must
    # carry the ``long_context`` block (cp width, ring hop/byte totals that
    # reconcile with the hop model) and the cp_prefill_chunk / cp_ring_hop
    # events — with the compile-once evidence intact despite the ring
    "serve_long_context.py": {"serving": "long_context"},
    # multi-replica router (PR 15): the report must carry the validated
    # ``router`` section — per-replica serving sections + the fleet
    # roll-up with affinity/migration evidence — and the routing /
    # handoff / degradation events on the timeline
    "serve_router.py": {"router": True},
}


@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs_on_cpu_sim(script, tmp_path):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env.pop("TDP_RUNREPORT", None)
    env["TDP_CPU_SIM"] = "8"
    env["TDP_SMOKE"] = "1"  # examples that support it shrink their step count
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get("PYTHONPATH", "")
    env.pop("TDP_TRACE", None)
    report_path = trace_path = None
    if script in OBS_EXAMPLES:
        report_path = tmp_path / "RUNREPORT.json"
        env["TDP_RUNREPORT"] = str(report_path)
        if OBS_EXAMPLES[script].get("comm"):
            trace_path = tmp_path / "trace.json"
            env["TDP_TRACE"] = str(trace_path)
    res = subprocess.run(
        [sys.executable, str(REPO / "examples" / script)],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert res.returncode == 0, (
        f"{script} failed (rc={res.returncode})\n"
        f"--- stdout ---\n{res.stdout[-2000:]}\n--- stderr ---\n{res.stderr[-2000:]}"
    )
    if report_path is None:
        return

    # the run must leave a schema-valid, self-consistent report behind
    from torchdistpackage_tpu.obs import validate_runreport

    assert report_path.exists(), (
        f"{script} exited 0 but wrote no RUNREPORT.json\n{res.stdout[-1000:]}")
    report = json.loads(report_path.read_text())
    errs = validate_runreport(report)
    assert errs == [], f"{script} RUNREPORT invalid: {errs}"
    assert report["steps"] > 0
    assert report["step_time_s"]["n"] > 0
    assert report["compile"]["count"] >= 1
    # markdown sibling rides along
    assert report_path.with_suffix(".md").exists()

    probe = OBS_EXAMPLES[script]
    if probe.get("counter"):
        counters = report["counters"]
        assert probe["counter"] in counters, (script, counters)
        val = counters[probe["counter"]][probe["field"]]
        assert isinstance(val, (int, float)) and val >= 0.0, (script, val)
        if probe["field"] == "bubble_fraction":
            assert val < 1.0
        if probe["counter"] == "moe":
            assert sum(counters["moe"]["expert_tokens"]) > 0

    if probe.get("zb"):
        # the zero-bubble A/B's evidence: schedule named, the zb bubble
        # strictly below the paired 1f1b reference, timed arms recorded,
        # and the schedule-build events on the timeline
        pipe = report["counters"]["pipeline"]
        assert pipe["schedule"] == "zb", pipe
        assert pipe["bubble_fraction"] < pipe["bubble_fraction_1f1b"], pipe
        assert pipe["step_time_zb_s"] > 0 and pipe["step_time_1f1b_s"] > 0
        kinds = {e["kind"] for e in report["events"]}
        assert {"zb_wgrad_deferred", "zb_cooldown_filled"} <= kinds, kinds

    if probe.get("resilience"):
        res = report.get("resilience")
        assert res, (script, "no resilience section")
        assert res["verdict"] == probe["resilience"], (script, res)
        assert res["rollbacks"] >= 1 and res["faults_injected"] >= 1, res
        kinds = {e["kind"] for e in report["events"]}
        assert {"fault_injected", "rollback"} <= kinds, (script, kinds)

    if probe.get("serving"):
        srv = report.get("serving")
        assert srv, (script, "no serving section")
        assert srv["requests"]["completed"] > 0, srv
        assert srv["tokens_per_sec"] > 0, srv
        for key in ("ttft_s", "tpot_s"):
            assert {"p50", "p95", "p99"} <= set(srv[key]), (key, srv[key])
        assert 0.0 < srv["slot_occupancy"]["mean"] <= 1.0, srv
        assert 0.0 < srv["kv_pool"]["mean_utilization"] <= 1.0, srv
        # compile-once: one decode + one prefill signature for the whole run
        assert srv["decode_signatures"] == 1, srv
        assert srv["prefill_signatures"] == 1, srv
        kinds = {e["kind"] for e in report["events"]}
        assert {"request_admitted", "prefill_chunk",
                "request_retired", "slots_snapshot"} <= kinds, kinds
        if probe["serving"] == "stress":
            from torchdistpackage_tpu.obs import SERVING_VERDICTS

            assert srv["verdict"] in SERVING_VERDICTS, srv["verdict"]
            prios = srv["priorities"]
            assert len(prios) >= 2, (script, prios)
            for row in prios.values():
                assert {"p50", "p95", "p99"} <= set(row["ttft_s"]), row
            # the SIGTERM demo drained and its events hit the timeline
            assert "engine_drained" in kinds, kinds
            assert "preemption" in kinds, kinds  # the real signal arrived
            # the fast-path phase: shared-system-prompt traffic hit the
            # prefix cache and the speculative engine drove the run the
            # report records (hit/accept rates validated in [0, 1])
            assert srv["prefix_hit_rate"] > 0, srv
            assert 0.0 <= srv["spec_accept_rate"] <= 1.0, srv
            assert srv["spec"]["k"] >= 1, srv
            assert {"prefix_hit", "spec_draft", "spec_verify"} <= kinds, kinds
        if probe["serving"] == "long_context":
            lc = srv.get("long_context")
            assert lc, (script, "no long_context block")
            assert lc["cp"] >= 2 and lc["cp_axis"], lc
            assert lc["prefill_chunks"] > 0, lc
            # every ring hop the engine booked is on the timeline's model:
            # hops = calls * 4 * (cp-1) * nlayers, bytes follow the pool
            assert lc["ring_hops"] > 0 and lc["ring_bytes"] > 0, lc
            assert lc["ring_hops"] % lc["prefill_calls"] == 0, lc
            assert {"cp_prefill_chunk", "cp_ring_hop"} <= kinds, kinds

    if probe.get("router"):
        rt = report.get("router")
        assert rt, (script, "no router section")
        fleet = rt["fleet"]
        # disaggregation + affinity did the work: warm traffic landed on
        # its KV, every request handed prefill->decode by block
        # migration, warm handoffs shared prefix blocks on arrival
        assert fleet["affinity"]["hit_rate"] > 0, fleet["affinity"]
        assert fleet["migrations"]["handoffs"] >= 1, fleet["migrations"]
        assert fleet["migrations"]["bytes"] > 0, fleet["migrations"]
        assert fleet["migrations"]["shared_blocks"] > 0, fleet["migrations"]
        # the chaos phase killed a replica: evacuated, fleet degraded
        assert fleet["verdict"] == "degraded", fleet
        assert fleet["evacuations"] >= 1 and fleet["n_alive"] < len(
            rt["replicas"]), fleet
        # the elastic phase (PR 19): the autoscaler revived the corpse
        # under the burst and parked the surplus in the calm tail, and
        # the chunked wire healed its seeded chunk drop under the retry
        # budget (no re-prefill fallback spent)
        asc = fleet["autoscale"]
        assert asc["verdict"] == "elastic", asc
        assert asc["scale_ups"] >= 1 and asc["scale_downs"] >= 1, asc
        assert fleet["migrations"]["retries"] >= 1, fleet["migrations"]
        assert fleet["migrations"]["fallbacks"] == 0, fleet["migrations"]
        # compile-once per live decode replica
        for row in rt["replicas"]:
            if row["alive"] and row["role"] in ("decode", "both"):
                assert row["decode_signatures"] == 1, row
        kinds = {e["kind"] for e in report["events"]}
        assert {"request_routed", "blocks_migrated", "request_migrated",
                "replica_degraded", "scale_decision",
                "migration_retry"} <= kinds, kinds

    if probe.get("autoplan"):
        # the PR-13 planner section: a chosen plan with per-term score
        # breakdowns, candidate/pruned accounting, and the selection
        # event on the timeline (validate_runreport already ranged it)
        aps = report.get("autoplan")
        assert aps, (script, "no autoplan section")
        assert aps["verdict"] == "ok" and aps["chosen"], aps
        assert aps["chosen"]["terms"] is not None
        assert aps["n_candidates"] > 0
        assert 0 <= aps["n_pruned_oom"] <= aps["n_candidates"]
        kinds = {e["kind"] for e in report["events"]}
        assert "plan_selected" in kinds, kinds
        if script == "train_moe.py":
            # PR 18: the MoE planner emitted real ep arms — the chosen
            # plan carries the ep mesh factor and the ranked set crossed
            # in ep>1 candidates (8 experts / 8 sim devices)
            assert "ep" in aps["chosen"]["mesh_axes"], aps["chosen"]
            assert any(r.get("ep", 1) > 1 for r in aps["ranked"]), aps

    if probe.get("memory"):
        # the PR-6 memory section: per-program static breakdown captured
        # through the same AOT hook as the comm ledger, verdict validated
        mem = report["memory"]
        from torchdistpackage_tpu.obs import MEM_VERDICTS

        assert mem["verdict"] in MEM_VERDICTS, mem
        progs = mem["programs"]
        assert progs, (script, "no static mem ledgers captured")
        for p in progs:
            assert p["argument_bytes"] > 0, (script, p)
            assert p["peak_estimate_bytes"] >= p["temp_bytes"], (script, p)
        if probe["memory"] == "sharded":
            # FSDP evidence: at least one param leaf resident at a
            # fraction of its replicated (global) estimate
            rows = [r for p in progs for r in p.get("per_leaf", [])]
            sharded = [r for r in rows if r["shard_count"] > 1]
            assert sharded, (script, "no sharded leaves evidenced")
            assert all(
                r["resident_bytes"] < r["global_bytes"] for r in sharded)
            assert any(r["shard_count"] >= 8 for r in sharded), (
                script, "expected a fully FSDP-sharded leaf on the "
                "8-device sim", sorted({r['shard_count'] for r in sharded}))

    if probe.get("numerics"):
        num = report["numerics"]
        if probe["numerics"] == "healthy":
            # in-step stats flowed: per-step timeline with finite norms,
            # a dtype ledger from the compiled step, zero alerts
            assert num["timeline"], (script, "empty numerics timeline")
            assert num["summary"]["grad_norm_final"] > 0, num["summary"]
            assert num["alerts"]["count"] == 0, (script, num["alerts"])
            assert num["dtype_ledgers"], (script, "no dtype ledger")
            per = num["dtype_ledgers"][0]["per_dtype"]
            assert any(b["flops"] > 0 for b in per.values()), per
        if probe["numerics"] == "alert_before_rollback":
            # the chaos NaN spike surfaces as a numerics_alert, and it
            # lands on the timeline BEFORE the rollback decision
            assert num["alerts"]["by_reason"].get("nonfinite_loss"), num
            ev = report["events"]
            alert_t = min(e["t_mono"] for e in ev
                          if e["kind"] == "numerics_alert")
            rollback_t = min(e["t_mono"] for e in ev
                             if e["kind"] == "rollback")
            assert alert_t < rollback_t, (script, alert_t, rollback_t)

    if probe.get("comm"):
        # the comm section must ledger this example's parallelism dimension
        comm = report["comm"]
        assert comm, (script, "empty comm section")
        per_dim = comm["ledger"]["per_dim"]
        assert probe["comm"] in per_dim, (script, per_dim)
        assert per_dim[probe["comm"]]["bytes"] > 0, (script, per_dim)
        assert comm["verdict"] in ("comm-bound", "compute-bound", "unknown")
        # and the Perfetto trace must exist and validate
        from torchdistpackage_tpu.obs import validate_trace

        assert trace_path.exists(), f"{script} wrote no trace.json"
        trace = json.loads(trace_path.read_text())
        assert validate_trace(trace) == [], script
        assert any(e.get("ph") == "X" for e in trace["traceEvents"]), script


def test_examples_discovered():
    # guard against the glob silently matching nothing
    assert len(EXAMPLES) >= 6, EXAMPLES
