"""Communication observability: HLO collective ledger, alpha-beta cost
model + calibration, and the Perfetto trace exporter.

Ledger assertions run real compiled steps on the 8-device CPU sim (the
conftest mesh): a TP x DP train step must show the dp grad all-reduce at
~param bytes, and a MoE-style step must show the EP all-to-all classified
into the 'moe' dimension.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.dist.comm_bench import bench_collective
from torchdistpackage_tpu.obs import (
    COMM_RECORD_SCHEMA,
    CommModel,
    Telemetry,
    XlaStepTrace,
    build_trace,
    comm_record,
    comm_report,
    fit_alpha_beta,
    ledger_from_compiled,
    validate_runreport,
    validate_trace,
)
from torchdistpackage_tpu.obs.comm_ledger import (
    _expand_replica_groups,
    classify_axes,
    ledger_from_hlo,
    parse_hlo_collectives,
    render_table,
)
from torchdistpackage_tpu.obs.comm_model import (
    AxisCost,
    steps_for,
    wire_bytes,
)
from torchdistpackage_tpu.obs.events import set_default_event_log


@pytest.fixture(autouse=True)
def _fresh_default_log():
    set_default_event_log(None)
    yield
    set_default_event_log(None)


# ------------------------------------------------------------ HLO parsing


def test_parse_hlo_literal_groups_and_bytes():
    hlo = (
        "%all-reduce.1 = f32[2,16]{1,0} all-reduce(f32[2,16]{1,0} %x), "
        "channel_id=1, replica_groups={{0,2,4,6},{1,3,5,7}}, "
        'use_global_device_ids=true, to_apply=%add, '
        'metadata={op_name="jit(f)/psum"}'
    )
    (rec,) = parse_hlo_collectives(hlo)
    assert rec["op"] == "all-reduce"
    assert rec["bytes"] == 2 * 16 * 4
    assert rec["groups"] == [[0, 2, 4, 6], [1, 3, 5, 7]]
    assert rec["group_size"] == 4
    assert rec["op_name"] == "jit(f)/psum"


def test_parse_hlo_allgather_scales_by_group_size():
    hlo = (
        "%all-gather.1 = f32[4,16]{1,0} all-gather(f32[2,16]{1,0} %x), "
        "channel_id=2, replica_groups={{0,1},{2,3}}, dimensions={0}"
    )
    (rec,) = parse_hlo_collectives(hlo)
    # operand is the local shard; the full payload is shard * group
    assert rec["bytes"] == 2 * 16 * 4 * 2


def test_parse_hlo_skips_references_and_done_ops():
    hlo = "\n".join([
        "%all-to-all.2 = (f32[4,2]{1,0}, f32[4,2]{1,0}) "
        "all-to-all(f32[4,2]{1,0} %a, f32[4,2]{1,0} %b), channel_id=1, "
        "replica_groups={{0,1}}",
        "%gte = f32[4,2]{1,0} get-tuple-element((f32[4,2]{1,0}, "
        "f32[4,2]{1,0}) %all-to-all.2), index=0",
        "%all-gather-done.1 = f32[8]{0} all-gather-done(f32[8]{0} %ags)",
        "ROOT %t = (f32[4,2]{1,0}) tuple(f32[4,2]{1,0} %gte)",
    ])
    recs = parse_hlo_collectives(hlo)
    assert len(recs) == 1
    assert recs[0]["op"] == "all-to-all"
    # variadic form: full payload = sum of operand chunks
    assert recs[0]["bytes"] == 2 * (4 * 2 * 4)


def test_parse_hlo_async_start_counted_once():
    hlo = "\n".join([
        "%ar-start = f32[8]{0} all-reduce-start(f32[8]{0} %x), "
        "channel_id=5, replica_groups={{0,1,2,3}}",
        "%ar-done = f32[8]{0} all-reduce-done(f32[8]{0} %ar-start)",
    ])
    recs = parse_hlo_collectives(hlo)
    assert len(recs) == 1
    assert recs[0]["async"] is True
    assert recs[0]["bytes"] == 32


# jax 0.9 prints operands BY NAME ('all-reduce(%x)'): an operand's type is
# the result type of the instruction it names.  Each case: HLO text, then
# the (op, bytes, async) the parser must read from it.
_PARAMS = (
    "%p = f32[16,16]{1,0} parameter(0)\n"
    "%c = bf16[16,16]{1,0} convert(%p)\n"
    "%g = f32[64,16]{1,0} parameter(1)\n"
)
_GROUP4 = "channel_id=1, replica_groups={{0,1,2,3}}"


def _compiled_int8_ppermute():
    """The line the installed JAX itself prints for a four-device int8
    ``ppermute``: the next JAX that changes the text fails HERE."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    f = jax.jit(shard_map(
        lambda a: jax.lax.ppermute(
            a, "x", [(i, (i + 1) % 4) for i in range(4)]),
        mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    return f.lower(jnp.ones((8, 128), jnp.int8)).compile().as_text()


_BY_NAME_CASES = {
    "all-reduce": (
        _PARAMS + "%ar = f32[16,16]{1,0} all-reduce(%p), " + _GROUP4
        + ", to_apply=%add",
        [("all-reduce", 16 * 16 * 4, False)]),
    "all-gather": (  # the operand is the local shard: x group size
        _PARAMS + "%ag = f32[64,16]{1,0} all-gather(%p), " + _GROUP4
        + ", dimensions={0}",
        [("all-gather", 16 * 16 * 4 * 4, False)]),
    "reduce-scatter": (
        _PARAMS + "%rs = f32[16,16]{1,0} reduce-scatter(%g), " + _GROUP4
        + ", dimensions={0}, to_apply=%add",
        [("reduce-scatter", 64 * 16 * 4, False)]),
    "all-to-all": (  # variadic: the payload is the sum of the operands
        _PARAMS + "%s0 = f32[1,16,16]{2,1,0} bitcast(%p)\n"
        "%a2a = (f32[1,16,16]{2,1,0}, f32[1,16,16]{2,1,0}) "
        "all-to-all(%s0, %s0), channel_id=1, replica_groups={{0,1}}",
        [("all-to-all", 2 * 16 * 16 * 4, False)]),
    "collective-permute": (
        _compiled_int8_ppermute,
        [("collective-permute", 2 * 128, False)]),
    "start-done": (  # the -start's result is a tuple; its operand is not
        _PARAMS + "%ags = (f32[16,16]{1,0}, f32[64,16]{1,0}) "
        "all-gather-start(%p), " + _GROUP4 + ", dimensions={0}\n"
        "%agd = f32[64,16]{1,0} all-gather-done(%ags)",
        [("all-gather", 16 * 16 * 4 * 4, True)]),
    "tuple-result": (  # XLA's combiner: two dtypes in one all-reduce
        _PARAMS + "%ar = (f32[16,16]{1,0}, bf16[16,16]{1,0}) "
        "all-reduce(%p, %c), " + _GROUP4 + ", to_apply=%add",
        [("all-reduce", 16 * 16 * 4 + 16 * 16 * 2, False)]),
    "tpu-layout": (  # a TPU layout carries parentheses of its own
        "%p = s8[2,128]{1,0:T(8,128)(4,1)} parameter(0)\n"
        "%cp = s8[2,128]{1,0:T(8,128)(4,1)} collective-permute(%p), "
        "channel_id=1, source_target_pairs={{0,1},{1,0}}",
        [("collective-permute", 2 * 128, False)]),
    "no-definition": (  # nothing defines %nowhere: an error, never 0 bytes
        "%ar = f32[16,16]{1,0} all-reduce(%nowhere), " + _GROUP4,
        ValueError),
}


@pytest.mark.parametrize("case", list(_BY_NAME_CASES))
def test_parse_hlo_operands_by_name(case, devices8):
    hlo, want = _BY_NAME_CASES[case]
    if callable(hlo):
        hlo = hlo()
    if want is ValueError:
        with pytest.raises(ValueError, match=r"all-reduce\(%nowhere\)"):
            parse_hlo_collectives(hlo)
        return
    recs = parse_hlo_collectives(hlo)
    assert [(r["op"], r["bytes"], r["async"]) for r in recs] == want
    if case == "start-done":
        assert recs[0]["sched_distance"] == 0
    if case == "collective-permute":
        assert sorted(recs[0]["pairs"]) == [(0, 1), (1, 2), (2, 3), (3, 0)]


def test_parse_hlo_overlap_window_records_collectives_inside():
    """TP-under-PP overlap evidence (PR 14): collectives issued between an
    async op's -start and -done land in its ``overlapped_idx``, and
    ``tp_pp_overlap`` classifies them per dimension — here a tensor-axis
    all-gather + reduce-scatter pair inside a pipeline collective-permute
    window, the synergy-schedule ordering zero_bubble.py arranges."""
    from torchdistpackage_tpu.obs.comm_ledger import tp_pp_overlap

    hlo = "\n".join([
        "%cp-start = f32[8]{0} collective-permute-start(f32[8]{0} %x), "
        "channel_id=1, source_target_pairs={{0,2},{2,0},{1,3},{3,1}}",
        "%ag = f32[16]{0} all-gather(f32[8]{0} %a), channel_id=2, "
        "replica_groups={{0,1},{2,3}}, dimensions={0}",
        "%rs = f32[8]{0} reduce-scatter(f32[16]{0} %b), channel_id=3, "
        "replica_groups={{0,1},{2,3}}, dimensions={0}, to_apply=%add",
        "%cp-done = f32[8]{0} collective-permute-done(f32[8]{0} %cp-start)",
        "%ag2 = f32[16]{0} all-gather(f32[8]{0} %c), channel_id=4, "
        "replica_groups={{0,1},{2,3}}, dimensions={0}",
    ])
    recs = parse_hlo_collectives(hlo)
    assert len(recs) == 4
    cp = recs[0]
    assert cp["async"] is True
    # the window holds exactly the two collectives before -done; the
    # post-done all-gather is outside it
    assert cp["overlapped_idx"] == [1, 2]
    assert cp["sched_distance"] == 2
    assert recs[1]["overlapped_idx"] is None  # sync ops carry no window

    # classified through a 2x2 pipe x tensor mesh, the summary reports
    # the tp pair (all payload bytes) inside the pp permute's slack
    import numpy as np

    class _M:
        devices = np.arange(4).reshape(2, 2)
        axis_names = ("pipe", "tensor")
        shape = {"pipe": 2, "tensor": 2}

    class _D:
        def __init__(self, i):
            self.id = i

    _M.devices = np.array([[_D(0), _D(1)], [_D(2), _D(3)]], dtype=object)
    ledger = ledger_from_hlo(hlo, mesh=_M())
    rep = tp_pp_overlap(ledger)
    assert rep["pp_async_ops"] == 1
    assert rep["pp_windows_with_tp"] == 1
    assert rep["tp_ops_in_pp_windows"] == 2
    assert rep["tp_bytes_in_pp_windows"] == (16 * 4) + (16 * 4)
    assert rep["mean_pp_sched_distance"] == 2
    # an all-sync ledger (the CPU sim's shape) reports cleanly as zero
    assert tp_pp_overlap(None)["pp_async_ops"] == 0


def test_expand_replica_groups_iota():
    assert _expand_replica_groups("{{0,1},{2,3}}") == [[0, 1], [2, 3]]
    assert _expand_replica_groups("[2,4]<=[8]") == [
        [0, 1, 2, 3], [4, 5, 6, 7]]
    # transposed iota: arange(8).reshape(4,2).T.reshape(2,4)
    assert _expand_replica_groups("[2,4]<=[4,2]T(1,0)") == [
        [0, 2, 4, 6], [1, 3, 5, 7]]


def test_classify_axes():
    assert classify_axes(("data",)) == "dp"
    assert classify_axes(("moe_dp",)) == "dp"
    assert classify_axes(("tensor",)) == "tp"
    assert classify_axes(("pipe",)) == "pp"
    assert classify_axes(("moe_ep",)) == "moe"
    assert classify_axes(("data", "tensor")) == "other"  # mixed
    # the context axis classifies as cp since ring paged prefill (PR 20)
    # ledgers its ppermute hops there (cp_ring_overlap reads this bucket)
    assert classify_axes(("context",)) == "cp"


# ---------------------------------------------------- ledger on real steps


def test_ledger_tp_dp_step_dp_bytes_match_params(devices8):
    mesh = tpc.setup_process_groups([("data", 4), ("tensor", 2)])
    D = 32
    params = jnp.ones((D, D), jnp.float32)

    def body(p, x):
        # a per-device copy of p, as DataParallel takes its grads: the grad
        # of an UNVARYING p is already summed over the mesh by autodiff
        # (jax 0.9's typed shard_map), and the explicit sync below would be
        # a second all-reduce of the tree
        pv = jax.lax.pcast(p, ("data", "tensor"), to="varying")
        y = jax.lax.psum(x @ pv, "tensor")     # tp activation collective
        loss = (y ** 2).mean()[None]           # one per data shard
        g = jax.grad(lambda p_: ((x @ p_) ** 2).mean())(
            jax.lax.pcast(p, "data", to="varying"))
        g = jax.lax.psum(g, "data")            # dp grad sync
        return loss, g

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(), P("data")),
        out_specs=(P("data"), P())))
    compiled = f.lower(params, jnp.ones((8, D), jnp.float32)).compile()
    ledger = ledger_from_compiled(compiled, mesh=mesh)
    assert ledger is not None and ledger["n_collectives"] >= 2

    dp = ledger["per_dim"].get("dp")
    assert dp is not None, ledger["per_dim"]
    param_bytes = D * D * 4
    # the dp grad all-reduce moves exactly the param tree
    assert dp["bytes"] == param_bytes, (dp, param_bytes)
    assert "tp" in ledger["per_dim"], ledger["per_dim"]

    # mesh axes recorded for downstream consumers
    assert ledger["mesh_axes"] == {"data": 4, "tensor": 2}
    # render_table never crashes and names every dimension present
    table = render_table(ledger)
    assert "dp" in table and "tp" in table


def test_ledger_moe_step_all_to_all_detected(devices8):
    tpc.setup_process_groups([("data", 8)])
    tpc.build_moe_mesh(moe_ep_size=4)
    mesh = tpc.get_view("moe")

    def body(x):
        return jax.lax.all_to_all(
            x, "moe_ep", split_axis=1, concat_axis=0, tiled=True)

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("moe_ep"),), out_specs=P("moe_ep")))
    compiled = f.lower(jnp.ones((16, 8), jnp.float32)).compile()
    ledger = ledger_from_compiled(compiled, mesh=mesh)
    assert ledger is not None
    a2a = [c for c in ledger["collectives"] if c["op"] == "all-to-all"]
    assert a2a, [c["op"] for c in ledger["collectives"]]
    assert a2a[0]["dim"] == "moe"
    assert a2a[0]["axes"] == ["moe_ep"]
    assert ledger["per_dim"]["moe"]["bytes"] > 0


def test_ledger_without_mesh_still_enumerates():
    hlo = (
        "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), "
        "channel_id=1, replica_groups={{0,1}}"
    )
    ledger = ledger_from_hlo(hlo, mesh=None)
    assert ledger["n_collectives"] == 1
    assert ledger["collectives"][0]["dim"] == "other"
    assert ledger["mesh_axes"] is None


# ------------------------------------------------------------- cost model


def test_alpha_beta_math():
    model = CommModel({"data": AxisCost(alpha_s=1e-6, beta_Bps=1e9)})
    n, size = 4, 1 << 20
    # all_reduce: 2(n-1) latency steps + 2(n-1)/n * S wire bytes
    expect = 2 * 3 * 1e-6 + (2 * 3 / 4) * size / 1e9
    got = model.predict("all_reduce", size, n, axes=("data",))
    assert got == pytest.approx(expect, rel=1e-9)
    # hyphenated (ledger) spelling resolves to the same op
    assert model.predict("all-reduce", size, n, axes=("data",)) == got
    # ppermute: single hop, full payload on the wire
    assert model.predict("ppermute", size, n, axes=("data",)) == \
        pytest.approx(1e-6 + size / 1e9, rel=1e-9)
    # n=1: nothing to communicate
    assert model.predict("all_reduce", size, 1, axes=("data",)) == 0.0


def test_steps_and_wire_bytes():
    assert steps_for("all_reduce", 4) == 6
    assert steps_for("all_gather", 4) == 3
    assert steps_for("ppermute", 8) == 1
    assert wire_bytes("all_reduce", 1000, 4) == pytest.approx(1500.0)
    assert wire_bytes("all_gather", 1000, 4) == pytest.approx(750.0)
    assert wire_bytes("ppermute", 1000, 4) == pytest.approx(1000.0)


def test_calibration_fit_recovers_synthetic_alpha_beta():
    alpha, beta = 5e-6, 2.5e9
    rng = np.random.default_rng(0)
    samples = []
    for steps in (1, 3, 6, 14):
        for wire in (1e4, 1e6, 3e7):
            t = steps * alpha + wire / beta
            samples.append((steps, wire, t * rng.uniform(0.98, 1.02)))
    a, b = fit_alpha_beta(samples)
    assert a == pytest.approx(alpha, rel=0.25)
    assert b == pytest.approx(beta, rel=0.1)


def test_fit_alpha_beta_degenerate_latency_only():
    # all timings identical regardless of size: bandwidth unobservable
    a, b = fit_alpha_beta([(1, 0.0, 1e-5), (1, 0.0, 1e-5)])
    assert a == pytest.approx(1e-5)
    assert b == float("inf")


def test_calibrate_on_cpu_sim_mesh(devices8):
    mesh = tpc.setup_process_groups([("data", 4), ("tensor", 2)])
    model = CommModel.calibrate(
        mesh=mesh, sizes=(1 << 12, 1 << 16), ops=("all_reduce",),
        iters=2, warmup=1)
    assert model.source == "calibrated"
    assert set(model.axis_costs) == {"data", "tensor"}
    for c in model.axis_costs.values():
        assert c.kind == "calibrated"
        assert c.alpha_s >= 0.0
        assert c.beta_Bps > 0
    # a calibrated model predicts a finite, sane time for real shapes
    t = model.predict("all_reduce", 1 << 20, 4, axes=("data",))
    assert 0 <= t < 10


def test_comm_report_verdict_and_headroom():
    ledger = ledger_from_hlo(
        "%all-reduce.1 = f32[262144]{0} all-reduce(f32[262144]{0} %x), "
        "channel_id=1, replica_groups={{0,1,2,3}}",
        mesh=None,
    )
    model = CommModel({}, default=AxisCost(1e-6, 1e9), chip="test")
    # comm-bound: modeled comm exceeds modeled compute
    rep = comm_report(ledger, step_time_s=2e-3, model=model,
                      xla_flops=1e6, peak_flops=1e12)
    assert rep["verdict"] == "comm-bound"
    assert rep["modeled_comm_s"] > rep["modeled_compute_s"]
    assert rep["overlap_headroom_s"] >= 0
    # compute-bound: huge compute estimate flips the verdict
    rep2 = comm_report(ledger, step_time_s=2e-3, model=model,
                       xla_flops=1e12, peak_flops=1e12)
    assert rep2["verdict"] == "compute-bound"
    # no step time at all -> explicit unknown, never a crash
    rep3 = comm_report(ledger, step_time_s=None, model=model)
    assert rep3["verdict"] == "unknown"


# ------------------------------------------- comm_bench schema round-trip


def test_bench_collective_emits_obs_schema(devices8, tmp_path):
    mesh = tpc.setup_process_groups([("data", 8)])
    row = bench_collective("all_reduce", "data", nbytes=1 << 12, mesh=mesh,
                           warmup=1, iters=2)
    assert row["schema"] == COMM_RECORD_SCHEMA
    assert row["type"] == "comm"
    for k in ("op", "axis", "bytes", "time_s", "algbw_GBps", "busbw_GBps"):
        assert k in row, row
    assert row["op"] == "all_reduce" and row["axis"] == "data"
    # busbw factor for all_reduce over 8: 2*(8-1)/8
    assert row["busbw_GBps"] == pytest.approx(
        row["algbw_GBps"] * 2 * 7 / 8, rel=1e-9)

    # streams through JsonlSink unchanged (the satellite contract)
    from torchdistpackage_tpu.dist.comm_bench import test_collection

    path = tmp_path / "comm.jsonl"
    rows = test_collection(
        "data", sizes=(1 << 10,), ops=("all_reduce", "ppermute"),
        mesh=mesh, verbose=False, sink=str(path))
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == len(rows) == 2
    assert all(l["schema"] == COMM_RECORD_SCHEMA for l in lines)


def test_comm_record_builder():
    rec = comm_record("all_gather", "tensor", 4096, axis_size=2,
                      time_s=1e-4, algbw_GBps=1.0, busbw_GBps=0.5)
    assert rec["bytes"] == 4096 and rec["axis_size"] == 2
    minimal = comm_record("all_reduce", "data", 128)
    assert "time_s" not in minimal  # annotation-only records are legal


# ------------------------------------------------------------------ trace


def _run_telemetry(n_steps=3, **kw):
    tel = Telemetry(run="trace_test", tokens_per_step=8, report_path="",
                    trace_path="", **kw)
    f = jax.jit(lambda x: x * 2.0)
    step = tel.wrap_step(f)
    for i in range(n_steps):
        out = step(jnp.ones((4,)))
        tel.end_step(step=i, loss=out.sum())
    return tel


def test_trace_export_validates_and_loads(tmp_path):
    tel = _run_telemetry()
    tel.finalize(write=False, print_summary=False)
    from torchdistpackage_tpu.obs import export_trace

    path = tmp_path / "trace.json"
    trace = export_trace(tel, str(path))
    assert validate_trace(trace) == []
    # the file round-trips as JSON and still validates
    loaded = json.loads(path.read_text())
    assert validate_trace(loaded) == []
    evs = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    # every step contributes dispatch/device/fetch spans (data needs a prior
    # step's fetch, so >= 2 of those)
    names = {e["name"].split("[")[0] for e in evs}
    assert {"dispatch", "fetch"} <= names
    assert any(e["name"].startswith("device") for e in evs)
    # instant events from the event log ride along (run_start at least)
    kinds = [e["name"] for e in loaded["traceEvents"] if e["ph"] == "i"]
    assert "run_start" in kinds
    # spans are back-to-back and non-negative
    for e in evs:
        assert e["ts"] >= 0 and e["dur"] >= 0


def test_validate_trace_rejects_garbage():
    assert validate_trace(42)
    assert validate_trace({"no_events": []})
    assert validate_trace({"traceEvents": [{"ph": "Z", "name": "x"}]})
    assert validate_trace(
        {"traceEvents": [{"ph": "X", "name": "x", "ts": 0}]})  # no dur
    assert validate_trace({"traceEvents": []}) == []


def test_build_trace_empty_history_is_valid():
    trace = build_trace([], events=[])
    assert validate_trace(trace) == []


def test_xla_step_trace_window(tmp_path, monkeypatch):
    calls = []
    import jax.profiler as prof

    monkeypatch.setattr(prof, "start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(prof, "stop_trace", lambda: calls.append(("stop",)))
    xt = XlaStepTrace(str(tmp_path), trace_steps=(1, 2))
    for i in range(4):
        xt.on_step_start(i)
        xt.on_step_end(i)
    assert calls == [("start", str(tmp_path)), ("stop",)]
    assert xt.done
    # idempotent after the window
    xt.on_step_start(1)
    assert calls == [("start", str(tmp_path)), ("stop",)]


def test_xla_step_trace_close_stops_inflight(tmp_path, monkeypatch):
    calls = []
    import jax.profiler as prof

    monkeypatch.setattr(prof, "start_trace", lambda d: calls.append("start"))
    monkeypatch.setattr(prof, "stop_trace", lambda: calls.append("stop"))
    xt = XlaStepTrace(str(tmp_path), trace_steps=(0, 99))
    xt.on_step_start(0)
    assert xt.active
    xt.close()
    assert calls == ["start", "stop"] and not xt.active


# -------------------------------------------- Telemetry comm integration


def test_telemetry_runreport_comm_section(devices8, tmp_path):
    mesh = tpc.setup_process_groups([("data", 4), ("tensor", 2)])
    D = 16

    def body(p, x):
        # the grad of a per-device copy, as DataParallel takes it: the grad
        # of an UNVARYING p is already summed over "data" by autodiff (jax
        # 0.9's typed shard_map) and the sync below would be a second
        # all-reduce of the tree — which the ledger reads truthfully
        g = jax.grad(lambda p_: ((x @ p_) ** 2).mean())(
            jax.lax.pcast(p, "data", to="varying"))
        return jax.lax.psum(g, "data").mean()

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(), P("data")), out_specs=P()))
    tel = Telemetry(run="comm_int", report_path="", trace_path="", mesh=mesh)
    step = tel.wrap_step(f)
    p, x = jnp.ones((D, D)), jnp.ones((8, D))
    for i in range(3):
        out = step(p, x)
        tel.end_step(step=i, loss=out)
    report = tel.finalize(write=False, print_summary=False)
    assert validate_runreport(report) == []
    comm = report["comm"]
    assert comm, "comm section missing despite compiled step"
    assert comm["ledger"]["per_dim"]["dp"]["bytes"] == D * D * 4
    assert comm["verdict"] in ("comm-bound", "compute-bound")
    assert "modeled_comm_s" in comm and comm["modeled_comm_s"] >= 0
    assert "measured_step_s" in comm
    # ledger rows carry the fields the record schema promises
    for c in comm["ledger"]["collectives"]:
        for k in ("op", "bytes", "axes", "dim"):
            assert k in c
