"""Numerics observability (obs/numerics.py, obs/parity.py): in-step stats
vs numpy references, the clip-fold bitwise parity, the HLO dtype ledger
on synthetic and real compiled steps, Telemetry alerts/section/trace
wiring, and the acceptance demo — an fp-vs-int8 A/B through
tools/parity_diff.py rendering a ``bounded`` verdict with the int8 arm's
s8 byte shift.

Budget discipline (PR-6 convention): ONE module-scope A/B fixture runs
both tiny compiled fwd+grad steps; every report/ledger/parity test reads
from it.  The remaining compiles are sub-second toys.
"""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from torchdistpackage_tpu.obs import (
    DEFAULT_THRESHOLDS,
    JsonlSink,
    PARITY_VERDICTS,
    Telemetry,
    check_alerts,
    compare_streams,
    dtype_ledger_from_hlo,
    global_grad_norm,
    numerics_report,
    numerics_stats,
    param_divergence,
    parity_section,
    stream_of,
    validate_runreport,
)
from torchdistpackage_tpu.obs.events import EventLog, set_default_event_log
from torchdistpackage_tpu.parallel.clip import clip_grads_by_global_norm
from torchdistpackage_tpu.parallel.data_parallel import DataParallel


@pytest.fixture()
def _fresh_log():
    log = EventLog()
    set_default_event_log(log)
    yield log
    set_default_event_log(None)


# ------------------------------------------------------------- step stats


def _toy_grads():
    return {
        "blocks": [
            {"w": jnp.array([[3.0, 4.0]])},       # norm 5
            {"w": jnp.array([0.0, 12.0, 5.0])},   # norm 13
        ],
        "head": jnp.array([-8.0, 6.0]),           # norm 10
    }


def test_numerics_stats_against_numpy():
    grads = _toy_grads()
    params = jax.tree.map(lambda g: g * 2.0, grads)
    updates = jax.tree.map(lambda g: g * -0.01, grads)
    stats = jax.jit(
        lambda g, p, u: numerics_stats(g, params=p, updates=u)
    )(grads, params, updates)
    want = math.sqrt(5.0**2 + 13.0**2 + 10.0**2)
    assert np.isclose(float(stats["grad_norm"]), want)
    assert np.isclose(float(stats["param_norm"]), 2 * want)
    assert np.isclose(float(stats["update_norm"]), 0.01 * want)
    assert np.isclose(float(stats["update_ratio"]), 0.01 / 2.0, rtol=1e-4)
    assert float(stats["nonfinite_grads"]) == 0
    # per-layer-group breakdown: list blocks get indexed names
    g = stats["groups"]
    assert set(g) == {"blocks/0", "blocks/1", "head"}
    assert np.isclose(float(g["blocks/1"]["grad_norm"]), 13.0)
    assert np.isclose(float(g["head"]["update_ratio"]), 0.005, rtol=1e-4)


def test_numerics_stats_range_and_nonfinite():
    grads = {
        # 1 nan + 1 inf, 1 bf16-underflow (nonzero but < f32 tiny),
        # 1 f16-overflow, the rest plain
        "a": jnp.array([jnp.nan, jnp.inf, 1e-39, 7e4, 1.0, -1.0, 0.5, 0.25]),
    }
    stats = jax.jit(numerics_stats)(grads)
    assert float(stats["nonfinite_grads"]) == 2
    assert np.isclose(float(stats["bf16_underflow_frac"]), 1 / 8)
    assert np.isclose(float(stats["f16_overflow_frac"]), 2 / 8)  # inf counts
    # int8 dead zone: per-leaf amax is inf -> amax/254 = inf -> every
    # finite nonzero value sits under it; the gauge stays in [0, 1]
    assert 0.0 <= float(stats["int8_zero_frac"]) <= 1.0


def test_int8_dead_zone_fraction():
    # amax = 254 -> dead zone |x| < 1: exactly the two 0.5s (zeros excluded)
    grads = {"w": jnp.array([254.0, 0.5, -0.5, 0.0, 2.0, 100.0, 50.0, 3.0])}
    stats = jax.jit(numerics_stats)(grads)
    assert np.isclose(float(stats["int8_zero_frac"]), 2 / 8)


# -------------------------------------------------- clip-fold parity (S1)


def _prefold_global_norm(grads):
    """Inline copy of parallel/clip.py's pre-fold algorithm (PR-6 HEAD):
    the bitwise reference the shared reduction must reproduce."""
    from torchdistpackage_tpu.parallel.data_parallel import _vma

    by_axes = {}
    for g in jax.tree.leaves(grads):
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        axes = tuple(sorted(_vma(sq)))
        by_axes[axes] = by_axes.get(axes, 0.0) + sq
    total = jnp.zeros((), dtype=jnp.float32)
    for axes, sq in by_axes.items():
        total = total + (jax.lax.psum(sq, axes) if axes else sq)
    return jnp.sqrt(total)


def test_clipped_step_bitwise_vs_prefold(devices8):
    """The satellite bar: after folding the global norm into the shared
    obs.numerics reduction, a clipped sharded step is BITWISE identical
    to the pre-fold implementation."""
    mesh = Mesh(np.array(devices8), axis_names=("data",))
    grads = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (16, 8)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (8,)) * 100.0,
    }

    def new_fn(g):
        clipped, norm = clip_grads_by_global_norm(g, max_norm=1.0)
        return clipped, norm

    def old_fn(g):
        norm = _prefold_global_norm(g)
        scale = jnp.minimum(1.0, 1.0 / (norm + 1e-6))
        return jax.tree.map(lambda x: (x * scale).astype(x.dtype), g), norm

    specs = {"w": P("data"), "b": P()}
    run_new = jax.jit(shard_map(
        new_fn, mesh=mesh, in_specs=(specs,), out_specs=(specs, P())))
    run_old = jax.jit(shard_map(
        old_fn, mesh=mesh, in_specs=(specs,), out_specs=(specs, P())))
    c_new, n_new = run_new(grads)
    c_old, n_old = run_old(grads)
    assert np.asarray(n_new).tobytes() == np.asarray(n_old).tobytes()
    for k in grads:
        assert np.asarray(c_new[k]).tobytes() == np.asarray(c_old[k]).tobytes()
    # and the numerics grad_norm is the same number clipping used
    run_stats = jax.jit(shard_map(
        global_grad_norm, mesh=mesh, in_specs=(specs,), out_specs=P()))
    assert np.asarray(run_stats(grads)).tobytes() == (
        np.asarray(n_old).tobytes())


# ----------------------------------------------------------- dtype ledger


_HLO = """\
HloModule test, entry_computation_layout={(f32[4,16]{1,0})->f32[4,8]{1,0}}

ENTRY %main (p0: f32[4,16]) -> f32[4,8] {
  %p0 = f32[4,16]{1,0} parameter(0)
  %c = bf16[16,8]{1,0} constant({...})
  %cvt = bf16[4,16]{1,0} convert(f32[4,16]{1,0} %p0)
  %dot.1 = bf16[4,8]{1,0} dot(bf16[4,16]{1,0} %cvt, bf16[16,8]{1,0} %c), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %q = s8[4,8]{1,0} convert(bf16[4,8]{1,0} %dot.1)
  %gte = f32[4,8]{1,0} get-tuple-element(%whatever), index=0
  ROOT %out = f32[4,8]{1,0} convert(s8[4,8]{1,0} %q)
}
"""


def test_dtype_ledger_from_synthetic_hlo():
    led = dtype_ledger_from_hlo(_HLO, label="unit")
    per = led["per_dtype"]
    # bf16 buffers: cvt (4*16) + dot (4*8) at 2 B each; the constant is
    # bookkeeping-free?  No — constant is excluded (no compute)
    assert per["bf16"]["bytes"] == (4 * 16 + 4 * 8) * 2
    # dot FLOPs attributed to the OPERAND dtype: 2 * |out| * K
    assert per["bf16"]["flops"] == 2 * (4 * 8) * 16
    assert per["s8"]["bytes"] == 4 * 8
    # parameter / get-tuple-element excluded from byte accounting
    assert per["f32"]["bytes"] == 4 * 8 * 4  # the ROOT convert only
    assert led["total_flops"] == per["bf16"]["flops"]
    assert led["flop_frac"] == {"bf16": 1.0}
    assert 0.0 < led["byte_frac"]["bf16"] < 1.0


def test_dtype_ledger_scalar_and_tuple_shapes():
    text = """\
  %s = f32[] multiply(f32[] %a, f32[] %b)
  %t = (f32[4]{0}, s32[2]{0}) custom-call(f32[4]{0} %x), custom_call_target="x"
"""
    per = dtype_ledger_from_hlo(text)["per_dtype"]
    assert per["f32"]["bytes"] == 4 + 4 * 4  # scalar + tuple elem 0
    assert per["s32"]["bytes"] == 2 * 4      # tuple elem 1
    assert per["f32"]["ops"] == 2            # op counted once per instr


# ----------------------------------------------------------------- alerts


def test_check_alerts_thresholds():
    ok = {"loss": 1.0, "grad_norm": 1.0, "update_ratio": 1e-3,
          "nonfinite_grads": 0.0}
    assert check_alerts(ok) == []
    reasons = lambda rec, th=None: {a["reason"]
                                    for a in check_alerts(rec, th)}
    assert reasons({"loss": float("nan")}) == {"nonfinite_loss"}
    assert reasons({"grad_norm": 1e5}) == {"grad_explosion"}
    assert reasons({"grad_norm": 1e-9}) == {"grad_vanishing"}
    assert reasons({"grad_norm": 0.0}) == set()  # exact zero: no grads yet
    assert reasons({"update_ratio": 0.5}) == {"update_ratio_high"}
    assert reasons({"update_ratio": 1e-8}) == {"update_ratio_low"}
    assert reasons({"nonfinite_grads": 3.0}) == {"nonfinite_grads"}
    # overrides move the band (Telemetry(numerics_thresholds=...))
    assert reasons({"grad_norm": 50.0}, {"grad_norm_explode": 10.0}) == {
        "grad_explosion"}
    assert set(DEFAULT_THRESHOLDS) == {
        "grad_norm_explode", "grad_norm_vanish",
        "update_ratio_high", "update_ratio_low"}


def test_telemetry_alert_on_entering_bad_state_only(_fresh_log):
    tel = Telemetry(run="alerts", report_path=None)
    tel.end_step(step=0, loss=1.0)
    tel.end_step(step=1, loss=float("nan"))
    tel.end_step(step=2, loss=float("nan"))  # still bad: no re-fire
    tel.end_step(step=3, loss=1.0)           # recovers
    tel.end_step(step=4, loss=float("inf"))  # re-enters: fires again
    alerts = tel.events.of_kind("numerics_alert")
    assert [a["step"] for a in alerts] == [1, 4]
    assert all(a["reason"] == "nonfinite_loss" for a in alerts)
    rep = tel.finalize(print_summary=False)
    assert validate_runreport(rep) == []
    assert rep["numerics"]["alerts"] == {
        "count": 2, "by_reason": {"nonfinite_loss": 2},
        "first": {"step": 1, "reason": "nonfinite_loss",
                  "value": alerts[0]["value"]}}


def test_trace_exports_numerics_counter_tracks():
    from torchdistpackage_tpu.obs.trace import chrome_trace_events

    history = [{
        "type": "step", "step": i, "t_end_s": 5.0 + i,
        "step_time_s": 0.5, "span_device_s": 0.5,
        "grad_norm": 0.5 + i, "update_ratio": 1e-3,
    } for i in range(3)]
    events = chrome_trace_events(history)
    gn = [e for e in events if e.get("ph") == "C" and e["name"] == "grad_norm"]
    ur = [e for e in events
          if e.get("ph") == "C" and e["name"] == "update_ratio"]
    assert len(gn) == 3 and len(ur) == 3
    assert gn[0]["args"] == {"grad_norm": 0.5}


# ----------------------------------------------------------------- parity


def test_compare_streams_verdicts():
    a = {i: 1.0 + 0.1 * i for i in range(10)}
    assert compare_streams(a, dict(a))["verdict"] == "exact"
    b = {i: v * 1.001 for i, v in a.items()}
    cmp = compare_streams(a, b, rtol=0.05)
    assert cmp["verdict"] == "bounded"
    assert 0 < cmp["max_rel_delta"] < 0.05
    assert cmp["n_mismatch"] == 0
    bad = {**a, 7: 100.0}
    cmp = compare_streams(a, bad, rtol=0.05)
    assert cmp["verdict"] == "diverged"
    assert cmp["first_mismatch_step"] == 7 and cmp["n_mismatch"] == 1
    # one-sided non-finiteness diverges regardless of tolerance;
    # both-sided counts as agreement (the arms blew up identically)
    nan_b = {**a, 3: float("nan")}
    assert compare_streams(a, nan_b, rtol=1e9)["verdict"] == "diverged"
    nan_a = {**a, 3: float("nan")}
    assert compare_streams(nan_a, nan_b)["verdict"] != "diverged"
    assert compare_streams(a, {100: 1.0})["verdict"] == "unknown"


def test_stream_of_records_and_report():
    recs = [
        {"type": "step", "step": 0, "loss": 1.0},
        {"type": "event", "kind": "compile"},
        {"type": "step", "step": 1, "loss": 2.0, "grad_norm": 0.5},
        {"step": 2, "loss": "oops"},
    ]
    assert stream_of(recs) == {0: 1.0, 1: 2.0}
    assert stream_of(recs, key="grad_norm") == {1: 0.5}
    report = {"numerics": {"timeline": [
        {"step": 0, "loss": 3.0}, {"step": 1, "loss": 4.0}]}}
    assert stream_of(report) == {0: 3.0, 1: 4.0}


def test_param_divergence_ranks_leaves():
    a = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    b = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,)) * 1.5}
    div = param_divergence(a, b)
    assert div["per_leaf"][0]["path"].endswith("['b']")  # worst first
    assert div["per_leaf"][1]["diff_norm"] == 0.0
    assert np.isclose(div["per_leaf"][0]["rel"], 0.5)
    assert div["global"]["diff_norm"] > 0
    with pytest.raises(ValueError):
        param_divergence(a, {"w": jnp.ones((4, 4))})


def test_parity_section_worst_verdict_and_validation():
    sec = parity_section(
        streams=[{"key": "loss", "verdict": "exact", "n_common": 4},
                 {"key": "grad_norm", "verdict": "bounded", "n_common": 4}],
        labels=("fp", "int8"))
    assert sec["verdict"] == "bounded"
    assert sec["verdict"] in PARITY_VERDICTS
    # a numerics section carrying it validates end to end
    from torchdistpackage_tpu.obs.report import _validate_numerics

    num = numerics_report(parity=sec)
    assert _validate_numerics(num) == []
    bad = numerics_report(parity={"verdict": "sideways", "streams": []})
    assert _validate_numerics(bad) != []


# ------------------------------------- the A/B acceptance demo (module)


@pytest.fixture(scope="module")
def ab_runs(tmp_path_factory):
    """The acceptance-bar fixture: two tiny DP training runs on the 8-dev
    sim — exact grad reduction vs DataParallel(grad_compress='int8') —
    each leaving a RUNREPORT + JSONL record stream behind.  ONE compiled
    fwd+grad step per arm; every downstream test reads the artifacts."""
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), axis_names=("data",))
    tmp = tmp_path_factory.mktemp("ab")
    params = {
        "w1": np.asarray(
            jax.random.normal(jax.random.PRNGKey(0), (16, 32)) * 0.1),
        "w2": np.asarray(
            jax.random.normal(jax.random.PRNGKey(1), (32, 4)) * 0.1),
    }

    def loss_fn(p, b):
        return jnp.mean((jnp.tanh(b["x"] @ p["w1"]) @ p["w2"] - b["y"]) ** 2)

    opt = optax.sgd(1e-2)
    batch_host = {
        "x": np.asarray(jax.random.normal(jax.random.PRNGKey(2), (64, 16))),
        "y": np.asarray(jax.random.normal(jax.random.PRNGKey(3), (64, 4))),
    }
    out = {}
    for name, compress in (("fp", None), ("int8", "int8")):
        log = EventLog()
        set_default_event_log(log)
        dp = DataParallel(mesh=mesh, grad_compress=compress,
                          compress_min_size=0)
        p = dp.broadcast_params({k: np.array(v) for k, v in params.items()})
        s = opt.init(p)
        step = dp.make_train_step(loss_fn, opt, numerics=True)
        report_path = str(tmp / f"RUNREPORT_{name}.json")
        jsonl_path = str(tmp / f"records_{name}.jsonl")
        tel = Telemetry(run=name, report_path=report_path, mesh=mesh,
                        event_log=log, sinks=[JsonlSink(jsonl_path)])
        step = tel.wrap_step(step)
        batch = dp.shard_batch(batch_host)
        for i in range(6):
            p, s, loss, nstats = step(p, s, batch)
            tel.end_step(step=i, loss=loss, numerics=nstats)
        report = tel.finalize(print_summary=False)
        out[name] = {"report": report, "report_path": report_path,
                     "jsonl_path": jsonl_path, "params": jax.device_get(p)}
    set_default_event_log(None)
    return out


def test_ab_reports_validate_with_numerics(ab_runs):
    for arm in ("fp", "int8"):
        report = ab_runs[arm]["report"]
        assert validate_runreport(report) == [], arm
        num = report["numerics"]
        assert num["summary"]["steps"] == 6
        assert num["summary"]["grad_norm_final"] > 0
        assert len(num["timeline"]) == 6
        assert num["alerts"]["count"] == 0, num["alerts"]
        assert num["dtype_ledgers"], arm


def test_dtype_ledger_shows_int8_arm_shift(ab_runs):
    """The evidence channel: the quantized arm's compiled step must show
    s8 bytes; the fp arm must show none (and both run f32 matmuls)."""
    def per_dtype(arm):
        return ab_runs[arm]["report"]["numerics"]["dtype_ledgers"][0][
            "per_dtype"]

    fp, q = per_dtype("fp"), per_dtype("int8")
    assert "s8" not in fp
    assert q["s8"]["bytes"] > 0
    assert fp["f32"]["flops"] > 0 and q["f32"]["flops"] > 0


def test_parity_diff_cli_bounded_verdict(ab_runs, capsys):
    """Acceptance bar: tools/parity_diff.py on the fp-vs-int8 pair ->
    'bounded' drift verdict (exit 0), drift table + dtype shift rendered."""
    from torchdistpackage_tpu.tools.parity_diff import main

    rc = main([ab_runs["fp"]["report_path"], ab_runs["int8"]["report_path"],
               "--label-a", "fp32", "--label-b", "int8"])
    out = capsys.readouterr().out
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["verdict"] == "bounded"
    assert 0 < line["max_rel_delta"] < 0.05
    assert line["dtype_bytes_delta"]["s8"] > 0  # the int8 arm's byte shift
    assert "dtype ledger shift" in out and "s8" in out


def test_parity_diff_cli_jsonl_streams_and_divergence(ab_runs, capsys, tmp_path):
    """The CLI also compares raw JSONL record streams, and exits 1 when a
    stream genuinely diverged."""
    from torchdistpackage_tpu.tools.parity_diff import main

    rc = main([ab_runs["fp"]["jsonl_path"], ab_runs["int8"]["jsonl_path"]])
    assert rc == 0
    assert json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])["verdict"] in (
        "exact", "bounded")
    # forge a diverged arm: same stream with one poisoned step
    recs = [json.loads(ln) for ln in open(ab_runs["fp"]["jsonl_path"])
            if ln.strip()]
    steps = [r for r in recs if r.get("type") == "step"]
    steps[3]["loss"] = 1e6
    forged = tmp_path / "diverged.jsonl"
    forged.write_text("\n".join(json.dumps(r) for r in steps))
    rc = main([ab_runs["fp"]["jsonl_path"], str(forged)])
    assert rc == 1
    assert json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])[
        "verdict"] == "diverged"


def test_ab_param_divergence_bounded(ab_runs):
    """Per-leaf drift between the arms' final params stays at
    quantization-noise scale, and attaching the parity section keeps the
    report valid."""
    div = param_divergence(ab_runs["fp"]["params"], ab_runs["int8"]["params"])
    assert div["global"]["rel"] < 0.05, div["global"]
    cmp = compare_streams(
        stream_of([{"type": "step", "step": t["step"], "loss": t["loss"]}
                   for t in ab_runs["fp"]["report"]["numerics"]["timeline"]]),
        stream_of(ab_runs["int8"]["report"]))
    sec = parity_section(streams=[cmp], params=div, labels=("fp", "int8"))
    assert sec["verdict"] == "bounded"
    assert sec["params"]["n_leaves"] == 2
    tel = Telemetry(run="parity-carrier", report_path=None)
    tel.record_parity(sec)
    rep = tel.finalize(print_summary=False)
    assert validate_runreport(rep) == []
    assert rep["numerics"]["parity"]["verdict"] == "bounded"
