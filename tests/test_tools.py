"""Tests for the tools layer: profiler, NaN hunting, surgery/int8, SLURM
monitor (subprocess-mocked), and the bench-round trend gate."""

import pathlib
import subprocess
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistpackage_tpu.tools import (
    QuantizedLinear,
    check_model_params,
    check_tensors,
    dequantize_int8,
    determine_job_is_alive,
    find_nan_block,
    get_model_profile,
    int8_matmul,
    launch_job,
    nan_guard,
    profile_blocks,
    quantize_int8,
    quantize_params_int8,
    replace_params,
    report_prof,
)
from torchdistpackage_tpu.tools import slurm_job_monitor as sjm


# --------------------------------------------------------------- flash tune


def test_tune_flash_blocks_ranks_and_dedupes():
    """The autotuner must (a) run every distinct effective config after the
    kernel's gcd clamp (the four candidates below collapse to two at S=64),
    (b) return the fastest as best, and (c) report rel ratios vs the winner.
    CPU interpret mode, tiny shape — this is a harness test, not a perf one."""
    from torchdistpackage_tpu.tools import tune_flash_blocks

    best, report = tune_flash_blocks(
        batch=1, heads=2, seq=64, head_dim=8,
        candidates=[(32, 32), (64, 64), (128, 128), (256, 512)],
        steps=1, warmup=0,
    )
    ok = [r for r in report if r.get("ms") is not None]
    # (128,128) and (256,512) both clamp to (64,64): deduped
    assert len(ok) == 2, report
    assert {(r["block_q"], r["block_k"]) for r in ok} == {(32, 32), (64, 64)}
    assert best == (ok[0]["block_q"], ok[0]["block_k"])
    assert ok[0]["rel"] == 1.0 and all(r["rel"] >= 1.0 for r in ok)


# ---------------------------------------------------------------- profiler


def test_profile_blocks_and_report():
    w1 = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    w2 = jax.random.normal(jax.random.PRNGKey(1), (128, 64))
    blocks = [
        ("expand", lambda x: jnp.tanh(x @ w1)),
        ("contract", lambda x: x @ w2),
    ]
    x = jnp.ones((8, 64))
    profiles, out = profile_blocks(blocks, x, warmup=1, iters=2)
    assert out.shape == (8, 64)
    assert [p.name for p in profiles] == ["expand", "contract"]
    # activation bytes are exact: (8,128) f32 and (8,64) f32
    assert profiles[0].act_bytes == 8 * 128 * 4
    assert profiles[1].act_bytes == 8 * 64 * 4
    assert all(p.time_ms > 0 for p in profiles)
    rep = report_prof(profiles)
    assert "expand" in rep and "MB/ms" in rep and "TOTAL" in rep
    # one-call variant prints
    ps = get_model_profile(blocks, x, print_report=False)
    assert len(ps) == 2


def test_tree_profile_levels():
    from torchdistpackage_tpu.tools import aggregate_levels, report_tree
    from torchdistpackage_tpu.tools.profiler import BlockProfile

    # a ragged tree: enc/b0/{attn,mlp}, enc/b1, and a flat lambda next to it
    mk = lambda name, t, b: BlockProfile(
        name=name, time_ms=t, act_bytes=b, flops=1e9, bytes_accessed=1e6,
        temp_bytes=100)
    ps = [
        mk("enc/b0/attn", 1.0, 1000),
        mk("enc/b0/mlp", 2.0, 3000),
        mk("enc/b1", 1.0, 500),
        mk("head", 0.5, 200),
    ]
    levels = aggregate_levels(ps)
    assert sorted(levels) == [1, 2, 3]
    l1 = {p.name: p for p in levels[1]}
    assert l1["enc"].time_ms == 4.0 and l1["enc"].act_bytes == 4500
    assert l1["enc"].flops == 3e9 and l1["enc"].temp_bytes == 100  # max, not sum
    assert l1["head"].time_ms == 0.5
    l2 = {p.name: p for p in levels[2]}
    assert l2["enc/b0"].act_bytes == 4000 and l2["enc/b1"].act_bytes == 500
    assert l2["head"].act_bytes == 200  # shallow names persist at deeper levels
    rep = report_tree(ps)
    assert "== level 1 ==" in rep and "== level 3 ==" in rep
    assert "enc/b0/attn" in rep

    # measured end to end through profile_blocks with slash names
    w = jax.random.normal(jax.random.PRNGKey(0), (16, 16))
    blocks = [
        ("enc/attn", lambda x: x @ w),
        ("enc/mlp", lambda x: jnp.tanh(x)),
        ("head", lambda x: x.sum(keepdims=True)[None]),
    ]
    profs, _ = profile_blocks(blocks, jnp.ones((4, 16)), warmup=1, iters=1)
    lv = aggregate_levels(profs)
    assert {p.name for p in lv[1]} == {"enc", "head"}
    enc = next(p for p in lv[1] if p.name == "enc")
    assert enc.time_ms == profs[0].time_ms + profs[1].time_ms


# ---------------------------------------------------------------- nan tools


def test_check_tensors_paths():
    tree = {"a": jnp.ones((3,)), "b": {"c": jnp.array([1.0, jnp.nan])}}
    bad = check_tensors(tree, name="t")
    assert bad == ["t/b/c (nan=1, inf=0)"]
    with pytest.raises(FloatingPointError):
        check_tensors(tree, raise_on_bad=True)
    assert check_model_params({"w": jnp.zeros((2,))}) == []


def test_nan_guard_raises_inside_jit():
    @nan_guard(name="div")
    def f(x):
        return x / x  # nan at 0

    ok = jax.jit(f)(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(ok), 1.0)
    with pytest.raises(Exception):  # XLA wraps the callback error
        jax.block_until_ready(jax.jit(f)(jnp.zeros((4,))))


def test_find_nan_block():
    from torchdistpackage_tpu.obs.events import (
        EventLog,
        set_default_event_log,
    )

    blocks = [
        ("ok", lambda x: x + 1),
        ("bad", lambda x: jnp.log(x - 10.0)),  # negative -> nan
        ("after", lambda x: x * 2),
    ]
    log = EventLog()
    set_default_event_log(log)
    try:
        name, _ = find_nan_block(blocks, jnp.ones((4,)))
        assert name == "bad"
        # the hit is a structured timeline record, not just a return value
        ev = log.of_kind("nan_block_located")
        assert len(ev) == 1 and ev[0]["block"] == "bad" and ev[0]["index"] == 1
        assert ev[0]["n_bad"] == 1 and "bad" in ev[0]["bad_paths"][0]
        name, out = find_nan_block(blocks[:1], jnp.ones((4,)))
        assert name is None and float(out[0]) == 2.0
        assert len(log.of_kind("nan_block_located")) == 1  # clean walk: quiet
    finally:
        set_default_event_log(None)


def test_check_tensors_emit_lands_on_timeline():
    from torchdistpackage_tpu.obs.events import (
        EventLog,
        set_default_event_log,
    )

    log = EventLog()
    set_default_event_log(log)
    try:
        bad = check_tensors(
            {"g": jnp.array([1.0, jnp.inf])}, name="grads", emit=True)
        assert bad
        ev = log.of_kind("nan_watchdog")
        assert len(ev) == 1 and ev[0]["source"] == "check_tensors"
        assert ev[0]["fn"] == "grads" and ev[0]["n_bad"] == 1
        # healthy scans stay quiet even with emit on
        check_tensors({"g": jnp.ones((2,))}, emit=True)
        assert len(log.of_kind("nan_watchdog")) == 1
    finally:
        set_default_event_log(None)


# -------------------------------------------------------- bench trend gate


def test_bench_trend_regression_detection_and_numerics_columns(tmp_path):
    """The gate actually bites (a forged losing round exits nonzero) and
    the PR-7 ``grad_norm_final`` numerics column renders next to the
    throughput it certifies."""
    import json as _json

    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, main, trend

    assert "grad_norm_final" in AUX_KEYS
    line = {"metric": "m", "value": 100.0, "unit": "tok/s",
            "grad_norm_final": 0.37, "mfu": 0.4, "config": "c"}
    rounds = [(1, [line]), (2, [dict(line, value=90.0)])]
    report, warnings = trend(rounds, threshold=0.05)
    assert any("REGRESSION" in w for w in warnings)
    assert any("grad_norm_final=0.37" in ln for ln in report)
    for n, lines in rounds:
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            _json.dumps({"n": n, "tail": "\n".join(
                _json.dumps(l) for l in lines)}))
    assert main(["--dir", str(tmp_path)]) == 1


def test_bench_trend_overload_columns():
    """The PR-9 stress columns: a ``serve-overload`` line's goodput gates
    (``value``) with ``shed_rate``/``preempt_count`` rendered alongside —
    a goodput hold bought by shedding more is visible, not hidden."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert {"shed_rate", "preempt_count"} <= set(AUX_KEYS)
    line = {"metric": "serve-overload", "value": 850.0,
            "shed_rate": 0.21, "preempt_count": 3, "config": "c"}
    report, warnings = trend(
        [(1, [line]), (2, [dict(line, value=700.0, shed_rate=0.4)])],
        threshold=0.05)
    assert any("shed_rate=0.21" in ln for ln in report)
    assert any("preempt_count=3" in ln for ln in report)
    assert any("REGRESSION serve-overload" in w for w in warnings)


def test_bench_trend_fastpath_columns():
    """The PR-10 fast-path columns: ``serve-prefix-*`` / ``serve-spec-*``
    lines gate on tokens/s (``value``) with ``prefix_hit_rate`` /
    ``spec_accept_rate`` rendered alongside — a throughput hold with a
    collapsed hit or accept rate (the win evaporating) is visible in the
    trend, and a regression still trips the gate."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert {"prefix_hit_rate", "spec_accept_rate"} <= set(AUX_KEYS)
    warm = {"metric": "serve-prefix-warm", "value": 1850.0,
            "prefix_hit_rate": 0.95, "config": "c"}
    spec = {"metric": "serve-spec-on", "value": 1000.0,
            "spec_accept_rate": 0.27, "config": "c"}
    report, warnings = trend(
        [(1, [warm, spec]),
         (2, [dict(warm, value=1200.0, prefix_hit_rate=0.1),
              dict(spec, value=990.0, spec_accept_rate=0.25)])],
        threshold=0.05)
    assert any("prefix_hit_rate=0.95" in ln for ln in report)
    assert any("spec_accept_rate=0.27" in ln for ln in report)
    assert any("REGRESSION serve-prefix-warm" in w for w in warnings)
    assert not any("serve-spec-on" in w for w in warnings)  # -1% holds


def test_bench_trend_slo_columns():
    """The PR-11 SLO columns: the ``serve-overload`` line's raw tokens/s
    still gates (``value``), and ``goodput_tok_s`` / ``slo_attainment``
    render alongside — a throughput hold bought by missing every
    deadline (goodput collapsing under a steady headline) is visible in
    the trend, and a goodput-line regression still trips the gate when
    trended as its own series."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert {"slo_attainment", "goodput_tok_s"} <= set(AUX_KEYS)
    line = {"metric": "serve-overload", "value": 850.0,
            "shed_rate": 0.2, "preempt_count": 3,
            "goodput_tok_s": 800.0, "slo_attainment": 0.92, "config": "c"}
    report, warnings = trend(
        [(1, [line]),
         (2, [dict(line, goodput_tok_s=120.0, slo_attainment=0.15)])],
        threshold=0.05)
    assert any("goodput_tok_s=800.0" in ln for ln in report)
    assert any("slo_attainment=0.92" in ln for ln in report)
    assert any("slo_attainment=0.15" in ln for ln in report)
    # headline held -> no gate trip; the collapse is VISIBLE in the aux
    assert not warnings


def test_bench_trend_router_columns():
    """The PR-15 fleet columns: the ``serve-router-fleet`` line gates on
    fleet tokens/s (``value``) with ``fleet_goodput_tok_s`` /
    ``affinity_hit_rate`` / ``migration_bytes`` rendered alongside — a
    throughput hold with a collapsed affinity hit rate (warm traffic no
    longer landing on its KV) or ballooning migration bytes (handoffs
    shipping whole contexts instead of tails) is visible in the trend,
    and a fleet-line regression still trips the gate."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert {"fleet_goodput_tok_s", "affinity_hit_rate",
            "migration_bytes"} <= set(AUX_KEYS)
    line = {"metric": "serve-router-fleet", "value": 900.0,
            "fleet_goodput_tok_s": 900.0, "affinity_hit_rate": 0.88,
            "migration_bytes": 147456, "config": "c"}
    report, warnings = trend(
        [(1, [line]),
         (2, [dict(line, value=500.0, affinity_hit_rate=0.05,
                   migration_bytes=1200000)])],
        threshold=0.05)
    assert any("affinity_hit_rate=0.88" in ln for ln in report)
    assert any("fleet_goodput_tok_s=900.0" in ln for ln in report)
    assert any("migration_bytes=147456" in ln for ln in report)
    assert any("affinity_hit_rate=0.05" in ln for ln in report)
    assert any("REGRESSION serve-router-fleet" in w for w in warnings)


def test_bench_trend_fleet_slo_columns():
    """The PR-17 fleet-observability columns: ``fleet_slo_attainment``
    and ``migration_count`` ride the ``serve-router-fleet`` line (and
    the ``trace-replay`` line) — a fleet tokens/s hold with collapsing
    SLO attainment means throughput is being bought from deadline
    misses, and a migration-count explosion means the disaggregation
    tier started thrashing; both are visible in the trend and a
    headline regression still trips the gate."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert {"fleet_slo_attainment", "migration_count"} <= set(AUX_KEYS)
    line = {"metric": "serve-router-fleet", "value": 900.0,
            "fleet_goodput_tok_s": 900.0, "fleet_slo_attainment": 0.97,
            "migration_count": 12, "config": "c"}
    report, warnings = trend(
        [(1, [line]),
         (2, [dict(line, value=500.0, fleet_slo_attainment=0.4,
                   migration_count=480)])],
        threshold=0.05)
    assert any("fleet_slo_attainment=0.97" in ln for ln in report)
    assert any("migration_count=12" in ln for ln in report)
    assert any("fleet_slo_attainment=0.4" in ln for ln in report)
    assert any("migration_count=480" in ln for ln in report)


def test_bench_trend_moe_columns():
    """The PR-18 MoE dispatch columns: ``moe_pallas_tok_s`` and
    ``expert_imbalance`` ride the ``serve-moe-ab`` line — a speedup
    hold earned while the imbalance column climbs means the router is
    feeding the fused kernel ever-more-skewed batches (capacity drops
    coming), and a headline regression still trips the gate."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert {"moe_pallas_tok_s", "expert_imbalance"} <= set(AUX_KEYS)
    line = {"metric": "serve-moe-ab", "value": 1.2,
            "moe_pallas_tok_s": 900.0, "expert_imbalance": 0.45,
            "config": "c"}
    report, warnings = trend(
        [(1, [line]),
         (2, [dict(line, value=0.9, expert_imbalance=1.8)])],
        threshold=0.05)
    assert any("moe_pallas_tok_s=900.0" in ln for ln in report)
    assert any("expert_imbalance=0.45" in ln for ln in report)
    assert any("expert_imbalance=1.8" in ln for ln in report)
    assert any("REGRESSION serve-moe-ab" in w for w in warnings)


def test_bench_trend_paged_kernel_column():
    """The PR-12 paged-kernel columns: ``serve-paged-{gather,pallas}``
    lines gate on tokens/s (``value``) as their own series, and the
    ``serve-paged-ab`` line renders ``paged_pallas_tok_s`` in the aux
    trail — a pallas-arm regression trips the gate on its line and stays
    visible on the A/B roll-up."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert "paged_pallas_tok_s" in AUX_KEYS
    pallas = {"metric": "serve-paged-pallas", "value": 1850.0,
              "attn_impl": "pallas", "config": "c"}
    ab = {"metric": "serve-paged-ab", "value": 1.4,
          "paged_pallas_tok_s": 1850.0, "config": "c"}
    report, warnings = trend(
        [(1, [pallas, ab]),
         (2, [dict(pallas, value=1200.0),
              dict(ab, paged_pallas_tok_s=1200.0)])],
        threshold=0.05)
    assert any("paged_pallas_tok_s=1850.0" in ln for ln in report)
    assert any("REGRESSION serve-paged-pallas" in w for w in warnings)


def test_bench_trend_autoplan_columns():
    """The PR-13 planner columns: the ``bench.py --autoplan`` planned
    arm's line gates on tokens/s (``value``) with ``autoplan_tok_s`` /
    ``plan_modeled_step_s`` rendered alongside — a throughput hold with a
    drifting modeled step (the planner steering on stale numbers) is
    visible in the trend, and a planned-arm regression still trips the
    gate."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert {"autoplan_tok_s", "plan_modeled_step_s"} <= set(AUX_KEYS)
    line = {"metric": "gpt-tiny-train-throughput", "value": 530.0,
            "autoplan": "planned", "plan": "dp8",
            "autoplan_tok_s": 530.0, "plan_modeled_step_s": 0.0019,
            "config": "c ap-planned"}
    report, warnings = trend(
        [(1, [line]),
         (2, [dict(line, value=400.0, autoplan_tok_s=400.0)])],
        threshold=0.05)
    assert any("autoplan_tok_s=530.0" in ln for ln in report)
    assert any("plan_modeled_step_s=0.0019" in ln for ln in report)
    assert any("REGRESSION gpt-tiny-train-throughput" in w for w in warnings)


def test_bench_trend_bubble_columns():
    """The PR-14 pipeline columns (mirrors the ``autoplan_tok_s``
    pattern): a pp-plan line gates on tokens/s (``value``) with
    ``bubble_fraction`` / ``plan_pp_schedule`` rendered alongside — a
    throughput hold whose bubble crept back up, or whose schedule arm
    silently flipped from ``zb`` back to classic ``1f1b``, is visible in
    the trend, and a pp-line regression still trips the gate."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert {"bubble_fraction", "plan_pp_schedule"} <= set(AUX_KEYS)
    line = {"metric": "gpt-tiny-train-throughput", "value": 520.0,
            "autoplan": "planned", "plan": "dp2·pp4",
            "bubble_fraction": 0.5, "plan_pp_schedule": "zb",
            "config": "c ap-planned"}
    report, warnings = trend(
        [(1, [line]),
         (2, [dict(line, value=430.0, bubble_fraction=0.6,
                   plan_pp_schedule="1f1b")])],
        threshold=0.05)
    assert any("bubble_fraction=0.5" in ln for ln in report)
    assert any("plan_pp_schedule=zb" in ln for ln in report)
    assert any("plan_pp_schedule=1f1b" in ln for ln in report)
    assert any("REGRESSION gpt-tiny-train-throughput" in w for w in warnings)


def test_bench_trend_long_context_columns():
    """The PR-20 context-parallel prefill columns: the
    ``serve-longctx-ab`` line gates on the cp1/cpN TTFT speedup
    (``value``) with ``cp_prefill_ttft_s`` / ``long_ctx_tok_s`` rendered
    alongside — a speedup hold earned while the CP arm's absolute TTFT
    creeps up means both arms regressed together (the ratio hides it),
    and a headline regression still trips the gate."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert {"cp_prefill_ttft_s", "long_ctx_tok_s"} <= set(AUX_KEYS)
    line = {"metric": "serve-longctx-ab", "value": 1.6, "cp": 2,
            "context": 131072, "cp_prefill_ttft_s": 2.1,
            "long_ctx_tok_s": 240.0, "config": "c"}
    report, warnings = trend(
        [(1, [line]),
         (2, [dict(line, value=1.1, cp_prefill_ttft_s=4.7,
                   long_ctx_tok_s=110.0)])],
        threshold=0.05)
    assert any("cp_prefill_ttft_s=2.1" in ln for ln in report)
    assert any("long_ctx_tok_s=240.0" in ln for ln in report)
    assert any("cp_prefill_ttft_s=4.7" in ln for ln in report)
    assert any("REGRESSION serve-longctx-ab" in w for w in warnings)


def test_bench_trend_comm_bytes_column():
    """The PR-8 wire-bytes column: a line carrying ``comm_bytes_per_dim``
    renders its TOTAL in the aux trail, so a compressed collective
    silently re-inflating shows up in the trend."""
    from torchdistpackage_tpu.tools.bench_trend import AUX_KEYS, trend

    assert "comm_bytes_per_dim" in AUX_KEYS
    line = {"metric": "m", "value": 100.0, "unit": "tok/s",
            "comm_bytes_per_dim": {"dp": 1_000_000, "tp": 500_000},
            "config": "c"}
    report, _ = trend([(1, [line])], threshold=0.05)
    assert any("comm_bytes=1,500,000" in ln for ln in report)


# ------------------------------------------------------------- surgery/int8


def test_quantize_int8_accuracy():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 128)) * 0.02
    ql = quantize_int8(w)
    assert ql.q.dtype == jnp.int8 and ql.scale.shape == (128,)
    deq = dequantize_int8(ql)
    err = float(jnp.max(jnp.abs(deq - w)))
    assert err <= float(jnp.max(ql.scale)) * 0.51  # within half a quant step

    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256))
    y_ref = x @ w
    y_q = int8_matmul(x, ql)
    rel = float(jnp.linalg.norm(y_q - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 0.02
    # jit-compatible (QuantizedLinear is a pytree)
    y_jit = jax.jit(int8_matmul)(x, ql)
    np.testing.assert_allclose(np.asarray(y_jit), np.asarray(y_q), rtol=1e-5)


def test_quantize_params_sweep_and_replace():
    params = {
        "blk": {"w": jnp.ones((128, 64)), "ln": jnp.ones((64,)), "b": jnp.zeros((64,))},
        "emb": jnp.ones((8, 4)),  # too small -> untouched
    }
    qp = quantize_params_int8(params)
    assert isinstance(qp["blk"]["w"], QuantizedLinear)
    assert isinstance(qp["blk"]["ln"], jax.Array)  # 1-D untouched
    assert isinstance(qp["emb"], jax.Array)  # below min_size untouched

    # generic surgery: zero out biases by predicate
    zp = replace_params(
        params,
        lambda key, leaf: key.endswith("/b"),
        lambda key, leaf: jnp.full_like(leaf, 7.0),
    )
    assert float(zp["blk"]["b"][0]) == 7.0
    assert float(zp["blk"]["ln"][0]) == 1.0


# ------------------------------------------------------------ slurm monitor


def _fake_run(stdout_map):
    def run(cmd, **kw):
        key = cmd[0]
        out = stdout_map.get(key, "")
        return subprocess.CompletedProcess(cmd, 0, stdout=out, stderr="")

    return run


def test_launch_and_state_parsing():
    with mock.patch.object(
        sjm.subprocess, "run",
        side_effect=_fake_run({"sbatch": "Submitted batch job 4242\n"}),
    ):
        assert launch_job("train.sbatch") == "4242"
    with mock.patch.object(
        sjm.subprocess, "run",
        side_effect=_fake_run({"sacct": "4242  RUNNING\n4242.batch  RUNNING\n"}),
    ):
        assert sjm.get_job_state("4242") == "RUNNING"
        assert determine_job_is_alive("4242")
    with mock.patch.object(
        sjm.subprocess, "run",
        side_effect=_fake_run({"sacct": "4242  FAILED\n"}),
    ):
        assert not determine_job_is_alive("4242")
    # CANCELLED+ suffix normalization
    with mock.patch.object(
        sjm.subprocess, "run",
        side_effect=_fake_run({"sacct": "4242  CANCELLED+\n"}),
    ):
        assert sjm.get_job_state("4242") == "CANCELLED"


def test_monitor_relaunches_until_completed():
    states = iter(["FAILED", "RUNNING", "COMPLETED"])
    submitted = []

    def run(cmd, **kw):
        if cmd[0] == "sbatch":
            submitted.append(cmd)
            return subprocess.CompletedProcess(cmd, 0, stdout=f"Submitted batch job {100 + len(submitted)}\n", stderr="")
        if cmd[0] == "sacct":
            jid = cmd[2]
            return subprocess.CompletedProcess(cmd, 0, stdout=f"{jid}  {next(states)}\n", stderr="")
        return subprocess.CompletedProcess(cmd, 0, stdout="", stderr="")

    with mock.patch.object(sjm.subprocess, "run", side_effect=run), \
         mock.patch.object(sjm.time, "sleep"):
        final = sjm.monitor_job("train.sbatch", max_relaunches=3)
    assert final == "102"  # one relaunch after FAILED
    assert len(submitted) == 2
