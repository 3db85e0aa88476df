"""Tests for the tools layer: profiler, NaN hunting, surgery/int8, SLURM
monitor (subprocess-mocked)."""

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
