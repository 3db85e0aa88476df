"""Tests for the utility layer: determinism, partitioning, logging, EMA,
checkpointing — reference test pattern per SURVEY §4 (golden comparisons)."""

import builtins

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.parallel import ShardedEMA
from torchdistpackage_tpu.utils import (
    CheckpointManager,
    axis_unique_key,
    disable_non_master_print,
    enable_all_print,
    fix_rand,
    get_mp_ckpt_suffix,
    load_checkpoint,
    master_print,
    partition_params,
    save_checkpoint,
)


def test_fix_rand_deterministic():
    k1 = fix_rand(7)
    a = np.random.rand(4)
    k2 = fix_rand(7)
    b = np.random.rand(4)
    assert np.array_equal(a, b)
    assert jnp.array_equal(k1, k2)
    x1 = jax.random.normal(k1, (8,))
    x2 = jax.random.normal(k2, (8,))
    assert jnp.array_equal(x1, x2)


def test_partition_params_balanced_and_stable():
    params = {
        "big": np.zeros((100,)),
        "mid": np.zeros((60,)),
        "small_a": np.zeros((10,)),
        "small_b": np.zeros((10,)),
    }
    parts = partition_params(params, 2, return_dict=True)
    assert len(parts) == 2
    # all leaves present exactly once
    all_keys = sorted(k for p in parts for k in p)
    assert all_keys == sorted(params.keys())
    # loads balanced: 100 vs 60+10+10
    loads = sorted(sum(v.size for v in p.values()) for p in parts)
    assert loads == [80, 100]
    # deterministic across calls (the invariant multi-process code relies on)
    parts2 = partition_params(params, 2, return_dict=True)
    assert [sorted(p) for p in parts] == [sorted(p) for p in parts2]
    # never empty while leaves >= n
    parts4 = partition_params(params, 4)
    assert all(len(p) >= 1 for p in parts4)


def test_axis_unique_key(devices8):
    from jax import shard_map

    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devices8[:8])
    mesh = tpc.get_view()

    def body(key):
        k_data = axis_unique_key(key[0], "data")
        bits = jax.random.bits(k_data, (1,), dtype=jnp.uint32)
        return bits[None]

    key = jax.random.PRNGKey(0)[None]
    out = jax.jit(
        shard_map(
            body, mesh=mesh,
            in_specs=(P(),),
            out_specs=P("data", "tensor"),
        )
    )(key)
    arr = np.asarray(out)  # (4, 2): rows = data index, cols = tensor index
    # same key within a data group (tensor replicas agree) ...
    assert np.all(arr[:, 0] == arr[:, 1])
    # ... different keys across data shards
    assert len(set(arr[:, 0].tolist())) == 4


def test_master_print_gating(capsys):
    master_print("hello")
    assert "hello" in capsys.readouterr().out
    disable_non_master_print()
    try:
        print("gated")  # process 0 in tests -> still prints
        assert "gated" in capsys.readouterr().out
    finally:
        enable_all_print()
    assert builtins.print is print


def test_sharded_ema_matches_dense(devices8):
    """Golden test in the reference's style (sharded_ema vs dense EMA,
    examples/test_shard_ema.py:32-56)."""
    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devices8)
    mesh = tpc.get_view()
    key = jax.random.PRNGKey(0)
    params = {
        "w": jax.random.normal(key, (16, 8)),
        "b": jax.random.normal(key, (3,)),  # not divisible by 4 -> replicated
    }
    specs = {"w": P(None, "tensor"), "b": P()}
    ema = ShardedEMA(decay=0.9, mesh=mesh)
    state = ema.init(params, specs)

    dense = jax.tree.map(lambda p: np.asarray(p, np.float32), params)
    for i in range(3):
        params = jax.tree.map(lambda p: p + 0.1 * (i + 1), params)
        state = ema.update(state, params)
        dense = jax.tree.map(
            lambda e, p: e * 0.9 + np.asarray(p, np.float32) * 0.1, dense, params
        )

    # EMA state is actually sharded over data axis on the divisible leaf
    w_spec = state["w"].sharding.spec
    assert "data" in jax.tree_util.tree_leaves(tuple(w_spec))
    assert ema.verify_with_gt(state, dense, atol=1e-6)


def test_checkpoint_roundtrip(tmp_path, devices8):
    tpc.setup_process_groups([("data", 2), ("tensor", 4)], devices=devices8)
    mesh = tpc.get_view()
    params = {
        "w": jax.device_put(
            jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
            tpc.sharding(None, "tensor"),
        ),
        "step": jnp.int32(7),
    }
    path = str(tmp_path / "ckpt1")
    save_checkpoint(path, params)

    # restore host-side
    host = load_checkpoint(path)
    assert np.array_equal(host["w"], np.arange(32).reshape(8, 4))
    assert int(host["step"]) == 7

    # restore into a DIFFERENT sharding (resharded resume)
    restored = load_checkpoint(
        path,
        template=params,
        mesh=mesh,
        specs={"w": P("tensor", None), "step": P()},
    )
    assert restored["w"].sharding.spec == P("tensor", None)
    assert np.array_equal(np.asarray(restored["w"]), np.arange(32).reshape(8, 4))


def test_checkpoint_manager_resume(tmp_path):
    state = {"w": jnp.ones((4,)), "step": jnp.int32(0)}
    with CheckpointManager(str(tmp_path / "run"), max_to_keep=2) as mgr:
        assert mgr.latest_step() is None
        for s in range(3):
            mgr.save(s, {"w": state["w"] * s, "step": jnp.int32(s)}, wait=True)
        assert mgr.latest_step() == 2
        assert sorted(mgr.all_steps()) == [1, 2]  # retention dropped step 0
        out = mgr.restore(template=state)
        assert int(out["step"]) == 2
        assert np.allclose(out["w"], 2.0)


def test_mp_ckpt_suffix(devices8):
    assert get_mp_ckpt_suffix() == ""  # no mesh -> no suffix
    tpc.setup_process_groups([("data", 2), ("pipe", 2), ("tensor", 2)], devices=devices8)
    suffix = get_mp_ckpt_suffix()
    assert suffix == "_tp_0_pp_0"  # single-process: local device at origin


def test_checkpoint_moe_model_roundtrip(tmp_path, devices8):
    """The MoE GPT's heterogeneous block list with EP-sharded expert stacks
    saves and restores through Orbax with its shardings intact — the
    checkpoint/resume subsystem must cover the MoE flagship, not just dense
    pytrees."""
    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_moe_param_specs,
        init_gpt_moe_params,
    )
    from jax.sharding import NamedSharding

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_every=2,
    )
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    tpc.build_moe_mesh(moe_ep_size=4)
    mesh = tpc.get_view("moe")
    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    specs = gpt_moe_param_specs(cfg, tp_axis=None, ep_axis="moe_ep")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    assert sharded["blocks"][1]["moe"]["experts"]["w1"].sharding.spec == P(
        "moe_ep", None, None
    )

    path = str(tmp_path / "moe_ckpt")
    save_checkpoint(path, sharded)
    restored = load_checkpoint(path, template=sharded, mesh=mesh, specs=specs)
    assert restored["blocks"][1]["moe"]["experts"]["w1"].sharding.spec == P(
        "moe_ep", None, None
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        jax.device_get(sharded),
        jax.device_get(restored),
    )


def test_prefetch_to_sharding(devices8):
    """Batches come out device-resident with the requested sharding, in
    order, for prefetch depths 0/1/2 (and > the iterator length)."""
    import numpy as np

    from torchdistpackage_tpu.utils import microbatch, prefetch_to_sharding

    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    batches = [
        {"x": np.full((16, 4), i, np.float32), "y": np.arange(16) + i}
        for i in range(5)
    ]
    for depth in (0, 1, 2, 7):
        out = list(prefetch_to_sharding(batches, mesh, P("data"), prefetch=depth))
        assert len(out) == 5
        for i, b in enumerate(out):
            assert b["x"].sharding.spec == P("data")
            assert float(b["x"][0, 0]) == i  # order preserved
            assert int(b["y"][0]) == i

    mb = microbatch(batches[0], 4)
    assert mb["x"].shape == (4, 4, 4)
    import pytest as _pytest

    with _pytest.raises(ValueError, match="not divisible"):
        microbatch(batches[0], 5)


def test_global_batch_from_local_single_process(devices8):
    """Single-process degenerate case: global_batch_from_local must produce
    exactly what shard_batch does (same values, same shardings) — the
    multi-host path's contract is 'identical result, no full-batch host
    copy', which single-process CI can check for the value half."""
    import numpy as np

    from torchdistpackage_tpu.utils import global_batch_from_local, shard_batch

    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devices8)
    mesh = tpc.get_view()
    batch = {
        "x": np.arange(8 * 4, dtype=np.float32).reshape(8, 4),
        "y": np.arange(8, dtype=np.int32),
    }
    got = global_batch_from_local(batch, mesh, P("data"))
    want = shard_batch(batch, mesh, P("data"))
    for k in batch:
        assert got[k].sharding == want[k].sharding, k
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))

    # per-leaf spec tree variant
    got2 = global_batch_from_local(
        batch, mesh, {"x": P(("data", "tensor")), "y": P()}
    )
    assert got2["x"].sharding.spec == P(("data", "tensor"))
    assert got2["y"].sharding.spec == P()
    np.testing.assert_array_equal(np.asarray(got2["x"]), batch["x"])


def test_metrics_logger(tmp_path):
    """JSONL records, step timing, compile-excluded throughput average, and
    EMA companions."""
    import json as _json
    import time as _time

    from torchdistpackage_tpu.utils import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    ml = MetricsLogger(path=path, tokens_per_step=1000, ema=0.5, print_every=0)
    for i in range(4):
        _time.sleep(0.01)
        ml.log(i, loss=float(4 - i))
    assert len(ml.history) == 4
    # first record has no interval; second's throughput is excluded from avg
    assert "step_time_s" not in ml.history[0]
    assert "tok_per_sec" in ml.history[1]
    assert "tok_per_sec_avg" not in ml.history[1]
    assert "tok_per_sec_avg" in ml.history[2]
    # EMA companions move toward the new value
    assert ml.history[1]["loss_ema"] == 0.5 * 4.0 + 0.5 * 3.0
    with open(path) as f:
        lines = [_json.loads(l) for l in f]
    assert [r["step"] for r in lines] == [0, 1, 2, 3]
    assert lines[3]["loss"] == 1.0


def test_graceful_shutdown_and_auto_resume(tmp_path, devices8):
    """Preemption plumbing (VERDICT r4 #8): a real SIGTERM sets the flag
    (second TERM would hard-kill — not exercised), handlers restore on
    exit, and auto_resume returns (0, template) fresh vs (latest+1,
    restored) after a save.  Exact-trajectory resume at the flagship scale
    lives in examples/train_preemptible.py (CI: test_examples)."""
    import os
    import signal

    from torchdistpackage_tpu.utils import (
        CheckpointManager,
        GracefulShutdown,
        auto_resume,
    )

    prev = signal.getsignal(signal.SIGTERM)
    with GracefulShutdown() as stop:
        assert not stop.requested
        os.kill(os.getpid(), signal.SIGTERM)
        assert stop.requested
    assert signal.getsignal(signal.SIGTERM) is prev  # handler restored

    tpc.setup_process_groups([("data", 8)], devices=devices8)
    template = {"x": jnp.arange(8.0), "step_loss": jnp.float32(0.0)}
    with CheckpointManager(str(tmp_path / "ck")) as mgr:
        start, state = auto_resume(mgr, template)
        assert start == 0 and state is template
        mgr.save(3, {"x": jnp.arange(8.0) * 2, "step_loss": jnp.float32(1.5)},
                 wait=True)
        start, state = auto_resume(mgr, template)
        assert start == 4
        np.testing.assert_array_equal(np.asarray(state["x"]),
                                      np.arange(8.0) * 2)
        assert float(state["step_loss"]) == 1.5
