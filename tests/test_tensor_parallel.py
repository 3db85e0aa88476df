"""TP+SP golden tests — the reference's discipline (test_tpmlp.py:11-41,
test_attn.py:11-47, test_transformer.py:13-44): same full weights, serial
model vs TP/TP+SP model, forward AND gradient parity.  Ours is stronger: the
TP gradients come back as global arrays directly comparable to serial grads
(no manual shard gathering), and the non-SP input-grad all-reduce the
reference is missing (SURVEY.md §3.4) is exercised by the grad checks."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.parallel.tensor_parallel import (
    TransformerConfig,
    init_transformer_params,
    transformer_forward,
    transformer_param_specs,
)

CFG = TransformerConfig(dim=32, nheads=4, nlayers=2, ffn_mult=2, causal=True)
B, S = 2, 16


def _setup_tp(devices8, tp=4):
    tpc.setup_process_groups([("data", len(devices8) // tp), ("tensor", tp)], devices=devices8)
    return tpc.get_view()


def _sp_out_spec(sp):
    # SP output stays seq-sharded (gather_output=False); shard_map reassembles
    return P(None, "tensor", None) if sp else P()


@pytest.fixture(scope="module")
def serial_golden():
    """The serial reference, computed ONCE for the whole file as a single
    ``value_and_grad(has_aux=True)`` program: forward output, loss, and
    grads all come out of ONE compile (tier-1 budget: fwd+grad pairs fold
    into one program)."""
    params = init_transformer_params(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, CFG.dim))

    @jax.jit
    def vg(p, xx):
        def loss_with_out(pp):
            out = transformer_forward(pp, xx, CFG)
            return jnp.mean(out**2), out

        return jax.value_and_grad(loss_with_out, has_aux=True)(p)

    (loss, out), grads = vg(params, x)
    return {
        "params": params, "x": x, "out": np.asarray(out),
        "loss": float(loss), "grads": jax.device_get(grads),
    }


@pytest.mark.parametrize("sp", [False, True])
def test_tp_transformer_matches_serial(devices8, serial_golden, sp):
    mesh = _setup_tp(devices8)
    params, x = serial_golden["params"], serial_golden["x"]

    # TP: shard the *same global arrays* by spec; shard_map sees local shards
    specs = transformer_param_specs(CFG, axis="tensor")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    x_sh = jax.device_put(x, NamedSharding(mesh, P()))

    # forward + loss + grad parity from ONE compiled program: the shard_map
    # forward's output rides out as value_and_grad aux
    def tp_loss_with_out(p, xx):
        out = shard_map(
            functools.partial(
                transformer_forward, cfg=CFG, axis="tensor", sp=sp, gather_output=False
            ),
            mesh=mesh,
            in_specs=(specs, P()),
            out_specs=_sp_out_spec(sp),
        )(p, xx)
        return jnp.mean(out**2), out

    (tp_loss_val, tp_out), tp_grads = jax.jit(
        jax.value_and_grad(tp_loss_with_out, has_aux=True)
    )(sharded, x_sh)
    np.testing.assert_allclose(
        np.asarray(tp_out), serial_golden["out"], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        float(tp_loss_val), serial_golden["loss"], rtol=1e-5)
    flat_s, _ = jax.tree_util.tree_flatten_with_path(serial_golden["grads"])
    flat_t, _ = jax.tree_util.tree_flatten_with_path(tp_grads)
    for (path, gs), (_, gt) in zip(flat_s, flat_t):
        np.testing.assert_allclose(
            np.asarray(gt), np.asarray(gs), rtol=5e-5, atol=5e-5,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}",
        )


def test_tp_dp_composition(devices8):
    """TP=2 x DP=4 train step: grads pmean over data, TP collectives inside —
    params must follow the serial trajectory."""
    import optax

    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    tp = 2
    tpc.setup_process_groups([("data", 4), ("tensor", tp)], devices=devices8)
    mesh = tpc.get_view()
    params = init_transformer_params(jax.random.PRNGKey(0), CFG)
    specs = transformer_param_specs(CFG, axis="tensor")
    opt = optax.sgd(1e-2)

    def loss_fn(p, batch):
        out = transformer_forward(p, batch["x"], CFG, axis="tensor", sp=True)
        return jnp.mean((out - batch["y"]) ** 2)

    dp = DataParallel(mesh=mesh)
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(loss_fn, opt, param_specs=specs)

    def serial_loss(p, batch):
        out = transformer_forward(p, batch["x"], CFG)
        return jnp.mean((out - batch["y"]) ** 2)

    sparams, sstate = params, opt.init(params)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    for i in range(3):
        kx, ky = jax.random.split(jax.random.PRNGKey(10 + i))
        batch = {
            "x": jax.random.normal(kx, (8, S, CFG.dim)),
            "y": jax.random.normal(ky, (8, S, CFG.dim)),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        sharded, state, dloss = step(sharded, state, dp.shard_batch(batch))
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    w_tp = np.asarray(sharded["blocks"][0]["mlp"]["w1"])
    w_s = np.asarray(sparams["blocks"][0]["mlp"]["w1"])
    np.testing.assert_allclose(w_tp, w_s, rtol=1e-4, atol=1e-5)
