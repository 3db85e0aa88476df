"""Parallel grad-norm clipping: global norm over mixed shardings must equal
the serial norm — the capability the reference's clip only has for PP
(clip_grad_parallel.py:54-58)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.parallel.clip import (
    DynamicLossScale,
    clip_grads_by_global_norm,
    global_grad_norm,
)


def test_global_norm_mixed_shardings(devices8):
    tpc.setup_process_groups([("data", 2), ("pipe", 2), ("tensor", 2)], devices=devices8)
    mesh = tpc.get_view()
    grads = {
        "tp": jax.random.normal(jax.random.PRNGKey(0), (8, 6)),      # sharded over tensor
        "pp": jax.random.normal(jax.random.PRNGKey(1), (4, 5)),      # sharded over pipe
        "rep": jax.random.normal(jax.random.PRNGKey(2), (7,)),       # replicated
    }
    specs = {"tp": P(None, "tensor"), "pp": P("pipe"), "rep": P()}
    placed = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), grads, specs
    )

    def body(g):
        n = global_grad_norm(g)
        clipped, pre = clip_grads_by_global_norm(g, 1.0)
        n2 = global_grad_norm(clipped)
        return n, pre, n2

    n, pre, n2 = jax.jit(
        shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=(P(), P(), P()))
    )(placed)

    want = float(
        np.sqrt(sum(np.sum(np.square(np.asarray(v))) for v in grads.values()))
    )
    np.testing.assert_allclose(float(n), want, rtol=1e-5)
    np.testing.assert_allclose(float(pre), want, rtol=1e-5)
    assert float(n2) <= 1.0 + 1e-5


def test_dynamic_loss_scale():
    dls = DynamicLossScale(init_scale=8.0, growth_interval=2)
    state = dls.init()
    grads = {"w": jnp.ones((3,)) * 8.0}
    g, state, finite = dls.unscale_and_update(grads, state)
    assert bool(finite)
    np.testing.assert_allclose(np.asarray(g["w"]), 1.0)
    # inf grads: zeroed, scale halved
    bad = {"w": jnp.array([jnp.inf, 1.0, 2.0])}
    g, state2, finite = dls.unscale_and_update(bad, state)
    assert not bool(finite)
    assert float(state2.scale) == float(state.scale) / 2
    np.testing.assert_allclose(np.asarray(g["w"]), 0.0)
