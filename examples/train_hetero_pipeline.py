"""End-to-end example: HETEROGENEOUS pipeline stages — different activation
widths on every inter-stage edge, the analogue of the reference's shape-meta
handshake capability (parallel/pipeline_parallel/comm.py:26-105), expressed
statically as a max-edge bus with per-stage lax.switch dispatch
(`make_heterogeneous_stage`).

A 2-stage funnel model: stage 0 widens D0=64 -> D1=96, stage 1 narrows
D1=96 -> D2=32; the 1F1B scheduler carries one uniform bus vector sized to
the largest edge, every edge contract is validated at trace time, and the
grads equal serial AD through the composed model.

- real TPU chips:      python examples/train_hetero_pipeline.py
- 8-device CPU sim:    TDP_CPU_SIM=8 python examples/train_hetero_pipeline.py
"""

import functools
import os

if os.environ.get("TDP_CPU_SIM"):
    # XLA_FLAGS handling is centralized in dist/overlap.py (test_repo_lint
    # bans direct writes); cpu_sim also pins the cpu platform, replacing
    # the old post-import jax.config.update dance.
    from torchdistpackage_tpu.dist.overlap import cpu_sim

    cpu_sim(os.environ["TDP_CPU_SIM"])

import jax

import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from torchdistpackage_tpu import setup_distributed, tpc
from torchdistpackage_tpu.parallel.pipeline_parallel import (
    make_heterogeneous_stage,
    pipeline_1f1b,
)


def main():
    setup_distributed()
    ndev = len(jax.devices())
    pp = 2 if ndev % 2 == 0 else 1
    tpc.setup_process_groups([("pipe", pp)], devices=jax.devices()[:pp])
    mesh = tpc.get_view()

    mbs, M = 4, 4
    D0, D1, D2 = 64, 96, 32
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    params = {
        "wide": {"w": jax.random.normal(k0, (D0, D1)) / np.sqrt(D0)},
        "narrow": {"w": jax.random.normal(k1, (D1, D2)) / np.sqrt(D1)},
    }

    def widen(p, x, m):
        return jnp.tanh(x @ p["wide"]["w"])

    def narrow(p, x, m):
        return jnp.tanh(x @ p["narrow"]["w"])

    stage_fns = [widen, narrow] if pp == 2 else [
        lambda p, x, m: narrow(p, widen(p, x, m), m)
    ]
    edges = (
        [jax.ShapeDtypeStruct((mbs, d), jnp.float32) for d in (D0, D1, D2)]
        if pp == 2
        else [jax.ShapeDtypeStruct((mbs, d), jnp.float32) for d in (D0, D2)]
    )
    wrap_first, stage_fn, wrap_last = make_heterogeneous_stage(
        stage_fns, edges)

    vg = shard_map(
        functools.partial(
            pipeline_1f1b,
            first_fn=wrap_first(lambda p, mb: mb),
            stage_fn=stage_fn,
            last_fn=wrap_last(lambda p, y, t: jnp.mean((y - t) ** 2)),
            num_microbatches=M,
            stage_takes_mb=True,
        ),
        mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=(P(), P()),
    )

    opt = optax.adam(1e-2)
    state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, s, x, t):
        loss, grads = vg(p, x, t)
        updates, s = opt.update(grads, s, p)
        return jax.tree.map(jnp.add, p, updates), s, loss

    steps = 3 if os.environ.get("TDP_SMOKE") else 30
    kx, kt = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (M, mbs, D0))
    t = jax.random.normal(kt, (M, mbs, D2))
    for i in range(steps):
        params, state, loss = step(params, state, x, t)
        print(f"step {i}: loss {float(loss):.4f}")
    assert np.isfinite(float(loss))
    print("heterogeneous pipeline example done")


if __name__ == "__main__":
    main()
