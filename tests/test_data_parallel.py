"""Golden DP tests — the reference's NaiveDDP-vs-TorchDDP discipline
(examples/test_ddp.py:27-71): same seed, DP-sharded step vs single-device
step, params must match after N iters."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.parallel.data_parallel import DataParallel


def make_mlp_params(key, din=16, dh=32, dout=4):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (din, dh)) * 0.1,
        "b1": jnp.zeros((dh,)),
        "w2": jax.random.normal(k2, (dh, dout)) * 0.1,
        "b2": jnp.zeros((dout,)),
    }


def mlp_loss(params, batch):
    x, y = batch["x"], batch["y"]
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    return jnp.mean((logits - y) ** 2)


def _data(key, n=64, din=16, dout=4):
    kx, ky = jax.random.split(key)
    return {
        "x": jax.random.normal(kx, (n, din)),
        "y": jax.random.normal(ky, (n, dout)),
    }


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_dp_matches_single_device(devices8, grad_accum):
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.adam(1e-2)

    # serial golden: full batch on one device
    ref_params = jax.tree.map(lambda x: x, params)
    ref_state = opt.init(ref_params)

    @jax.jit
    def ref_step(p, s, b):
        loss, g = jax.value_and_grad(mlp_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    dp = DataParallel()
    dpar = dp.broadcast_params(params)
    dstate = opt.init(dpar)
    step = dp.make_train_step(mlp_loss, opt, grad_accum_iters=grad_accum)

    for i in range(5):
        batch = _data(jax.random.PRNGKey(100 + i))
        ref_params, ref_state, ref_loss = ref_step(ref_params, ref_state, batch)
        dpar, dstate, dloss = step(dpar, dstate, dp.shard_batch(batch))
        # mean loss over shards == global mean (equal shard sizes)
        np.testing.assert_allclose(float(dloss), float(ref_loss), rtol=1e-4, atol=1e-5)

    for k in params:
        np.testing.assert_allclose(
            np.asarray(dpar[k]), np.asarray(ref_params[k]), rtol=1e-3, atol=1e-5
        )


def test_grad_reduce_overrides_moe_dp_semantics(devices8):
    """The reference's params-to-ignore exists so MoE expert params skip the
    main DDP reduce and sync over 'moe_dp' instead (naive_ddp.py:46-49 +
    moe_dp.md).  Here that is a per-param axis override: expert grads reduce
    over moe_dp only; shared grads over the full data group."""
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from torchdistpackage_tpu.parallel.data_parallel import (
        pvary_params,
        reduce_gradients,
    )

    tpc.setup_process_groups([("data", 8)], devices=devices8)
    moe_mesh = tpc.build_moe_mesh(moe_ep_size=4)

    params = {"shared": jnp.ones((4,)), "expert": jnp.ones((4,))}
    specs = {"shared": P(), "expert": P("moe_ep")}  # experts differ per ep rank
    x = jnp.arange(8.0)

    def body(p, xx):
        p = pvary_params(p, ("moe_dp", "moe_ep"))

        def loss(p):
            return jnp.mean(xx) * (jnp.sum(p["shared"]) + jnp.sum(p["expert"]))

        g = jax.grad(loss)(p)
        g = reduce_gradients(
            g,
            axis=("moe_dp", "moe_ep"),
            grad_reduce_overrides={"expert": ("moe_dp",)},
        )
        return g

    g = jax.jit(
        shard_map(
            body,
            mesh=moe_mesh,
            in_specs=(specs, P(("moe_dp", "moe_ep"))),
            out_specs={"shared": P(), "expert": P("moe_ep")},
        )
    )(params, x)
    # shared grad = global mean(x) = 3.5, averaged over all 8 shards
    np.testing.assert_allclose(np.asarray(g["shared"]), 3.5, rtol=1e-6)
    # device (dp, ep) holds x element dp*4+ep, so its local grad is that
    # value.  Override + 'mean' = mean over the GLOBAL batch: psum over
    # moe_dp, normalized by the full data-group size (8) — each expert sees
    # only 1/ep of the batch, so this is the true d(global mean loss)/d(w),
    # matching serial training exactly (see test_moe.py).  For ep rank j:
    # (j + (j+4)) / 8.
    want = (np.arange(4.0) * 2 + 4.0) / 8.0
    got = np.asarray(g["expert"])
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_sum_reduce_op(devices8):
    # The reference's SUM mode is unreachable (naive_ddp.py:53 bug); ours works.
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    dp_sum = DataParallel(reduce_op="sum")
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)
    dpar = dp_sum.broadcast_params(params)
    dstate = opt.init(dpar)
    step = dp_sum.make_train_step(mlp_loss, opt)
    batch = _data(jax.random.PRNGKey(2))
    out_params, _, _ = step(dpar, dstate, dp_sum.shard_batch(batch))
    # sum-reduced grads = 8x mean-reduced grads -> different update than mean
    dp_mean = DataParallel(reduce_op="mean")
    step_m = dp_mean.make_train_step(mlp_loss, opt)
    # fresh copies: the first step donated its inputs, and device_put may
    # alias identical replicated buffers
    dpar2 = dp_mean.broadcast_params(make_mlp_params(jax.random.PRNGKey(0)))
    out_params_m, _, _ = step_m(dpar2, opt.init(dpar2), dp_mean.shard_batch(batch))
    assert not np.allclose(np.asarray(out_params["w1"]), np.asarray(out_params_m["w1"]))


def test_int8_ring_pmean_bounded_error(devices8):
    """The quantized ring mean equals the exact pmean within the symmetric
    int8 bound, and every rank holds bit-identical results (a rank keeping
    its own chunk exact would make replicated params drift)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from torchdistpackage_tpu.dist.compressed import int8_ring_pmean

    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32)) * 3.0

    def body(g):
        local = g  # per-shard slice [1, 64, 32] -> squeeze
        approx = int8_ring_pmean(local[0], "data")
        # the ring's output is invariance-TYPED over the axis (what lets it
        # compose with TP/PP under check_vma) — pvary back to per-rank form
        # so the test can fetch every rank's copy and prove bit-identity of
        # the VALUES too, not just trust the type
        from torchdistpackage_tpu.parallel.data_parallel import _mark_varying

        approx = _mark_varying(approx, ("data",))
        exact = jax.lax.pmean(local[0], "data")
        exact = _mark_varying(exact, ("data",))
        return approx[None], exact[None]

    approx, exact = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P("data"),), out_specs=(P("data"), P("data"))
        )
    )(g)
    approx, exact = np.asarray(approx), np.asarray(exact)
    # every rank's copy identical
    for r in range(1, 8):
        np.testing.assert_array_equal(approx[r], approx[0])
    # error bounded by a few per-hop quantization steps
    amax = np.abs(g).max()
    bound = 5 * amax / 127.0
    assert np.max(np.abs(approx[0] - exact[0])) < bound, (
        np.max(np.abs(approx[0] - exact[0])), bound
    )
    # and it's actually close in relative terms
    np.testing.assert_allclose(approx[0], exact[0], atol=bound, rtol=0.1)


def test_int8_compressed_training_converges(devices8):
    """DataParallel(grad_compress='int8') trains: the trajectory stays close
    to the exact-reduction run (quantization noise well under SGD scale) and
    the loss decreases."""
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)

    def run(compress):
        dp = DataParallel(grad_compress=compress, compress_min_size=0)
        # fresh host copy: the step donates its inputs, and device_put may
        # alias the original buffers across runs
        p = dp.broadcast_params(jax.tree.map(np.asarray, params))
        s = opt.init(p)
        step = dp.make_train_step(mlp_loss, opt)
        losses = []
        # FIXED batch: loss must then decrease monotonically-ish; with fresh
        # random batches each step the loss sequence is not comparable
        batch = dp.shard_batch(_data(jax.random.PRNGKey(100)))
        for i in range(5):
            p, s, loss = step(p, s, batch)
            losses.append(float(loss))
        return p, losses

    p_exact, l_exact = run(None)
    p_q, l_q = run("int8")
    assert l_q[-1] < l_q[0]
    np.testing.assert_allclose(l_q, l_exact, rtol=0.05)
    for k in p_exact:
        np.testing.assert_allclose(
            np.asarray(p_q[k]), np.asarray(p_exact[k]), rtol=0.1, atol=5e-3
        )


@pytest.mark.slow  # tier-1 budget: int8 grad compression and TP parity
# each hold fast-tier on their own (test_compression.py goldens /
# test_gpt.test_tp_matches_serial); this point is the hybrid-mesh
# composition
@pytest.mark.heavy
def test_int8_compression_composes_with_tp(devices8):
    """grad_compress='int8' on a (data, tensor) mesh — the hybrid scenario
    where wire bytes matter most (reference Intro.md:69-77) and which the
    old check_vma=False design rejected outright.  The compressed TP run
    must track the exact TP run within quantization noise, and the model
    (TP-sharded leaves included) must keep training."""
    from jax.sharding import PartitionSpec as P

    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_loss,
        gpt_param_specs,
        init_gpt_params,
    )

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=16, ffn_mult=2)
    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devices8)
    mesh = tpc.get_view()
    specs = gpt_param_specs(cfg, tp_axis="tensor")
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    opt = optax.sgd(1e-2)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    batch = {
        "tokens": np.asarray(
            jax.random.randint(k1, (8, 16), 0, cfg.vocab_size)),
        "targets": np.asarray(
            jax.random.randint(k2, (8, 16), 0, cfg.vocab_size)),
    }

    def run(compress):
        dp = DataParallel(mesh=mesh, grad_compress=compress,
                          compress_min_size=0)
        p = dp.broadcast_params(jax.tree.map(np.asarray, params),
                                param_specs=specs)
        s = opt.init(p)
        step = dp.make_train_step(
            lambda pp, bb: gpt_loss(pp, bb, cfg, axis="tensor", sp=True),
            opt,
            param_specs=specs,
            batch_spec={"tokens": P("data"), "targets": P("data")},
        )
        from torchdistpackage_tpu.utils.data import shard_batch

        b = shard_batch(batch, mesh, {"tokens": P("data"), "targets": P("data")})
        losses = []
        for _ in range(3):
            p, s, loss = step(p, s, b)
            losses.append(float(loss))
        return p, losses

    p_exact, l_exact = run(None)
    p_q, l_q = run("int8")
    assert l_q[-1] < l_q[0]
    np.testing.assert_allclose(l_q, l_exact, rtol=0.05)
    # a TP-sharded leaf and a replicated leaf both stay close to exact
    np.testing.assert_allclose(
        np.asarray(p_q["blocks"]["mlp"]["w1"]),
        np.asarray(p_exact["blocks"]["mlp"]["w1"]),
        rtol=0.1, atol=5e-3,
    )
    np.testing.assert_allclose(
        np.asarray(p_q["tok_emb"]), np.asarray(p_exact["tok_emb"]),
        rtol=0.1, atol=5e-3,
    )


def test_int8_ring_singleton_axis_is_invariance_typed(devices8):
    """A 1-member data axis must still yield an invariance-typed result —
    the bare-return regression failed check_vma at the sharded out_specs
    (caught by review; the grad path is DataParallel(mesh=('data',1) x tp))."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from torchdistpackage_tpu.dist.compressed import int8_ring_pmean

    tpc.setup_process_groups([("data", 1), ("tensor", 2)], devices=devices8[:2])
    mesh = tpc.get_view()

    def body(g):
        out = int8_ring_pmean(g[0], "data")
        return out[None]

    got = jax.jit(
        shard_map(body, mesh=mesh, in_specs=(P("data"),), out_specs=P())
    )(jnp.arange(8.0).reshape(1, 8))
    np.testing.assert_array_equal(np.asarray(got), np.arange(8.0).reshape(1, 8))
