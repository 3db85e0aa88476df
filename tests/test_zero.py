"""ZeRO golden tests — the reference's discipline (examples/test_zero_optim.py:
27-66): Bf16ZeroOptimizer vs plain DDP+Adam, params must track.  Here: ZeRO
(sharded masters/state) vs single-device adam on the same seed, plus the
hybrid intra-node variant and TP composition."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from jax.sharding import PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.parallel.zero import ZeroOptimizer, zero_partition_spec
from tests.test_data_parallel import _data, make_mlp_params, mlp_loss


def test_zero_partition_spec():
    spec, d = zero_partition_spec((32, 16), P(), "data", 8)
    assert spec == P("data") and d == 0
    spec, d = zero_partition_spec((30, 16), P(), "data", 8)
    assert spec == P(None, "data") and d == 1
    spec, d = zero_partition_spec((30, 15), P(), "data", 8)
    assert spec == P() and d == -1
    # TP-sharded dim is not reusable: data goes to the next free dim
    spec, d = zero_partition_spec((32, 16), P("tensor"), "data", 8)
    assert spec == P("tensor", "data") and d == 1


def _gpt_microbatched_serial_step(cfg, M, opt):
    """Shared serial golden for the GPT pipeline tests: mean loss over M
    microbatches + one jitted optimizer step (one copy — the pipelined
    tests compare their trajectories against THIS)."""
    from torchdistpackage_tpu.models import gpt_loss

    def serial_loss(p, batch):
        losses = [
            gpt_loss(
                p,
                {"tokens": batch["tokens"][m], "targets": batch["targets"][m]},
                cfg,
            )
            for m in range(M)
        ]
        return jnp.mean(jnp.stack(losses))

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    return serial_step


def _serial_trajectory(params, opt, nsteps=4):
    state = opt.init(params)

    @jax.jit
    def step(p, s, b):
        loss, g = jax.value_and_grad(mlp_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    hist = []
    for i in range(nsteps):
        batch = _data(jax.random.PRNGKey(100 + i))
        params, state, loss = step(params, state, batch)
        hist.append(float(loss))
    return params, hist


@pytest.mark.parametrize("accum", [1, 2])
def test_zero_matches_serial_adam(devices8, accum):
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.adam(1e-2)
    ref_params, ref_losses = _serial_trajectory(params, opt)

    zero = ZeroOptimizer(opt)
    zp = zero.place_params(params)
    zs = zero.init(zp)
    # masters really are sharded over data
    m = zs["master"]["w1"]
    assert m.sharding.spec == P("data")
    step = zero.make_train_step(mlp_loss, grad_accum_iters=accum)

    for i in range(4):
        batch = _data(jax.random.PRNGKey(100 + i))
        zp, zs, loss = step(zp, zs, zero_shard_batch(batch))
        np.testing.assert_allclose(float(loss), ref_losses[i], rtol=1e-4, atol=1e-5)

    for k in params:
        np.testing.assert_allclose(
            np.asarray(zp[k]), np.asarray(ref_params[k]), rtol=1e-3, atol=1e-5
        )


def zero_shard_batch(batch):
    import jax
    from jax.sharding import NamedSharding

    mesh = tpc.get_view()
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), batch
    )


def test_hybrid_zero(devices8):
    """Shard state over the intra 'node' sub-axis only; grads still average
    over the whole data group (Intro.md:69-77 semantics)."""
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    view = tpc.build_hybrid_mesh(intra_size=4)
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.adam(1e-2)
    ref_params, ref_losses = _serial_trajectory(params, opt)

    zero = ZeroOptimizer(
        opt,
        mesh=view,
        shard_axis="data_intra",
        grad_reduce_axes=("data_inter", "data_intra"),
    )
    zp = zero.place_params(params)
    zs = zero.init(zp)
    # master sharded 4-way (intra), replicated over inter
    assert zs["master"]["w1"].sharding.spec == P("data_intra")
    step = zero.make_train_step(mlp_loss)

    from jax.sharding import NamedSharding

    for i in range(4):
        batch = _data(jax.random.PRNGKey(100 + i))
        batch = jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(view, P(("data_inter", "data_intra")))
            ),
            batch,
        )
        zp, zs, loss = step(zp, zs, batch)
        np.testing.assert_allclose(float(loss), ref_losses[i], rtol=1e-4, atol=1e-5)

    for k in params:
        np.testing.assert_allclose(
            np.asarray(zp[k]), np.asarray(ref_params[k]), rtol=1e-3, atol=1e-5
        )


@pytest.mark.parametrize("num_chunks", [1, 2])
@pytest.mark.heavy
def test_zero_1f1b_hybrid(devices8, num_chunks):
    """North-star composition (VERDICT r2 item 3): hybrid ZeRO x 1F1B
    pipeline x DP.  Mesh data=4 (hybrid intra=2) x pipe=2; the 1F1B schedule
    supplies (loss, grads) via ``value_and_grad_fn`` and ZeRO scatters them
    to ``data_intra`` owner shards — the reference's Bf16ZeroOptimizer under
    PP+DP training (zero_optim.py:98-287 composed per Readme.md:56).
    Trajectory must match serial Adam for 3 steps.  ``num_chunks=2`` runs
    the same composition under the INTERLEAVED schedule (the config
    ``dryrun_multichip`` exercises): ZeRO shards the [V, P, Lc, ...] master
    leaves over pipe AND data_intra."""
    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_interleaved_param_specs,
        gpt_loss,
        gpt_param_specs,
        gpt_pipeline_1f1b,
        init_gpt_params,
        interleave_stage_params,
    )

    cfg = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=4, max_seq=16, ffn_mult=2)
    M, mbs, S = 4, 2, 16
    tpc.setup_process_groups([("data", 4), ("pipe", 2)], devices=devices8)
    view = tpc.build_hybrid_mesh(intra_size=2)
    flat_params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    if num_chunks > 1:
        params = interleave_stage_params(flat_params, num_chunks, 2)
        specs = gpt_interleaved_param_specs(cfg, tp_axis=None)
    else:
        params = flat_params
        specs = gpt_param_specs(cfg, tp_axis=None, pipe_axis="pipe")
    opt = optax.adam(1e-2)

    def vg_fn(p, batch):
        return gpt_pipeline_1f1b(
            p, batch, cfg, num_microbatches=M, num_chunks=num_chunks
        )

    zero = ZeroOptimizer(
        opt,
        mesh=view,
        shard_axis="data_intra",
        grad_reduce_axes=("data_inter", "data_intra"),
        param_specs=specs,
    )
    zp = zero.place_params(params)
    zs = zero.init(zp)
    # a pipe-stacked block weight gets its master sharded over BOTH pipe
    # (stage slab) and data_intra (zero shard)
    wqkv_spec = zs["master"]["blocks"]["attn"]["wqkv"].sharding.spec
    assert "pipe" in jax.tree.leaves(tuple(wqkv_spec)) or wqkv_spec[0] == "pipe"
    assert any("data_intra" in (e if isinstance(e, tuple) else (e,))
               for e in wqkv_spec if e is not None)
    step = zero.make_train_step(
        value_and_grad_fn=vg_fn,
        batch_spec={
            "tokens": P(None, ("data_inter", "data_intra")),
            "targets": P(None, ("data_inter", "data_intra")),
        },
    )

    sparams, sstate = flat_params, opt.init(flat_params)
    serial_step = _gpt_microbatched_serial_step(cfg, M, opt)

    from jax.sharding import NamedSharding

    for i in range(3):
        k1, k2 = jax.random.split(jax.random.PRNGKey(30 + i))
        batch = {
            "tokens": jax.random.randint(k1, (M, mbs * 4, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, (M, mbs * 4, S), 0, cfg.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(view, P(None, ("data_inter", "data_intra")))
            ),
            batch,
        )
        zp, zs, dloss = step(zp, zs, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for name in ["tok_emb", "pos_emb", "head"]:
        np.testing.assert_allclose(
            np.asarray(zp[name]),
            np.asarray(sparams[name]),
            rtol=1e-3,
            atol=1e-5,
            err_msg=f"param divergence at {name}",
        )
    got_w1 = np.asarray(zp["blocks"]["mlp"]["w1"])
    if num_chunks > 1:
        # [V, P, Lc, ...] back to serial layer order (slab v*P+s)
        got_w1 = got_w1.reshape(-1, *got_w1.shape[3:])
    np.testing.assert_allclose(
        got_w1,
        np.asarray(sparams["blocks"]["mlp"]["w1"]),
        rtol=1e-3,
        atol=1e-5,
    )


def test_zero_with_tp(devices8):
    """ZeRO over data axis composed with TP=2 sharded transformer params."""
    import functools

    from torchdistpackage_tpu.parallel.tensor_parallel import (
        TransformerConfig,
        init_transformer_params,
        transformer_forward,
        transformer_param_specs,
    )

    cfg = TransformerConfig(dim=32, nheads=4, nlayers=1, ffn_mult=2)
    S = 16
    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devices8)
    mesh = tpc.get_view()
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    specs = transformer_param_specs(cfg, axis="tensor")
    opt = optax.adam(1e-2)

    def tp_loss(p, batch):
        out = transformer_forward(p, batch["x"], cfg, axis="tensor", sp=True)
        return jnp.mean((out - batch["y"]) ** 2)

    def serial_loss(p, batch):
        out = transformer_forward(p, batch["x"], cfg)
        return jnp.mean((out - batch["y"]) ** 2)

    sstate = opt.init(params)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    zero = ZeroOptimizer(opt, mesh=mesh, param_specs=specs)
    zp = zero.place_params(params)
    zs = zero.init(zp)
    # a TP-sharded weight gets data inserted on its free dim
    assert zs["master"]["blocks"][0]["mlp"]["w1"].sharding.spec == P("data", "tensor")
    step = zero.make_train_step(tp_loss)

    sparams = params
    from jax.sharding import NamedSharding

    for i in range(3):
        kx, ky = jax.random.split(jax.random.PRNGKey(10 + i))
        batch = {
            "x": jax.random.normal(kx, (8, S, cfg.dim)),
            "y": jax.random.normal(ky, (8, S, cfg.dim)),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), batch
        )
        zp, zs, dloss = step(zp, zs, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    np.testing.assert_allclose(
        np.asarray(zp["blocks"][0]["mlp"]["w1"]),
        np.asarray(sparams["blocks"][0]["mlp"]["w1"]),
        rtol=1e-3,
        atol=1e-5,
    )


@pytest.mark.slow  # tier-1 budget: ZeRO trajectory parity and ring-CP
# parity each hold fast-tier on their own; this point is the
# (data, context) grad-reduce composition
@pytest.mark.heavy
def test_zero_with_ring_context_parallel(devices8):
    """ZeRO composed with ring context parallelism: optimizer state shards
    over 'data' while grads reduce over (data, context) — the context axis
    is just another grad-reduce axis to ZeRO.  Trajectory matches serial."""
    import dataclasses

    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_loss,
        init_gpt_params,
    )

    cfg = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=16, ffn_mult=2)
    cfg_cp = dataclasses.replace(cfg, attn_impl="ring", context_axis="context")
    tpc.setup_process_groups([("data", 2), ("context", 4)], devices=devices8)
    mesh = tpc.get_view()
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-2)

    zero = ZeroOptimizer(
        opt,
        mesh=mesh,
        shard_axis="data",
        grad_reduce_axes=("data", "context"),
    )
    zp = zero.place_params(params)
    zs = zero.init(zp)
    step = zero.make_train_step(
        lambda p, b: gpt_loss(p, b, cfg_cp),
        batch_spec={
            "tokens": P("data", "context"),
            "targets": P("data", "context"),
        },
    )

    sparams, sstate = params, opt.init(params)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(lambda p, b: gpt_loss(p, b, cfg))(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    from jax.sharding import NamedSharding

    for i in range(3):
        k1, k2 = jax.random.split(jax.random.PRNGKey(80 + i))
        batch = {
            "tokens": jax.random.randint(k1, (4, 16), 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, (4, 16), 0, cfg.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P("data", "context"))
            ),
            batch,
        )
        zp, zs, dloss = step(zp, zs, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for name in ["tok_emb", "head"]:
        np.testing.assert_allclose(
            np.asarray(zp[name]), np.asarray(sparams[name]),
            rtol=1e-3, atol=1e-5, err_msg=f"param divergence at {name}",
        )


@pytest.mark.heavy
def test_zero_with_moe_expert_overrides(devices8):
    """ZeRO x MoE (the DeepSpeed-style pairing): optimizer state sharded
    over 'moe_dp' with expert grads reduced over moe_dp ONLY
    (grad_reduce_overrides) while dense params reduce over the full data
    group — trajectory must match serial Adam.  Masters of EP-sharded
    expert stacks end up sharded over BOTH moe_ep (expert dim) and moe_dp
    (zero shard dim)."""
    from torchdistpackage_tpu.parallel.moe import (
        MoEConfig,
        init_moe_params,
        moe_forward,
        moe_grad_reduce_overrides,
        moe_param_specs,
    )

    cfg = MoEConfig(dim=16, ffn_dim=32, num_experts=4, top_k=2, capacity_factor=4.0)
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    tpc.build_moe_mesh(moe_ep_size=4)
    mesh = tpc.get_view("moe")
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-2)

    def loss_fn(p, batch, ep_axis=None):
        y, _aux = moe_forward(p, batch["x"], cfg, ep_axis=ep_axis)
        return jnp.mean((y - batch["y"]) ** 2)

    import functools

    zero = ZeroOptimizer(
        opt,
        mesh=mesh,
        shard_axis="moe_dp",
        grad_reduce_axes=("moe_dp", "moe_ep"),
        param_specs=moe_param_specs("moe_ep"),
        grad_reduce_overrides=moe_grad_reduce_overrides(),
    )
    zp = zero.place_params(params)
    zs = zero.init(zp)
    # expert master: EP on the expert dim AND zero-sharded on a free dim
    w1_spec = tuple(zs["master"]["experts"]["w1"].sharding.spec)
    assert "moe_ep" in w1_spec and "moe_dp" in w1_spec, w1_spec
    step = zero.make_train_step(
        functools.partial(loss_fn, ep_axis="moe_ep"),
        batch_spec={"x": P(("moe_dp", "moe_ep")), "y": P(("moe_dp", "moe_ep"))},
    )

    sparams, sstate = params, opt.init(params)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    from jax.sharding import NamedSharding

    for i in range(3):
        kx, ky = jax.random.split(jax.random.PRNGKey(10 + i))
        batch = {
            "x": jax.random.normal(kx, (8, 8, cfg.dim)),
            "y": jax.random.normal(ky, (8, 8, cfg.dim)),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        sh = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(("moe_dp", "moe_ep")))
            ),
            batch,
        )
        zp, zs, dloss = step(zp, zs, sh)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for name in ("w1", "b1", "w2", "b2"):
        np.testing.assert_allclose(
            np.asarray(zp["experts"][name]),
            np.asarray(sparams["experts"][name]),
            rtol=1e-4, atol=1e-5,
            err_msg=f"expert param {name} diverged",
        )
    np.testing.assert_allclose(
        np.asarray(zp["router"]["w"]),
        np.asarray(sparams["router"]["w"]),
        rtol=1e-4, atol=1e-5,
    )


def test_zero_override_must_contain_shard_axis():
    """An override that excludes the shard axis cannot deliver owner shards
    — rejected up front."""
    import numpy as _np
    from jax.sharding import Mesh

    mesh = Mesh(_np.array(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="must contain"):
        ZeroOptimizer(
            optax.adam(1e-2),
            mesh=mesh,
            shard_axis="data",
            grad_reduce_axes=("data",),
            grad_reduce_overrides={"experts": ()},
        )


@pytest.mark.heavy
def test_zero_moe_1f1b_full_stack(devices8):
    """The full expert-model stack: ZeRO(moe_dp) x EP x MoE-DP x PP(1F1B),
    aux ON — sharded optimizer state, expert-override grad reduction, and
    the pipelined MoE GPT all composed in one step; trajectory must match
    the per-(microbatch, data-shard) serial golden (the chunked evaluation
    is distributed routing's exact semantics)."""
    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_moe_pipeline_1f1b,
        gpt_moe_pipeline_param_specs,
        init_gpt_moe_params,
        stack_moe_stage_params,
    )
    from torchdistpackage_tpu.parallel.moe import moe_grad_reduce_overrides

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=4, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2,
        moe_capacity_factor=4.0, moe_aux_weight=1e-2,
    )
    M, mbs, PP = 4, 2, 2
    tpc.setup_process_groups([("pipe", PP), ("data", 4)], devices=devices8)
    tpc.build_moe_mesh(moe_ep_size=2)
    mesh = tpc.get_view("moe")  # (pipe, moe_dp=2, moe_ep=2)

    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    stage_params = stack_moe_stage_params(params, cfg, PP)
    specs = gpt_moe_pipeline_param_specs(cfg, PP, ep_axis="moe_ep")
    opt = optax.adam(1e-2)

    zero = ZeroOptimizer(
        opt,
        mesh=mesh,
        shard_axis="moe_dp",
        grad_reduce_axes=("moe_dp", "moe_ep"),
        param_specs=specs,
        grad_reduce_overrides=moe_grad_reduce_overrides(),
    )
    zp = zero.place_params(stage_params)
    zs = zero.init(zp)
    # an expert master leaf carries pipe (stage), moe_ep (expert dim), AND
    # moe_dp (zero shard) all at once
    w1_spec = tuple(zs["master"]["blocks"][1]["moe"]["experts"]["w1"].sharding.spec)
    flat = [a for e in w1_spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    assert {"pipe", "moe_ep", "moe_dp"} <= set(flat), w1_spec

    step = zero.make_train_step(
        value_and_grad_fn=lambda p, b: gpt_moe_pipeline_1f1b(
            p, b, cfg, num_microbatches=M, ep_axis="moe_ep"
        ),
        batch_spec={
            "tokens": P(None, ("moe_dp", "moe_ep")),
            "targets": P(None, ("moe_dp", "moe_ep")),
        },
    )

    sparams, sstate = params, opt.init(params)

    from tests.test_moe import chunked_moe_serial_loss

    serial_loss = chunked_moe_serial_loss(cfg, M, nshards=4)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    from jax.sharding import NamedSharding

    S = cfg.max_seq
    for i in range(2):
        k1, k2 = jax.random.split(jax.random.PRNGKey(40 + i))
        batch = {
            "tokens": jax.random.randint(k1, (M, mbs * 4, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, (M, mbs * 4, S), 0, cfg.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(None, ("moe_dp", "moe_ep")))
            ),
            batch,
        )
        zp, zs, dloss = step(zp, zs, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    lpp = cfg.nlayers // PP
    np.testing.assert_allclose(
        np.asarray(zp["blocks"][1]["moe"]["experts"]["w1"])[0],
        np.asarray(sparams["blocks"][1]["moe"]["experts"]["w1"]),
        rtol=1e-4, atol=1e-5, err_msg="stage-0 expert w1 diverged",
    )
    np.testing.assert_allclose(
        np.asarray(zp["blocks"][1]["moe"]["experts"]["w1"])[1],
        np.asarray(sparams["blocks"][lpp + 1]["moe"]["experts"]["w1"]),
        rtol=1e-4, atol=1e-5, err_msg="stage-1 expert w1 diverged",
    )
    np.testing.assert_allclose(
        np.asarray(zp["blocks"][1]["moe"]["router"]["w"])[0],
        np.asarray(sparams["blocks"][1]["moe"]["router"]["w"]),
        rtol=1e-4, atol=1e-5, err_msg="router diverged (aux grad path)",
    )
    np.testing.assert_allclose(
        np.asarray(zp["head"]), np.asarray(sparams["head"]),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.heavy
def test_zero_1f1b_tp_nosp_sharded_transfers(devices8):
    """ZeRO x non-SP TP x PP over the TP-SHARDED inter-stage transfers:
    the sharded optimizer consumes the pipeline's (loss, grads) while the
    activations ride the pipe sliced 1/tp — closing the composition matrix
    for the transfer mechanism.  Trajectory must match serial SGD (see the
    optimizer note below)."""
    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_loss,
        gpt_param_specs,
        gpt_pipeline_1f1b,
        init_gpt_params,
    )

    cfg = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=4, max_seq=16, ffn_mult=2)
    M, mbs, S = 4, 2, 16
    tpc.setup_process_groups(
        [("data", 2), ("pipe", 2), ("tensor", 2)], devices=devices8
    )
    mesh = tpc.get_view()
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    specs = gpt_param_specs(cfg, tp_axis="tensor", pipe_axis="pipe")
    # sgd: linear in grads, so the trajectory comparison stays a TIGHT
    # golden (adam's m/sqrt(v) amplifies benign summation-order noise well
    # past any honest tolerance after a few steps); the ZeRO machinery
    # under test is optimizer-agnostic
    opt = optax.sgd(1e-1)

    def vg_fn(p, batch):
        # pinned True (not the auto-default): if the auto rule ever
        # regresses, this test must keep covering the SHARDED path
        return gpt_pipeline_1f1b(
            p, batch, cfg, num_microbatches=M, tp_axis="tensor", sp=False,
            shard_transfers=True,
        )

    zero = ZeroOptimizer(
        opt,
        mesh=mesh,
        shard_axis="data",
        grad_reduce_axes=("data",),
        param_specs=specs,
    )
    zp = zero.place_params(params)
    zs = zero.init(zp)
    step = zero.make_train_step(
        value_and_grad_fn=vg_fn,
        batch_spec={"tokens": P(None, "data"), "targets": P(None, "data")},
    )

    sparams, sstate = params, opt.init(params)
    serial_step = _gpt_microbatched_serial_step(cfg, M, opt)

    from jax.sharding import NamedSharding

    for i in range(3):
        k1, k2 = jax.random.split(jax.random.PRNGKey(35 + i))
        batch = {
            "tokens": jax.random.randint(k1, (M, mbs * 2, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, (M, mbs * 2, S), 0, cfg.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(None, "data"))),
            batch,
        )
        zp, zs, dloss = step(zp, zs, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    np.testing.assert_allclose(
        np.asarray(zp["blocks"]["mlp"]["w1"]),
        np.asarray(sparams["blocks"]["mlp"]["w1"]),
        rtol=1e-3, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(zp["tok_emb"]), np.asarray(sparams["tok_emb"]),
        rtol=1e-3, atol=1e-5,
    )


# ------------------------------------------------------- int8 grad compression


def test_int8_ring_reduce_scatter_matches_psum_scatter(devices8):
    """The int8 ring reduce-scatter delivers the same owner tiles as the
    exact psum_scatter (within the symmetric-quantization bound), for a
    leading and a non-leading scatter dim, and falls back exactly on
    ragged tiles."""
    from jax import shard_map

    from torchdistpackage_tpu.dist.compressed import int8_ring_reduce_scatter

    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (8, 64, 24))) * 2.0

    for dim in (0, 1):
        def body(x):
            approx = int8_ring_reduce_scatter(x, "data", dim)
            exact = jax.lax.psum_scatter(
                x, "data", scatter_dimension=dim, tiled=True)
            return approx, exact

        out_spec = P("data") if dim == 0 else P(None, "data")
        approx, exact = jax.jit(
            shard_map(
                body, mesh=mesh, in_specs=(P(),),
                out_specs=(out_spec, out_spec),
            )
        )(jnp.asarray(g))
        bound = 8 * np.abs(g).max() * 8 / 127.0  # 8 addends, n-1 requant hops
        np.testing.assert_allclose(
            np.asarray(approx), np.asarray(exact), atol=bound, rtol=0.05)

    # ragged tile (20 % 8 != 0): refused loudly, same contract as tiled
    # psum_scatter (ZeRO never routes such leaves here — they replicate)
    with pytest.raises(ValueError, match="must divide"):
        jax.jit(
            shard_map(
                lambda x: int8_ring_reduce_scatter(x, "data", 2),
                mesh=mesh, in_specs=(P(),), out_specs=P(None, None, "data"))
        )(jnp.zeros((8, 64, 20)))


@pytest.mark.parametrize("hybrid", [False, True], ids=["flat", "hybrid"])
def test_zero_int8_compression_tracks_exact(devices8, hybrid):
    """ZeroOptimizer(grad_compress='int8') — VERDICT r4 weak #4: the int8
    ring composed into the ZeRO reduce-to-owner.  The compressed trajectory
    must track the exact ZeRO run within quantization noise on both the
    flat layout (ring scatter over 'data') and the hybrid layout (ring
    scatter over 'data_intra' + int8 ring over the 'data_inter' DCN leg)."""
    from jax.sharding import NamedSharding

    tpc.setup_process_groups([("data", 8)], devices=devices8)
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)

    if hybrid:
        mesh = tpc.build_hybrid_mesh(intra_size=4)
        kw = dict(mesh=mesh, shard_axis="data_intra",
                  grad_reduce_axes=("data_inter", "data_intra"))
        bspec = P(("data_inter", "data_intra"))
    else:
        mesh = tpc.get_view()
        kw = dict(mesh=mesh)
        bspec = P("data")

    def run(compress):
        zero = ZeroOptimizer(opt, grad_compress=compress,
                             compress_min_size=0, **kw)
        zp = zero.place_params(jax.tree.map(np.asarray, params))
        zs = zero.init(zp)
        step = zero.make_train_step(mlp_loss)
        losses = []
        batch = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, bspec)),
            _data(jax.random.PRNGKey(100)),
        )
        for _ in range(5):
            zp, zs, loss = step(zp, zs, batch)
            losses.append(float(loss))
        return zp, losses

    p_exact, l_exact = run(None)
    p_q, l_q = run("int8")
    assert l_q[-1] < l_q[0]
    np.testing.assert_allclose(l_q, l_exact, rtol=0.05)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p_q[k]), np.asarray(p_exact[k]), rtol=0.1, atol=5e-3)


def test_zero_int8_wire_format_in_jaxpr(devices8):
    """The compressed reduce really moves int8 over the wire: the step's
    jaxpr must contain s8 ppermutes with grad_compress='int8' and none
    without (the non-compressed path may still ppermute activations in
    other tests' pipelines — here the MLP has no other ring traffic)."""
    from jax import shard_map

    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    params = make_mlp_params(jax.random.PRNGKey(0))

    def jaxpr_for(compress):
        zero = ZeroOptimizer(optax.sgd(1e-2), mesh=mesh,
                             grad_compress=compress, compress_min_size=0)
        _, zspecs, sdims = zero._specs_for(params)

        def reduce_body(g):
            return zero.reduce_grads_to_shard(g, sdims)

        return str(jax.make_jaxpr(
            shard_map(reduce_body, mesh=mesh,
                      in_specs=(jax.tree.map(lambda _: P(), params),),
                      out_specs=zspecs)
        )(params))

    import re

    compressed = jaxpr_for("int8")
    exact = jaxpr_for(None)
    def s8_permutes(j):
        return [ln for ln in j.splitlines()
                if "ppermute" in ln and re.search(r"\b[si]8\[", ln)]
    assert s8_permutes(compressed), "no int8 ppermute in compressed jaxpr"
    assert not s8_permutes(exact)
