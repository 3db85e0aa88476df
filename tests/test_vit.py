"""ViT model family tests — reference pattern (SURVEY §4): TP-sharded model
vs serial model from the same weights, allclose on outputs and training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.models import (
    ViTConfig,
    init_vit_params,
    patchify,
    vit_forward,
    vit_loss,
    vit_param_specs,
)
from torchdistpackage_tpu.parallel import DataParallel

CFG = ViTConfig(
    image_size=32, patch_size=8, channels=3, num_classes=16,
    dim=64, nheads=4, nlayers=2, ffn_mult=2,
)


def _batch(key, n=8):
    ki, kl = jax.random.split(key)
    return {
        "images": jax.random.normal(ki, (n, 32, 32, 3)),
        "labels": jax.random.randint(kl, (n,), 0, CFG.num_classes),
    }


def test_patchify_shapes_and_content():
    img = jnp.arange(2 * 32 * 32 * 3, dtype=jnp.float32).reshape(2, 32, 32, 3)
    p = patchify(img, 8)
    assert p.shape == (2, 16, 8 * 8 * 3)
    # first patch of first image == top-left 8x8 block, row-major
    np.testing.assert_array_equal(
        np.asarray(p[0, 0]).reshape(8, 8, 3), np.asarray(img[0, :8, :8, :])
    )


def test_vit_forward_serial():
    params = init_vit_params(jax.random.PRNGKey(0), CFG)
    batch = _batch(jax.random.PRNGKey(1))
    logits = jax.jit(lambda p, x: vit_forward(p, x, CFG))(params, batch["images"])
    assert logits.shape == (8, CFG.num_classes)
    loss = vit_loss(params, batch, CFG)
    assert np.isfinite(float(loss))


def test_vit_tp_matches_serial(devices8):
    """Golden: TP=2 (+class-parallel head/CE) vs serial, same weights."""
    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devices8)
    mesh = tpc.get_view()
    params = init_vit_params(jax.random.PRNGKey(0), CFG)
    batch = _batch(jax.random.PRNGKey(1))

    serial_logits = vit_forward(params, batch["images"], CFG)
    serial_loss = vit_loss(params, batch, CFG)

    specs = vit_param_specs(CFG, tp_axis="tensor")
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, tpc.sharding(*s)), params, specs,
    )

    tp_fn = jax.jit(
        shard_map(
            lambda p, b: (
                vit_forward(p, b["images"], CFG, axis="tensor", sp=True),
                vit_loss(p, b, CFG, axis="tensor", sp=True),
            ),
            mesh=mesh,
            in_specs=(specs, P()),
            out_specs=(P(None, "tensor"), P()),
        )
    )
    tp_logits, tp_loss = tp_fn(sharded, batch)
    np.testing.assert_allclose(
        np.asarray(tp_logits), np.asarray(serial_logits), rtol=2e-4, atol=2e-5
    )
    np.testing.assert_allclose(float(tp_loss), float(serial_loss), rtol=1e-5)


@pytest.mark.heavy
def test_vit_dp_training_converges(devices8):
    """DP train smoke in the reference's test_ddp style: loss decreases and
    matches a single-device run."""
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    params = init_vit_params(jax.random.PRNGKey(0), CFG)
    opt = optax.adam(1e-3)
    batch = _batch(jax.random.PRNGKey(1), n=16)

    # single-device reference
    rp, rs = params, opt.init(params)

    @jax.jit
    def ref_step(p, s, b):
        l, g = jax.value_and_grad(lambda pp: vit_loss(pp, b, CFG))(p)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, l

    dp = DataParallel()
    fp = dp.broadcast_params(params)
    fs = opt.init(fp)
    step = dp.make_train_step(
        lambda p, b: vit_loss(p, b, CFG), opt,
        batch_spec={"images": P("data"), "labels": P("data")},
    )

    losses = []
    for _ in range(4):
        rp, rs, rl = ref_step(rp, rs, batch)
        fp, fs, fl = step(fp, fs, dp.shard_batch(batch))
        assert np.isclose(float(rl), float(fl), rtol=1e-4, atol=1e-5)
        losses.append(float(fl))
    assert losses[-1] < losses[0]


@pytest.mark.slow  # tier-1 budget: ring-CP parity holds fast-tier on the
# GPT trunk (test_gpt ring/rope/zigzag points), ViT parity via
# test_vit_dp_training_converges + the ViT-MoE tests; this point is the
# bidirectional-attention composition
@pytest.mark.heavy
def test_vit_ring_cp_matches_serial(devices8):
    """ViT with non-causal ring context parallelism over the patch tokens
    must match the serial model (forward + grads)."""
    import dataclasses

    cfg_cp = dataclasses.replace(CFG, attn_impl="ring", context_axis="context")
    tpc.setup_process_groups([("context", 4)], devices=devices8[:4])
    mesh = tpc.get_view()
    params = init_vit_params(jax.random.PRNGKey(0), CFG)
    batch = _batch(jax.random.PRNGKey(1))

    def cp_loss(p, b):
        return vit_loss(p, b, cfg_cp)

    sm = shard_map(
        cp_loss,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params), P()),
        out_specs=P(),
    )
    got = jax.jit(sm)(params, batch)
    want = vit_loss(params, batch, CFG)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)

    g_got = jax.jit(jax.grad(lambda p, b: sm(p, b)))(params, batch)
    g_want = jax.grad(lambda p, b: vit_loss(p, b, CFG))(params, batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        g_got,
        g_want,
    )


@pytest.mark.heavy
def test_vit_1f1b_training_matches_serial(devices8):
    """ViT under the 1F1B pipeline x DP x TP(+SP): the reference's PP
    capability is demonstrated on a VISION classifier
    (examples/model_parallel/test_pipeline.py:54-123); here the native ViT
    must trajectory-match the serial model (golden, not just liveness)."""
    from torchdistpackage_tpu.models import vit_pipeline_1f1b

    M, mbs = 4, 2
    tpc.setup_process_groups(
        [("data", 2), ("pipe", 2), ("tensor", 2)], devices=devices8
    )
    mesh = tpc.get_view()
    params = init_vit_params(jax.random.PRNGKey(0), CFG)
    specs = vit_param_specs(CFG, tp_axis="tensor", pipe_axis="pipe")

    def vg_fn(p, batch):
        return vit_pipeline_1f1b(
            p, batch, CFG, num_microbatches=M, tp_axis="tensor", sp=True
        )

    opt = optax.sgd(5e-2)
    dp = DataParallel(mesh=mesh)
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    from jax.sharding import NamedSharding

    step = dp.make_train_step(
        value_and_grad_fn=vg_fn,
        optimizer=opt,
        param_specs=specs,
        batch_spec={"images": P(None, "data"), "labels": P(None, "data")},
    )

    sparams, sstate = params, opt.init(params)

    def serial_loss(p, batch):
        losses = [
            vit_loss(
                p,
                {"images": batch["images"][m], "labels": batch["labels"][m]},
                CFG,
            )
            for m in range(M)
        ]
        return jnp.mean(jnp.stack(losses))

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    for i in range(2):
        ki, kl = jax.random.split(jax.random.PRNGKey(80 + i))
        batch = {
            "images": jax.random.normal(ki, (M, mbs * 2, 32, 32, 3)),
            "labels": jax.random.randint(kl, (M, mbs * 2), 0, CFG.num_classes),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P(None, "data"))),
            batch,
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for path, got, want in [
        ("patch_proj.w", sharded["patch_proj"]["w"], sparams["patch_proj"]["w"]),
        ("head.w", sharded["head"]["w"], sparams["head"]["w"]),
        ("blocks.mlp.w1", sharded["blocks"]["mlp"]["w1"], sparams["blocks"]["mlp"]["w1"]),
    ]:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
            err_msg=f"param divergence at {path}",
        )


@pytest.mark.heavy
def test_vit_1f1b_with_cp_matches_serial(devices8):
    """ViT x CP x PP (VERDICT r3 weak #7).  Unlike GPT-CP (loss is a mean
    over context-LOCAL tokens -> context behaves as a data axis), the ViT
    loss pmean-pools over context INSIDE the model, so context must be a
    MODEL axis: params stay context-invariant-typed and shard_map AD
    resolves each leaf correctly on its own — inside-the-pool leaves get
    the automatic transpose-psum over their genuinely-varying cotangents
    (sum of shares), after-the-pool leaves (class head) see invariant
    cotangents and keep their single full grad.  An axis-wide 'sum'
    override would double-count the head; axis-wide 'mean' would halve the
    shares — only per-leaf resolution is correct, and the vma machinery IS
    that resolution.  Two optimizer steps must track the serial model."""
    import dataclasses

    from torchdistpackage_tpu.models import vit_pipeline_1f1b

    cfg_cp = dataclasses.replace(
        CFG, attn_impl="ring", context_axis="context")
    M, mbs = 2, 2
    tpc.setup_process_groups(
        [("data", 2), ("pipe", 2), ("context", 2)], devices=devices8
    )
    mesh = tpc.get_view()
    params = init_vit_params(jax.random.PRNGKey(0), CFG)
    specs = vit_param_specs(CFG, tp_axis=None, pipe_axis="pipe")

    def vg_fn(p, batch):
        return vit_pipeline_1f1b(p, batch, cfg_cp, num_microbatches=M)

    opt = optax.sgd(5e-2)
    dp = DataParallel(mesh=mesh, axis="data")  # context = model axis
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    from jax.sharding import NamedSharding

    step = dp.make_train_step(
        value_and_grad_fn=vg_fn,
        optimizer=opt,
        param_specs=specs,
        batch_spec={"images": P(None, "data"), "labels": P(None, "data")},
    )

    sparams, sstate = params, opt.init(params)

    def serial_loss(p, batch):
        return jnp.mean(jnp.stack([
            vit_loss(
                p,
                {"images": batch["images"][m], "labels": batch["labels"][m]},
                CFG,
            )
            for m in range(M)
        ]))

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    for i in range(2):
        ki, kl = jax.random.split(jax.random.PRNGKey(90 + i))
        batch = {
            "images": jax.random.normal(ki, (M, mbs * 2, 32, 32, 3)),
            "labels": jax.random.randint(kl, (M, mbs * 2), 0, CFG.num_classes),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P(None, "data"))),
            batch,
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for path, got, want in [
        ("patch_proj.w", sharded["patch_proj"]["w"], sparams["patch_proj"]["w"]),
        ("head.w", sharded["head"]["w"], sparams["head"]["w"]),
        ("blocks.mlp.w1", sharded["blocks"]["mlp"]["w1"], sparams["blocks"]["mlp"]["w1"]),
    ]:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
            err_msg=f"param divergence at {path}",
        )


@pytest.mark.heavy
def test_vit_moe_encoder_trains_both_routers():
    """ViT-MoE (V-MoE style): the encoder MoE family where expert_choice
    routing is LEGAL (cfg.block.causal=False — the same layer the GPT
    family rejects).  Both routers train serially: loss decreases, EC aux
    identically 0, token-choice aux > 0."""
    import dataclasses

    from torchdistpackage_tpu.models import (
        init_vit_moe_params,
        vit_moe_forward,
        vit_moe_loss,
    )

    base = ViTConfig(
        image_size=32, patch_size=8, channels=3, num_classes=16,
        dim=32, nheads=4, nlayers=4, ffn_mult=2,
        moe_experts=4, moe_every=2, moe_capacity_factor=2.0,
    )
    batch = {
        "images": jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3)),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 16),
    }
    for router in ("topk", "expert_choice"):
        cfg = dataclasses.replace(base, moe_router=router)
        params = init_vit_moe_params(jax.random.PRNGKey(0), cfg)
        _, aux = vit_moe_forward(params, batch["images"], cfg)
        if router == "expert_choice":
            assert float(aux) == 0.0  # balanced by construction
        else:
            assert float(aux) > 0.0
        opt = optax.adam(1e-2)
        state = opt.init(params)

        @jax.jit
        def step(p, s):
            loss, g = jax.value_and_grad(
                lambda pp: vit_moe_loss(pp, batch, cfg))(p)
            u, s = opt.update(g, s, p)
            return jax.tree.map(jnp.add, p, u), s, loss

        losses = []
        for _ in range(5):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        assert np.all(np.isfinite(losses)) and losses[-1] < losses[0], (
            router, losses)


@pytest.mark.slow  # tier-1 budget: ViT-MoE stays fast-tier via
# test_vit_moe_encoder_trains_both_routers, EP-matches-serial via
# test_llama.test_mixtral_style_moe_ep_matches_serial; this point is
# their composition on the ViT trunk
@pytest.mark.heavy
def test_vit_moe_ep_training_matches_serial(devices8):
    """ViT-MoE under EP x MoE-DP with expert-grad overrides tracks the
    chunked serial model (each device routes its LOCAL rows) — the MoE-DP
    discipline of test_moe.py applied to the encoder family, with the
    expert-choice router (only legal in an encoder)."""
    from torchdistpackage_tpu.models import (
        init_vit_moe_params,
        vit_moe_loss,
        vit_moe_param_specs,
    )
    from torchdistpackage_tpu.parallel.moe import moe_grad_reduce_overrides

    cfg = ViTConfig(
        image_size=32, patch_size=8, channels=3, num_classes=16,
        dim=32, nheads=4, nlayers=2, ffn_mult=2,
        moe_experts=4, moe_every=2, moe_capacity_factor=4.0,
        moe_router="expert_choice",
    )
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    tpc.build_moe_mesh(moe_ep_size=4)
    mesh = tpc.get_view("moe")  # moe_dp=2 x moe_ep=4

    params = init_vit_moe_params(jax.random.PRNGKey(0), cfg)
    specs = vit_moe_param_specs(cfg, tp_axis=None, ep_axis="moe_ep")
    opt = optax.sgd(5e-2)

    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    dp = DataParallel(
        mesh=mesh,
        axis=("moe_dp", "moe_ep"),
        grad_reduce_overrides=moe_grad_reduce_overrides(),
    )
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        lambda p, b: vit_moe_loss(p, b, cfg, ep_axis="moe_ep"),
        opt,
        param_specs=specs,
        batch_spec={
            "images": P(("moe_dp", "moe_ep")),
            "labels": P(("moe_dp", "moe_ep")),
        },
    )

    # serial golden: mean of per-device-row-chunk losses (local routing)
    def serial_loss(p, b):
        losses = [
            vit_moe_loss(
                p,
                {"images": b["images"][d : d + 1], "labels": b["labels"][d : d + 1]},
                cfg,
            )
            for d in range(8)
        ]
        return jnp.mean(jnp.stack(losses))

    sparams, sstate = params, opt.init(params)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    from jax.sharding import NamedSharding

    for i in range(2):
        ki, kl = jax.random.split(jax.random.PRNGKey(95 + i))
        batch = {
            "images": jax.random.normal(ki, (8, 32, 32, 3)),
            "labels": jax.random.randint(kl, (8,), 0, 16),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(("moe_dp", "moe_ep")))),
            batch,
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    # expert leaf (EP-sharded) and a dense leaf both track serial
    np.testing.assert_allclose(
        np.asarray(sharded["blocks"][1]["moe"]["experts"]["w1"]),
        np.asarray(sparams["blocks"][1]["moe"]["experts"]["w1"]),
        rtol=1e-3, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(sharded["head"]["w"]), np.asarray(sparams["head"]["w"]),
        rtol=1e-3, atol=1e-5,
    )
