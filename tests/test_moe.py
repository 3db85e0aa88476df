"""MoE golden tests, in the reference's discipline (SURVEY.md §4): same
weights, serial model vs EP-sharded model, forward AND training parity.
The reference has no native MoE dispatch to test against (it delegates to
DeepSpeed forks, explore/moe/ds_fmoe_main.py) — the golden here is a dense
per-token mixture computed with plain einsums."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.parallel.moe import (
    MoEConfig,
    init_moe_params,
    moe_forward,
    moe_grad_reduce_overrides,
    moe_param_specs,
)

CFG = MoEConfig(dim=16, ffn_dim=32, num_experts=4, top_k=2, capacity_factor=4.0)


def dense_mixture_golden(params, x, cfg):
    """Every token through every expert, combined by renormalized top-k gates
    (valid when capacity drops nothing)."""
    B, S, D = x.shape
    t = x.reshape(-1, D)
    probs = jax.nn.softmax((t @ params["router"]["w"]).astype(jnp.float32), axis=-1)
    gv, gi = jax.lax.top_k(probs, cfg.top_k)
    gv = gv / jnp.sum(gv, axis=-1, keepdims=True)
    w = jnp.zeros_like(probs)
    for j in range(cfg.top_k):
        w = w + jax.nn.one_hot(gi[:, j], cfg.num_experts) * gv[:, j : j + 1]
    e = params["experts"]
    h = jax.nn.gelu(jnp.einsum("td,edf->etf", t, e["w1"]) + e["b1"][:, None, :])
    out = jnp.einsum("etf,efd->etd", h, e["w2"]) + e["b2"][:, None, :]
    y = jnp.einsum("te,etd->td", w.astype(x.dtype), out)
    return y.reshape(B, S, D)


def test_moe_serial_matches_dense_golden():
    params = init_moe_params(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, CFG.dim))
    y, aux = moe_forward(params, x, CFG)
    golden = dense_mixture_golden(params, x, CFG)
    np.testing.assert_allclose(np.asarray(y), np.asarray(golden), rtol=1e-5, atol=1e-5)
    assert np.isfinite(float(aux)) and float(aux) > 0


@pytest.mark.heavy
@pytest.mark.parametrize("mode", [
    True,
    # the flash-policy variants are each a full extra grad compile of the
    # same parity claim — slow tier keeps the matrix, the fast tier keeps
    # the representative mode (tier-1 budget; dense flash-remat parity
    # stays fast-tier in test_gpt.py)
    pytest.param("flash", marks=pytest.mark.slow),
    pytest.param("flash_offload", marks=pytest.mark.slow),
])
def test_gpt_moe_serial_remat_modes_match(mode):
    """The non-pipeline MoE path supports activation checkpointing (before
    this, only the dense family and the MoE pipeline did): every remat mode
    must be numerically identical to remat=False through the heterogeneous
    dense/expert block loop, flash attention included."""
    from torchdistpackage_tpu.models import (
        GPTConfig, gpt_moe_loss, init_gpt_moe_params,
    )

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=4, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2, moe_capacity_factor=4.0,
        moe_aux_weight=1e-2, attn_impl="flash",
    )
    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    batch = {
        "tokens": jax.random.randint(k1, (2, 16), 0, cfg.vocab_size),
        "targets": jax.random.randint(k2, (2, 16), 0, cfg.vocab_size),
    }
    g0 = jax.jit(jax.grad(
        lambda p: gpt_moe_loss(p, batch, cfg, remat=False)))(params)
    g1 = jax.jit(jax.grad(
        lambda p: gpt_moe_loss(p, batch, cfg, remat=mode)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
            err_msg=f"remat={mode}"),
        g0, g1,
    )


def test_gpt_moe_gqa_specs_match_params(devices8):
    """GQA through the MoE family: the spec tree must mirror the GQA param
    leaves (wq/wkv, not wqkv) or every tree.map/shard_map dies on structure
    mismatch — and the EP-sharded model must run with kv_heads set."""
    from torchdistpackage_tpu.models import (
        GPTConfig, gpt_moe_loss, gpt_moe_param_specs, init_gpt_moe_params,
    )

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2, moe_capacity_factor=4.0,
        attn_impl="flash", kv_heads=2,
    )
    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    specs = gpt_moe_param_specs(cfg, tp_axis=None, ep_axis="moe_ep")
    # structure compatibility IS the test
    jax.tree.map(lambda a, s: None, params, specs)

    tpc.setup_process_groups([("data", 4)], devices=devices8[:4])
    tpc.build_moe_mesh(moe_ep_size=4)
    mesh = tpc.get_view("moe")
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    batch = {
        "tokens": jax.random.randint(k1, (4, 16), 0, 64),
        "targets": jax.random.randint(k2, (4, 16), 0, 64),
    }
    loss = jax.jit(shard_map(
        lambda p, b: jax.lax.pmean(
            gpt_moe_loss(p, b, cfg, ep_axis="moe_ep"), ("moe_dp", "moe_ep")),
        mesh=mesh,
        in_specs=(specs, {"tokens": P(("moe_dp", "moe_ep")),
                          "targets": P(("moe_dp", "moe_ep"))}),
        out_specs=P(),
    ))(params, batch)
    assert np.isfinite(float(loss))


# 'dense' is the oracle the sorted dispatch is held to.  The point that
# DROPS (priority + dumpster row) holds the claim in the fast tier since the
# fused kernel and its test went (PR 28); the rest of the router x capacity
# matrix stays slow-tier.
@pytest.mark.parametrize("router,cf", [
    ("topk", 0.6),    # drops: priority/dumpster path exercised
    pytest.param("topk", 4.0, marks=pytest.mark.slow),    # no drops
    pytest.param("expert_choice", 1.0, marks=pytest.mark.slow),
])
def test_sorted_dispatch_matches_dense(router, cf):
    """The index-based (gather/scatter-add) dispatch must reproduce the
    dense [T,E,C] einsum path — same routing decision, same outputs and
    GRADS, for both routers, including a capacity that actually drops."""
    import dataclasses

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, CFG.dim))
    dense_cfg = dataclasses.replace(
        CFG, router=router, capacity_factor=cf, dispatch="dense")
    sort_cfg = dataclasses.replace(dense_cfg, dispatch="sorted")
    params = init_moe_params(jax.random.PRNGKey(0), dense_cfg)

    def loss(p, cfg):
        y, aux = moe_forward(p, x, cfg)
        return jnp.mean(y * y) + aux

    ls, gs = jax.value_and_grad(functools.partial(loss, cfg=sort_cfg))(params)
    ld, gd = jax.value_and_grad(functools.partial(loss, cfg=dense_cfg))(params)
    np.testing.assert_allclose(float(ls), float(ld), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        gs, gd,
    )


def test_dispatch_auto_threshold():
    """'auto' picks dense below _DENSE_DISPATCH_MAX elements and sorted
    above; explicit settings always win."""
    from torchdistpackage_tpu.parallel.moe import _DENSE_DISPATCH_MAX, _use_sorted

    E = CFG.num_experts
    assert not _use_sorted("auto", T=32, E=E, capacity=8)
    # T*E*C just over the line -> sorted
    big_T = _DENSE_DISPATCH_MAX // (E * 8) + 1
    assert _use_sorted("auto", T=big_T, E=E, capacity=8)
    assert _use_sorted("sorted", T=2, E=E, capacity=1)
    assert not _use_sorted("dense", T=big_T, E=E, capacity=8)


@pytest.mark.parametrize("bad", ["pallas", "cuda"])
def test_dispatch_values_validated(bad):
    """The dispatch values are the ones that exist: the fused Pallas
    dispatch went in PR 28 (it never lowered for the TPU), and every layer
    that takes the option names the list when it refuses."""
    import dataclasses

    from torchdistpackage_tpu.models import GPTConfig
    from torchdistpackage_tpu.parallel.moe import (
        MOE_DISPATCHES, resolve_moe_dispatch)

    assert MOE_DISPATCHES == ("dense", "sorted", "auto")
    for make in (lambda: dataclasses.replace(CFG, dispatch=bad),
                 lambda: resolve_moe_dispatch(bad),
                 lambda: GPTConfig(vocab_size=64, dim=32, nheads=4,
                                   nlayers=2, max_seq=32, moe_experts=4,
                                   moe_dispatch=bad)):
        with pytest.raises(ValueError, match="'dense', 'sorted', 'auto'"):
            make()


def test_resolve_moe_dispatch_records_auto():
    """'auto' resolves per backend (the size rule on the CPU; 'sorted' on a
    TPU is test_chip_smoke's assertion) and records the choice on the
    event timeline; explicit values pass through."""
    from torchdistpackage_tpu.obs.events import EventLog, set_default_event_log
    from torchdistpackage_tpu.parallel.moe import resolve_moe_dispatch

    log = EventLog()
    set_default_event_log(log)
    try:
        assert resolve_moe_dispatch("auto") == "auto"
        assert resolve_moe_dispatch(None) == "auto"
        sel = log.of_kind("moe_dispatch_selected")
        assert len(sel) == 2 and sel[-1]["chosen"] == "auto"
    finally:
        set_default_event_log(None)
    for ok in ("dense", "sorted"):
        assert resolve_moe_dispatch(ok) == ok


# Fast-tier EP coverage is test_moe_ep_matches_serial below; this
# EP=4-vs-serial-chunks golden stays slow-tier.
@pytest.mark.slow
def test_sorted_dispatch_under_ep_matches_serial(devices8):
    """Sorted dispatch feeds the same [E, C, D] all_to_all machinery: EP=4
    must equal the serial sorted layer per device chunk."""
    import dataclasses

    cfg = dataclasses.replace(CFG, dispatch="sorted")
    mesh = _moe_view(devices8)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, cfg.dim))

    specs = moe_param_specs("moe_ep")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    xspec = P(("moe_dp", "moe_ep"))
    x_sh = jax.device_put(x, NamedSharding(mesh, xspec))

    def fwd(p, xx):
        y, aux = moe_forward(p, xx, cfg, ep_axis="moe_ep")
        return y

    out = jax.jit(
        shard_map(fwd, mesh=mesh, in_specs=(specs, xspec), out_specs=xspec)
    )(sharded, x_sh)
    chunks = []
    for d in range(8):
        yd, _ = moe_forward(params, x[d : d + 1], cfg)
        chunks.append(yd)
    want = jnp.concatenate(chunks, axis=0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_moe_capacity_drops_are_zero():
    # capacity 1 slot/expert: overflowing tokens must contribute exactly zero
    cfg = MoEConfig(dim=8, ffn_dim=16, num_experts=2, top_k=1, capacity_factor=0.01)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.dim))
    y, _ = moe_forward(params, x, cfg)
    y = np.asarray(y).reshape(-1, cfg.dim)
    # at most 2 tokens (1 per expert) produce nonzero output
    nonzero = np.sum(np.any(np.abs(y) > 0, axis=-1))
    assert nonzero <= 2, nonzero
    assert np.all(np.isfinite(y))


def _moe_view(devices8, ep=4):
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    tpc.build_moe_mesh(moe_ep_size=ep)
    return tpc.get_view("moe")


def test_moe_ep_matches_serial(devices8):
    mesh = _moe_view(devices8)
    params = init_moe_params(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, CFG.dim))

    serial, _ = moe_forward(params, x, CFG)

    specs = moe_param_specs("moe_ep")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    xspec = P(("moe_dp", "moe_ep"))
    x_sh = jax.device_put(x, NamedSharding(mesh, xspec))

    def fwd(p, xx):
        y, aux = moe_forward(p, xx, CFG, ep_axis="moe_ep")
        return y, jax.lax.pmean(aux, ("moe_dp", "moe_ep"))

    out, aux = jax.jit(
        shard_map(fwd, mesh=mesh, in_specs=(specs, xspec), out_specs=(xspec, P()))
    )(sharded, x_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(serial), rtol=1e-5, atol=1e-5)
    assert np.isfinite(float(aux))


def test_moedp_training_matches_serial(devices8):
    """EP=4 x MoE-DP=2 train step with expert-grad override must track the
    single-device trajectory (the reference's MoEDP capability,
    naive_ddp.py:233-441, tested as in examples/test_ddp.py)."""
    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    mesh = _moe_view(devices8)
    params = init_moe_params(jax.random.PRNGKey(0), CFG)
    specs = moe_param_specs("moe_ep")
    opt = optax.sgd(5e-2)

    def loss_fn(p, batch, ep_axis=None):
        y, _aux = moe_forward(p, batch["x"], CFG, ep_axis=ep_axis)
        return jnp.mean((y - batch["y"]) ** 2)

    dp = DataParallel(
        mesh=mesh,
        axis=("moe_dp", "moe_ep"),
        grad_reduce_overrides=moe_grad_reduce_overrides(),
    )
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        functools.partial(loss_fn, ep_axis="moe_ep"),
        opt,
        param_specs=specs,
        batch_spec={"x": P(("moe_dp", "moe_ep")), "y": P(("moe_dp", "moe_ep"))},
    )

    sparams, sstate = params, opt.init(params)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    for i in range(3):
        kx, ky = jax.random.split(jax.random.PRNGKey(10 + i))
        batch = {
            "x": jax.random.normal(kx, (8, 8, CFG.dim)),
            "y": jax.random.normal(ky, (8, 8, CFG.dim)),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        sh_batch = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P(("moe_dp", "moe_ep")))),
            batch,
        )
        sharded, state, dloss = step(sharded, state, sh_batch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for name in ("w1", "b1", "w2", "b2"):
        np.testing.assert_allclose(
            np.asarray(sharded["experts"][name]),
            np.asarray(sparams["experts"][name]),
            rtol=1e-4,
            atol=1e-5,
            err_msg=f"expert param {name} diverged",
        )
    np.testing.assert_allclose(
        np.asarray(sharded["router"]["w"]),
        np.asarray(sparams["router"]["w"]),
        rtol=1e-4,
        atol=1e-5,
    )


@pytest.mark.heavy
def test_gpt_moe_training_matches_serial(devices8):
    """The BASELINE.md MoE milestone end-to-end: an MoE GPT (expert FFN every
    other block) trained EP x MoE-DP x TP(+SP) on the moe mesh view must
    track the serial trajectory — the reference's MoEDP capability
    (naive_ddp.py:233-441 + process_topo.py:118-143) applied to a full LM."""
    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_moe_loss,
        gpt_moe_param_specs,
        init_gpt_moe_params,
    )
    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2,
        # no token drops -> serial and EP dispatch see identical routing
        moe_capacity_factor=4.0,
        # the aux loss is a product of per-batch means, so the local-batch
        # aux deliberately differs from the serial full-batch aux; golden
        # trajectory equality needs it off (aux-on training is covered by
        # test_gpt_moe_aux_trains)
        moe_aux_weight=0.0,
    )
    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devices8)
    tpc.build_moe_mesh(moe_ep_size=2)
    mesh = tpc.get_view("moe")
    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    specs = gpt_moe_param_specs(cfg, tp_axis="tensor", ep_axis="moe_ep")
    opt = optax.adam(1e-2)

    dp = DataParallel(
        mesh=mesh,
        axis=("moe_dp", "moe_ep"),
        grad_reduce_overrides=moe_grad_reduce_overrides(),
    )
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        lambda p, b: gpt_moe_loss(p, b, cfg, axis="tensor", sp=True, ep_axis="moe_ep"),
        opt,
        param_specs=specs,
        batch_spec={
            "tokens": P(("moe_dp", "moe_ep")),
            "targets": P(("moe_dp", "moe_ep")),
        },
    )

    sparams, sstate = params, opt.init(params)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(lambda p, b: gpt_moe_loss(p, b, cfg))(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    B, S = 8, 16
    for i in range(3):
        k1, k2 = jax.random.split(jax.random.PRNGKey(50 + i))
        batch = {
            "tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, (B, S), 0, cfg.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(("moe_dp", "moe_ep")))
            ),
            batch,
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    # dense AND expert params track the serial run
    moe_block = sharded["blocks"][1]["moe"]
    serial_moe = sparams["blocks"][1]["moe"]
    for name in ("w1", "w2"):
        np.testing.assert_allclose(
            np.asarray(moe_block["experts"][name]),
            np.asarray(serial_moe["experts"][name]),
            rtol=1e-3, atol=1e-5,
            err_msg=f"expert param {name} diverged",
        )
    np.testing.assert_allclose(
        np.asarray(sharded["blocks"][0]["mlp"]["w1"]),
        np.asarray(sparams["blocks"][0]["mlp"]["w1"]),
        rtol=1e-3, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(sharded["head"]), np.asarray(sparams["head"]),
        rtol=1e-3, atol=1e-5,
    )


def chunked_moe_serial_loss(cfg, M, nshards, rows_per_shard=2):
    """Serial golden for distributed MoE training: the mean of per-
    (microbatch, data-shard) chunk losses — each device routes (and
    balances) its LOCAL rows, so this chunked evaluation IS the
    distributed semantics (gpt_moe_pipeline_1f1b NB).  Shared by the DP,
    interleaved, and ZeRO composition goldens."""
    from torchdistpackage_tpu.models import gpt_moe_loss

    def serial_loss(p, batch):
        losses = [
            gpt_moe_loss(
                p,
                {
                    "tokens": batch["tokens"][
                        m, rows_per_shard * d : rows_per_shard * (d + 1)
                    ],
                    "targets": batch["targets"][
                        m, rows_per_shard * d : rows_per_shard * (d + 1)
                    ],
                },
                cfg,
            )
            for m in range(M)
            for d in range(nshards)
        ]
        return jnp.mean(jnp.stack(losses))

    return serial_loss


import pytest as _pytest


# PR-21 tier-1 payback (the suite dies at its 870 s kill line, and the MoE
# dispatch repair turned fast failures into ~30 s of passes): [sorted] — what
# 'auto' means on a TPU — stays the fast-tier holder of MoE x 1F1B.  The
# dense materialization is what 'auto' picks at every toy size, so the other
# MoE goldens hold it; remat='flash' under 1F1B is held by
# test_gpt.py::test_gpt_1f1b_remat_flash_matches_serial and
# test_gpt_moe_serial_remat_modes_match.
@_pytest.mark.parametrize(
    "moe_dispatch", [
        _pytest.param("dense", marks=_pytest.mark.slow),
        "sorted",
        _pytest.param("sorted+rematflash", marks=_pytest.mark.slow),
    ])
@pytest.mark.heavy
def test_gpt_moe_1f1b_matches_serial_microbatched(devices8, moe_dispatch):
    """MoE × PP: the MoE GPT under the 1F1B schedule (EP × MoE-DP × PP) must
    track a serial model trained on the mean of per-microbatch losses — the
    reference's MoE-DP (naive_ddp.py:233-441) composed with its PP+DP layout
    (Readme.md:56), which the reference never wires together.  The aux
    (load-balance) loss is ON: it rides the scheduler's stage-aux channel,
    so this also goldens the aux gradient path through the pipeline.

    The serial golden evaluates per (microbatch, data-shard) chunk: the aux
    term is a product of per-batch means (nonlinear in tokens), and under
    EP×MoE-DP each device routes its LOCAL tokens — so the distributed loss
    is the mean over M×dp chunk losses, which is what the golden computes
    (CE is linear in equal chunks, so it is unaffected)."""
    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_moe_loss,
        gpt_moe_pipeline_1f1b,
        gpt_moe_pipeline_param_specs,
        init_gpt_moe_params,
        stack_moe_stage_params,
    )
    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    # 'sorted+rematflash' additionally runs the MoE pipeline under the
    # remat='flash' policy with Pallas flash attention — the policy must
    # hold through the heterogeneous dense/expert block stack too
    dispatch, _, variant = moe_dispatch.partition("+")
    remat = "flash" if variant == "rematflash" else True
    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=4, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2,
        moe_capacity_factor=4.0,  # no drops: serial and EP routing identical
        moe_aux_weight=1e-2,
        moe_dispatch=dispatch,  # both materializations through PP x EP
        attn_impl="flash" if remat == "flash" else "naive",
    )
    M, mbs = 4, 2
    PP = 2
    tpc.setup_process_groups([("pipe", PP), ("data", 4)], devices=devices8)
    tpc.build_moe_mesh(moe_ep_size=2)
    mesh = tpc.get_view("moe")  # (pipe, moe_dp=2, moe_ep=2)

    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    stage_params = stack_moe_stage_params(params, cfg, PP)
    specs = gpt_moe_pipeline_param_specs(cfg, PP, ep_axis="moe_ep")

    def vg_fn(p, batch):
        return gpt_moe_pipeline_1f1b(
            p, batch, cfg, num_microbatches=M, ep_axis="moe_ep", remat=remat
        )

    opt = optax.sgd(1e-1)
    dp = DataParallel(
        mesh=mesh,
        axis=("moe_dp", "moe_ep"),
        grad_reduce_overrides=moe_grad_reduce_overrides(),
    )
    sharded = dp.broadcast_params(stage_params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        value_and_grad_fn=vg_fn,
        optimizer=opt,
        param_specs=specs,
        batch_spec={
            "tokens": P(None, ("moe_dp", "moe_ep")),
            "targets": P(None, ("moe_dp", "moe_ep")),
        },
    )

    sparams, sstate = params, opt.init(params)

    serial_loss = chunked_moe_serial_loss(cfg, M, nshards=4)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    S = cfg.max_seq
    for i in range(2):
        k1, k2 = jax.random.split(jax.random.PRNGKey(70 + i))
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
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    # per-position pipelined params vs the serial block list: position i of
    # stage s is serial block s*(L/P)+i
    lpp = cfg.nlayers // PP
    for i in range(lpp):
        got = np.asarray(
            jax.tree_util.tree_leaves(sharded["blocks"][i])[0]
        )
        for s_idx in range(PP):
            want_block = sparams["blocks"][s_idx * lpp + i]
            np.testing.assert_allclose(
                got[s_idx],
                np.asarray(jax.tree_util.tree_leaves(want_block)[0]),
                rtol=1e-4, atol=1e-5,
                err_msg=f"block position {i} stage {s_idx} diverged",
            )
    # expert params specifically (the aux gradient path feeds the router)
    moe_pos = 1  # blocks 1 and 3 are expert blocks (moe_every=2)
    np.testing.assert_allclose(
        np.asarray(sharded["blocks"][moe_pos]["moe"]["router"]["w"])[0],
        np.asarray(sparams["blocks"][1]["moe"]["router"]["w"]),
        rtol=1e-4, atol=1e-5, err_msg="router diverged (aux grad path)",
    )
    np.testing.assert_allclose(
        np.asarray(sharded["blocks"][moe_pos]["moe"]["experts"]["w1"])[1],
        np.asarray(sparams["blocks"][3]["moe"]["experts"]["w1"]),
        rtol=1e-4, atol=1e-5, err_msg="stage-1 expert w1 diverged",
    )
    np.testing.assert_allclose(
        np.asarray(sharded["head"]),
        np.asarray(sparams["head"]),
        rtol=1e-4, atol=1e-5,
    )


def test_gpt_moe_aux_trains(devices8):
    """With the load-balance aux ON (the Switch recipe), distributed EP
    training is finite and the loss decreases."""
    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_moe_loss,
        gpt_moe_param_specs,
        init_gpt_moe_params,
    )
    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2,
        moe_capacity_factor=1.25, moe_aux_weight=1e-2,
    )
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    tpc.build_moe_mesh(moe_ep_size=4)
    mesh = tpc.get_view("moe")
    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    specs = gpt_moe_param_specs(cfg, tp_axis=None, ep_axis="moe_ep")
    opt = optax.adam(1e-2)

    dp = DataParallel(
        mesh=mesh,
        axis=("moe_dp", "moe_ep"),
        grad_reduce_overrides=moe_grad_reduce_overrides(),
    )
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        lambda p, b: gpt_moe_loss(p, b, cfg, ep_axis="moe_ep"),
        opt,
        param_specs=specs,
        batch_spec={
            "tokens": P(("moe_dp", "moe_ep")),
            "targets": P(("moe_dp", "moe_ep")),
        },
    )

    losses = []
    for i in range(4):
        k1, _ = jax.random.split(jax.random.PRNGKey(60 + i))
        tokens = jax.random.randint(k1, (8, 16), 0, cfg.vocab_size)
        # copy task (target[i] = tokens[i-1]): learnable only via attention
        targets = jnp.concatenate([tokens[:, :1], tokens[:, :-1]], axis=1)
        batch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(("moe_dp", "moe_ep")))
            ),
            {"tokens": tokens, "targets": targets},
        )
        sharded, state, loss = step(sharded, state, batch)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]


@pytest.mark.heavy
@pytest.mark.slow
def test_gpt_moe_interleaved_1f1b_matches_serial(devices8):
    """MoE x INTERLEAVED PP: the MoE GPT under the V=2 virtual-chunk 1F1B
    schedule (EP x MoE-DP x PP x V) — L=8 so each of the 4 slabs carries the
    same [dense, expert] pattern; aux ON through the stage-aux channel with
    the chunk index folded into its grads' recompute.  Golden vs the
    per-(microbatch, data-shard) serial chunk mean, like the V=1 test.

    ``slow``: this single composition golden compiled for ~210 s of the
    870 s tier-1 budget on the CPU sim (/tmp/_t1_durations.json, PR 6) —
    a quarter of the whole suite for one test.  Its two factors stay
    independently covered in the fast tier (MoE x PP:
    ``test_gpt_moe_1f1b_matches_serial_microbatched``; the interleaved
    schedule itself: ``test_pipeline.test_interleaved_1f1b_matches_serial``
    over four (P, V, M) shapes), so the fast tier keeps the coverage and
    the full/pre-commit tier keeps the composed golden."""
    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_moe_loss,
        gpt_moe_pipeline_1f1b,
        gpt_moe_pipeline_param_specs,
        init_gpt_moe_params,
        stack_moe_stage_params,
    )
    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=8, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2,
        moe_capacity_factor=4.0, moe_aux_weight=1e-2,
    )
    M, mbs, PP, VC = 4, 2, 2, 2
    tpc.setup_process_groups([("pipe", PP), ("data", 4)], devices=devices8)
    tpc.build_moe_mesh(moe_ep_size=2)
    mesh = tpc.get_view("moe")

    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    stage_params = stack_moe_stage_params(params, cfg, PP, num_chunks=VC)
    # [V, P, ...] leaves, stage dim sharded
    assert stage_params["blocks"][0]["attn"]["wqkv"].shape[:2] == (VC, PP)
    specs = gpt_moe_pipeline_param_specs(cfg, PP, ep_axis="moe_ep", num_chunks=VC)

    def vg_fn(p, batch):
        return gpt_moe_pipeline_1f1b(
            p, batch, cfg, num_microbatches=M, ep_axis="moe_ep", num_chunks=VC
        )

    opt = optax.sgd(1e-1)
    dp = DataParallel(
        mesh=mesh,
        axis=("moe_dp", "moe_ep"),
        grad_reduce_overrides=moe_grad_reduce_overrides(),
    )
    sharded = dp.broadcast_params(stage_params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        value_and_grad_fn=vg_fn,
        optimizer=opt,
        param_specs=specs,
        batch_spec={
            "tokens": P(None, ("moe_dp", "moe_ep")),
            "targets": P(None, ("moe_dp", "moe_ep")),
        },
    )

    sparams, sstate = params, opt.init(params)

    serial_loss = chunked_moe_serial_loss(cfg, M, nshards=4)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    S = cfg.max_seq
    for i in range(2):
        k1, k2 = jax.random.split(jax.random.PRNGKey(90 + i))
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
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    # position i of slab (v, s) is serial block (v*P + s)*Lc + i; Lc=2 here,
    # position 1 is the expert block of each slab
    lc = cfg.nlayers // (PP * VC)
    for v in range(VC):
        for s_idx in range(PP):
            g = (v * PP + s_idx) * lc
            np.testing.assert_allclose(
                np.asarray(sharded["blocks"][0]["attn"]["wqkv"])[v, s_idx],
                np.asarray(sparams["blocks"][g]["attn"]["wqkv"]),
                rtol=1e-4, atol=1e-5,
                err_msg=f"slab (chunk {v}, stage {s_idx}) dense attn diverged",
            )
            np.testing.assert_allclose(
                np.asarray(sharded["blocks"][1]["moe"]["experts"]["w1"])[v, s_idx],
                np.asarray(sparams["blocks"][g + 1]["moe"]["experts"]["w1"]),
                rtol=1e-4, atol=1e-5,
                err_msg=f"slab (chunk {v}, stage {s_idx}) experts diverged",
            )
    np.testing.assert_allclose(
        np.asarray(sharded["blocks"][1]["moe"]["router"]["w"])[0, 0],
        np.asarray(sparams["blocks"][1]["moe"]["router"]["w"]),
        rtol=1e-4, atol=1e-5, err_msg="router diverged (aux grad path)",
    )


def test_expert_choice_serial_matches_dense_golden():
    """Expert-choice routing: each expert picks its top-C tokens.  Golden =
    dense per-(expert, token) mixture with the same selection computed by
    hand; also: every expert is EXACTLY full (the balance-by-construction
    property) and the aux loss is identically zero."""
    import dataclasses

    cfg = dataclasses.replace(CFG, router="expert_choice", capacity_factor=1.0)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.dim))
    y, aux = moe_forward(params, x, cfg)
    assert float(aux) == 0.0

    B, S, D = x.shape
    T, E = B * S, cfg.num_experts
    t = x.reshape(T, D)
    probs = np.asarray(
        jax.nn.softmax((t @ params["router"]["w"]).astype(jnp.float32), axis=-1)
    )
    import math as _math

    # EC capacity per Zhou et al.: ceil(T * cf / E) — top_k does NOT scale it
    C = max(1, int(_math.ceil(T * cfg.capacity_factor / E)))
    w = np.zeros((T, E))
    for e in range(E):
        picks = np.argsort(-probs[:, e], kind="stable")[:C]
        w[picks, e] = probs[picks, e]
    e_p = params["experts"]
    h = jax.nn.gelu(jnp.einsum("td,edf->etf", t, e_p["w1"]) + e_p["b1"][:, None, :])
    out = jnp.einsum("etf,efd->etd", h, e_p["w2"]) + e_p["b2"][:, None, :]
    want = jnp.einsum("te,etd->td", jnp.asarray(w, x.dtype), out).reshape(B, S, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_expert_choice_ep_matches_serial(devices8):
    """EC routing under EP=4 must equal the serial EC layer (the dispatch
    tensors feed the same all_to_all machinery as token-choice)."""
    import dataclasses

    # capacity_factor=1.0 -> C = ceil(8*1/4) = 2 < T=8 local tokens, so the
    # top-C SELECTION (not just dense routing) is exercised under EP
    cfg = dataclasses.replace(CFG, router="expert_choice", capacity_factor=1.0)
    mesh = _moe_view(devices8)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, cfg.dim))

    specs = moe_param_specs("moe_ep")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    xspec = P(("moe_dp", "moe_ep"))
    x_sh = jax.device_put(x, NamedSharding(mesh, xspec))

    def fwd(p, xx):
        y, aux = moe_forward(p, xx, cfg, ep_axis="moe_ep")
        return y

    out = jax.jit(
        shard_map(fwd, mesh=mesh, in_specs=(specs, xspec), out_specs=xspec)
    )(sharded, x_sh)
    # EC is per-device-batch routing: each device picks over ITS tokens, so
    # compare against the serial layer applied per device-chunk
    chunks = []
    for d in range(8):
        yd, _ = moe_forward(params, x[d : d + 1], cfg)
        chunks.append(yd)
    want = jnp.concatenate(chunks, axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_expert_choice_leaks_future_tokens():
    """The leak detector behind the causal guard: under EC routing, token
    t's OUTPUT changes when only a FUTURE token changes — because each
    expert ranks its top-C over the whole sequence, a perturbation at the
    end can evict/admit earlier tokens from an expert's pick list.  This is
    exactly why moe_forward(causal=True) rejects router='expert_choice'."""
    import dataclasses

    # capacity < T so the top-C pick is genuinely selective
    cfg = dataclasses.replace(CFG, router="expert_choice", capacity_factor=1.0)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.dim))

    y1, _ = moe_forward(params, x, cfg)
    # perturb ONLY the last token; a causal layer would leave y[:, :-1] bit-
    # identical (token-choice routing does — checked below as the control)
    x2 = x.at[:, -1, :].add(10.0)
    y2, _ = moe_forward(params, x2, cfg)
    assert not np.allclose(np.asarray(y1[:, :-1]), np.asarray(y2[:, :-1])), (
        "expected EC routing to leak future tokens into earlier outputs"
    )

    # control: token-choice routing with no drops is per-token causal-safe —
    # earlier outputs must be unchanged by a future-token perturbation
    tc = dataclasses.replace(CFG, router="topk", capacity_factor=float(16 * 2))
    p_tc = init_moe_params(jax.random.PRNGKey(0), tc)
    z1, _ = moe_forward(p_tc, x, tc, causal=True)
    z2, _ = moe_forward(p_tc, x2, tc, causal=True)
    np.testing.assert_allclose(
        np.asarray(z1[:, :-1]), np.asarray(z2[:, :-1]), rtol=0, atol=0
    )


def test_causal_topk_no_leak_with_drops():
    """The subtler token-choice leak: choice-major capacity priority lets a
    future token's 1st choice evict an earlier token's 2nd-choice slot.
    causal=True switches to token-major priority — earlier outputs must be
    BIT-identical under a future-token perturbation even when capacity
    drops are routine (cf=0.5), for both dispatch materializations.
    The non-causal default with the same config is demonstrably unsafe,
    which is what makes this a real guarantee rather than a vacuous one."""
    import dataclasses

    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, CFG.dim))
    x2 = x.at[:, -1, :].add(10.0)

    leaked_somewhere = False
    for dispatch in ("dense", "sorted"):
        cfg = dataclasses.replace(
            CFG, router="topk", capacity_factor=0.5, dispatch=dispatch)
        params = init_moe_params(jax.random.PRNGKey(0), cfg)
        z1, _ = moe_forward(params, x, cfg, causal=True)
        z2, _ = moe_forward(params, x2, cfg, causal=True)
        np.testing.assert_allclose(
            np.asarray(z1[:, :-1]), np.asarray(z2[:, :-1]), rtol=0, atol=0,
            err_msg=f"causal topk leaked under dispatch={dispatch}",
        )
        # sanity that capacity actually bites in this config: the
        # non-causal (choice-major) route must differ somewhere across the
        # two inputs' earlier tokens, else the test proves nothing
        y1, _ = moe_forward(params, x, cfg)
        y2, _ = moe_forward(params, x2, cfg)
        leaked_somewhere |= not np.allclose(
            np.asarray(y1[:, :-1]), np.asarray(y2[:, :-1]))
    assert leaked_somewhere, (
        "choice-major routing showed no eviction leak — capacity too high "
        "for the guard test to be meaningful"
    )


def test_expert_choice_causal_guard():
    """router='expert_choice' + causal=True must raise — both at the layer
    (moe_forward) and through the autoregressive GPT-MoE family, which
    passes causal=True unconditionally."""
    import dataclasses

    import pytest

    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_moe_loss,
        init_gpt_moe_params,
    )

    cfg = dataclasses.replace(CFG, router="expert_choice")
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, 8, cfg.dim))
    with pytest.raises(ValueError, match="expert_choice.*causal"):
        moe_forward(params, x, cfg, causal=True)

    gcfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2,
        moe_capacity_factor=1.0, moe_router="expert_choice",
    )
    gp = init_gpt_moe_params(jax.random.PRNGKey(0), gcfg)
    batch = {
        "tokens": jnp.zeros((2, 16), jnp.int32),
        "targets": jnp.zeros((2, 16), jnp.int32),
    }
    with pytest.raises(ValueError, match="expert_choice.*causal"):
        gpt_moe_loss(gp, batch, gcfg)


@pytest.mark.slow  # tier-1 budget: MoE parity and ring-CP parity each
# hold fast-tier on their own (remat_modes_match[True] /
# test_gpt.test_gpt_ring_cp_remat_flash_matches_serial); this point is
# the composition
@pytest.mark.heavy
def test_gpt_moe_with_ring_cp_matches_serial(devices8):
    """MoE × CP (the long-context expert-model pairing): an MoE GPT with
    ring attention over the context axis — attention sees the full sequence
    via the ring, each shard routes its LOCAL tokens.  With capacity high
    enough for zero drops, per-token top-k routing is identical under any
    chunking, so loss AND grads must match the serial model exactly (aux
    off: the load-balance product-of-means is per-chunk by design)."""
    import dataclasses

    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_moe_loss,
        init_gpt_moe_params,
    )

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2,
        moe_capacity_factor=4.0, moe_aux_weight=0.0,
        attn_impl="ring", context_axis="context",
    )
    cfg_serial = dataclasses.replace(
        cfg, attn_impl="naive", context_axis=None
    )
    cp = 4
    tpc.setup_process_groups([("context", cp)], devices=devices8[:cp])
    mesh = tpc.get_view()
    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    batch = {
        "tokens": jax.random.randint(k1, (4, 16), 0, cfg.vocab_size),
        "targets": jax.random.randint(k2, (4, 16), 0, cfg.vocab_size),
    }

    def cp_loss(p, b):
        # mean over LOCAL tokens -> close with pmean over context
        return jax.lax.pmean(gpt_moe_loss(p, b, cfg), "context")

    bspec = {"tokens": P(None, "context"), "targets": P(None, "context")}
    sm = shard_map(cp_loss, mesh=mesh, in_specs=(P(), bspec), out_specs=P())
    got = jax.jit(sm)(params, batch)
    want = gpt_moe_loss(params, batch, cfg_serial)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)

    g_got = jax.jit(jax.grad(lambda p, b: sm(p, b)))(params, batch)
    g_want = jax.grad(lambda p, b: gpt_moe_loss(p, b, cfg_serial))(params, batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        g_got,
        g_want,
    )


@pytest.mark.heavy
def test_gpt_moe_1f1b_with_tp_nosp_sharded_transfers(devices8):
    """MoE x TP(non-SP) x EP x PP — the expert stack with TENSOR parallelism
    through the pipeline, riding the TP-sharded inter-stage transfers
    (auto-enabled for non-SP TP).  Golden vs the chunked serial MoE loss;
    two optimizer steps track serial params."""
    from torchdistpackage_tpu.models import (
        GPTConfig,
        gpt_moe_pipeline_1f1b,
        gpt_moe_pipeline_param_specs,
        init_gpt_moe_params,
        stack_moe_stage_params,
    )
    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=4, max_seq=16, ffn_mult=2,
        moe_experts=4, moe_top_k=2, moe_every=2,
        moe_capacity_factor=4.0,  # no drops: serial and EP routing identical
        moe_aux_weight=1e-2,
    )
    M, mbs, PP = 4, 2, 2
    tpc.setup_process_groups(
        [("pipe", PP), ("data", 2), ("tensor", 2)], devices=devices8
    )
    tpc.build_moe_mesh(moe_ep_size=2)
    mesh = tpc.get_view("moe")  # (pipe, moe_dp=1, moe_ep=2, tensor=2)

    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    stage_params = stack_moe_stage_params(params, cfg, PP)
    specs = gpt_moe_pipeline_param_specs(
        cfg, PP, ep_axis="moe_ep", tp_axis="tensor")

    def vg_fn(p, batch):
        return gpt_moe_pipeline_1f1b(
            p, batch, cfg, num_microbatches=M, tp_axis="tensor", sp=False,
            ep_axis="moe_ep",
        )

    opt = optax.sgd(1e-1)
    dp = DataParallel(
        mesh=mesh,
        axis=("moe_dp", "moe_ep"),
        grad_reduce_overrides=moe_grad_reduce_overrides(),
    )
    sharded = dp.broadcast_params(stage_params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        value_and_grad_fn=vg_fn,
        optimizer=opt,
        param_specs=specs,
        batch_spec={
            "tokens": P(None, ("moe_dp", "moe_ep")),
            "targets": P(None, ("moe_dp", "moe_ep")),
        },
    )

    sparams, sstate = params, opt.init(params)
    serial_loss = chunked_moe_serial_loss(cfg, M, nshards=2)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    S = cfg.max_seq
    for i in range(2):
        k1, k2 = jax.random.split(jax.random.PRNGKey(75 + i))
        batch = {
            "tokens": jax.random.randint(k1, (M, mbs * 2, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, (M, mbs * 2, S), 0, cfg.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(None, ("moe_dp", "moe_ep")))
            ),
            batch,
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    # a TP-sharded expert leaf and the replicated head both track serial
    np.testing.assert_allclose(
        np.asarray(sharded["head"]), np.asarray(sparams["head"]),
        rtol=1e-3, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(sharded["blocks"][0]["mlp"]["w1"]),
        np.asarray(
            jnp.stack([sparams["blocks"][0]["mlp"]["w1"],
                       sparams["blocks"][2]["mlp"]["w1"]])
        ),
        rtol=1e-3, atol=1e-5,
    )


# ------------------------------------------------------ ragged serving dispatch


def test_serve_forward_matches_nodrop():
    """moe_serve_forward (ragged route-then-group, jax.lax.ragged_dot —
    VERDICT r4 weak #5) must equal the dense mixture golden and the
    no-drop capacity path exactly (same routing decision, every token
    kept; only float summation order differs), for gelu AND swiglu
    experts, prefill-sized and decode-sized T."""
    import dataclasses

    from torchdistpackage_tpu.parallel.moe import moe_serve_forward

    for act in ("gelu", "swiglu"):
        cfg = dataclasses.replace(CFG, act=act, capacity_factor=1.25)
        params = init_moe_params(jax.random.PRNGKey(0), cfg)
        for shape in ((2, 16), (3, 1)):  # prefill and decode shapes
            x = jax.random.normal(jax.random.PRNGKey(1), (*shape, cfg.dim))
            got = jax.jit(lambda p, a: moe_serve_forward(p, a, cfg))(params, x)
            nodrop = dataclasses.replace(
                cfg, capacity_factor=cfg.num_experts / cfg.top_k)
            want, _aux = moe_forward(params, x, nodrop)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
                err_msg=f"act={act} shape={shape}")
            if act == "gelu":
                golden = dense_mixture_golden(params, x, cfg)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(golden), rtol=1e-4, atol=1e-4)


def test_serve_forward_row_budget():
    """The whole point of the ragged path: expert compute touches exactly
    T*top_k rows — no [T, E, C] tensors, no E/top_k padding.  Verified
    structurally: the jaxpr contains ragged_dot ops on [T*k, ...] operands
    and NO dense-dispatch einsum intermediate of T*E*C elements."""
    params = init_moe_params(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, CFG.dim))
    T, k, E = 2 * 16, CFG.top_k, CFG.num_experts

    from torchdistpackage_tpu.parallel.moe import moe_serve_forward

    jaxpr = jax.make_jaxpr(lambda p, a: moe_serve_forward(p, a, CFG))(params, x)
    s = str(jaxpr)
    assert "ragged_dot" in s
    # the no-drop capacity path would materialize [T, E, C=T] dispatch
    # tensors (T*E*T elements); they must not exist here
    assert f"{T},{E},{T}" not in s.replace(" ", "")


def test_serve_forward_rejects_expert_choice():
    import dataclasses

    from torchdistpackage_tpu.parallel.moe import moe_serve_forward

    cfg = dataclasses.replace(CFG, router="expert_choice")
    params = init_moe_params(jax.random.PRNGKey(0), CFG)
    x = jnp.zeros((1, 4, CFG.dim))
    with pytest.raises(NotImplementedError, match="topk"):
        moe_serve_forward(params, x, cfg)
