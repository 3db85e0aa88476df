"""Flagship GPT golden tests: the sharded model (TP / TP+SP / TP+SP+PP+DP)
must match the serial model — the reference's golden-comparison discipline
(SURVEY.md §4) applied to a full LM with vocab-parallel embedding/CE."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.models import (
    GPTConfig,
    gpt_forward,
    gpt_loss,
    gpt_param_specs,
    gpt_pipeline_1f1b,
    gpt_pipeline_loss,
    init_gpt_params,
)
from torchdistpackage_tpu.parallel import DataParallel

CFG = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=4, max_seq=16, ffn_mult=2)
B, S = 4, 16


def _data(key):
    k1, k2 = jax.random.split(key)
    tokens = jax.random.randint(k1, (B, S), 0, CFG.vocab_size)
    targets = jax.random.randint(k2, (B, S), 0, CFG.vocab_size)
    return {"tokens": tokens, "targets": targets}


@pytest.fixture
def params():
    return init_gpt_params(jax.random.PRNGKey(0), CFG)


def test_serial_forward_shapes(params):
    batch = _data(jax.random.PRNGKey(1))
    logits = jax.jit(lambda p, t: gpt_forward(p, t, CFG))(params, batch["tokens"])
    assert logits.shape == (B, S, CFG.vocab_size)
    loss = jax.jit(lambda p, b: gpt_loss(p, b, CFG))(params, batch)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("sp", [
    # sp=True is the stricter point (TP collectives + sequence sharding);
    # the sp=False program is a sub-graph of it and stays slow-tier
    # (tier-1 budget, PR-20 payback)
    pytest.param(False, marks=pytest.mark.slow),
    True,
])
def test_tp_matches_serial(devices8, params, sp):
    tp = 4
    tpc.setup_process_groups([("tensor", tp)], devices=devices8[:tp])
    mesh = tpc.get_view()
    specs = gpt_param_specs(CFG, tp_axis="tensor")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    batch = _data(jax.random.PRNGKey(1))

    def tp_loss(p, b):
        return gpt_loss(p, b, CFG, axis="tensor", sp=sp)

    fn = jax.jit(
        shard_map(
            tp_loss,
            mesh=mesh,
            in_specs=(specs, P()),
            out_specs=P(),
        )
    )
    got = fn(sharded, batch)
    want = gpt_loss(params, batch, CFG)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)

    # grads of the sharded model must equal the serial grads
    g_got = jax.jit(
        jax.grad(
            lambda p, b: shard_map(
                tp_loss, mesh=mesh, in_specs=(specs, P()), out_specs=P()
            )(p, b)
        )
    )(sharded, batch)
    g_want = jax.grad(lambda p: gpt_loss(p, batch, CFG))(params)
    for (path, gw), (_, gg) in zip(
        jax.tree_util.tree_flatten_with_path(g_want)[0],
        jax.tree_util.tree_flatten_with_path(g_got)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(gg),
            np.asarray(gw),
            rtol=5e-4,
            atol=1e-5,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}",
        )


@pytest.mark.heavy
def test_tp_sp_pp_dp_training_matches_serial(devices8, params):
    """The full composition: DP=2 x PP=2 x TP=2 (+SP), pipelined GPT loss in a
    DataParallel train step, vs the serial model on the full batch."""
    M, mbs = 4, 2  # microbatches per data shard
    tpc.setup_process_groups(
        [("data", 2), ("pipe", 2), ("tensor", 2)], devices=devices8
    )
    mesh = tpc.get_view()
    specs = gpt_param_specs(CFG, tp_axis="tensor", pipe_axis="pipe")

    def loss_fn(p, batch):
        return gpt_pipeline_loss(
            p, batch, CFG, num_microbatches=M, tp_axis="tensor", sp=True
        )

    opt = optax.sgd(1e-1)
    dp = DataParallel(mesh=mesh)
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        loss_fn,
        opt,
        param_specs=specs,
        batch_spec={"tokens": P(None, "data"), "targets": P(None, "data")},
    )

    sparams, sstate = params, opt.init(params)

    def serial_loss(p, batch):
        losses = [
            gpt_loss(
                p,
                {"tokens": batch["tokens"][m], "targets": batch["targets"][m]},
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
        k1, k2 = jax.random.split(jax.random.PRNGKey(10 + i))
        # global batch: [M, mbs * dp, S]
        batch = {
            "tokens": jax.random.randint(k1, (M, mbs * 2, S), 0, CFG.vocab_size),
            "targets": jax.random.randint(k2, (M, mbs * 2, S), 0, CFG.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P(None, "data"))), batch
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for name in ["tok_emb", "head"]:
        np.testing.assert_allclose(
            np.asarray(sharded[name]),
            np.asarray(sparams[name]),
            rtol=1e-4,
            atol=1e-5,
            err_msg=f"param divergence at {name}",
        )
    np.testing.assert_allclose(
        np.asarray(sharded["blocks"]["mlp"]["w1"]),
        np.asarray(sparams["blocks"]["mlp"]["w1"]),
        rtol=1e-4,
        atol=1e-5,
    )


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for v in val if isinstance(val, (list, tuple)) else [val]:
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)


def _ppermute_bytes(fn, *args):
    """Total bytes of ppermute operands in fn's jaxpr (per call site, not
    per execution) — the pipe-edge payload diagnostic."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return sum(
        int(np.prod(e.invars[0].aval.shape)) * e.invars[0].aval.dtype.itemsize
        for e in _iter_eqns(jaxpr.jaxpr)
        if e.primitive.name == "ppermute"
    )


@pytest.mark.parametrize("num_chunks", [1, 2])
@pytest.mark.heavy
def test_gpt_1f1b_tp_nosp_sharded_transfers_match_serial(
        devices8, params, num_chunks):
    """The scatter_gather_tensors analogue (reference comm.py:108-155): under
    non-SP TP the inter-stage state is carried sliced 1/tp over the tensor
    axis.  (a) goldens unchanged — PP=2 x TP=2 (no SP) 1F1B training tracks
    the serial model, for the classic AND the interleaved (V=2, circular
    wrap edges) schedule; (b) the pipe ppermute payload bytes drop by
    exactly tp_size vs shard_transfers=False."""
    M, mbs = 4, 2
    tpc.setup_process_groups([("pipe", 2), ("tensor", 2)], devices=devices8[:4])
    mesh = tpc.get_view()
    orig_params = params
    if num_chunks > 1:
        from torchdistpackage_tpu.models import (
            gpt_interleaved_param_specs,
            interleave_stage_params,
        )

        params = interleave_stage_params(params, num_chunks, 2)
        specs = gpt_interleaved_param_specs(CFG, tp_axis="tensor")
    else:
        specs = gpt_param_specs(CFG, tp_axis="tensor", pipe_axis="pipe")

    def make_vg(shard_transfers):
        def vg_fn(p, batch):
            return gpt_pipeline_1f1b(
                p, batch, CFG, num_microbatches=M, tp_axis="tensor", sp=False,
                shard_transfers=shard_transfers, num_chunks=num_chunks,
            )

        return shard_map(
            vg_fn, mesh=mesh,
            in_specs=(specs, {"tokens": P(), "targets": P()}),
            out_specs=(P(), specs),
        )

    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    k1, k2 = jax.random.split(jax.random.PRNGKey(21))
    batch = {
        "tokens": jax.random.randint(k1, (M, mbs, S), 0, CFG.vocab_size),
        "targets": jax.random.randint(k2, (M, mbs, S), 0, CFG.vocab_size),
    }

    loss, grads = jax.jit(make_vg(True))(sharded, batch)

    def serial_loss(p, b):
        return jnp.mean(jnp.stack([
            gpt_loss(
                p, {"tokens": b["tokens"][m], "targets": b["targets"][m]}, CFG
            )
            for m in range(M)
        ]))

    sloss, sgrads = jax.value_and_grad(serial_loss)(orig_params, batch)
    np.testing.assert_allclose(float(loss), float(sloss), rtol=1e-5, atol=1e-6)
    if num_chunks > 1:
        from torchdistpackage_tpu.models import deinterleave_stage_params

        grads = deinterleave_stage_params(grads, num_chunks, 2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        grads, sgrads,
    )

    # payload diagnostic: transfers carry 1/tp of the state
    on = _ppermute_bytes(make_vg(True), sharded, batch)
    off = _ppermute_bytes(make_vg(False), sharded, batch)
    assert on * 2 == off, (on, off)


@pytest.mark.heavy
def test_gpt_1f1b_remat_flash_matches_serial(devices8):
    """The remat='flash' policy (save the Pallas kernel's o/lse, skip its
    fwd re-run in backward) under the pipelined stack — scan over the block
    slab inside shard_map, PP=2 x TP=2 (+SP) — must track the serial
    un-checkpointed model in loss AND grads."""
    cfg = dataclasses.replace(CFG, attn_impl="flash")
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    M, mbs = 4, 2
    tpc.setup_process_groups([("pipe", 2), ("tensor", 2)], devices=devices8[:4])
    mesh = tpc.get_view()
    specs = gpt_param_specs(cfg, tp_axis="tensor", pipe_axis="pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )

    def vg_fn(p, batch):
        return gpt_pipeline_1f1b(
            p, batch, cfg, num_microbatches=M, tp_axis="tensor", sp=True,
            remat="flash",
        )

    sm = shard_map(
        vg_fn, mesh=mesh,
        in_specs=(specs, {"tokens": P(), "targets": P()}),
        out_specs=(P(), specs),
    )
    k1, k2 = jax.random.split(jax.random.PRNGKey(33))
    batch = {
        "tokens": jax.random.randint(k1, (M, mbs, S), 0, cfg.vocab_size),
        "targets": jax.random.randint(k2, (M, mbs, S), 0, cfg.vocab_size),
    }
    loss, grads = jax.jit(sm)(sharded, batch)

    def serial_loss(p, b):
        return jnp.mean(jnp.stack([
            gpt_loss(
                p, {"tokens": b["tokens"][m], "targets": b["targets"][m]}, cfg
            )
            for m in range(M)
        ]))

    sloss, sgrads = jax.value_and_grad(serial_loss)(params, batch)
    np.testing.assert_allclose(float(loss), float(sloss), rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        grads, sgrads,
    )


def test_gpt_ring_cp_remat_flash_matches_serial(devices8, params):
    """remat='flash' x ring context parallelism: the ring op calls the flash
    kernel once per hop, so the policy saves each hop's named (o, lse)
    partials — grads must still match the serial un-checkpointed model."""
    cfg_cp = dataclasses.replace(CFG, attn_impl="ring", context_axis="context")
    tpc.setup_process_groups([("context", 4)], devices=devices8[:4])
    mesh = tpc.get_view()
    batch = _data(jax.random.PRNGKey(7))

    def cp_loss(p, b):
        return jax.lax.pmean(
            gpt_loss(p, b, cfg_cp, remat="flash"), "context"
        )

    bspec = {"tokens": P(None, "context"), "targets": P(None, "context")}
    sm = shard_map(cp_loss, mesh=mesh, in_specs=(P(), bspec), out_specs=P())
    g_got = jax.jit(jax.grad(lambda p, b: sm(p, b)))(params, batch)
    g_want = jax.grad(lambda p, b: gpt_loss(p, b, CFG))(params, batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        g_got, g_want,
    )

    # the policy must actually capture residuals through the ring op (a
    # wrapper hiding the checkpoint_name tags would silently degrade to
    # plain block remat while the goldens above stay green)
    try:
        from jax._src.ad_checkpoint import saved_residuals
    except ImportError:
        pytest.skip("saved_residuals moved — introspection needs re-porting")
    from collections import Counter

    shapes = {}
    for mode in (True, "flash"):
        res = saved_residuals(
            lambda p, b: shard_map(
                lambda p, b: jax.lax.pmean(
                    gpt_loss(p, b, cfg_cp, remat=mode), "context"),
                mesh=mesh, in_specs=(P(), bspec), out_specs=P(),
            )(p, b),
            params, batch)
        shapes[mode] = Counter(aval.str_short() for aval, _ in res)
    assert sum((shapes["flash"] - shapes[True]).values()) > 0, (
        "remat='flash' saved nothing beyond plain remat under ring CP")


def test_gpt_1f1b_training_matches_serial(devices8, params):
    """Full-composition 1F1B: DP=2 x PP=2 x TP=2 (+SP) with the interleaved
    schedule supplying (loss, grads) directly to the DataParallel step; two
    optimizer steps must track the serial model — the strongest form of the
    reference's golden discipline applied to the 1F1B scheduler."""
    M, mbs = 4, 2
    tpc.setup_process_groups(
        [("data", 2), ("pipe", 2), ("tensor", 2)], devices=devices8
    )
    mesh = tpc.get_view()
    specs = gpt_param_specs(CFG, tp_axis="tensor", pipe_axis="pipe")

    def vg_fn(p, batch):
        return gpt_pipeline_1f1b(
            p, batch, CFG, num_microbatches=M, tp_axis="tensor", sp=True
        )

    opt = optax.sgd(1e-1)
    dp = DataParallel(mesh=mesh)
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        value_and_grad_fn=vg_fn,
        optimizer=opt,
        param_specs=specs,
        batch_spec={"tokens": P(None, "data"), "targets": P(None, "data")},
    )

    sparams, sstate = params, opt.init(params)

    def serial_loss(p, batch):
        losses = [
            gpt_loss(
                p,
                {"tokens": batch["tokens"][m], "targets": batch["targets"][m]},
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
        k1, k2 = jax.random.split(jax.random.PRNGKey(20 + i))
        batch = {
            "tokens": jax.random.randint(k1, (M, mbs * 2, S), 0, CFG.vocab_size),
            "targets": jax.random.randint(k2, (M, mbs * 2, S), 0, CFG.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P(None, "data"))), batch
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for name in ["tok_emb", "pos_emb", "head"]:
        np.testing.assert_allclose(
            np.asarray(sharded[name]),
            np.asarray(sparams[name]),
            rtol=1e-4,
            atol=1e-5,
            err_msg=f"param divergence at {name}",
        )
    np.testing.assert_allclose(
        np.asarray(sharded["blocks"]["mlp"]["w1"]),
        np.asarray(sparams["blocks"]["mlp"]["w1"]),
        rtol=1e-4,
        atol=1e-5,
    )


@pytest.mark.parametrize("impl,xent_chunk", [
    ("ring", None), ("ulysses", None), ("ring", 2),
])
def test_gpt_context_parallel_matches_serial(devices8, params, impl, xent_chunk):
    """Context parallelism wired into the MODEL family (VERDICT r2 item 4):
    a GPT with ``attn_impl='ring'|'ulysses'`` + ``context_axis`` runs with
    the sequence sharded over the context axis end-to-end (CP tokens in,
    CP activations through every block, pos-emb at the shard's global
    offset) and must match the serial model's loss AND grads.
    ``xent_chunk=2`` additionally streams the head+CE over sequence chunks
    — the natural long-context pairing (CP divides the sequence, the
    streamed CE removes the [B, S_loc, V] logits)."""
    cp = 4
    cfg_cp = dataclasses.replace(CFG, attn_impl=impl, context_axis="context")
    tpc.setup_process_groups([("context", cp)], devices=devices8[:cp])
    mesh = tpc.get_view()
    batch = _data(jax.random.PRNGKey(1))

    def cp_loss(p, b):
        # loss is the mean over LOCAL tokens -> close with pmean over context
        return jax.lax.pmean(
            gpt_loss(p, b, cfg_cp, xent_chunk=xent_chunk), "context"
        )

    bspec = {"tokens": P(None, "context"), "targets": P(None, "context")}
    sm = shard_map(cp_loss, mesh=mesh, in_specs=(P(), bspec), out_specs=P())
    got = jax.jit(sm)(params, batch)
    want = gpt_loss(params, batch, CFG)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)

    g_got = jax.jit(jax.grad(lambda p, b: sm(p, b)))(params, batch)
    g_want = jax.grad(lambda p, b: gpt_loss(p, b, CFG))(params, batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        g_got,
        g_want,
    )


@pytest.mark.slow  # tier-1 budget: ring-CP grad parity stays fast-tier
# via test_gpt_ring_cp_remat_flash_matches_serial and the rope/zigzag
# params; this point adds the 2-step optimizer loop over a data×context
# mesh (DataParallel treating both axes as data)
@pytest.mark.heavy
def test_gpt_ring_training_matches_serial(devices8, params):
    """Train the ring-CP GPT over a data x context mesh with DataParallel
    treating BOTH axes as data axes (grads pmean over data AND context);
    two optimizer steps must track the serial model."""
    cfg_cp = dataclasses.replace(CFG, attn_impl="ring", context_axis="context")
    tpc.setup_process_groups([("data", 2), ("context", 4)], devices=devices8)
    mesh = tpc.get_view()
    opt = optax.adam(1e-2)

    dp = DataParallel(mesh=mesh, axis=("data", "context"))
    sharded = dp.broadcast_params(params)
    state = opt.init(sharded)
    step = dp.make_train_step(
        lambda p, b: gpt_loss(p, b, cfg_cp),
        opt,
        batch_spec={"tokens": P("data", "context"), "targets": P("data", "context")},
    )

    sparams, sstate = params, opt.init(params)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(lambda p, b: gpt_loss(p, b, CFG))(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    for i in range(2):
        k1, k2 = jax.random.split(jax.random.PRNGKey(40 + i))
        batch = {
            "tokens": jax.random.randint(k1, (B, S), 0, CFG.vocab_size),
            "targets": jax.random.randint(k2, (B, S), 0, CFG.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P("data", "context"))
            ),
            batch,
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for name in ["tok_emb", "pos_emb", "head"]:
        np.testing.assert_allclose(
            np.asarray(sharded[name]),
            np.asarray(sparams[name]),
            rtol=1e-3,
            atol=1e-5,
            err_msg=f"param divergence at {name}",
        )


@pytest.mark.heavy
def test_gpt_1f1b_with_ring_cp_matches_serial(devices8, params):
    """DP x PP x CP: the 1F1B pipeline with ring-attention stages — sequence
    sharded over 'context' THROUGH the pipeline (stage 0 embeds local chunks
    at their global offsets, every stage's blocks run ring attention over the
    context ring, last stage's CE closes per-chunk) — must track serial."""
    cfg_cp = dataclasses.replace(CFG, attn_impl="ring", context_axis="context")
    M, mbs = 4, 2
    tpc.setup_process_groups(
        [("data", 2), ("pipe", 2), ("context", 2)], devices=devices8
    )
    mesh = tpc.get_view()
    specs = gpt_param_specs(CFG, tp_axis=None, pipe_axis="pipe")

    def vg_fn(p, batch):
        return gpt_pipeline_1f1b(p, batch, cfg_cp, num_microbatches=M)

    opt = optax.sgd(1e-1)
    dp = DataParallel(mesh=mesh, axis=("data", "context"))
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        value_and_grad_fn=vg_fn,
        optimizer=opt,
        param_specs=specs,
        batch_spec={
            "tokens": P(None, "data", "context"),
            "targets": P(None, "data", "context"),
        },
    )

    sparams, sstate = params, opt.init(params)

    def serial_loss(p, batch):
        losses = [
            gpt_loss(
                p,
                {"tokens": batch["tokens"][m], "targets": batch["targets"][m]},
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
        k1, k2 = jax.random.split(jax.random.PRNGKey(70 + i))
        batch = {
            "tokens": jax.random.randint(k1, (M, mbs * 2, S), 0, CFG.vocab_size),
            "targets": jax.random.randint(k2, (M, mbs * 2, S), 0, CFG.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(None, "data", "context"))
            ),
            batch,
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    for name in ["tok_emb", "pos_emb", "head"]:
        np.testing.assert_allclose(
            np.asarray(sharded[name]),
            np.asarray(sparams[name]),
            rtol=1e-4,
            atol=1e-5,
            err_msg=f"param divergence at {name}",
        )


def test_dropout_sharded_rng(devices8):
    """The SURVEY §7 'per-axis sharded RNG' hard part, exercised in a real
    model: with ``dropout_key = axis_unique_key(key, 'data')``, DATA shards
    draw different dropout masks while TENSOR shards (replicated activations,
    non-SP) draw identical ones — and dropout off is exactly deterministic."""
    from torchdistpackage_tpu.parallel.data_parallel import _mark_varying
    from torchdistpackage_tpu.utils import axis_unique_key

    cfg = GPTConfig(
        vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=16, ffn_mult=2,
        dropout_rate=0.5,
    )
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tpc.setup_process_groups([("data", 2), ("tensor", 2)], devices=devices8[:4])
    mesh = tpc.get_view()
    specs = gpt_param_specs(cfg, tp_axis="tensor")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    # IDENTICAL tokens on every data shard: any output difference across the
    # data axis can only come from the dropout masks
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)

    def fwd(p, toks):
        key = axis_unique_key(jax.random.PRNGKey(7), "data")
        h = gpt_embed(p, toks, "tensor")
        from torchdistpackage_tpu.parallel.tensor_parallel import scan_blocks

        h = scan_blocks(p["blocks"], h, cfg.block, "tensor", False, dropout_key=key)
        # stack every device's local view: [data*tensor, B, S, D]
        return _mark_varying(h[None], ("data", "tensor"))

    from torchdistpackage_tpu.models.gpt import gpt_embed

    out = jax.jit(
        shard_map(
            fwd,
            mesh=mesh,
            in_specs=(specs, P()),
            out_specs=P(("data", "tensor")),
        )
    )(sharded, tokens)
    out = np.asarray(out)  # rows: [d0t0, d0t1, d1t0, d1t1]
    np.testing.assert_allclose(out[0], out[1], rtol=1e-5, atol=1e-6,
                               err_msg="TP shards must agree on dropout masks")
    np.testing.assert_allclose(out[2], out[3], rtol=1e-5, atol=1e-6,
                               err_msg="TP shards must agree on dropout masks")
    assert np.max(np.abs(out[0] - out[2])) > 1e-3, (
        "data shards must draw DIFFERENT dropout masks"
    )

    # rate>0 but no key -> deterministic identity with the rate-0 model
    logits_nokey = gpt_forward(params, tokens, cfg)
    cfg0 = dataclasses.replace(cfg, dropout_rate=0.0)
    np.testing.assert_allclose(
        np.asarray(logits_nokey),
        np.asarray(gpt_forward(params, tokens, cfg0)),
        rtol=1e-6,
    )


@pytest.mark.parametrize("sp,kv_heads", [
    # kv_heads=2 stays fast at sp=True and kv_heads=1 (MQA, the extreme
    # grouping) at both sp points — the (sp=False, kv_heads=2) program
    # is the least-novel corner and rides the slow tier (tier-1 budget,
    # PR-20 payback)
    (False, 1),
    (True, 1),
    (True, 2),
    pytest.param(False, 2, marks=pytest.mark.slow),
])
def test_gpt_gqa_tp_matches_serial(devices8, sp, kv_heads):
    """Grouped-query attention through the MODEL family: a GQA/MQA GPT
    (separate wq + stacked wkv leaves, flash kernel with kv index maps)
    under TP=2 (+SP) must match the serial GQA model in loss AND grads —
    and its param count must match the config's accounting."""
    cfg = dataclasses.replace(CFG, attn_impl="flash", kv_heads=kv_heads)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    n_leaves = sum(x.size for x in jax.tree.leaves(params))
    assert n_leaves == cfg.num_params(), (n_leaves, cfg.num_params())

    tp = 2
    tpc.setup_process_groups([("tensor", tp)], devices=devices8[:tp])
    mesh = tpc.get_view()
    specs = gpt_param_specs(cfg, tp_axis="tensor")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    batch = _data(jax.random.PRNGKey(2))
    sm = shard_map(
        lambda p, b: gpt_loss(p, b, cfg, axis="tensor", sp=sp),
        mesh=mesh, in_specs=(specs, {"tokens": P(), "targets": P()}),
        out_specs=P(),
    )
    if kv_heads % tp != 0:
        # MQA's single KV head cannot split across 2 TP shards: the BYTE
        # count divides (hd/2 columns each) so sharding succeeds silently —
        # the whole-head guard in attention_partial must catch it at trace
        with pytest.raises(ValueError, match="whole heads"):
            jax.jit(sm)(sharded, batch)
        return
    got = jax.jit(sm)(sharded, batch)
    want = gpt_loss(params, batch, cfg)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)

    g_got = jax.jit(jax.grad(lambda p, b: sm(p, b)))(sharded, batch)
    g_want = jax.grad(lambda p, b: gpt_loss(p, b, cfg))(params, batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        g_got, g_want,
    )


def test_apply_rope_matches_reference():
    """Half-split rotary math vs a direct numpy construction, plus the
    relative-position property softmax attention relies on: the rotated
    q.k dot depends on positions only through their difference."""
    from torchdistpackage_tpu.parallel.tensor_parallel import apply_rope

    B, H, S, hd = 1, 1, 6, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, hd))
    pos = jnp.arange(S)
    got = np.asarray(apply_rope(x, pos))

    half = hd // 2
    inv = 10000.0 ** (-np.arange(half) / half)
    ang = np.arange(S)[:, None] * inv[None, :]
    x1, x2 = np.asarray(x)[..., :half], np.asarray(x)[..., half:]
    want = np.concatenate(
        [x1 * np.cos(ang) - x2 * np.sin(ang),
         x1 * np.sin(ang) + x2 * np.cos(ang)], axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    # relative property: <R(p+c)q, R(k+c)k> == <R(p)q, R(k)k>
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, H, 1, hd))
    def dot(c):
        qa = apply_rope(q, jnp.array([3 + c]))
        ka = apply_rope(k, jnp.array([1 + c]))
        return float(jnp.sum(qa * ka))
    np.testing.assert_allclose(dot(0), dot(17), rtol=1e-5)


def test_gpt_rope_tp_matches_serial(devices8):
    """pos='rope' (no pos_emb table; q/k rotated inside attention) under
    TP=2+SP must match the serial rope model in loss AND grads; the param
    tree has no pos_emb leaf and num_params accounts for it."""
    cfg = dataclasses.replace(CFG, attn_impl="flash", pos="rope")
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    assert "pos_emb" not in params
    n_leaves = sum(x.size for x in jax.tree.leaves(params))
    assert n_leaves == cfg.num_params(), (n_leaves, cfg.num_params())

    tpc.setup_process_groups([("tensor", 2)], devices=devices8[:2])
    mesh = tpc.get_view()
    specs = gpt_param_specs(cfg, tp_axis="tensor")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    batch = _data(jax.random.PRNGKey(3))
    sm = shard_map(
        lambda p, b: gpt_loss(p, b, cfg, axis="tensor", sp=True),
        mesh=mesh, in_specs=(specs, {"tokens": P(), "targets": P()}),
        out_specs=P(),
    )
    got = jax.jit(sm)(sharded, batch)
    want = gpt_loss(params, batch, cfg)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)

    g_got = jax.jit(jax.grad(lambda p, b: sm(p, b)))(sharded, batch)
    g_want = jax.grad(lambda p, b: gpt_loss(p, b, cfg))(params, batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        g_got, g_want,
    )


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_gpt_rope_ring_cp_matches_serial(devices8, layout):
    """RoPE under ring context parallelism: each shard rotates its chunk at
    the chunk's GLOBAL positions (contiguous offset or zigzag rows) — the
    distributed rope model must match the serial rope model exactly."""
    from torchdistpackage_tpu.ops.ring_attention import zigzag_permute

    cp = 4
    cfg_cp = dataclasses.replace(
        CFG, attn_impl="ring", context_axis="context", pos="rope",
        cp_layout=layout)
    cfg_serial = dataclasses.replace(CFG, attn_impl="flash", pos="rope")
    rope_params = init_gpt_params(jax.random.PRNGKey(0), cfg_serial)
    tpc.setup_process_groups([("context", cp)], devices=devices8[:cp])
    mesh = tpc.get_view()
    batch = _data(jax.random.PRNGKey(11))
    dist_batch = (
        jax.tree.map(lambda a: zigzag_permute(a, cp, seq_dim=-1), batch)
        if layout == "zigzag" else batch
    )

    def cp_loss(p, b):
        return jax.lax.pmean(gpt_loss(p, b, cfg_cp), "context")

    bspec = {"tokens": P(None, "context"), "targets": P(None, "context")}
    sm = shard_map(cp_loss, mesh=mesh, in_specs=(P(), bspec), out_specs=P())
    got = jax.jit(sm)(rope_params, dist_batch)
    want = gpt_loss(rope_params, batch, cfg_serial)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)


def test_gpt_remat_grads_match():
    """Activation-checkpointed grads must equal un-checkpointed grads."""
    cfg = GPTConfig(vocab_size=64, dim=32, nheads=2, nlayers=3, max_seq=16,
                    ffn_mult=2, dtype=jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    batch = {
        "tokens": jax.random.randint(k1, (2, 16), 0, 64),
        "targets": jax.random.randint(k2, (2, 16), 0, 64),
    }
    g0 = jax.jit(jax.grad(lambda p: gpt_loss(p, batch, cfg, remat=False)))(params)
    g1 = jax.jit(jax.grad(lambda p: gpt_loss(p, batch, cfg, remat=True)))(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


@pytest.mark.heavy
def test_gpt_remat_flash_policy_matches_and_saves_residuals():
    """remat='flash' (save the flash kernel's o/lse, skip its fwd re-run in
    the backward) must be numerically identical to remat=True, and the
    policy must actually capture the named residuals — otherwise it silently
    degrades to plain block remat and the perf claim is fiction."""
    cfg = GPTConfig(vocab_size=64, dim=32, nheads=2, nlayers=3, max_seq=16,
                    ffn_mult=2, dtype=jnp.float32, attn_impl="flash")
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    batch = {
        "tokens": jax.random.randint(k1, (2, 16), 0, 64),
        "targets": jax.random.randint(k2, (2, 16), 0, 64),
    }
    g1 = jax.jit(jax.grad(lambda p: gpt_loss(p, batch, cfg, remat=True)))(params)
    for mode in ("flash", "flash_offload"):
        g2 = jax.jit(jax.grad(
            lambda p: gpt_loss(p, batch, cfg, remat=mode)))(params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6,
                                       err_msg=f"remat={mode}")

    # the policy must save MORE than plain block remat: exactly the
    # scan-stacked flash o [L, B*H, S, hd] and lse.  (saved_residuals is
    # private in this jax version; skip the introspection half if it moves.)
    try:
        from jax._src.ad_checkpoint import saved_residuals
    except ImportError:
        import pytest

        pytest.skip("saved_residuals moved — residual-capture check needs "
                    "re-porting to this jax version")
    from collections import Counter

    shapes = {}
    for mode in (True, "flash", "flash_offload"):
        res = saved_residuals(
            lambda p: gpt_loss(p, batch, cfg, remat=mode), params)
        shapes[mode] = Counter(aval.str_short() for aval, _ in res)
    L, BH, S, hd = (cfg.nlayers, 2 * cfg.nheads, cfg.max_seq,
                    cfg.dim // cfg.nheads)
    # the offloaded residuals carry the <host> memory-space annotation —
    # proving they land in pinned_host, not merely that they were saved
    for mode, tag in (("flash", ""), ("flash_offload", "<host>")):
        extra = shapes[mode] - shapes[True]
        assert f"float32{tag}[{L},{BH},{S},{hd}]" in extra, (mode, dict(extra))


def test_offload_guardrail():
    """remat='flash_offload' where plain 'flash' fits pays a host round
    trip for nothing — the trace-time advisory must fire there, stay
    quiet when the footprint is genuinely HBM-scale, and stay quiet on
    backends that report no memory limit (the CPU sim)."""
    import warnings

    from torchdistpackage_tpu.parallel.tensor_parallel import (
        layers as tl,
    )
    from torchdistpackage_tpu.parallel.tensor_parallel import offload_advice

    cfg = GPTConfig(vocab_size=64, dim=32, nheads=2, nlayers=3, max_seq=16,
                    ffn_mult=2, dtype=jnp.float32, attn_impl="flash").block
    # tiny model vs a 16 GB chip: advice fires
    msg = offload_advice(cfg, (2, 16, 32), 3, hbm_bytes=16 * 2**30)
    assert msg is not None and "flash" in msg
    # footprint at >= half of HBM: offload is load-bearing, no advice
    assert offload_advice(cfg, (2, 16, 32), 3, hbm_bytes=10_000) is None
    # unknown HBM (CPU sim): silent
    assert offload_advice(cfg, (2, 16, 32), 3, hbm_bytes=None) is None

    # end to end: scan_blocks warns under a monkeypatched device limit
    gcfg = GPTConfig(vocab_size=64, dim=32, nheads=2, nlayers=3, max_seq=16,
                     ffn_mult=2, dtype=jnp.float32, attn_impl="flash")
    params = init_gpt_params(jax.random.PRNGKey(0), gcfg)
    batch = {
        "tokens": jnp.zeros((2, 16), jnp.int32),
        "targets": jnp.zeros((2, 16), jnp.int32),
    }
    orig = tl._device_hbm_bytes
    tl._device_hbm_bytes = lambda: 16 * 2**30
    try:
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            jax.eval_shape(
                lambda p: gpt_loss(p, batch, gcfg, remat="flash_offload"),
                params)
        assert any("flash_offload" in str(w.message) for w in rec), rec
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            jax.eval_shape(
                lambda p: gpt_loss(p, batch, gcfg, remat="flash"), params)
        assert not any("flash_offload" in str(w.message) for w in rec)
    finally:
        tl._device_hbm_bytes = orig


def test_remat_mode_validated():
    """A misspelled remat policy string must raise, not silently degrade to
    plain block remat (checkpoint_block funnels every remat= kwarg)."""
    from torchdistpackage_tpu.parallel.tensor_parallel import checkpoint_block

    for ok in (False, None, True, "flash", "flash_offload"):
        checkpoint_block(lambda x: x, ok)
    with pytest.raises(ValueError, match="remat"):
        checkpoint_block(lambda x: x, "Flash")


def test_streamed_head_loss_matches_full():
    """The seq-chunked streaming CE equals the full-logits CE; a chunk that
    doesn't divide S fails loudly (silent full-logits fallback would defeat
    the memory contract)."""
    params = init_gpt_params(jax.random.PRNGKey(0), CFG)
    batch = _data(jax.random.PRNGKey(1))
    full = gpt_loss(params, batch, CFG)
    for chunk in (4, 8, 16):
        got = gpt_loss(params, batch, CFG, xent_chunk=chunk)
        np.testing.assert_allclose(float(got), float(full), rtol=1e-6)
    with pytest.raises(ValueError, match="not divisible"):
        gpt_loss(params, batch, CFG, xent_chunk=5)
    # grads agree too
    g_full = jax.grad(lambda p: gpt_loss(p, batch, CFG))(params)
    g_chunk = jax.grad(lambda p: gpt_loss(p, batch, CFG, xent_chunk=8))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        g_chunk,
        g_full,
    )


# num_chunks=1 demoted to slow for tier-1 budget (PR 13): the
# per-(stage, microbatch, layer) dropout-key threading and its
# bwd-recompute replay are exercised fast-tier by the interleaved
# num_chunks=2 variant (the same mask recipe driven through the MORE
# general schedule, chunk index folded in); the plain-1F1B point keeps
# running in the slow tier.
@pytest.mark.parametrize("num_chunks", [
    pytest.param(1, marks=pytest.mark.slow), 2,
])
@pytest.mark.heavy
def test_gpt_1f1b_dropout(devices8, params, num_chunks):
    """Dropout THROUGH the 1F1B pipeline: per-(stage, microbatch, layer)
    masks via the schedule's microbatch-index threading; deterministic for a
    fixed key (the bwd recompute replays the same chain), different for a
    different key, and exactly the no-dropout path when the key is None.
    num_chunks=2 checks the same determinism under the INTERLEAVED schedule
    (the chunk index is folded into the key and replayed by the recompute)."""
    from torchdistpackage_tpu.models import (
        gpt_interleaved_param_specs,
        interleave_stage_params,
    )
    from torchdistpackage_tpu.utils import axis_unique_key

    cfg_do = dataclasses.replace(CFG, dropout_rate=0.3)
    M, mbs = 4, 2
    tpc.setup_process_groups(
        [("data", 2), ("pipe", 2), ("tensor", 2)], devices=devices8
    )
    mesh = tpc.get_view()
    if num_chunks > 1:
        params = interleave_stage_params(params, num_chunks, 2)
        specs = gpt_interleaved_param_specs(CFG, tp_axis="tensor")
    else:
        specs = gpt_param_specs(CFG, tp_axis="tensor", pipe_axis="pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    bspec = {"tokens": P(None, "data"), "targets": P(None, "data")}

    def vg(p, b, seed):
        key = axis_unique_key(jax.random.PRNGKey(seed), "data")
        loss, grads = gpt_pipeline_1f1b(
            p, b, cfg_do, num_microbatches=M, tp_axis="tensor", sp=True,
            dropout_key=key, num_chunks=num_chunks,
        )
        from torchdistpackage_tpu.parallel.data_parallel import _vma

        axes = tuple(a for a in ("data",) if a in _vma(loss))
        return (jax.lax.pmean(loss, axes) if axes else loss), grads

    k1, k2 = jax.random.split(jax.random.PRNGKey(90))
    batch = {
        "tokens": jax.random.randint(k1, (M, mbs * 2, S), 0, CFG.vocab_size),
        "targets": jax.random.randint(k2, (M, mbs * 2, S), 0, CFG.vocab_size),
    }
    dbatch = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P(None, "data"))), batch
    )

    run = jax.jit(
        shard_map(
            vg, mesh=mesh, in_specs=(specs, bspec, P()), out_specs=(P(), specs)
        ),
        static_argnums=(),
    )
    l_a, g_a = run(sharded, dbatch, jnp.int32(0))
    l_a2, _ = run(sharded, dbatch, jnp.int32(0))
    l_b, _ = run(sharded, dbatch, jnp.int32(1))
    assert np.isfinite(float(l_a))
    np.testing.assert_allclose(float(l_a), float(l_a2), rtol=0, atol=0,
                               err_msg="same key must be deterministic")
    assert abs(float(l_a) - float(l_b)) > 1e-6, "different keys must differ"
    for leaf in jax.tree.leaves(g_a):
        assert np.all(np.isfinite(np.asarray(leaf)))

    # key=None must be EXACTLY the no-dropout path (identical to running
    # with dropout_rate=0)
    from torchdistpackage_tpu.parallel.data_parallel import _vma

    def _norm(loss):
        axes = tuple(a for a in ("data",) if a in _vma(loss))
        return jax.lax.pmean(loss, axes) if axes else loss

    def vg_none(p, b):
        loss, grads = gpt_pipeline_1f1b(
            p, b, cfg_do, num_microbatches=M, tp_axis="tensor", sp=True,
            dropout_key=None, num_chunks=num_chunks,
        )
        return _norm(loss), grads

    def vg_off(p, b):
        loss, grads = gpt_pipeline_1f1b(
            p, b, CFG, num_microbatches=M, tp_axis="tensor", sp=True,
            num_chunks=num_chunks,
        )
        return _norm(loss), grads

    def run_plain(f):
        sm = shard_map(
            f, mesh=mesh, in_specs=(specs, bspec), out_specs=(P(), specs)
        )
        loss, _ = jax.jit(sm)(sharded, dbatch)
        return float(loss)

    np.testing.assert_allclose(
        run_plain(vg_none), run_plain(vg_off), rtol=0, atol=0,
        err_msg="key=None must equal the dropout_rate=0 path exactly",
    )


def test_streamed_head_loss_under_dp(devices8, params):
    """The streamed CE must work INSIDE shard_map with a data-sharded batch
    (the scan carry closes over the data-varying vma) and match serial."""
    tpc.setup_process_groups([("data", 4)], devices=devices8[:4])
    mesh = tpc.get_view()
    batch = _data(jax.random.PRNGKey(1))

    def dp_loss(p, b):
        return jax.lax.pmean(
            gpt_loss(p, b, CFG, xent_chunk=8), "data"
        )

    got = jax.jit(
        shard_map(
            dp_loss,
            mesh=mesh,
            in_specs=(P(), {"tokens": P("data"), "targets": P("data")}),
            out_specs=P(),
        )
    )(params, batch)
    want = gpt_loss(params, batch, CFG)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


@pytest.mark.slow  # tier-1 budget: the zigzag layout (host permute +
# owned-position embedding gather) stays fast-tier via
# test_gpt_rope_ring_cp_matches_serial[zigzag]; this point re-proves it
# with learned pos-emb + full loss/grad goldens
@pytest.mark.heavy
def test_gpt_zigzag_ring_matches_serial(devices8, params):
    """Zigzag (load-balanced) ring CP through the full GPT: tokens/targets
    host-permuted to the zigzag layout, pos-emb gathered at the owned
    positions — loss AND grads must equal the serial model (the mean CE is
    permutation-invariant)."""
    from torchdistpackage_tpu.ops.ring_attention import zigzag_permute

    cp = 4
    cfg_zz = dataclasses.replace(
        CFG, attn_impl="ring", context_axis="context", cp_layout="zigzag"
    )
    tpc.setup_process_groups([("context", cp)], devices=devices8[:cp])
    mesh = tpc.get_view()
    batch = _data(jax.random.PRNGKey(1))
    zz_batch = jax.tree.map(lambda a: zigzag_permute(a, cp, seq_dim=1), batch)

    def cp_loss(p, b):
        return jax.lax.pmean(gpt_loss(p, b, cfg_zz), "context")

    bspec = {"tokens": P(None, "context"), "targets": P(None, "context")}
    sm = shard_map(cp_loss, mesh=mesh, in_specs=(P(), bspec), out_specs=P())
    got = jax.jit(sm)(params, zz_batch)
    want = gpt_loss(params, batch, CFG)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)

    g_got = jax.jit(jax.grad(lambda p, b: sm(p, b)))(params, zz_batch)
    g_want = jax.grad(lambda p, b: gpt_loss(p, b, CFG))(params, batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        g_got,
        g_want,
    )


def test_gpt_interleaved_1f1b_matches_serial(devices8, params):
    """INTERLEAVED 1F1B (virtual pipeline stages, num_chunks=2): chunk v of
    stage s holds layer slab v*P+s, transfers ride CIRCULAR ppermutes (the
    wrap edge advances a microbatch to its next chunk), and the whole
    DP=2 x PP=2 x TP=2(+SP) x V=2 composition must trajectory-match the
    serial model — the scheduler generalization reduces exactly to the
    classic schedule at V=1, and this goldens the V>1 index math
    (sigma(v,m) order, mirrored backward, ring slots min(VM, 2PV-1))."""
    from torchdistpackage_tpu.models import (
        gpt_interleaved_param_specs,
        interleave_stage_params,
    )

    M, mbs, VC = 4, 2, 2
    tpc.setup_process_groups(
        [("data", 2), ("pipe", 2), ("tensor", 2)], devices=devices8
    )
    mesh = tpc.get_view()
    iparams = interleave_stage_params(params, VC, 2)
    specs = gpt_interleaved_param_specs(CFG, tp_axis="tensor")

    def vg_fn(p, batch):
        return gpt_pipeline_1f1b(
            p, batch, CFG, num_microbatches=M, tp_axis="tensor", sp=True,
            num_chunks=VC,
        )

    opt = optax.sgd(1e-1)
    dp = DataParallel(mesh=mesh)
    sharded = dp.broadcast_params(iparams, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        value_and_grad_fn=vg_fn,
        optimizer=opt,
        param_specs=specs,
        batch_spec={"tokens": P(None, "data"), "targets": P(None, "data")},
    )

    sparams, sstate = params, opt.init(params)

    def serial_loss(p, batch):
        losses = [
            gpt_loss(
                p,
                {"tokens": batch["tokens"][m], "targets": batch["targets"][m]},
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
        k1, k2 = jax.random.split(jax.random.PRNGKey(40 + i))
        batch = {
            "tokens": jax.random.randint(k1, (M, mbs * 2, S), 0, CFG.vocab_size),
            "targets": jax.random.randint(k2, (M, mbs * 2, S), 0, CFG.vocab_size),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P(None, "data"))), batch
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    # compare per-slab: interleaved blocks [V, P, 1, ...] hold serial layer
    # v*P + s at [v, s, 0]
    sblocks = sparams["blocks"]
    iblocks = sharded["blocks"]
    for v in range(VC):
        for st in range(2):
            g = v * 2 + st
            np.testing.assert_allclose(
                np.asarray(iblocks["mlp"]["w1"])[v, st, 0],
                np.asarray(sblocks["mlp"]["w1"])[g],
                rtol=1e-4, atol=1e-5,
                err_msg=f"slab {g} (chunk {v} stage {st}) diverged",
            )
    for name in ["tok_emb", "pos_emb", "head"]:
        np.testing.assert_allclose(
            np.asarray(sharded[name]), np.asarray(sparams[name]),
            rtol=1e-4, atol=1e-5, err_msg=f"param divergence at {name}",
        )


def test_gpt_interleaved_requires_divisible_microbatches(devices8, params):
    """M % P != 0 must be rejected up front (the sigma spacing breaks)."""
    tpc.setup_process_groups([("pipe", 2)], devices=devices8[:2])
    mesh = tpc.get_view()
    from torchdistpackage_tpu.models import (
        gpt_interleaved_param_specs,
        interleave_stage_params,
    )

    iparams = interleave_stage_params(params, 2, 2)
    specs = gpt_interleaved_param_specs(CFG)
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), iparams, specs
    )
    M = 3
    batch = {
        "tokens": jnp.zeros((M, 2, S), jnp.int32),
        "targets": jnp.zeros((M, 2, S), jnp.int32),
    }
    with pytest.raises(ValueError, match="divisible by pipe size"):
        jax.jit(
            shard_map(
                lambda p, b: gpt_pipeline_1f1b(
                    p, b, CFG, num_microbatches=M, num_chunks=2
                ),
                mesh=mesh,
                in_specs=(specs, P()),
                out_specs=(P(), specs),
            )
        )(sharded, batch)


def test_interleave_roundtrip(devices8, params):
    """Layout portability: interleave -> deinterleave is the identity (a
    checkpoint from either pipelined layout resumes in the other).  The ViT
    CP x PP guard that used to live here is gone: the composition is now
    supported (context as a MODEL axis) and golden-tested in
    test_vit.py::test_vit_1f1b_with_cp_matches_serial."""
    from torchdistpackage_tpu.models import (
        deinterleave_stage_params,
        interleave_stage_params,
    )

    ip = interleave_stage_params(params, 2, 2)
    back = deinterleave_stage_params(ip, 2, 2)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params,
        back,
    )
    with pytest.raises(ValueError, match="not an interleaved layout"):
        deinterleave_stage_params(ip, 4, 2)
