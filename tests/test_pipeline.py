"""Pipeline-parallel golden tests — stronger than the reference's PP smoke
test (examples/model_parallel/test_pipeline.py just checks liveness): the
pipelined forward and loss/grads must MATCH the serial model exactly."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.parallel.pipeline_parallel import (
    last_stage_value,
    partition_balanced,
    partition_uniform,
    pipeline_1f1b,
    pipeline_forward,
    pipeline_loss,
    pipeline_zb_1f1b,
    ring_slots,
    stack_stage_params,
    stacked_param_specs,
    zb_schedule_ticks,
)
from torchdistpackage_tpu.parallel.tensor_parallel import (
    TransformerConfig,
    block_forward,
    init_block_params,
)

CFG = TransformerConfig(dim=32, nheads=4, nlayers=4, ffn_mult=2, causal=True)
MBS, S, M = 2, 16, 4  # microbatch size, seq, num microbatches


def test_partitioners():
    assert partition_uniform(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
    w = [1, 1, 8, 1, 1, 1]
    bounds = partition_balanced(w, 3)
    assert len(bounds) == 3
    assert bounds[0][0] == 0 and bounds[-1][1] == 6
    # contiguous and non-empty
    for (a, b), (c, d) in zip(bounds, bounds[1:]):
        assert b == c and b > a
    # the heavy layer is alone-ish: max part weight is 8
    assert max(sum(w[a:b]) for a, b in bounds) == 8


def _layers_and_stack():
    keys = jax.random.split(jax.random.PRNGKey(0), CFG.nlayers)
    layers = [init_block_params(k, CFG) for k in keys]
    return layers, stack_stage_params(layers)


def _serial_forward(layers, x):
    for lp in layers:
        x = block_forward(lp, x, CFG)
    return x


def _stage_fn(stage_params, x):
    """One pipeline stage = scan over its slab of stacked layers."""

    def body(h, lp):
        return block_forward(lp, h, CFG), None

    out, _ = jax.lax.scan(body, x, stage_params)
    return out


@pytest.mark.parametrize("pp", [2, 4])
def test_pipeline_forward_matches_serial(devices8, pp):
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    layers, stacked = _layers_and_stack()
    specs = stacked_param_specs(stacked, "pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (M, MBS, S, CFG.dim))

    def body(params, mbs):
        out = pipeline_forward(params, mbs, _stage_fn, num_microbatches=M)
        return last_stage_value(out)

    fwd = jax.jit(shard_map(body, mesh=mesh, in_specs=(specs, P()), out_specs=P()))
    out = fwd(sharded, x)

    want = jnp.stack([_serial_forward(layers, x[m]) for m in range(M)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.heavy
def test_pipeline_loss_and_grads_match_serial(devices8):
    pp = 4
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    layers, stacked = _layers_and_stack()
    specs = stacked_param_specs(stacked, "pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (M, MBS, S, CFG.dim))
    y = jax.random.normal(jax.random.PRNGKey(2), (M, MBS, S, CFG.dim))

    def mb_loss(out, tgt):
        return jnp.mean((out - tgt) ** 2)

    def pp_loss(params, xx, yy):
        return shard_map(
            functools.partial(
                pipeline_loss,
                stage_fn=_stage_fn,
                loss_fn=mb_loss,
                num_microbatches=M,
            ),
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=P(),
        )(params, xx, yy)

    def serial_loss(stacked_params, xx, yy):
        def one(m):
            h = xx[m]

            def body(h, lp):
                return block_forward(lp, h, CFG), None

            h, _ = jax.lax.scan(body, h, stacked_params)
            return jnp.mean((h - yy[m]) ** 2)

        return jnp.mean(jnp.stack([one(m) for m in range(M)]))

    ref_loss, ref_grads = jax.value_and_grad(serial_loss)(stacked, x, y)
    pl, pg = jax.jit(jax.value_and_grad(pp_loss))(sharded, x, y)
    np.testing.assert_allclose(float(pl), float(ref_loss), rtol=1e-5)
    for (path, gs), (_, gp) in zip(
        jax.tree_util.tree_flatten_with_path(ref_grads)[0],
        jax.tree_util.tree_flatten_with_path(pg)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(gp),
            np.asarray(gs),
            rtol=5e-5,
            atol=5e-5,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}",
        )


@pytest.mark.parametrize("sp", [False, True])
def test_pipeline_with_tp_probe(devices8, sp):
    """Regression: the scan-carry vma probe must track the stage OUTPUT's
    varying axes, not guess from the first param leaf — PP x TP non-SP
    (output psum-reduced over tensor => carry must NOT be tensor-varying)
    and PP x TP SP (seq-sharded carry => tensor-varying) both trace."""
    pp, tp = 2, 2
    tpc.setup_process_groups([("pipe", pp), ("tensor", tp)], devices=devices8[:4])
    mesh = tpc.get_view()
    layers, stacked = _layers_and_stack()
    from torchdistpackage_tpu.parallel.tensor_parallel import block_param_specs

    bspecs = block_param_specs("tensor")
    specs = jax.tree.map(
        lambda s: P("pipe", *tuple(s)), bspecs, is_leaf=lambda x: isinstance(x, P)
    )
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (M, MBS, S, CFG.dim))

    def stage_fn(sp_params, h):
        def body(h, lp):
            return block_forward(lp, h, CFG, axis="tensor", sp=sp), None

        h, _ = jax.lax.scan(body, h, sp_params)
        return h

    in_x_spec = P(None, None, "tensor") if sp else P()

    def body(params, mbs):
        out = pipeline_forward(params, mbs, stage_fn, num_microbatches=M)
        return last_stage_value(out)

    fwd = jax.jit(
        shard_map(body, mesh=mesh, in_specs=(specs, in_x_spec), out_specs=in_x_spec)
    )
    out = fwd(sharded, x)

    want = jnp.stack(
        [_serial_forward(layers, x[m]) for m in range(M)]
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def _1f1b_value_and_grad(mesh, specs, M, pp=4, sched=pipeline_1f1b):
    """shard_map-wrapped (loss, grads) fn for the stage-only 1F1B (or,
    with ``sched=pipeline_zb_1f1b``, zero-bubble) pipeline."""

    def first_fn(params, mb):
        return mb

    def last_fn(params, yy, tgt):
        return jnp.mean((yy - tgt) ** 2)

    def stage_fn(params, h):
        def body(h, lp):
            return block_forward(lp, h, CFG), None

        out, _ = jax.lax.scan(body, h, params)
        return out

    def vg(params, xx, yy):
        return shard_map(
            functools.partial(
                sched,
                first_fn=first_fn,
                stage_fn=stage_fn,
                last_fn=last_fn,
                num_microbatches=M,
            ),
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=(P(), specs),
        )(params, xx, yy)

    return vg


@pytest.fixture(scope="module")
def serial_1f1b_ref():
    """Module-scope cache of the serial (loss, grads) reference per
    microbatch count M — the (2, 4) and (4, 4) schedule combos share one
    compiled serial program instead of re-deriving it per test (the PR-5
    shared-bundle pattern; tier-1 budget)."""
    cache = {}

    def get(m):
        if m not in cache:
            _, stacked = _layers_and_stack()
            x = jax.random.normal(jax.random.PRNGKey(1), (m, MBS, S, CFG.dim))
            y = jax.random.normal(jax.random.PRNGKey(2), (m, MBS, S, CFG.dim))

            def serial_loss(sp, xx, yy):
                def one(i):
                    def body(h, lp):
                        return block_forward(lp, h, CFG), None

                    h, _ = jax.lax.scan(body, xx[i], sp)
                    return jnp.mean((h - yy[i]) ** 2)

                return jnp.mean(jnp.stack([one(i) for i in range(m)]))

            ref_loss, ref_grads = jax.jit(
                jax.value_and_grad(serial_loss))(stacked, x, y)
            cache[m] = {
                "stacked": stacked, "x": x, "y": y,
                "loss": float(ref_loss), "grads": jax.device_get(ref_grads),
            }
        return cache[m]

    return get


# (4, 9) — the odd-M point at depth — demoted to slow for tier-1 budget
# (PR 13): it was 21 s of mostly compile for one extra (P, M) grid point,
# while the fast tier keeps P=4 at both a divisible (M=4) and a
# smaller-than-schedule (M=2) microbatch count plus the P=2 base case.
# (2, 4) demoted in PR 14: the zero-bubble golden at the same (P, M)
# exercises the identical serial ref + stage composition through the
# strictly harder split-backward path, so the classic schedule keeps its
# P=4 points in the fast tier and pays for the new ZB grid.
@pytest.mark.parametrize("pp,m", [
    pytest.param(2, 4, marks=pytest.mark.slow),
    (4, 4),
    pytest.param(4, 9, marks=pytest.mark.slow),
    (4, 2),
])
@pytest.mark.heavy
def test_pipeline_1f1b_matches_serial(devices8, serial_1f1b_ref, pp, m):
    """The 1F1B schedule's (loss, grads) must equal serial AD exactly —
    including M not divisible by / smaller than schedule-derived constants."""
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    ref = serial_1f1b_ref(m)
    stacked, x, y = ref["stacked"], ref["x"], ref["y"]
    specs = stacked_param_specs(stacked, "pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )

    loss, grads = jax.jit(_1f1b_value_and_grad(mesh, specs, m, pp))(sharded, x, y)

    ref_loss, ref_grads = ref["loss"], ref["grads"]
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (path, gs), (_, gp) in zip(
        jax.tree_util.tree_flatten_with_path(ref_grads)[0],
        jax.tree_util.tree_flatten_with_path(grads)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gs), rtol=5e-5, atol=5e-5,
            err_msg=f"1F1B grad mismatch at {jax.tree_util.keystr(path)}",
        )


# ------------------------------------------------------------- zero-bubble


# The ZB grid shares the module-scope serial refs with the 1F1B grid
# (tier-1 budget, the PR-6 shared-bundle rule): (2, 4) the base case,
# (4, 4) depth with one block per stage, (4, 2) M smaller than the
# schedule constants — the dgrad/wgrad split must clamp exactly like the
# fused schedule does.
@pytest.mark.parametrize("pp,m", [(2, 4), (4, 4), (4, 2)])
@pytest.mark.heavy
def test_pipeline_zb_matches_serial(devices8, serial_1f1b_ref, pp, m):
    """The zero-bubble schedule's (loss, grads) must equal serial AD —
    the deferred wgrad drain reassembles exactly the param cotangents the
    fused backward produces."""
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    ref = serial_1f1b_ref(m)
    stacked, x, y = ref["stacked"], ref["x"], ref["y"]
    specs = stacked_param_specs(stacked, "pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )

    loss, grads = jax.jit(
        _1f1b_value_and_grad(mesh, specs, m, pp, sched=pipeline_zb_1f1b)
    )(sharded, x, y)

    np.testing.assert_allclose(float(loss), float(ref["loss"]), rtol=1e-5)
    for (path, gs), (_, gp) in zip(
        jax.tree_util.tree_flatten_with_path(ref["grads"])[0],
        jax.tree_util.tree_flatten_with_path(grads)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gs), rtol=5e-5, atol=5e-5,
            err_msg=f"ZB grad mismatch at {jax.tree_util.keystr(path)}",
        )


@pytest.mark.heavy
def test_zb_deep_stage_dropout_parity_with_1f1b(devices8):
    """Interleaved-depth config under per-microbatch dropout: P=4 stages
    each scanning TWO blocks (L=8 — the slab depth the interleaved
    schedule distributes), a bernoulli mask drawn per (stage, microbatch)
    via ``stage_takes_mb``.  The ZB schedule must reproduce classic
    1F1B's (loss, grads) to tight tolerance: the dropout key folds
    replay identically in the forward, the dgrad recompute AND the
    deferred wgrad recompute."""
    pp, m = 4, 4
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    stacked = stack_stage_params([init_block_params(k, CFG) for k in keys])
    specs = stacked_param_specs(stacked, "pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (m, MBS, S, CFG.dim))
    y = jax.random.normal(jax.random.PRNGKey(2), (m, MBS, S, CFG.dim))
    drop_key = jax.random.PRNGKey(7)

    def stage_fn(params, h, mb_idx):
        def body(h, lp):
            return block_forward(lp, h, CFG), None

        h, _ = jax.lax.scan(body, h, params)
        k = jax.random.fold_in(
            jax.random.fold_in(drop_key, jax.lax.axis_index("pipe")), mb_idx)
        mask = jax.random.bernoulli(k, 0.9, h.shape).astype(h.dtype) / 0.9
        return h * mask

    def vg(sched):
        return shard_map(
            functools.partial(
                sched,
                first_fn=lambda p, mb: mb,
                stage_fn=stage_fn,
                last_fn=lambda p, o, t: jnp.mean((o - t) ** 2),
                num_microbatches=m,
                stage_takes_mb=True,
            ),
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=(P(), specs),
        )

    loss_zb, g_zb = jax.jit(vg(pipeline_zb_1f1b))(sharded, x, y)
    loss_1f, g_1f = jax.jit(vg(pipeline_1f1b))(sharded, x, y)
    np.testing.assert_allclose(float(loss_zb), float(loss_1f), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6),
        g_zb, g_1f,
    )


def test_zb_tp_pp_composition(devices8):
    """TP x PP under the zero-bubble schedule (the synergy-paper mesh,
    arXiv 2510.27257): SP-sharded stages through zb match classic 1F1B
    at tight tolerance (schedule-vs-schedule, so no vma gate — both arms
    share whatever reduction semantics the shard_map in use has), and
    the compiled step's comm ledger shows BOTH
    the pipe boundary permutes and the tensor-axis collectives —
    ``tp_pp_overlap`` runs on it (zeros on the sync-only CPU sim; the
    async evidence needs TPU + the overlap preset, disclosed in its
    docstring)."""
    from torchdistpackage_tpu.obs.comm_ledger import (
        ledger_from_compiled, tp_pp_overlap,
    )
    from torchdistpackage_tpu.parallel.tensor_parallel import (
        block_param_specs,
    )

    pp, tp, m = 2, 2, 4
    tpc.setup_process_groups(
        [("pipe", pp), ("tensor", tp)], devices=devices8[:4])
    mesh = tpc.get_view()
    layers, stacked = _layers_and_stack()
    bspecs = block_param_specs("tensor")
    specs = jax.tree.map(
        lambda s: P("pipe", *tuple(s)), bspecs,
        is_leaf=lambda x: isinstance(x, P))
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (m, MBS, S, CFG.dim))
    y = jax.random.normal(jax.random.PRNGKey(2), (m, MBS, S, CFG.dim))

    def stage_fn(p, h):
        def body(h, lp):
            return block_forward(lp, h, CFG, axis="tensor", sp=True), None

        h, _ = jax.lax.scan(body, h, p)
        return h

    io = P(None, None, "tensor")  # [M, MBS, S, D] seq-sharded (SP)

    def vg(sched):
        def body(params, xx, yy):
            from torchdistpackage_tpu.parallel.data_parallel import _vma

            loss, grads = sched(
                params, xx, yy,
                first_fn=lambda p, mb: mb,
                stage_fn=stage_fn,
                last_fn=lambda p, o, t: jnp.mean((o - t) ** 2),
                num_microbatches=m,
            )
            axes = tuple(a for a in ("tensor",) if a in _vma(loss))
            return (jax.lax.pmean(loss, axes) if axes else loss), grads

        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(specs, io, io),
            out_specs=(P(), specs)))

    zb = vg(pipeline_zb_1f1b)
    compiled = zb.lower(sharded, x, y).compile()
    loss_zb, g_zb = compiled(sharded, x, y)
    loss_1f, g_1f = vg(pipeline_1f1b)(sharded, x, y)
    np.testing.assert_allclose(float(loss_zb), float(loss_1f), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6),
        g_zb, g_1f,
    )

    ledger = ledger_from_compiled(compiled, mesh=mesh)
    assert ledger is not None
    per_dim = ledger["per_dim"]
    assert per_dim.get("pp", {}).get("ops", 0) > 0, per_dim
    assert per_dim.get("tp", {}).get("ops", 0) > 0, per_dim
    rep = tp_pp_overlap(ledger)
    assert set(rep) == {
        "pp_async_ops", "pp_windows_with_tp", "tp_ops_in_pp_windows",
        "tp_bytes_in_pp_windows", "mean_pp_sched_distance"}


def test_zb_wgrad_queue_structure(devices8):
    """The split's structural signature, from the jaxpr (no execution):
    the main scan carries the THREE [M, ...] wgrad-queue buffers (saved
    input x, output cotangent g, input cotangent dx) and NO weight-grad
    accumulator — param-shaped float carries belong to the drain scan
    only.  Also pins the tick accounting ``zb_schedule_ticks`` reports
    and the schedule-build events."""
    from torchdistpackage_tpu.obs.events import default_event_log

    pp, m = 4, 8
    assert zb_schedule_ticks(m, pp) == (m + 2 * (pp - 1), m)
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    _, stacked = _layers_and_stack()
    specs = stacked_param_specs(stacked, "pipe")
    stacked_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), stacked
    )
    x = jax.ShapeDtypeStruct((m, MBS, S, CFG.dim), jnp.float32)
    y = jax.ShapeDtypeStruct((m, MBS, S, CFG.dim), jnp.float32)

    log = default_event_log()
    before = len(log.of_kind("zb_cooldown_filled"))
    jaxpr = jax.make_jaxpr(
        _1f1b_value_and_grad(mesh, specs, m, pp, sched=pipeline_zb_1f1b)
    )(stacked_shapes, x, y).jaxpr
    carries = _scan_carry_avals(jaxpr)
    queue = [a for a in carries if a.shape == (m, MBS, S, CFG.dim)]
    assert len(queue) >= 3, (
        f"expected the (x, g, dx) wgrad queue carries of shape "
        f"{(m, MBS, S, CFG.dim)}, found {len(queue)}"
    )
    # the schedule-build events fired at trace time with the accounting
    evs = log.of_kind("zb_cooldown_filled")
    assert len(evs) > before
    assert evs[-1]["main_ticks"] == m + 2 * (pp - 1)
    assert evs[-1]["wgrad_ticks"] == m
    assert evs[-1]["bubble_fraction"] < evs[-1]["bubble_fraction_1f1b"]


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for v in vals:
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    yield from _iter_eqns(sub)


def _scan_carry_avals(jaxpr):
    """All scan-carry avals anywhere in the jaxpr."""
    out = []
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name == "scan":
            inner = eqn.params["jaxpr"].jaxpr
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            out.extend(v.aval for v in inner.invars[nc : nc + nk])
    return out


def test_1f1b_activation_memory_bounded(devices8):
    """The schedule's memory guarantee: the scan carries a ring buffer of
    ring_slots(M, P) = min(M, 2P-1) stage inputs — NOT M of them.  Verified
    structurally: some scan carry has the [R, mbs, S, D] ring shape, and no
    scan carry holds a float activation buffer with leading dim M."""
    pp, m = 4, 16
    R = ring_slots(m, pp)
    assert R == 7 < m
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    _, stacked = _layers_and_stack()
    specs = stacked_param_specs(stacked, "pipe")
    x = jax.ShapeDtypeStruct((m, MBS, S, CFG.dim), jnp.float32)
    y = jax.ShapeDtypeStruct((m, MBS, S, CFG.dim), jnp.float32)
    stacked_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), stacked
    )

    jaxpr = jax.make_jaxpr(_1f1b_value_and_grad(mesh, specs, m, pp))(
        stacked_shapes, x, y
    ).jaxpr
    carries = _scan_carry_avals(jaxpr)
    assert carries, "expected at least one scan in the 1F1B jaxpr"
    ring = [a for a in carries if a.shape == (R, MBS, S, CFG.dim)]
    assert ring, f"expected a ring-buffer carry of shape {(R, MBS, S, CFG.dim)}"
    leaked = [
        a for a in carries
        if jnp.issubdtype(a.dtype, jnp.floating) and a.shape[:1] == (m,)
    ]
    assert not leaked, f"O(M) float buffers carried through the scan: {leaked}"


@pytest.mark.slow  # tier-1 budget: per-stage heterogeneity stays fast-tier
# via test_balanced_stage_stack_pipelines_skewed_load (unequal stage
# SIZES through padded slabs + masks); this point adds the per-stage
# COMPUTE variant (stage_index-branched nonlinearities) of the same
# serial-golden claim
@pytest.mark.heavy
def test_heterogeneous_stage_fn_matches_serial(devices8):
    """Per-stage heterogeneous compute — ``stage_fn`` branches on
    :func:`stage_index` (each stage applies a DIFFERENT nonlinearity after its
    block), the capability the reference demonstrates with arbitrary per-stage
    fwd_fn/bwd_fn pairs (Intro.md:54-66).  Golden vs the serial model, loss
    AND grads, via the 1F1B schedule."""
    from torchdistpackage_tpu.parallel.pipeline_parallel import stage_index

    pp, m = 4, 4
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    layers, stacked = _layers_and_stack()
    specs = stacked_param_specs(stacked, "pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (m, MBS, S, CFG.dim))
    y = jax.random.normal(jax.random.PRNGKey(2), (m, MBS, S, CFG.dim))

    acts = [jnp.tanh, jax.nn.gelu, jnp.sin, lambda h: h * jax.nn.sigmoid(h)]

    def het_stage_fn(params, h):
        def body(h, lp):
            return block_forward(lp, h, CFG), None

        h, _ = jax.lax.scan(body, h, params)
        return jax.lax.switch(stage_index(), acts, h)

    def vg(params, xx, yy):
        return shard_map(
            functools.partial(
                pipeline_1f1b,
                first_fn=lambda p, mb: mb,
                stage_fn=het_stage_fn,
                last_fn=lambda p, o, t: jnp.mean((o - t) ** 2),
                num_microbatches=m,
            ),
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=(P(), specs),
        )(params, xx, yy)

    loss, grads = jax.jit(vg)(sharded, x, y)

    def serial_loss(sp, xx, yy):
        def one(i):
            h = xx[i]
            for stage, lp in enumerate(sp):
                slab = jax.tree.map(lambda a: a[None], lp)
                h2, _ = jax.lax.scan(
                    lambda c, l: (block_forward(l, c, CFG), None), h, slab
                )
                h = acts[stage](h2)
            return jnp.mean((h - yy[i]) ** 2)

        return jnp.mean(jnp.stack([one(i) for i in range(m)]))

    # serial over the per-layer list, then restack grads to compare
    ref_loss, ref_grad_layers = jax.value_and_grad(
        lambda ls, xx, yy: serial_loss(ls, xx, yy)
    )(layers, x, y)
    ref_grads = stack_stage_params(ref_grad_layers)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (path, gs), (_, gp) in zip(
        jax.tree_util.tree_flatten_with_path(ref_grads)[0],
        jax.tree_util.tree_flatten_with_path(grads)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gs), rtol=5e-5, atol=5e-5,
            err_msg=f"heterogeneous grad mismatch at {jax.tree_util.keystr(path)}",
        )


def test_pipeline_with_dp(devices8):
    """PP=2 x DP=4: pipelined loss inside a DataParallel train step."""
    import optax

    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    pp = 2
    tpc.setup_process_groups([("data", 4), ("pipe", pp)], devices=devices8)
    mesh = tpc.get_view()
    layers, stacked = _layers_and_stack()
    specs = stacked_param_specs(stacked, "pipe")

    def loss_fn(params, batch):
        return pipeline_loss(
            params,
            batch["x"],
            batch["y"],
            stage_fn=_stage_fn,
            loss_fn=lambda o, t: jnp.mean((o - t) ** 2),
            num_microbatches=M,
        )

    opt = optax.sgd(1e-2)
    dp = DataParallel(mesh=mesh)
    sharded = dp.broadcast_params(stacked, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        loss_fn,
        opt,
        param_specs=specs,
        batch_spec={"x": P(None, "data"), "y": P(None, "data")},
    )

    # serial reference on the full batch
    def serial_loss(sp, batch):
        def body(h, lp):
            return block_forward(lp, h, CFG), None

        losses = []
        for m in range(M):
            h, _ = jax.lax.scan(body, batch["x"][m], sp)
            losses.append(jnp.mean((h - batch["y"][m]) ** 2))
        return jnp.mean(jnp.stack(losses))

    sparams, sstate = stacked, opt.init(stacked)

    @jax.jit
    def serial_step(p, s, b):
        loss, g = jax.value_and_grad(serial_loss)(p, b)
        u, s = opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s, loss

    for i in range(2):
        kx, ky = jax.random.split(jax.random.PRNGKey(10 + i))
        batch = {
            "x": jax.random.normal(kx, (M, 8, S, CFG.dim)),
            "y": jax.random.normal(ky, (M, 8, S, CFG.dim)),
        }
        sparams, sstate, sloss = serial_step(sparams, sstate, batch)
        dbatch = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P(None, "data"))), batch
        )
        sharded, state, dloss = step(sharded, state, dbatch)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-4, atol=1e-5)

    np.testing.assert_allclose(
        np.asarray(sharded["mlp"]["w1"]),
        np.asarray(sparams["mlp"]["w1"]),
        rtol=1e-4,
        atol=1e-5,
    )


@pytest.mark.heavy
def test_balanced_stage_stack_pipelines_skewed_load(devices8):
    """VERDICT r2 item 6: a deliberately SKEWED layer->stage assignment
    (balanced bounds with unequal stage sizes) must pipeline correctly via
    padded slabs + layer masks — loss AND grads of the real layers match
    serial AD, and the padding layers' grads are exactly zero."""
    from torchdistpackage_tpu.parallel.pipeline_parallel import (
        balanced_stage_stack,
    )
    from torchdistpackage_tpu.parallel.tensor_parallel import scan_blocks

    pp, m = 2, 4
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    layers, serial_stacked = _layers_and_stack()

    # declared per-layer costs force unequal stages: [(0,1), (1,4)]
    weights = [3.0, 1.0, 1.0, 1.0]
    stacked, mask, bounds = balanced_stage_stack(layers, weights, pp)
    assert bounds == [(0, 1), (1, 4)]
    max_len = mask.shape[1]
    assert jax.tree.leaves(stacked)[0].shape[0] == pp * max_len

    specs = stacked_param_specs(stacked, "pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )

    def first_fn(params, mb):
        return mb

    def last_fn(params, yy, tgt):
        return jnp.mean((yy - tgt) ** 2)

    def stage_fn(params, h):
        local_mask = mask[jax.lax.axis_index("pipe")]  # [max_len], tiny gather
        return scan_blocks(params, h, CFG, layer_mask=local_mask)

    def vg(params, xx, yy):
        return shard_map(
            functools.partial(
                pipeline_1f1b,
                first_fn=first_fn,
                stage_fn=stage_fn,
                last_fn=last_fn,
                num_microbatches=m,
            ),
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=(P(), specs),
        )(params, xx, yy)

    x = jax.random.normal(jax.random.PRNGKey(1), (m, MBS, S, CFG.dim))
    y = jax.random.normal(jax.random.PRNGKey(2), (m, MBS, S, CFG.dim))
    loss, grads = jax.jit(vg)(sharded, x, y)

    def serial_loss(sp, xx, yy):
        def one(i):
            def body(h, lp):
                return block_forward(lp, h, CFG), None

            h, _ = jax.lax.scan(body, xx[i], sp)
            return jnp.mean((h - yy[i]) ** 2)

        return jnp.mean(jnp.stack([one(i) for i in range(m)]))

    ref_loss, ref_grads = jax.value_and_grad(serial_loss)(serial_stacked, x, y)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)

    # map padded-slab rows back to serial layer indices; padding rows
    # (row_to_layer = -1) must have exactly-zero grads
    row_to_layer = []
    for s, (a, b) in enumerate(bounds):
        row_to_layer.extend(list(range(a, b)) + [-1] * (max_len - (b - a)))
    for (path, gs), (_, gp) in zip(
        jax.tree_util.tree_flatten_with_path(ref_grads)[0],
        jax.tree_util.tree_flatten_with_path(grads)[0],
    ):
        gp = np.asarray(gp)
        gs = np.asarray(gs)
        for row, layer in enumerate(row_to_layer):
            if layer < 0:
                np.testing.assert_array_equal(
                    gp[row], np.zeros_like(gp[row]),
                    err_msg=f"padding grad nonzero at {jax.tree_util.keystr(path)}",
                )
            else:
                np.testing.assert_allclose(
                    gp[row], gs[layer], rtol=5e-5, atol=5e-5,
                    err_msg=f"skewed-pipeline grad mismatch at "
                            f"{jax.tree_util.keystr(path)} row {row}",
                )


def test_balanced_stage_stack_with_ring_cp(devices8):
    """Skewed stages + ring-attention blocks: the where-masked padding must
    be collective-safe (a ppermute inside a branch-divergent cond would
    deadlock — the mask differs across pipe stages by construction)."""
    from torchdistpackage_tpu.parallel.pipeline_parallel import (
        balanced_stage_stack,
    )
    from torchdistpackage_tpu.parallel.tensor_parallel import scan_blocks

    cfg_cp = TransformerConfig(
        dim=32, nheads=4, nlayers=4, ffn_mult=2, causal=True,
        attn_impl="ring", context_axis="context",
    )
    pp, m = 2, 4
    tpc.setup_process_groups(
        [("pipe", pp), ("context", 2)], devices=devices8[:4]
    )
    mesh = tpc.get_view()
    layers, serial_stacked = _layers_and_stack()
    stacked, mask, bounds = balanced_stage_stack(layers, [3.0, 1.0, 1.0, 1.0], pp)
    assert bounds == [(0, 1), (1, 4)]

    specs = stacked_param_specs(stacked, "pipe")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), stacked, specs
    )

    def stage_fn(params, h):
        local_mask = mask[jax.lax.axis_index("pipe")]
        return scan_blocks(params, h, cfg_cp, layer_mask=local_mask)

    def vg(params, xx, yy):
        def body(params, xx, yy):
            loss, grads = pipeline_1f1b(
                params, xx, yy,
                first_fn=lambda p, mb: mb,
                stage_fn=stage_fn,
                last_fn=lambda p, o, tgt: jnp.mean((o - tgt) ** 2),
                num_microbatches=m,
            )
            from torchdistpackage_tpu.parallel.data_parallel import _vma

            axes = tuple(a for a in ("context",) if a in _vma(loss))
            return (jax.lax.pmean(loss, axes) if axes else loss), grads

        io = P(None, None, "context")  # [M, MBS, S, D]: seq sharded over cp
        return shard_map(
            body, mesh=mesh, in_specs=(specs, io, io), out_specs=(P(), specs)
        )(params, xx, yy)

    x = jax.random.normal(jax.random.PRNGKey(1), (m, MBS, S, CFG.dim))
    y = jax.random.normal(jax.random.PRNGKey(2), (m, MBS, S, CFG.dim))
    loss, grads = jax.jit(vg)(sharded, x, y)

    def serial_loss(sp, xx, yy):
        def one(i):
            def body(h, lp):
                return block_forward(lp, h, CFG), None

            h, _ = jax.lax.scan(body, xx[i], sp)
            return jnp.mean((h - yy[i]) ** 2)

        return jnp.mean(jnp.stack([one(i) for i in range(m)]))

    ref_loss = serial_loss(serial_stacked, x, y)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)


def _interleaved_specs(itree, pipe_axis="pipe"):
    """[V, P, Lc, ...] leaves: shard dim 1 (the stage dim) over pipe."""
    return jax.tree.map(
        lambda a: P(None, pipe_axis, *([None] * (a.ndim - 2))), itree
    )


def _interleave(stacked, vv, pp):
    return jax.tree.map(
        lambda a: a.reshape(vv, pp, a.shape[0] // (vv * pp), *a.shape[1:]),
        stacked,
    )


def _interleaved_vg(mesh, specs, M, vv):
    """shard_map-wrapped (loss, grads) for the INTERLEAVED stage-only 1F1B —
    identity first_fn, so this also covers the degenerate
    (first_vjp_in_cond=False) path under V > 1."""

    def first_fn(params, mb):
        return mb

    def last_fn(params, yy, tgt):
        return jnp.mean((yy - tgt) ** 2)

    def stage_fn(params, h, m, v):
        slab = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, v, 0, keepdims=False)[0],
            params,
        )

        def body(h, lp):
            return block_forward(lp, h, CFG), None

        out, _ = jax.lax.scan(body, h, slab)
        return out

    def vg(params, xx, yy):
        return shard_map(
            functools.partial(
                pipeline_1f1b,
                first_fn=first_fn,
                stage_fn=stage_fn,
                last_fn=last_fn,
                num_microbatches=M,
                num_chunks=vv,
            ),
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=(P(), specs),
        )(params, xx, yy)

    return vg


# (2, 2, 2) and (2, 4, 6) demoted to slow in PR 14 (tier-1 budget payback
# for the new ZB grid): the fast tier keeps the base interleave (2, 2, 4)
# and the deep-pipe point (4, 2, 4); the M-smaller-than-schedule and
# deep-chunk edges stay covered in the slow tier.
@pytest.mark.parametrize("pp,vv,m", [
    (2, 2, 4),
    pytest.param(2, 2, 2, marks=pytest.mark.slow),
    (4, 2, 4),
    pytest.param(2, 4, 6, marks=pytest.mark.slow),
])
def test_interleaved_1f1b_matches_serial(devices8, pp, vv, m):
    """The interleaved (virtual-chunk) schedule's (loss, grads) must equal
    serial AD exactly for every (P, V, M) shape — chunk v of stage s holds
    layer slab v*P+s, so the round-robin reassembly must reproduce the
    serial layer order.  The stack is built with L = P*V layers (one per
    slab) so deep-pipeline cases run too."""
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    keys = jax.random.split(jax.random.PRNGKey(0), pp * vv)
    layers = [init_block_params(k, CFG) for k in keys]
    stacked = stack_stage_params(layers)
    itree = _interleave(stacked, vv, pp)
    specs = _interleaved_specs(itree)
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), itree, specs
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (m, MBS, S, CFG.dim))
    y = jax.random.normal(jax.random.PRNGKey(2), (m, MBS, S, CFG.dim))

    loss, grads = jax.jit(_interleaved_vg(mesh, specs, m, vv))(sharded, x, y)

    def serial_loss(stacked_flat, xx, yy):
        def one(xm, ym):
            h = xm
            def body(h, lp):
                return block_forward(lp, h, CFG), None
            out, _ = jax.lax.scan(body, h, stacked_flat)
            return jnp.mean((out - ym) ** 2)

        return jnp.mean(jax.vmap(one)(xx, yy))

    want_loss, want_g = jax.value_and_grad(serial_loss)(stacked, x, y)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-5, atol=1e-6)
    got_flat = jax.tree.map(
        lambda a: np.asarray(a).reshape(-1, *a.shape[3:]), grads
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        got_flat,
        want_g,
    )


def test_interleaved_wrong_stage_fn_arity_raises(devices8):
    """num_chunks > 1 with a stage_fn that can't take (p, x, m, v) must be
    rejected with a contract error naming the required signature, not an
    opaque TypeError from inside tracing (ADVICE r3)."""
    tpc.setup_process_groups([("pipe", 2)], devices=devices8[:2])
    mesh = tpc.get_view()
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    itree = _interleave(stack_stage_params([init_block_params(k, CFG) for k in keys]), 2, 2)
    specs = _interleaved_specs(itree)
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), itree, specs
    )
    x = jnp.zeros((4, MBS, S, CFG.dim))
    y = jnp.zeros((4, MBS, S, CFG.dim))

    def two_arg_stage(params, h):  # V=1-style signature: must be rejected
        return h

    with pytest.raises(ValueError, match=r"\(params, x, microbatch_idx"):
        jax.jit(
            shard_map(
                functools.partial(
                    pipeline_1f1b,
                    first_fn=lambda p, mb: mb,
                    stage_fn=two_arg_stage,
                    last_fn=lambda p, yy, t: jnp.mean((yy - t) ** 2),
                    num_microbatches=4,
                    num_chunks=2,
                ),
                mesh=mesh,
                in_specs=(specs, P(), P()),
                out_specs=(P(), specs),
            )
        )(sharded, x, y)

    # a *args stage_fn is unintrospectable-compatible and must pass the check
    def var_stage(*args):
        return args[1]

    loss, _ = jax.jit(
        shard_map(
            functools.partial(
                pipeline_1f1b,
                first_fn=lambda p, mb: mb,
                stage_fn=var_stage,
                last_fn=lambda p, yy, t: jnp.mean((yy - t) ** 2),
                num_microbatches=4,
                num_chunks=2,
            ),
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=(P(), specs),
        )
    )(sharded, x, y)
    assert np.isfinite(float(loss))


def test_interleaved_1f1b_ring_memory_bounded(devices8):
    """Interleaved memory guarantee: the scan carries ring_slots(M, P, V) =
    min(VM, 2PV-1) chunk inputs — NOT V*M of them."""
    pp, vv, m = 2, 2, 8
    R = ring_slots(m, pp, vv)
    assert R == 7 < vv * m
    tpc.setup_process_groups([("pipe", pp)], devices=devices8[:pp])
    mesh = tpc.get_view()
    _, stacked = _layers_and_stack()
    itree = _interleave(stacked, vv, pp)
    specs = _interleaved_specs(itree)
    stacked_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), itree
    )
    x = jax.ShapeDtypeStruct((m, MBS, S, CFG.dim), jnp.float32)
    y = jax.ShapeDtypeStruct((m, MBS, S, CFG.dim), jnp.float32)

    jaxpr = jax.make_jaxpr(_interleaved_vg(mesh, specs, m, vv))(
        stacked_shapes, x, y
    ).jaxpr
    carries = _scan_carry_avals(jaxpr)
    ring = [a for a in carries if a.shape == (R, MBS, S, CFG.dim)]
    assert ring, f"expected a ring-buffer carry of shape {(R, MBS, S, CFG.dim)}"
    leaked = [
        a for a in carries
        if jnp.issubdtype(a.dtype, jnp.floating) and a.shape[:1] == (vv * m,)
    ]
    assert not leaked, f"O(VM) float buffers carried through the scan: {leaked}"


def test_heterogeneous_bus_stages_match_serial(devices8):
    """TRUE heterogeneous stage activations (VERDICT r3 missing #4): stage 0
    maps D0=8 -> D1=12, stage 1 maps D1=12 -> D2=6 — different widths on
    every edge, carried through the scheduler as a max-edge bus with
    lax.switch per-stage dispatch (the reference's shape-meta handshake,
    comm.py:26-105, moved to trace time).  Loss and grads must equal serial
    AD through the composed heterogeneous model."""
    from torchdistpackage_tpu.parallel.pipeline_parallel import (
        make_heterogeneous_stage,
    )

    tpc.setup_process_groups([("pipe", 2)], devices=devices8[:2])
    mesh = tpc.get_view()
    mbs, M2 = 2, 4
    D0, D1, D2 = 8, 12, 6
    k0, k1, kx, ky = jax.random.split(jax.random.PRNGKey(3), 4)
    params = {
        "w0": jax.random.normal(k0, (D0, D1)) / np.sqrt(D0),
        "w1": jax.random.normal(k1, (D1, D2)) / np.sqrt(D1),
    }

    def s0(p, x, m):
        return jnp.tanh(x @ p["w0"])

    def s1(p, x, m):
        return jnp.tanh(x @ p["w1"])

    edges = [
        jax.ShapeDtypeStruct((mbs, D0), jnp.float32),
        jax.ShapeDtypeStruct((mbs, D1), jnp.float32),
        jax.ShapeDtypeStruct((mbs, D2), jnp.float32),
    ]
    wrap_first, stage_fn, wrap_last = make_heterogeneous_stage([s0, s1], edges)
    first_fn = wrap_first(lambda p, mb: mb)
    last_fn = wrap_last(lambda p, y, t: jnp.mean((y - t) ** 2))

    vg = shard_map(
        functools.partial(
            pipeline_1f1b,
            first_fn=first_fn,
            stage_fn=stage_fn,
            last_fn=last_fn,
            num_microbatches=M2,
            stage_takes_mb=True,
        ),
        mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=(P(), P()),
    )
    x = jax.random.normal(kx, (M2, mbs, D0))
    y = jax.random.normal(ky, (M2, mbs, D2))
    loss, grads = jax.jit(vg)(params, x, y)

    def serial_loss(p, xx, yy):
        h = jnp.tanh(xx @ p["w0"])
        out = jnp.tanh(h @ p["w1"])
        return jnp.mean(jnp.mean((out - yy) ** 2, axis=(1, 2)))

    want_loss, want_g = jax.value_and_grad(serial_loss)(params, x, y)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        grads, want_g,
    )

    # trace-time handshake: a stage that breaks the edge contract fails
    # with the named edge, not a shape error deep in the schedule
    bad_edges = [
        jax.ShapeDtypeStruct((mbs, D0), jnp.float32),
        jax.ShapeDtypeStruct((mbs, D1 + 1), jnp.float32),  # wrong contract
        jax.ShapeDtypeStruct((mbs, D2), jnp.float32),
    ]
    wf, sf, wl = make_heterogeneous_stage([s0, s1], bad_edges)
    with pytest.raises(ValueError, match="edge contract"):
        jax.eval_shape(
            shard_map(
                functools.partial(
                    pipeline_1f1b,
                    first_fn=wf(lambda p, mb: mb),
                    stage_fn=sf,
                    last_fn=wl(lambda p, y, t: jnp.mean(y)),
                    num_microbatches=M2,
                    stage_takes_mb=True,
                ),
                mesh=mesh,
                in_specs=(P(), P(), P()),
                out_specs=(P(), P()),
            ),
            params, x, y,
        )


def test_heterogeneous_bus_guards(devices8):
    """Misuse fails at trace time: stage-count != pipe size (lax.switch
    would silently clamp), and an int edge on a float bus (values past the
    float's integer-exact range would corrupt silently)."""
    from torchdistpackage_tpu.parallel.pipeline_parallel import (
        make_heterogeneous_stage,
    )

    f32 = jnp.float32
    edges3 = [jax.ShapeDtypeStruct((2, 4), f32)] * 4
    fns3 = [lambda p, x, m: x] * 3
    wf, sf, wl = make_heterogeneous_stage(fns3, edges3)
    tpc.setup_process_groups([("pipe", 2)], devices=devices8[:2])
    mesh = tpc.get_view()
    with pytest.raises(ValueError, match="one fn per stage"):
        jax.eval_shape(
            shard_map(
                functools.partial(
                    pipeline_1f1b,
                    first_fn=wf(lambda p, mb: mb),
                    stage_fn=sf,
                    last_fn=wl(lambda p, y, t: jnp.mean(y)),
                    num_microbatches=2,
                    stage_takes_mb=True,
                ),
                mesh=mesh, in_specs=(P(), P(), P()), out_specs=(P(), P()),
            ),
            {"w": jnp.zeros((2,))}, jnp.zeros((2, 2, 4)), jnp.zeros((2, 2, 4)),
        )

    with pytest.raises(ValueError, match="integer and float"):
        make_heterogeneous_stage(
            [lambda p, x, m: x.astype(f32)],
            [jax.ShapeDtypeStruct((2, 4), jnp.int32),
             jax.ShapeDtypeStruct((2, 4), f32)],
        )
