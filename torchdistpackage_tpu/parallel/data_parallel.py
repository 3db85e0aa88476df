"""Data parallelism — the TPU-native analogue of ``NaiveDDP``
(``torchdistpackage/ddp/naive_ddp.py:13-230``) and its ``GradBucket``
(naive_ddp.py:444-478).

The reference implements DP with per-param autograd hooks, a 25 MB flat grad
bucket and an all-reduce on a dedicated CUDA stream to overlap with backward.
Under XLA none of that machinery is needed: the batch axis is sharded over the
``data`` mesh axis, gradients are reduced inside the compiled step, and XLA's
async collectives overlap the reduce with remaining backward compute
automatically (the scheduler sees the whole graph).  What we keep from the
reference is the *semantics*:

- param broadcast at wrap time  -> :meth:`DataParallel.broadcast_params`
  (replicated placement; naive_ddp.py:58,226-230)
- reduce-op choice (avg/sum)    -> ``reduce_op=`` (naive_ddp.py:50-56 — NB the
  reference's string test makes SUM unreachable; we support it properly)
- ``_ddp_params_and_buffers_to_ignore`` -> ``grad_reduce_overrides=`` — params
  matched by name reduce over *different* axes (or none).  This is exactly
  what the reference's ignore list exists for: MoE expert params are ignored
  by the main DDP and reduced over the ``moe_dp`` group instead
  (naive_ddp.py:46-49 + moe_dp.md).
- grad accumulation with reduce only on the last microbatch
  (naive_ddp.py:73,108-110; Readme.md:56) -> ``grad_accum_iters`` microbatch
  ``lax.scan`` inside the jitted step, single reduce at the end.

Mechanically: params are ``pvary``-ed over the data axes at step entry so that
in-step AD keeps *local* per-shard gradients (instead of shard_map's implicit
transpose-psum), giving one explicit, overlappable reduce site — mirroring the
reference's "reduce once after backward" design while letting XLA schedule it.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax

from jax.lax import axis_size
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..dist.topology import DATA_AXIS, tpc
from ..utils import profiling as prof

AxisName = Union[str, Tuple[str, ...]]
PyTree = Any


def sharding_cache_key(tree) -> tuple:
    """Hashable cache key capturing each leaf's actual placement — two calls
    with the same pytree STRUCTURE but different shardings (e.g. a spec tree
    change between runs) must not reuse a compiled step built for the other."""
    return tuple(
        str(getattr(getattr(x, "sharding", None), "spec", None))
        for x in jax.tree.leaves(tree)
    )


def step_cache_key(*trees) -> tuple:
    """Structure + shape/dtype + placement key for lazily-compiled train
    steps — shared by DataParallel / ZeroOptimizer / FSDP so every step cache
    keys on the same thing.  Shapes matter beyond structure: derived specs
    (e.g. zero_partition_spec) depend on leaf shapes, so a same-structure
    tree with different shapes must not reuse a compiled step."""
    return tuple(jax.tree.structure(t) for t in trees) + (
        tuple(
            (jnp.shape(x), str(getattr(x, "dtype", type(x))))
            for x in jax.tree.leaves(trees)
        ),
        sharding_cache_key(trees),
    )


def _key_str(path) -> str:
    """'block1/w' style name for a tree path (for override matching)."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _vma(x) -> frozenset:
    """The set of mesh axes a traced value is varying over."""
    return frozenset(getattr(jax.typeof(x), "vma", frozenset()))


def _vaxes(x, axes) -> Tuple[str, ...]:
    """The subset of ``axes`` that ``x`` actually varies over."""
    return tuple(a for a in axes if a in _vma(x))


def _mark_varying(x, axes: Tuple[str, ...]):
    # idempotent: pcast rejects varying->varying, so only mark what's missing
    axes = tuple(a for a in axes if a not in _vma(x))
    if not axes:
        return x
    return jax.lax.pcast(x, axes, to="varying")


def pvary_params(params: PyTree, axes: Tuple[str, ...]) -> PyTree:
    """Mark params varying over ``axes`` (where not already) so in-step AD
    yields local per-shard grads instead of implicitly psum-ing them."""

    return jax.tree.map(lambda p: _mark_varying(p, axes), params)


def reduce_gradients(
    grads: PyTree,
    axis: AxisName = DATA_AXIS,
    reduce_op: Union[str, Dict[str, str]] = "mean",
    grad_reduce_overrides: Optional[Dict[str, Tuple[str, ...]]] = None,
    compress: Optional[str] = None,
    compress_min_size: int = 65536,
    compress_policy: Optional[Dict[str, bool]] = None,
) -> PyTree:
    """Reduce a gradient pytree over the data axes (traced; call inside
    shard_map).  Analogue of ``NaiveDDP.reduce_gradients``
    (naive_ddp.py:197-224) minus the stream bookkeeping.

    ``grad_reduce_overrides``: ``{name_substring: axes_tuple}`` — grads whose
    '/'-joined key path matches a substring reduce over the given axes instead
    (empty tuple = no reduction at all; the grad stays per-shard, the analogue
    of the reference's params-to-ignore).  First match wins.

    Override + ``'mean'`` semantics: the result is the mean over the *global*
    batch — the grad is psum-ed over the override axes and normalized by the
    FULL data-group size.  This matters for MoE-DP (expert grads reduce over
    'moe_dp' only): the all_to_all transpose has already summed each expert's
    cotangents across its EP peers, so normalizing by the moe_dp size alone
    would over-count by the EP size.  The reference papers over this inside
    DeepSpeed's expert-grad scaling; here it is explicit.

    ``compress='int8'``: leaves with >= ``compress_min_size`` elements
    reduce their MEAN-op axes through the int8 quantized ring
    (:func:`...dist.compressed.int8_ring_pmean`) — ~2.7x fewer wire bytes at
    bounded quantization noise; small leaves, sum-op axes and override
    leaves keep the exact reduction.  The ring is vma-legal
    (invariance-typed output), so compression composes with TP/PP meshes.

    ``compress_policy``: per-leaf choices keyed by the '/'-joined leaf
    path (``{name: bool}``) — when given it REPLACES the size threshold
    (the ``grad_compress='auto'`` path: ``DataParallel`` derives the
    policy from ``CommModel.predict_compressed`` per leaf and passes it
    here; leaves absent from the dict stay exact).

    ``reduce_op`` may be a single op or a per-axis dict ``{axis: op}``
    (unlisted axes default to 'mean').  Per-axis 'sum' is for objectives
    whose per-rank grads over one data-like axis are SHARES of the full
    gradient for EVERY param (e.g. a sum-of-per-shard-losses objective).
    NB: when only part of the model sits inside the shared region — ViT's
    class head runs AFTER the context-axis patch pooling — no axis-wide op
    is right (sum double-counts the outside leaves, mean halves the
    shares); leave such an axis OUT of ``axis`` entirely so shard_map AD
    resolves each leaf through its cotangent vma (model-axis treatment,
    see tests/test_vit.py::test_vit_1f1b_with_cp_matches_serial).
    """
    default_axes = (axis,) if isinstance(axis, str) else tuple(axis)
    _validate_reduce_op(reduce_op)
    op_of = functools.partial(_axis_op, reduce_op)
    overrides = grad_reduce_overrides or {}

    def reduce_leaf(path, g):
        name = _key_str(path)
        matched = False
        axes = default_axes
        for tok, ax in overrides.items():
            if tok in name:
                axes = tuple(ax)
                matched = True
                break
        # only reduce over axes the grad actually varies on (a grad can
        # already be unvarying over an axis, e.g. after implicit psum)
        vaxes = _vaxes(g, axes)
        if not matched:
            mean_axes = tuple(a for a in vaxes if op_of(a) == "mean")
            sum_axes = tuple(a for a in vaxes if op_of(a) == "sum")
            use_ring = False
            if compress in ("int8", "auto") and mean_axes:
                use_ring = (
                    bool(compress_policy.get(name, False))
                    if compress_policy is not None
                    else g.size >= compress_min_size
                )
            if use_ring:
                from ..dist.compressed import int8_ring_pmean

                for a in mean_axes:  # nested means == joint mean (equal sizes)
                    g = int8_ring_pmean(g, a)
            elif mean_axes:
                g = jax.lax.pmean(g, mean_axes)
            if sum_axes:
                g = jax.lax.psum(g, sum_axes)
            return g
        if not axes:
            return g  # explicitly ignored — raw per-shard grad
        if vaxes:
            g = jax.lax.psum(g, vaxes)
        # mean-op semantics for overrides: normalize by the FULL size of the
        # mean-op default axes (see the MoE note above); sum-op axes
        # contribute no normalization
        denom = 1
        for a in default_axes:
            if op_of(a) == "mean":
                denom *= axis_size(a)
        if denom > 1:
            g = g / denom
        return g

    return jax.tree_util.tree_map_with_path(reduce_leaf, grads)


def _validate_reduce_op(reduce_op) -> None:
    ops = reduce_op.values() if isinstance(reduce_op, dict) else (reduce_op,)
    for op in ops:
        if op not in ("mean", "sum"):
            raise ValueError(f"reduce op must be 'mean' or 'sum', got {op!r}")


def _axis_op(reduce_op, a: str) -> str:
    """The reduce op for axis ``a`` ('mean' when unlisted in a dict)."""
    if isinstance(reduce_op, dict):
        return reduce_op.get(a, "mean")
    return reduce_op


def _opt_state_specs(opt_state, params, param_specs, spec_of):
    """PartitionSpec tree for an optimizer state: any subtree whose pytree
    structure mirrors the params (adam's mu/nu, sgd momentum, ...) gets the
    param specs; every other leaf (step counters, scalars) falls back to its
    observed placement."""
    pdef = jax.tree_util.tree_structure(params)
    multi = pdef.num_leaves > 1  # a 1-leaf params tree would match any leaf

    def build(node):
        if multi:
            try:
                if jax.tree_util.tree_structure(node) == pdef:
                    return param_specs
            except Exception:
                pass
        if isinstance(node, dict):
            return {k: build(v) for k, v in node.items()}
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # NamedTuple
            return type(node)(*(build(c) for c in node))
        if isinstance(node, (list, tuple)):
            return type(node)(build(c) for c in node)
        return spec_of(node)

    return build(opt_state)


def _reduce_loss(loss, axes: Tuple[str, ...], reduce_op):
    """The LOGGED loss always averages over the data-like axes, whatever the
    grad ops: 'sum' describes how per-rank GRAD SHARES combine (ViT-CP's
    pooled loss has equal per-rank loss values whose sum would double-count;
    the reference's avg/sum switch likewise concerns gradients only,
    naive_ddp.py:50-56)."""
    del reduce_op
    return jax.lax.pmean(loss, axes)


def local_value_and_grad(
    loss_fn: Callable[[PyTree, PyTree], jnp.ndarray],
    params: PyTree,
    batch: PyTree,
    grad_accum_iters: int = 1,
    reduce_fn: Optional[Callable[[PyTree], PyTree]] = None,
):
    """(loss, grads) of the local mean loss; with accumulation, scans
    microbatches (split from the leading batch dim) summing grads locally —
    the reference's reduce-only-on-last-microbatch semantics
    (naive_ddp.py:108-110).  Traced; call inside shard_map.  The scan carry's
    varying axes are derived from an abstract eval so this works under any
    TP/SP/PP composition inside ``loss_fn``.

    ``reduce_fn`` (the overlap path): applied to each microbatch's grads
    INSIDE the scan — the cross-shard reduction (pmean / psum_scatter)
    rides along with the backward instead of landing as one post-hoc sync,
    so it overlaps the next microbatch's compute, and (for a scattering
    reduce) the accumulator holds only the 1/N shard.  Any LINEAR
    reduction composes exactly: mean-of-per-microbatch-reductions equals
    the reduction of the accumulated mean.  The returned grads are then
    already reduced — callers must not reduce again."""
    if grad_accum_iters == 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if reduce_fn is not None:
            grads = reduce_fn(grads)
        return loss, grads

    def split(x):
        b = x.shape[0]
        if b % grad_accum_iters != 0:
            raise ValueError(
                f"local batch dim {b} not divisible by grad_accum_iters {grad_accum_iters}"
            )
        return x.reshape(grad_accum_iters, b // grad_accum_iters, *x.shape[1:])

    def vag(p, mb):
        l, g = jax.value_and_grad(loss_fn)(p, mb)
        if reduce_fn is not None:
            g = reduce_fn(g)
        return l, g

    micro = jax.tree.map(split, batch)
    first = jax.tree.map(lambda m: m[0], micro)
    loss_aval, grads_aval = jax.eval_shape(vag, params, first)

    def zeros_like_aval(a):
        z = jnp.zeros(a.shape, a.dtype)
        vm = tuple(getattr(a, "vma", ()))
        return _mark_varying(z, vm) if vm else z

    def body(carry, mb):
        ls, gs = carry
        l, g = vag(params, mb)
        return (ls + l, jax.tree.map(jnp.add, gs, g)), None

    (loss, grads), _ = jax.lax.scan(
        body,
        (zeros_like_aval(loss_aval), jax.tree.map(zeros_like_aval, grads_aval)),
        micro,
    )
    inv = 1.0 / grad_accum_iters
    return loss * inv, jax.tree.map(lambda g: g * inv, grads)


def normalize_model_axis_grads(loss, grads, mesh, data_axes: Tuple[str, ...]):
    """Rescale raw local grads for model-axis redundancy: over non-data axes
    the in-step AD has already summed each param's cotangents (shard_map
    transpose semantics), so the grads correspond to the *sum* of the
    per-model-shard losses; the true per-data-shard loss is their mean.
    Returns (grads, other_axes) where other_axes are the non-data mesh axes
    the loss varies on."""
    other = tuple(a for a in mesh.axis_names if a not in data_axes and a in _vma(loss))
    r = 1
    for a in other:
        r *= mesh.shape[a]
    if r > 1:
        grads = jax.tree.map(lambda g: g / r, grads)
    return grads, other


class DataParallel:
    """Builder of data-parallel (optionally grad-accumulating) train steps.

    Usage (cf. examples/test_ddp.py:27-71 in the reference)::

        dp = DataParallel()                      # uses tpc's mesh, 'data' axis
        params = dp.broadcast_params(params)     # replicated placement
        step = dp.make_train_step(loss_fn, optax_opt)
        params, opt_state, loss = step(params, opt_state, dp.shard_batch(batch))
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        axis: AxisName = DATA_AXIS,
        reduce_op: Union[str, Dict[str, str]] = "mean",
        grad_reduce_overrides: Optional[Dict[str, Tuple[str, ...]]] = None,
        grad_compress: Optional[str] = None,
        compress_min_size: int = 65536,
        comm_model: Optional[Any] = None,
    ) -> None:
        self.mesh = mesh if mesh is not None else tpc.get_view()
        self.axis = axis
        _validate_reduce_op(reduce_op)
        self.reduce_op = reduce_op
        self.grad_reduce_overrides = dict(grad_reduce_overrides or {})
        if grad_compress not in (None, "int8", "auto"):
            raise ValueError(
                f"unknown grad_compress {grad_compress!r}; DataParallel "
                f"supports None, 'int8' or 'auto' ('int8_ef' needs the "
                f"persistent residual state only ZeroOptimizer carries)")
        data_axes = (axis,) if isinstance(axis, str) else tuple(axis)
        if grad_compress is not None and not any(
            _axis_op(reduce_op, a) == "mean" for a in data_axes
        ):
            raise ValueError(
                "grad_compress needs at least one mean-op data axis — with "
                "every axis on 'sum' every leaf would take the exact path"
            )
        self.grad_compress = grad_compress
        self.compress_min_size = compress_min_size
        # 'auto' scores each leaf's reduction through this model's
        # predict_compressed (None -> the per-generation table model for
        # the mesh); pass CommModel.calibrate(...) for measured decisions
        self.comm_model = comm_model

    # ------------------------------------------------------------- placement

    def broadcast_params(self, params: PyTree, param_specs: Optional[PyTree] = None) -> PyTree:
        """Place params on the mesh — replicated by default (the analogue of
        rank-0 state_dict broadcast, naive_ddp.py:226-230), or per-leaf
        ``param_specs`` PartitionSpecs for TP-sharded params."""
        if param_specs is None:
            return jax.device_put(params, NamedSharding(self.mesh, P()))
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            params,
            param_specs,
            is_leaf=lambda x: x is None,
        )

    def shard_batch(self, batch: PyTree) -> PyTree:
        """Shard every leaf's leading dim over the data axis (delegates to
        the general :func:`..utils.data.shard_batch` so the placement rule
        exists once)."""
        from ..utils.data import shard_batch

        return shard_batch(batch, self.mesh, P(self.axis))

    # ------------------------------------------------------------ train step

    def make_train_step(
        self,
        loss_fn: Optional[Callable[[PyTree, PyTree], jnp.ndarray]] = None,
        optimizer=None,
        grad_accum_iters: int = 1,
        param_specs: Optional[PyTree] = None,
        batch_spec: Optional[PyTree] = None,
        donate: bool = True,
        value_and_grad_fn: Optional[Callable] = None,
        accum_reduce: str = "final",
        numerics: bool = False,
    ):
        """Build a jitted SPMD train step.

        - ``loss_fn(params, batch) -> scalar`` runs on the *local* batch shard
          (per-device view, as inside shard_map).
        - ``optimizer`` is an optax GradientTransformation.
        - ``grad_accum_iters > 1``: the local batch's leading dim is split into
          that many microbatches and scanned, grads summed locally and reduced
          over the data axis **once** (reference semantics, naive_ddp.py:108-110).
        - ``param_specs``: per-leaf PartitionSpec pytree when params are not
          replicated (TP composition); default replicated.
        - ``batch_spec``: per-leaf PartitionSpec for the batch; default sharded
          on dim 0 over the data axis.
        - ``value_and_grad_fn(params, batch) -> (loss, grads)``: supply the
          loss AND grads directly instead of ``loss_fn`` — for schedules whose
          backward cannot be expressed as outer AD, e.g. the 1F1B pipeline
          (``pipeline_parallel.pipeline_1f1b`` / ``gpt_pipeline_1f1b``), whose
          backward interleaves with its forward inside one scan.
        - ``accum_reduce='microbatch'`` (overlap path; loss_fn +
          grad_accum only): reduce each microbatch's grads INSIDE the
          accumulation scan so the reduction overlaps the next
          microbatch's compute, instead of one post-hoc sync after the
          scan.  Exact for the mean/sum reductions (linear); trades
          ``iters``× the reduction traffic for the overlap and composes
          with ``overlap.configure()``'s async-collective presets.
        - ``numerics=True``: fuse ``obs.numerics.numerics_stats`` over the
          reduced grads / pre-update params / optimizer updates INTO the
          compiled step — the step returns ``(params, opt_state, loss,
          stats)`` where ``stats`` is a dict of f32 scalars (global +
          per-layer-group norms, update ratio, non-finite counts,
          low-precision range fractions) to hand to
          ``Telemetry.end_step(..., numerics=stats)``.  One program, no
          extra dispatch; donation is unaffected (the stats read the
          values the step already holds).
        """
        if (loss_fn is None) == (value_and_grad_fn is None):
            raise ValueError("pass exactly one of loss_fn / value_and_grad_fn")
        if optimizer is None:
            raise ValueError("make_train_step requires an optax optimizer")
        if value_and_grad_fn is not None and grad_accum_iters != 1:
            raise ValueError(
                "grad_accum_iters applies to the loss_fn path only; a "
                "value_and_grad_fn (e.g. pipeline_1f1b) owns its own "
                "microbatching"
            )
        if accum_reduce not in ("final", "microbatch"):
            raise ValueError(
                f"accum_reduce must be 'final' or 'microbatch', got {accum_reduce!r}")
        # grad_compress x accum_reduce='microbatch' is SUPPORTED (validated
        # here on purpose — the combination used to ride through
        # unexamined): the quantized ring replaces the per-microbatch
        # pmean inside the accumulation scan, and averaging the
        # per-microbatch quantized means is the same estimator at the same
        # noise bound (quantization error averages like the grads do;
        # parity-tested in tests/test_compression.py).
        mesh = self.mesh
        axis = self.axis
        data_axes = (axis,) if isinstance(axis, str) else tuple(axis)

        def make_reduce_fn(policy):
            @prof.scoped(prof.GRAD_REDUCE)
            def reduce_fn(grads):
                return reduce_gradients(
                    grads, axis, self.reduce_op, self.grad_reduce_overrides,
                    compress=self.grad_compress,
                    compress_min_size=self.compress_min_size,
                    compress_policy=policy,
                )
            return reduce_fn

        in_scan = accum_reduce == "microbatch" and value_and_grad_fn is None

        def make_step(policy):
            reduce_fn = make_reduce_fn(policy)

            def step(params, opt_state, batch):
                # Keep grads local over the data axes (one explicit reduce
                # below).
                p_local = pvary_params(params, data_axes)
                if value_and_grad_fn is not None:
                    loss, grads = value_and_grad_fn(p_local, batch)
                else:
                    loss, grads = local_value_and_grad(
                        loss_fn, p_local, batch, grad_accum_iters,
                        reduce_fn=reduce_fn if in_scan else None,
                    )
                grads, other = normalize_model_axis_grads(
                    loss, grads, mesh, data_axes)
                # grad_compress='int8'/'auto' swaps the chosen leaves' pmean
                # for the quantized ring — vma-legal (see dist/compressed.py),
                # so the SAME step body serves pure-DP and TP/PP-composed
                # meshes.  (normalize after an in-scan reduce is exact: it
                # only scales.)
                if not in_scan:
                    grads = reduce_fn(grads)
                if other:
                    loss = jax.lax.pmean(loss, other)
                dax = _vaxes(loss, data_axes)
                if dax:
                    loss = _reduce_loss(loss, dax, self.reduce_op)
                with jax.named_scope(prof.OPTIMIZER):
                    updates, opt_state = optimizer.update(
                        grads, opt_state, params)
                if numerics:
                    # monitoring rides in the SAME compiled program as
                    # training: norms over the reduced grads, the pre-update
                    # params and the optimizer updates (update_ratio =
                    # |update|/|param|), sharing the clip reduction
                    from ..obs.numerics import numerics_stats

                    nstats = numerics_stats(
                        grads, params=params, updates=updates)
                with jax.named_scope(prof.OPTIMIZER):
                    params = jax.tree.map(jnp.add, params, updates)
                if numerics:
                    return params, opt_state, loss, nstats
                return params, opt_state, loss

            return step

        def policy_for(params):
            """The 'auto' per-leaf compress/exact choices — decided on the
            HOST from static leaf shapes via CommModel.predict_compressed,
            recorded as a structured ``compress_policy`` event (once per
            compiled signature)."""
            if self.grad_compress != "auto":
                return None
            from ..dist.compressed import auto_compress_policy
            from ..obs.events import emit_event

            mean_axes = tuple(
                a for a in data_axes if _axis_op(self.reduce_op, a) == "mean")
            leaves = [
                (_key_str(path), jnp.shape(x), jnp.dtype(x.dtype).itemsize)
                for path, x in jax.tree_util.tree_flatten_with_path(params)[0]
            ]
            policy, records = auto_compress_policy(
                leaves, "all_reduce", mean_axes, mesh,
                model=self.comm_model, min_size=self.compress_min_size)
            emit_event(
                "compress_policy", family="data_parallel", mode="auto",
                op="all_reduce", axes=list(mean_axes),
                n_leaves=len(records),
                n_compressed=sum(1 for r in records if r["compress"]),
                leaves=records)
            return policy

        # The shard_map specs depend on the pytree structure of the arguments,
        # which we only see at first call — build and cache the jitted fn then.
        cache = {}

        def jit_for(params, opt_state, batch):
            key = step_cache_key(params, opt_state, batch)
            if key not in cache:
                def spec_of(x):
                    sh = getattr(x, "sharding", None)
                    spec = getattr(sh, "spec", None)
                    return spec if spec is not None else P()

                in_param_specs = (
                    param_specs if param_specs is not None else jax.tree.map(lambda _: P(), params)
                )
                in_batch_specs = (
                    batch_spec if batch_spec is not None else jax.tree.map(lambda _: P(axis), batch)
                )
                # optimizer state (e.g. adam moments) mirrors the params'
                # sharding when created via opt.init(placed_params); prefer
                # the structural mapping (moment subtrees that mirror the
                # param pytree get the PARAM specs) and fall back to actual
                # placement
                opt_specs = _opt_state_specs(
                    opt_state, params, in_param_specs, spec_of)
                # the numerics stats dict is all psum-reduced scalars —
                # replicated, so a P() prefix spec covers the subtree
                out_specs = (
                    (in_param_specs, opt_specs, P(), P()) if numerics
                    else (in_param_specs, opt_specs, P()))
                sm = shard_map(
                    make_step(policy_for(params)),
                    mesh=mesh,
                    in_specs=(in_param_specs, opt_specs, in_batch_specs),
                    out_specs=out_specs,
                )
                cache[key] = jax.jit(sm, donate_argnums=(0, 1) if donate else ())
                # the one call of a signature that makes its program ready
                prof.note_program("train", cache[key],
                                  (params, opt_state, batch))
            return cache[key]

        def jitted(params, opt_state, batch):
            return jit_for(params, opt_state, batch)(params, opt_state, batch)

        # AOT hook: callers that need the compiled executable's artifacts
        # (Telemetry's ledgers, a caller's cost analysis) lower through the
        # same cache — `hasattr(step, "lower")` is the Telemetry contract.
        jitted.lower = lambda p, s, b: jit_for(p, s, b).lower(p, s, b)
        return jitted
