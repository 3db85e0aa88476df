"""ZeRO-1/2 optimizer-state sharding — analogue of ``Bf16ZeroOptimizer``
(``torchdistpackage/ddp/zero_optim.py``, 318 LoC), including the hybrid
intra-node variant (``dist/node_group.py`` + Intro.md:69-77).

The reference greedily partitions params across the dp group
(zero_optim.py:19-41), keeps fp32 masters of the own shard only
(zero_optim.py:159-170), ``dist.reduce``-es each grad to its owner
(zero_optim.py:203) or flat-buckets + all-reduces on a side stream, and
"all-gathers" updated params as per-param broadcasts from the owner
(zero_optim.py:280-287 — its known perf weak point).

TPU-native design: **per-leaf sharding instead of greedy per-rank
partitioning.**  Every param leaf gets a *zero spec* — its TP PartitionSpec
with the shard axis inserted on the first free, divisible dimension.  The
compiled step then:

- ``psum_scatter``-s grads over the shard axis straight to their owner shard
  (one fused reduce+scatter vs the reference's per-param reduce-to-owner),
- updates the fp32 master shard and inner-optimizer state shard locally
  inside ``shard_map``,
- casts masters to the training dtype *then* reshards them to the param
  sharding via ``with_sharding_constraint`` — XLA emits the param all-gather
  (in bf16, half the bytes) and schedules/overlaps it, replacing the
  reference's per-param owner broadcasts.

ZeRO-2 grad sharding falls out: the post-reduce grad only exists as the local
shard, and the optimizer update touches 1/N of the state.  Hybrid ZeRO = pass
``shard_axis='data_intra'`` on a hybrid mesh view
(``tpc.build_hybrid_mesh``): state shards over the ICI-local sub-axis while
grads still average over the whole data group, exactly the reference's trick
that keeps the param all-gather off the slow cross-node links.

Composes with TP transparently: zero specs start from the TP specs, and all
shard-level math runs on local arrays.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple, Union

import jax

from jax.lax import axis_size
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..dist.topology import DATA_AXIS, tpc
from .data_parallel import (
    _vaxes,
    _vma,
    local_value_and_grad,
    normalize_model_axis_grads,
    pvary_params,
)

PyTree = Any
AxisName = Union[str, Tuple[str, ...]]


def _norm_spec(spec: Optional[P], ndim: int) -> Tuple:
    entries = tuple(spec) if spec is not None else ()
    return entries + (None,) * (ndim - len(entries))


def zero_partition_spec(
    shape: Tuple[int, ...],
    spec: Optional[P],
    axis: str,
    axis_size: int,
) -> Tuple[P, Optional[int]]:
    """Insert ``axis`` into ``spec`` on the first free dim divisible by
    ``axis_size``.  Returns (new_spec, shard_dim) — shard_dim is ``-1`` when
    the leaf stays replicated (no divisible free dim; e.g. tiny LN params —
    the same leaves the reference's greedy numel partition would place whole,
    zero_optim.py:19-41)."""
    entries = list(_norm_spec(spec, len(shape)))
    for d, (size, used) in enumerate(zip(shape, entries)):
        if used is None and size % axis_size == 0 and size > 0:
            entries[d] = axis
            while entries and entries[-1] is None:
                entries.pop()
            return P(*entries), d
    return spec if spec is not None else P(), -1


class ZeroOptimizer:
    """Wrap an optax optimizer with ZeRO-style sharded state.

    Usage::

        zero = ZeroOptimizer(optax.adam(3e-4))          # shard over 'data'
        params = zero.place_params(params)               # bf16, TP/replicated
        state = zero.init(params)                        # fp32 masters, sharded
        step = zero.make_train_step(loss_fn)
        params, state, loss = step(params, state, batch)

    Hybrid: build ``tpc.build_hybrid_mesh(intra)`` and pass
    ``mesh=view, shard_axis='data_intra',
    grad_reduce_axes=('data_inter', 'data_intra')``.
    """

    def __init__(
        self,
        inner,
        mesh: Optional[Mesh] = None,
        shard_axis: str = DATA_AXIS,
        grad_reduce_axes: Optional[Tuple[str, ...]] = None,
        param_specs: Optional[PyTree] = None,
        param_dtype: Any = None,
        master_dtype: Any = jnp.float32,
        grad_reduce_overrides: Optional[dict] = None,
        grad_compress: Optional[str] = None,
        compress_min_size: int = 65536,
        comm_model: Optional[Any] = None,
        gather_compress: Union[str, None] = "follow",
    ) -> None:
        self.inner = inner
        self.mesh = mesh if mesh is not None else tpc.get_view()
        self.shard_axis = shard_axis
        if grad_reduce_axes is None:
            grad_reduce_axes = (shard_axis,)
        if shard_axis not in grad_reduce_axes:
            raise ValueError(
                f"shard_axis {shard_axis!r} must be one of grad_reduce_axes {grad_reduce_axes}"
            )
        self.grad_reduce_axes = tuple(grad_reduce_axes)
        # ``{name_substring: axes}`` like DataParallel's (reduce_gradients
        # docstring): matching leaves psum over THESE axes only, normalized
        # by the FULL data-group size (the MoE-DP expert semantics — the
        # all_to_all transpose already summed over EP).  ZeRO additionally
        # needs each override to still contain ``shard_axis`` so the grad
        # can psum_scatter to its owner master shard.
        self.grad_reduce_overrides = dict(grad_reduce_overrides or {})
        for tok, ax in self.grad_reduce_overrides.items():
            if shard_axis not in tuple(ax):
                raise ValueError(
                    f"grad_reduce_overrides[{tok!r}]={tuple(ax)} must contain "
                    f"shard_axis {shard_axis!r}: ZeRO owners are shards of "
                    f"that axis (for MoE, shard over 'moe_dp' — the axis "
                    f"expert grads reduce on)"
                )
            extra = set(ax) - set(self.grad_reduce_axes)
            if extra:
                raise ValueError(
                    f"grad_reduce_overrides[{tok!r}] axes {sorted(extra)} not "
                    f"in grad_reduce_axes {self.grad_reduce_axes}"
                )
        self.param_specs = param_specs
        self.param_dtype = param_dtype
        self.master_dtype = master_dtype
        # 'int8' swaps the f32 psum_scatter for the int8 ring reduce-scatter
        # (~4x fewer wire bytes on the shard axis; for hybrid layouts the
        # cross-node psum over the remaining grad_reduce_axes rides the int8
        # ring too) on leaves >= compress_min_size elements.  Small and
        # override (MoE expert) leaves keep the exact path.
        # 'int8_ef' additionally carries a per-leaf error-feedback residual
        # in the optimizer state (state['ef']): each step compresses
        # grad + residual and persists the quantization error, so the lossy
        # reduction's bias cancels over steps (dist.compressed.ef_compress).
        # 'auto' decides per leaf from CommModel.predict_compressed and
        # records a compress_policy event at step build.
        if grad_compress not in (None, "int8", "int8_ef", "auto"):
            raise ValueError(
                f"unknown grad_compress {grad_compress!r}; ZeroOptimizer "
                f"supports None, 'int8', 'int8_ef' or 'auto'")
        self.grad_compress = grad_compress
        self.compress_min_size = compress_min_size
        self.comm_model = comm_model
        # The updated masters travel BACK as a param all-gather every step
        # (the regroup below) — as many bytes as the grad reduction itself,
        # so compression that stops at grads caps out around 1.6x on the
        # axis.  'follow' (default) re-gathers the COMPRESSED leaves through
        # the invariance-typed int8 masked-psum gather
        # (dist.compressed.int8_psum_all_gather) whenever grad_compress is
        # active: the wire carries quantized params, masters stay full
        # precision (noise does not accumulate — QAT-style), and the parity
        # harness bounds the drift.  Pass None to keep the exact bf16/f32
        # re-gather.
        if gather_compress not in (None, "int8", "follow"):
            raise ValueError(
                f"unknown gather_compress {gather_compress!r}")
        self.gather_compress = gather_compress

    # ----------------------------------------------------------------- specs

    def _specs_for(self, params: PyTree) -> Tuple[PyTree, PyTree, PyTree]:
        """(param_specs, zero_specs, shard_dims) trees for a params tree."""
        n = self.mesh.shape[self.shard_axis]
        p_specs = (
            self.param_specs
            if self.param_specs is not None
            else jax.tree.map(lambda _: P(), params)
        )
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_s = treedef.flatten_up_to(p_specs)
        pairs = [zero_partition_spec(x.shape, s, self.shard_axis, n) for x, s in zip(flat_p, flat_s)]
        zero_specs = jax.tree_util.tree_unflatten(treedef, [sp for sp, _ in pairs])
        shard_dims = jax.tree_util.tree_unflatten(treedef, [d for _, d in pairs])
        return p_specs, zero_specs, shard_dims

    def _local_shape(self, x, spec) -> jax.ShapeDtypeStruct:
        entries = _norm_spec(spec, x.ndim)
        shp = list(x.shape)
        for d, e in enumerate(entries):
            if e is None:
                continue
            axes = (e,) if isinstance(e, str) else tuple(e)
            for a in axes:
                shp[d] //= self.mesh.shape[a]
        return jax.ShapeDtypeStruct(tuple(shp), self.master_dtype)

    def _state_specs_from(self, params: PyTree, zero_specs: PyTree) -> PyTree:
        """Specs for the inner optimizer state, resolved structurally via
        ``optax.tree_map_params``: param-shaped state leaves (adam's mu/nu...)
        inherit the corresponding master's zero spec; everything else (count
        scalars etc.) replicates."""
        import optax

        local_master = jax.tree.map(self._local_shape, params, zero_specs)
        state_shape = jax.eval_shape(self.inner.init, local_master)
        return optax.tree_map_params(
            self.inner,
            lambda _leaf, spec: spec,
            state_shape,
            zero_specs,
            transform_non_params=lambda _: P(),
        )

    # ------------------------------------------------------------- placement

    def place_params(self, params: PyTree) -> PyTree:
        """Cast to the training dtype (bf16 flow of zero_optim.py:7-13) and
        place with the param (TP) sharding."""
        p_specs, _, _ = self._specs_for(params)
        dt = self.param_dtype

        def put(x, s):
            x = x.astype(dt) if dt is not None else x
            return jax.device_put(x, NamedSharding(self.mesh, s))

        return jax.tree.map(put, params, p_specs)

    def _ef_specs(self, p_specs: PyTree) -> PyTree:
        """Specs for the error-feedback residuals: per-DEVICE-of-the-data-
        group values of the leaf's LOCAL (TP-sharded) shape — stored with a
        leading dim of the data-group size sharded over
        ``grad_reduce_axes`` (local view: ``[1, *local_leaf]``)."""
        axes = tuple(self.grad_reduce_axes)
        return jax.tree.map(
            lambda s: P(axes, *tuple(s)), p_specs,
            is_leaf=lambda x: isinstance(x, P))

    def init(self, params: PyTree) -> PyTree:
        """Create sharded fp32 masters + inner optimizer state
        (zero_optim.py:159-174 analogue, sharded by construction).  With
        ``grad_compress='int8_ef'`` the state additionally carries ``ef``
        — one zero-initialized f32 residual per leaf (full leaf shape per
        data-group member; the input-side error-feedback memory
        :meth:`reduce_grads_to_shard` updates every step)."""
        p_specs, zero_specs, _ = self._specs_for(params)
        mdt = self.master_dtype

        master = jax.jit(
            lambda p: jax.tree.map(lambda x: x.astype(mdt), p),
            out_shardings=jax.tree.map(lambda s: NamedSharding(self.mesh, s), zero_specs),
        )(params)

        # build the inner state on *local* shard shapes inside shard_map so
        # leaf shapes match what update() will see
        inner_state = jax.jit(
            shard_map(
                self.inner.init,
                mesh=self.mesh,
                in_specs=(zero_specs,),
                out_specs=self._state_specs_from(params, zero_specs),
            )
        )(master)
        state = {"master": master, "inner": inner_state}
        if self.grad_compress == "int8_ef":
            ndev = 1
            for a in self.grad_reduce_axes:
                ndev *= int(self.mesh.shape[a])
            ef = jax.tree.map(
                lambda x, s: jax.device_put(
                    jnp.zeros((ndev,) + tuple(jnp.shape(x)), jnp.float32),
                    NamedSharding(self.mesh, P(tuple(self.grad_reduce_axes),
                                               *tuple(s)))),
                params, p_specs,
            )
            state["ef"] = ef
        return state

    # ------------------------------------------------------------ traced core

    def _compress_decisions(self, params: PyTree, shard_dims: PyTree):
        """Host-side per-leaf compress/exact choices (shapes are static):
        ``(policy {name: bool}, auto records or None)``.  Override (MoE
        expert) and replicated (no divisible dim) leaves never compress;
        'int8'/'int8_ef' apply the size threshold; 'auto' scores the
        shard-axis reduce-scatter through ``CommModel.predict_compressed``
        (``dist.compressed.auto_compress_policy``)."""
        from .data_parallel import _key_str

        if self.grad_compress is None:
            return {}, None
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        d_flat = jax.tree_util.tree_leaves(shard_dims)
        itemsize = jnp.dtype(self.master_dtype).itemsize
        policy: dict = {}
        eligible = []
        for (path, x), d in zip(flat, d_flat):
            name = _key_str(path)
            matched = any(tok in name for tok in self.grad_reduce_overrides)
            if matched or d < 0:
                policy[name] = False
                continue
            eligible.append((name, tuple(jnp.shape(x)), itemsize))
        if self.grad_compress == "auto":
            from ..dist.compressed import auto_compress_policy

            pol, records = auto_compress_policy(
                eligible, "reduce_scatter", (self.shard_axis,), self.mesh,
                model=self.comm_model, min_size=self.compress_min_size)
            policy.update(pol)
            return policy, records
        for name, shape, _ in eligible:
            size = 1
            for s in shape:
                size *= int(s)
            policy[name] = size >= self.compress_min_size
        return policy, None

    def reduce_grads_to_shard(
        self,
        grads_local: PyTree,
        shard_dims: PyTree,
        policy: Optional[dict] = None,
        ef: Optional[PyTree] = None,
    ):
        """Traced: mean-reduce grads over ``grad_reduce_axes`` delivering only
        the owner shard (fused psum_scatter; the reference's reduce-to-owner,
        zero_optim.py:203).

        Override leaves (``grad_reduce_overrides``) psum over their override
        axes only, still normalized by the FULL data-group size — the MoE-DP
        expert semantics (see :func:`..data_parallel.reduce_gradients`).

        ``grad_compress``: compressed leaves (``policy`` — per-leaf choices
        from :meth:`_compress_decisions`; derived from the size threshold
        when None) replace the f32 ``psum_scatter`` with
        :func:`...dist.compressed.int8_ring_reduce_scatter` (1 int8
        byte/elem on the wire vs 4 — the reduction only ever moves grads
        TOWARD their owner, so no gather leg exists to pay for), and any
        remaining cross-axes (hybrid's ``data_inter`` — the DCN leg) ride
        :func:`...dist.compressed.int8_ring_pmean`.

        ``ef`` (the 'int8_ef' path): a per-leaf residual tree — each
        compressed leaf reduces ``Q(grad + residual)`` and the new
        residual (the quantization error, ``dist.compressed.ef_compress``)
        is returned: ``(grads_shard, new_ef)`` instead of the bare tree.
        """
        from .data_parallel import _key_str

        if policy is None:
            policy, _ = self._compress_decisions(grads_local, shard_dims)

        n = axis_size(self.shard_axis)
        total = n
        for a in self.grad_reduce_axes:
            if a != self.shard_axis:
                total *= axis_size(a)

        flat = jax.tree_util.tree_flatten_with_path(grads_local)
        paths_leaves, treedef = flat
        d_flat = jax.tree_util.tree_leaves(shard_dims)
        e_flat = (
            jax.tree_util.tree_leaves(ef) if ef is not None
            else [None] * len(d_flat)
        )

        out_leaves, ef_leaves = [], []
        for (path, g), d, e in zip(paths_leaves, d_flat, e_flat):
            g = g.astype(self.master_dtype)
            axes = self.grad_reduce_axes
            matched = False
            name = _key_str(path)
            for tok, ax in self.grad_reduce_overrides.items():
                if tok in name:
                    axes = tuple(ax)
                    matched = True
                    break
            other = tuple(a for a in axes if a != self.shard_axis)
            compress = bool(policy.get(name, False))
            if d < 0:  # replicated leaf
                vaxes = _vaxes(g, axes)
                if matched:
                    # override semantics: full-group mean (EP overcount)
                    g = (jax.lax.psum(g, vaxes) if vaxes else g) / total
                else:
                    g = jax.lax.pmean(g, vaxes) if vaxes else g
                out_leaves.append(g)
                ef_leaves.append(e)
                continue
            if compress:
                from ..dist.compressed import (
                    ef_compress,
                    int8_ring_pmean,
                    int8_ring_reduce_scatter,
                )

                if e is not None:
                    # input-side error feedback: compress grad + carried
                    # residual, persist this step's quantization error
                    g, e = ef_compress(g + e)
                g = int8_ring_reduce_scatter(g, self.shard_axis, d)
            else:
                g = jax.lax.psum_scatter(
                    g, self.shard_axis, scatter_dimension=d, tiled=True)
            o = _vaxes(g, other)
            if o:
                if compress:
                    for a in o:
                        # the ring pmean's mean * size == the psum, with the
                        # int8 wire (the hybrid DCN leg)
                        g = int8_ring_pmean(g, a) * axis_size(a)
                else:
                    g = jax.lax.psum(g, o)
            out_leaves.append(g / total)
            ef_leaves.append(e)

        out = jax.tree_util.tree_unflatten(treedef, out_leaves)
        if ef is None:
            return out
        return out, jax.tree_util.tree_unflatten(treedef, ef_leaves)

    def apply_gradients(
        self,
        grads_shard: PyTree,
        state_local: PyTree,
    ) -> Tuple[PyTree, PyTree]:
        """Traced: inner optimizer step on the local master shard.  Returns
        (new_master_local, new_state_local)."""
        master = state_local["master"]
        updates, inner_state = self.inner.update(grads_shard, state_local["inner"], master)
        master = jax.tree.map(jnp.add, master, updates)
        return master, {"master": master, "inner": inner_state}

    # ------------------------------------------------------------ train step

    def make_train_step(
        self,
        loss_fn: Optional[Callable[[PyTree, PyTree], jnp.ndarray]] = None,
        grad_accum_iters: int = 1,
        batch_spec: Optional[PyTree] = None,
        donate: bool = True,
        value_and_grad_fn: Optional[Callable] = None,
        accum_reduce: str = "final",
    ):
        """Jitted SPMD train step with the ZeRO update.  ``loss_fn`` sees the
        local batch shard, as in :class:`DataParallel`.

        ``value_and_grad_fn(params, batch) -> (loss, grads)`` replaces
        ``loss_fn`` for schedules whose backward cannot be expressed as outer
        AD — the 1F1B pipeline (``pipeline_parallel.pipeline_1f1b`` /
        ``gpt_pipeline_1f1b``) interleaves its backward with its forward
        inside one scan.  This is what makes the north-star composition
        (hybrid ZeRO × 1F1B × TP × DP, the reference's zero_optim.py:98-287
        under Readme.md:56's PP+DP recipe) buildable: the pipeline produces
        the local grads, ZeRO scatters them to owner shards and updates the
        sharded fp32 masters exactly as in the loss_fn path.

        ``accum_reduce='microbatch'`` (overlap path; loss_fn + grad_accum
        only): the owner psum_scatter runs per microbatch INSIDE the
        accumulation scan — ZeRO-2's per-bucket reduce-scatter during the
        backward, overlapping the next microbatch's compute — and the
        accumulator holds only the 1/N grad shard instead of the full
        tree (the grad-memory win that lets accumulation scale).  Exact
        (the scatter is linear); trades ``iters``× the scatter traffic
        for overlap + memory, and composes with ``overlap.configure()``'s
        async-collective presets."""
        if (loss_fn is None) == (value_and_grad_fn is None):
            raise ValueError("pass exactly one of loss_fn / value_and_grad_fn")
        if value_and_grad_fn is not None and grad_accum_iters != 1:
            raise ValueError(
                "grad_accum_iters applies to the loss_fn path only; a "
                "value_and_grad_fn (e.g. pipeline_1f1b) owns its own "
                "microbatching"
            )
        if accum_reduce not in ("final", "microbatch"):
            raise ValueError(
                f"accum_reduce must be 'final' or 'microbatch', got {accum_reduce!r}")
        if (
            self.grad_compress == "int8_ef"
            and accum_reduce == "microbatch"
            and grad_accum_iters > 1
        ):
            # the residual is one-per-STEP state; the microbatch path
            # reduces inside the accumulation scan where the reduce_fn is
            # stateless — silently dropping the feedback would defeat the
            # mode, so the combination is rejected by name
            raise ValueError(
                "grad_compress='int8_ef' does not compose with "
                "accum_reduce='microbatch': the error-feedback residual "
                "updates once per step, but 'microbatch' reduces inside "
                "the accumulation scan; use accum_reduce='final' or "
                "grad_compress='int8'")
        mesh = self.mesh
        data_axes = self.grad_reduce_axes
        ef_mode = self.grad_compress == "int8_ef"

        cache = {}

        def jit_for(params, state, batch):
            from .data_parallel import _key_str, step_cache_key

            key = step_cache_key(params, state, batch)
            if key not in cache:
                p_specs, zero_specs, shard_dims = self._specs_for(params)
                policy, records = self._compress_decisions(params, shard_dims)
                if records is not None:
                    # the 'auto' decision trail: one structured event per
                    # compiled signature (the compression RUNREPORT section
                    # reads it — obs.comm_model.compression_report)
                    from ..obs.events import emit_event

                    emit_event(
                        "compress_policy", family="zero", mode="auto",
                        op="reduce_scatter", axes=[self.shard_axis],
                        n_leaves=len(records),
                        n_compressed=sum(
                            1 for r in records if r["compress"]),
                        leaves=records)
                state_specs = {
                    "master": zero_specs,
                    "inner": self._state_specs_from(params, zero_specs),
                }
                if ef_mode:
                    state_specs["ef"] = self._ef_specs(p_specs)
                in_batch_specs = (
                    batch_spec
                    if batch_spec is not None
                    else jax.tree.map(lambda _: P(data_axes), batch)
                )

                in_scan = (
                    accum_reduce == "microbatch"
                    and value_and_grad_fn is None
                    and grad_accum_iters > 1
                )

                def core(params, state, batch):
                    """shard_map body: local grads -> scatter -> shard update.
                    With accum_reduce='microbatch' the scatter runs inside
                    the accumulation scan (per-bucket reduce-scatter during
                    the backward) and only the shard is accumulated; the
                    post-scan model-axis normalization is a pure scaling,
                    so applying it to the scattered grads is exact."""
                    p_local = pvary_params(params, data_axes)
                    if value_and_grad_fn is not None:
                        loss, grads = value_and_grad_fn(p_local, batch)
                    else:
                        loss, grads = local_value_and_grad(
                            loss_fn, p_local, batch, grad_accum_iters,
                            reduce_fn=(
                                (lambda g: self.reduce_grads_to_shard(
                                    g, shard_dims, policy=policy))
                                if in_scan else None
                            ),
                        )
                    grads, other = normalize_model_axis_grads(
                        loss, grads, mesh, data_axes
                    )
                    new_ef = None
                    if in_scan:
                        g_shard = grads
                    elif ef_mode:
                        # residual leaves are [1, *local_leaf] per device
                        # (leading dim = the data-group member)
                        e_loc = jax.tree.map(lambda r: r[0], state["ef"])
                        g_shard, new_ef = self.reduce_grads_to_shard(
                            grads, shard_dims, policy=policy, ef=e_loc)
                    else:
                        g_shard = self.reduce_grads_to_shard(
                            grads, shard_dims, policy=policy)
                    master, new_state = self.apply_gradients(g_shard, state)
                    if ef_mode:
                        new_state["ef"] = jax.tree.map(
                            lambda r: r[None], new_ef)

                    if other:
                        loss = jax.lax.pmean(loss, other)
                    dax = _vaxes(loss, data_axes)
                    if dax:
                        loss = jax.lax.pmean(loss, dax)
                    return master, new_state, loss

                sm = shard_map(
                    core,
                    mesh=mesh,
                    in_specs=(p_specs, state_specs, in_batch_specs),
                    out_specs=(zero_specs, state_specs, P()),
                )

                # --- the param re-gather: which leaves ride the int8 wire
                # back.  The masters' return trip moves as many bytes as
                # the grad reduction, so ``gather_compress`` (default
                # 'follow') re-gathers the COMPRESSED leaves through the
                # invariance-typed int8 masked-psum gather; masters stay
                # full precision (quantization noise does not accumulate).
                gather_mode = (
                    self.gather_compress if self.gather_compress != "follow"
                    else ("int8" if self.grad_compress is not None else None))
                flat_paths = jax.tree_util.tree_flatten_with_path(params)
                (pl, treedef) = flat_paths
                d_flat = jax.tree_util.tree_leaves(shard_dims)
                mask_leaves = [
                    gather_mode == "int8"
                    and policy.get(_key_str(path), False)
                    and d >= 0
                    for (path, _), d in zip(pl, d_flat)
                ]
                gmask = jax.tree_util.tree_unflatten(treedef, mask_leaves)
                dtype_tree = jax.tree.map(lambda x: x.dtype, params)
                regather_sm = None
                if any(mask_leaves):
                    regather_specs = jax.tree_util.tree_unflatten(
                        treedef,
                        [
                            ps if m else zs
                            for m, ps, zs in zip(
                                mask_leaves,
                                treedef.flatten_up_to(p_specs),
                                treedef.flatten_up_to(zero_specs),
                            )
                        ],
                    )

                    def regather_body(m_tree):
                        from ..dist.compressed import int8_psum_all_gather

                        def g1(m, d, msk, dt):
                            m = m.astype(dt)
                            if msk:
                                return int8_psum_all_gather(
                                    m, self.shard_axis, d)
                            return m

                        return jax.tree.map(
                            g1, m_tree, shard_dims, gmask, dtype_tree)

                    regather_sm = shard_map(
                        regather_body,
                        mesh=mesh,
                        in_specs=(zero_specs,),
                        out_specs=regather_specs,
                    )

                def step(params, state, batch):
                    master, new_state, loss = sm(params, state, batch)
                    # cast to training dtype on the shard, then reshard to the
                    # param placement — XLA emits the (bf16) all-gather, the
                    # analogue of the reference's param broadcast
                    # (zero_optim.py:280-287) as one overlappable collective;
                    # compressed leaves instead ride the explicit int8
                    # masked-psum gather built above.
                    gathered = (
                        regather_sm(master) if regather_sm is not None
                        else master)

                    def regroup(m, p, zs, ps, msk):
                        if msk:
                            return m  # already full + param-placed (int8)
                        m = m.astype(p.dtype)
                        m = jax.lax.with_sharding_constraint(m, NamedSharding(mesh, zs))
                        return jax.lax.with_sharding_constraint(m, NamedSharding(mesh, ps))

                    new_params = jax.tree.map(
                        regroup, gathered, params, zero_specs, p_specs, gmask)
                    return new_params, new_state, loss

                cache[key] = jax.jit(step, donate_argnums=(0, 1) if donate else ())
            return cache[key]

        def jitted(params, state, batch):
            return jit_for(params, state, batch)(params, state, batch)

        # AOT hook (the Telemetry/bench contract): lower the SAME cached
        # jit so ledgers/cost analysis see exactly the step being run
        jitted.lower = lambda p, s, b: jit_for(p, s, b).lower(p, s, b)
        return jitted
