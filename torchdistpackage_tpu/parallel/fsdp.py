"""FSDP (ZeRO-3 param sharding) + host offload — analogue of the reference's
FSDP2/CPU-offload study (``examples/fsdp2_offload_test.py``, 160 LoC:
per-block ``fully_shard`` wrap, manual ``.to('cpu', non_blocking=True)``
offload/reload, memory reporting).

TPU-native design: FSDP is *just a sharding* under GSPMD.  Params live
sharded over the data axis (the same :func:`zero_partition_spec` rule the
ZeRO optimizer uses, so ZeRO-1/2/3 are one consistent family); ``jit`` with
those in/out shardings makes XLA all-gather each weight right before its
matmul, reduce-scatter its grad right after, and overlap both with compute —
the per-block wrap/unwrap machinery of torch FSDP2 is the compiler's job
here.  Optimizer state inherits the param sharding, so state is ZeRO-3
sharded for free.

Host offload uses memory kinds (``pinned_host``) instead of ``.to('cpu')``:
the array keeps its sharding and donates back to HBM with a device_put.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax.lax import axis_size
from ..dist.topology import DATA_AXIS, tpc
from .zero import _norm_spec, zero_partition_spec

PyTree = Any


# ------------------------------------------------------- explicit gathers
# The GSPMD formulation below leaves WHERE the per-weight all-gather runs
# entirely to the compiler.  The overlap path makes the comm explicit so
# the latency-hiding scheduler (dist/overlap.py presets) has distinct,
# movable -start/-done pairs to hide: each leaf is gathered by an
# explicit ``all_gather`` exactly where the forward consumes it, and —
# because the transpose of all_gather is psum_scatter — AD issues each
# leaf's gradient reduce-scatter INSIDE the backward at the point that
# leaf's grad is produced, instead of one post-hoc full-tree sync.


def gather_params(
    params: PyTree,
    shard_dims: PyTree,
    axis: str,
    compress: Optional[str] = None,
    compress_min_size: int = 65536,
) -> PyTree:
    """All-gather every sharded leaf of a shard_map-local param tree back
    to full size (``shard_dims``: per-leaf gather dim, -1 = replicated —
    the layout :func:`zero_partition_spec` produces).  Traced; call
    inside shard_map over ``axis``.

    ``compress='int8'``: leaves whose GATHERED size clears
    ``compress_min_size`` elements ride
    :func:`...dist.compressed.int8_ring_all_gather` — 1 int8 byte/elem on
    the wire (vs 4 for f32) into a dequantized full-precision compute
    copy, and — because the ring's custom VJP is the int8 ring
    reduce-scatter — the leaf's GRAD reduction inside the backward rides
    the int8 wire too.  The resident shard (and the optimizer state it
    feeds) stays full precision; only the wire and the per-step compute
    copy are quantized."""
    n = axis_size(axis)

    def gather_one(p, d):
        if d < 0:
            return p
        if compress == "int8" and p.size * n >= compress_min_size and n > 1:
            from ..dist.compressed import int8_ring_all_gather

            return int8_ring_all_gather(p, axis, d)
        return jax.lax.all_gather(p, axis, axis=d, tiled=True)

    return jax.tree.map(gather_one, params, shard_dims)


def stacked_fsdp_specs(
    stacked: PyTree,
    axis: str,
    n: int,
    base_specs: Optional[PyTree] = None,
) -> Tuple[PyTree, PyTree]:
    """(specs, shard_dims) for a LAYER-STACKED param tree (leading dim =
    layer index): the FSDP axis is inserted on the first free divisible
    dim **past the stack dim**, so :func:`prefetched_layer_scan` can
    gather one layer at a time.  (Plain :meth:`FSDP.fsdp_specs` would
    happily shard the stack dim itself when the layer count divides the
    axis — correct for GSPMD, useless for per-layer prefetch.)"""
    flat_p, treedef = jax.tree_util.tree_flatten(stacked)
    if base_specs is None:
        flat_s = [None] * len(flat_p)
    else:
        flat_s = treedef.flatten_up_to(base_specs)
    specs, dims = [], []
    for p, s in zip(flat_p, flat_s):
        shape = np.shape(p)
        entries = _norm_spec(s, len(shape))
        tail_spec, d = zero_partition_spec(
            shape[1:], P(*entries[1:]), axis, n)
        tail = _norm_spec(tail_spec, len(shape) - 1)
        full = (entries[0],) + tuple(tail)
        while full and full[-1] is None:
            full = full[:-1]
        specs.append(P(*full))
        dims.append(d + 1 if d >= 0 else -1)
    return (
        jax.tree_util.tree_unflatten(treedef, specs),
        jax.tree_util.tree_unflatten(treedef, dims),
    )


def prefetched_layer_scan(
    stacked: PyTree,
    x: Any,
    apply_fn: Callable[[PyTree, Any, Any], Any],
    axis: str,
    shard_dims: PyTree,
    prefetch: bool = True,
    compress: Optional[str] = None,
    compress_min_size: int = 65536,
):
    """Scan a layer stack whose params are FSDP-sharded, gathering ONE
    layer's weights at a time — with the NEXT layer's all-gather issued
    before the current layer's compute, so the transfer hides behind the
    matmuls (a software double-buffer in the scan carry).

    ``stacked``: [L, ...]-stacked param tree, leaves sharded over ``axis``
    on ``shard_dims`` (per-STACKED-leaf dims from
    :func:`stacked_fsdp_specs`; never 0 — the stack dim must stay whole).
    ``apply_fn(layer_params_full, carry, i) -> carry`` is one layer's
    forward.  Backward: AD transposes each per-layer gather into a
    per-layer reduce-scatter inside the backward scan — grad comm is
    bucketed by layer, not deferred to a post-hoc sync.

    ``prefetch=False`` gathers in-loop with no lookahead (the A/B
    baseline — same numerics, one less carry buffer, no hiding).

    ``compress='int8'``: the per-layer prefetched gathers ride the int8
    ring (see :func:`gather_params`) — and so do the per-layer grad
    reduce-scatters AD emits in the backward scan (the ring's custom
    VJP).
    """
    for d in jax.tree.leaves(shard_dims):
        if d == 0:
            raise ValueError(
                "prefetched_layer_scan: a leaf is sharded on the stack "
                "dim (shard_dim 0); derive specs with stacked_fsdp_specs")
    leaves = jax.tree.leaves(stacked)
    L = leaves[0].shape[0]

    def gather_layer(i):
        lp = jax.tree.map(
            lambda v: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False),
            stacked,
        )
        # the per-STACKED dim shifts down by one after the layer index
        dims = jax.tree.map(lambda d: d - 1 if d >= 1 else -1, shard_dims)
        return gather_params(lp, dims, axis, compress=compress,
                             compress_min_size=compress_min_size)

    from .data_parallel import _mark_varying, _vma

    want = _vma(x)
    for leaf in leaves:
        want = want | _vma(leaf)
    x = _mark_varying(x, tuple(want))

    if not prefetch:
        def body(carry, i):
            return apply_fn(gather_layer(i), carry, i), None

        out, _ = jax.lax.scan(body, x, jnp.arange(L))
        return out

    def body(carry, i):
        h, cur = carry
        # issue the NEXT layer's gathers before this layer's compute: the
        # two are data-independent, so the scheduler overlaps them.  The
        # last iteration re-gathers layer L-1 into a dead buffer (one
        # wasted gather per scan — the price of a fixed carry structure).
        nxt = gather_layer(jnp.minimum(i + 1, L - 1))
        h = apply_fn(cur, h, i)
        return (h, nxt), None

    (out, _), _ = jax.lax.scan(body, (x, gather_layer(0)), jnp.arange(L))
    return out


class FSDP:
    """Fully-sharded data parallelism over ``shard_axis``.

    Usage::

        fsdp = FSDP()                                  # shard over 'data'
        params = fsdp.shard_params(params, tp_specs)   # weights ZeRO-3 sharded
        state = optimizer.init(params)                 # state inherits shards
        step = fsdp.make_train_step(loss_fn, optimizer,
                                    batch_spec=P('data'))
        params, state, loss = step(params, state, batch)

    Composes with TP: pass the TP specs as ``param_specs`` and the fsdp axis
    is inserted on the first remaining free dim of each leaf.
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        shard_axis: str = DATA_AXIS,
        param_specs: Optional[PyTree] = None,
    ) -> None:
        self.mesh = mesh if mesh is not None else tpc.get_view()
        self.shard_axis = shard_axis
        self.param_specs = param_specs

    # ----------------------------------------------------------------- specs

    def fsdp_specs(self, params: PyTree, param_specs: Optional[PyTree] = None) -> PyTree:
        """Per-leaf FSDP PartitionSpec: base (TP) spec + shard axis on the
        first free divisible dim; indivisible leaves stay replicated."""
        n = self.mesh.shape[self.shard_axis]
        base = param_specs if param_specs is not None else self.param_specs
        if base is None:
            base = jax.tree.map(lambda _: P(), params)
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_s = treedef.flatten_up_to(base)
        out = [
            zero_partition_spec(np.shape(p), s, self.shard_axis, n)[0]
            for p, s in zip(flat_p, flat_s)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def fsdp_shard_dims(self, params: PyTree, param_specs: Optional[PyTree] = None) -> PyTree:
        """Per-leaf dim the FSDP axis was inserted on by :meth:`fsdp_specs`
        (-1 = replicated) — what the explicit-gather overlap step needs to
        all-gather each leaf back."""
        n = self.mesh.shape[self.shard_axis]
        base = param_specs if param_specs is not None else self.param_specs
        if base is None:
            base = jax.tree.map(lambda _: P(), params)
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_s = treedef.flatten_up_to(base)
        out = [
            zero_partition_spec(np.shape(p), s, self.shard_axis, n)[1]
            for p, s in zip(flat_p, flat_s)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def shard_params(self, params: PyTree, param_specs: Optional[PyTree] = None) -> PyTree:
        """Place params with FSDP shardings (the ``fully_shard`` analogue,
        fsdp2_offload_test.py:32-75 — one call, no per-block wrapping)."""
        specs = self.fsdp_specs(params, param_specs)
        # remember the BASE (TP) specs: make_train_step re-derives the full
        # specs from (base, shapes), so the TP composition survives spec
        # re-derivation for any tree
        self._base_specs = param_specs if param_specs is not None else self.param_specs
        return jax.tree.map(
            lambda p, s: jax.device_put(p, NamedSharding(self.mesh, s)), params, specs
        )

    # ------------------------------------------------------------ train step

    def make_train_step(
        self,
        loss_fn: Callable[[PyTree, PyTree], jax.Array],
        optimizer,
        batch_spec: Any = P(DATA_AXIS),
        param_specs: Optional[PyTree] = None,
    ) -> Callable:
        """Jitted ``(params, opt_state, batch) -> (params, opt_state, loss)``.

        Params/opt-state stay FSDP-sharded across steps (pinned via
        out_shardings); the batch is data-sharded; XLA inserts the per-layer
        all-gathers and grad reduce-scatters and overlaps them with compute.
        """
        mesh = self.mesh
        # snapshot the base-specs context NOW so a later shard_params call
        # for a different tree cannot clobber what this step derives specs
        # from.  cap_base None (no shard_params yet) is adopted lazily at
        # first call — the step-then-shard order keeps working.
        cap_base = (
            param_specs if param_specs is not None
            else getattr(self, "_base_specs", None)
        )
        cap_was_empty = param_specs is None and cap_base is None

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree.map(
                lambda p, u: (p + u.astype(p.dtype)), params, updates
            )
            return params, opt_state, loss

        compiled: dict = {}

        def jitted(params, opt_state, batch):
            from .data_parallel import step_cache_key

            # keyed on structure + actual placement: a second call with a
            # different params pytree or batch sharding must not silently
            # reuse shardings derived from the first call's specs
            key = step_cache_key(params, opt_state, batch)
            if key not in compiled:
                # derive specs from the base (TP) specs — a cheap
                # deterministic function of (base, shapes) that reproduces
                # shard_params' result exactly.  A step created BEFORE any
                # shard_params adopts the instance's base lazily.
                if param_specs is not None:
                    # explicitly provided: errors must surface, not silently
                    # degrade to an FSDP-only layout
                    specs = self.fsdp_specs(params, param_specs)
                else:
                    base = cap_base
                    if cap_was_empty:
                        base = getattr(self, "_base_specs", None)
                    try:
                        specs = self.fsdp_specs(params, base)
                    except Exception:
                        # inherited base belongs to a different tree shape —
                        # derive from the instance default only
                        specs = self.fsdp_specs(params, None)
                p_sh = jax.tree.map(
                    lambda s: NamedSharding(mesh, s), specs,
                    is_leaf=lambda x: isinstance(x, P),
                )
                b_sh = jax.tree.map(
                    lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
                    batch_spec,
                    is_leaf=lambda x: isinstance(x, P),
                )
                # opt state mirrors whatever sharding its leaves already
                # carry; pin params so XLA cannot keep them gathered.
                compiled[key] = jax.jit(
                    step,
                    in_shardings=(p_sh, None, b_sh),
                    out_shardings=(p_sh, None, None),
                    donate_argnums=(0, 1),
                )
            return compiled[key](params, opt_state, batch)

        return jitted

    def make_overlap_train_step(
        self,
        loss_fn: Callable[[PyTree, PyTree], jax.Array],
        optimizer,
        batch_spec: Any = P(DATA_AXIS),
        param_specs: Optional[PyTree] = None,
        donate: bool = True,
        gather: str = "leaf",
        grad_compress: Optional[str] = None,
        compress_min_size: int = 65536,
    ) -> Callable:
        """Explicit-comm FSDP step (the overlap path, drop-in replacement
        for :meth:`make_train_step` on the same placements).

        Differences from the GSPMD step:

        - the step is a ``shard_map`` over the whole mesh: params enter as
          LOCAL shards and each leaf is regathered by an explicit
          ``all_gather`` where the forward consumes it — distinct
          ``-start``/``-done`` pairs the latency-hiding scheduler
          (``dist/overlap.py``) moves behind compute;
        - AD transposes each gather into a per-leaf **reduce-scatter
          issued inside the backward** at the point that leaf's grad is
          produced — no post-hoc full-tree sync, and the full-size grad
          never persists;
        - the optimizer update runs on the local shard (elementwise optax
          transforms are shard-exact), so params/opt state stay sharded
          end to end — true ZeRO-3.

        Conventions: ``loss_fn`` sees the LOCAL batch shard (the
        :class:`~.data_parallel.DataParallel` convention — it already
        receives the FULL param tree, regathered).  ``gather='none'``
        hands loss_fn the raw SHARDED leaves instead, for callers that
        gather at finer granularity themselves (e.g.
        :func:`prefetched_layer_scan` inside a scanned stack — pair it
        with :func:`stacked_fsdp_specs` placements).  Composes with a
        single data axis; for TP composition use the shard_map-aware
        :class:`~.zero.ZeroOptimizer` family instead.

        ``grad_compress='int8'`` (the bytes-on-the-wire lever): leaves
        whose gathered size clears ``compress_min_size`` ride the int8
        ring all-gather into the forward — and, via the ring's custom
        VJP, the int8 per-leaf reduce-scatter inside the backward
        (``dist/compressed.py``).  Resident shards and optimizer state
        stay full precision; the compute copy is quantized (~0.4%
        per-group noise — parity-bounded in tests/test_compression.py).
        """
        if gather not in ("leaf", "none"):
            raise ValueError(f"gather must be 'leaf' or 'none', got {gather!r}")
        if grad_compress not in (None, "int8"):
            raise ValueError(
                f"unknown grad_compress {grad_compress!r}; the overlap "
                f"step supports None or 'int8'")
        mesh = self.mesh
        ax = self.shard_axis
        from jax import shard_map
        from .data_parallel import _vaxes, pvary_params, step_cache_key

        compiled: dict = {}

        def jitted(params, opt_state, batch):
            key = step_cache_key(params, opt_state, batch)
            if key not in compiled:
                specs = self.fsdp_specs(params, param_specs)
                dims = self.fsdp_shard_dims(params, param_specs)
                from .data_parallel import _opt_state_specs

                opt_specs = _opt_state_specs(
                    opt_state, params, specs,
                    lambda x: getattr(getattr(x, "sharding", None), "spec", None) or P(),
                )
                b_spec = (
                    batch_spec if not isinstance(batch_spec, P)
                    else jax.tree.map(lambda _: batch_spec, batch)
                )

                def core(p_shard, opt_state, batch):
                    def gathered_loss(ps, b):
                        if gather == "leaf":
                            ps = gather_params(
                                ps, dims, ax, compress=grad_compress,
                                compress_min_size=compress_min_size)
                        return loss_fn(ps, b)

                    # the grad is taken of a per-device copy; the update
                    # below goes onto the shards as they came in, so a
                    # leaf that is replicated stays typed replicated
                    loss, grads = jax.value_and_grad(gathered_loss)(
                        pvary_params(p_shard, (ax,)), batch)
                    n = axis_size(ax)
                    # gathered leaves: the transpose already reduce-
                    # scattered (SUM over the axis) -> /n for the mean;
                    # replicated leaves carry raw local grads -> pmean
                    grads = jax.tree.map(
                        lambda g, d: (
                            g / n if d >= 0 else (
                                jax.lax.pmean(g, _vaxes(g, (ax,)))
                                if _vaxes(g, (ax,)) else g
                            )
                        ),
                        grads, dims,
                    )
                    updates, opt_state = optimizer.update(
                        grads, opt_state, p_shard)
                    p_shard = jax.tree.map(
                        lambda p, u: p + u.astype(p.dtype), p_shard, updates)
                    lax_ = _vaxes(loss, (ax,))
                    if lax_:
                        loss = jax.lax.pmean(loss, lax_)
                    return p_shard, opt_state, loss

                sm = shard_map(
                    core,
                    mesh=mesh,
                    in_specs=(specs, opt_specs, b_spec),
                    out_specs=(specs, opt_specs, P()),
                )
                compiled[key] = jax.jit(
                    sm, donate_argnums=(0, 1) if donate else ())
            return compiled[key](params, opt_state, batch)

        return jitted


# ------------------------------------------------------------- host offload


def offload_to_host(tree: PyTree, donate: bool = True) -> PyTree:
    """Move arrays to host memory (``pinned_host``), keeping their sharding —
    analogue of ``offload_model``'s ``.to('cpu', non_blocking=True)`` loop
    (fsdp2_offload_test.py:77-96).  Frees the HBM copy when ``donate``."""

    def put(x):
        if not isinstance(x, jax.Array):
            return x
        sh = x.sharding.with_memory_kind("pinned_host")
        return jax.device_put(x, sh, donate=donate)

    return jax.tree.map(put, tree)


def reload_to_device(tree: PyTree, donate: bool = True) -> PyTree:
    """Bring offloaded arrays back to device HBM — analogue of
    ``reload_model`` (fsdp2_offload_test.py:98-114)."""

    def put(x):
        if not isinstance(x, jax.Array):
            return x
        sh = x.sharding.with_memory_kind("device")
        return jax.device_put(x, sh, donate=donate)

    return jax.tree.map(put, tree)


def memory_report(label: str = "") -> dict:
    """Per-device HBM usage — analogue of the reference's memory reporting
    (fsdp2_offload_test.py:117-120).  Returns {} when the backend exposes no
    memory stats (CPU sim).  Reads through ``obs.mem_ledger.live_memory``,
    the repo's one ``memory_stats()`` call site (lint-enforced)."""
    from ..obs.mem_ledger import live_memory

    stats = {
        row["device"]: {
            "bytes_in_use": row["bytes_in_use"],
            "peak_bytes_in_use": row["peak_bytes_in_use"],
        }
        for row in live_memory()["per_device"]
    }
    if label and stats:
        from ..utils.logging import master_print

        used = max(v["bytes_in_use"] for v in stats.values())
        peak = max(v["peak_bytes_in_use"] for v in stats.values())
        master_print(
            f"[mem {label}] in_use={used/1e9:.3f} GB peak={peak/1e9:.3f} GB")
    return stats
