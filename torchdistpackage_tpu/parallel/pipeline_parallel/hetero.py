"""Heterogeneous stage activations for the SPMD pipeline — the analogue of
the reference's shape-meta handshake
(``torchdistpackage/parallel/pipeline_parallel/comm.py:26-105``), which lets
adjacent stages exchange tensors of different shapes/dtypes by sending a
(ndim, shape, dtype) preamble before every payload.

Under XLA the exchange is a ``ppermute`` inside one traced program, so the
carried state must have ONE static aval — a runtime shape handshake cannot
exist.  What CAN exist is the same capability expressed statically: the
inter-stage state becomes a flat **bus** sized to the largest edge, every
stage packs/unpacks its true activation to/from the bus, and the per-stage
computation dispatches through ``lax.switch`` on the stage index (every
branch has the bus aval in and out, so the program stays uniform).  The
shape contract the reference checks at runtime (stage s's output must be
what stage s+1 expects) is validated here at TRACE time, which is strictly
earlier.

Costs and constraints, stated honestly:

- wire + ring-buffer bytes are the LARGEST edge's, not each edge's own
  (padding rides the ppermute; the reference sends exact sizes).
- padding is provably inert: ``unpack`` reads only the leading
  ``size`` elements, so pad lanes never influence the forward, and the
  ``pad`` transpose discards their cotangents.
- stage fns must be collective-free (no TP/CP psums inside): the switch
  branches are pipe-divergent, and a collective inside divergent control
  flow is undefined (same rule pipeline_sched.py's scan body documents).
  This matches the reference's capability, whose heterogeneous stages are
  plain per-stage modules.
"""

from __future__ import annotations

from typing import Any, Callable, List, Sequence

import jax

from jax.lax import axis_size
import jax.numpy as jnp

from ...dist.topology import PIPE_AXIS

PyTree = Any


def _aval(x) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))


def _bus_aval(edges: Sequence[jax.ShapeDtypeStruct]) -> jax.ShapeDtypeStruct:
    size = max(int(jnp.prod(jnp.array(e.shape)) if e.shape else 1) for e in edges)
    dtype = jnp.result_type(*[e.dtype for e in edges])
    # an integer edge promoted onto a float bus would silently corrupt
    # values past the float's integer-exact range (int32 id >= 2^24 through
    # an f32 bus) — refuse the mix instead
    for e in edges:
        if jnp.issubdtype(e.dtype, jnp.integer) != jnp.issubdtype(dtype, jnp.integer):
            raise ValueError(
                f"bus dtype {dtype} cannot carry edge dtype {e.dtype} "
                f"exactly: integer and float edges cannot share one bus — "
                f"use a uniform edge dtype (or cast inside the stage fns)"
            )
    return jax.ShapeDtypeStruct((size,), dtype)


def bus_pack(x: jnp.ndarray, bus: jax.ShapeDtypeStruct) -> jnp.ndarray:
    """Flatten ``x`` into the leading elements of a bus-shaped vector."""
    flat = x.reshape(-1).astype(bus.dtype)
    pad = bus.shape[0] - flat.shape[0]
    if pad < 0:
        raise ValueError(f"edge {x.shape} exceeds the bus ({bus.shape[0]})")
    return jnp.pad(flat, (0, pad)) if pad else flat


def bus_unpack(bus_val: jnp.ndarray, edge: jax.ShapeDtypeStruct) -> jnp.ndarray:
    """Recover the true activation of ``edge`` from the bus vector."""
    size = 1
    for s in edge.shape:
        size *= s
    return bus_val[:size].reshape(edge.shape).astype(edge.dtype)


def make_heterogeneous_stage(
    stage_fns: List[Callable],
    edges: Sequence,
    pipe_axis: str = PIPE_AXIS,
):
    """Adapt P HETEROGENEOUS stage functions to ``pipeline_1f1b``'s
    uniform-state contract.

    ``stage_fns[s]``: ``(params, x, m) -> y`` where ``x`` has the aval of
    ``edges[s]`` and ``y`` the aval of ``edges[s+1]`` (``m`` is the
    microbatch index — pass ``stage_takes_mb=True`` to the scheduler).
    ``edges``: P+1 avals (arrays or ShapeDtypeStructs): ``edges[0]`` is
    ``first_fn``'s output, ``edges[s]`` the stage-s input, ``edges[P]``
    the last stage's output (what ``last_fn`` receives).

    Returns ``(wrap_first, stage_fn, wrap_last)``:

    - ``wrap_first(first_fn)``: first_fn's ``edges[0]`` output packed onto
      the bus;
    - ``stage_fn(params, bus, m)``: ``lax.switch`` on the stage index —
      branch s unpacks ``edges[s]``, runs ``stage_fns[s]``, packs
      ``edges[s+1]``; the output aval is verified against ``edges[s+1]``
      at trace time (the handshake, moved to trace time);
    - ``wrap_last(last_fn)``: ``last_fn(params, y, tgt)`` receives the
      unpacked ``edges[P]`` activation.
    """
    edges = [_aval(e) if not isinstance(e, jax.ShapeDtypeStruct) else e
             for e in edges]
    if len(stage_fns) != len(edges) - 1:
        raise ValueError(
            f"{len(stage_fns)} stage fns need {len(stage_fns) + 1} edge "
            f"avals, got {len(edges)}"
        )
    bus = _bus_aval(edges)
    P_ = len(stage_fns)

    def _branch(s):
        def run(params, bus_val, m):
            x = bus_unpack(bus_val, edges[s])
            y = stage_fns[s](params, x, m)
            got = _aval(y)
            want = edges[s + 1]
            if got.shape != want.shape or got.dtype != want.dtype:
                raise ValueError(
                    f"stage {s} produced {got.shape}/{got.dtype}, but stage "
                    f"{s + 1} expects {want.shape}/{want.dtype} — the edge "
                    f"contract (edges[{s + 1}]) is violated"
                )
            return bus_pack(y, bus)

        return run

    branches = [_branch(s) for s in range(P_)]

    def stage_fn(params, bus_val, m):
        n = axis_size(pipe_axis)  # static inside shard_map
        if n != P_:
            # without this, lax.switch CLAMPS the stage index: extra
            # stages silently re-run the last branch / missing stages never
            # run, and every bus aval matches so no shape error ever fires
            raise ValueError(
                f"{P_} heterogeneous stage fns on a {n}-rank "
                f"{pipe_axis!r} axis — one fn per stage is required"
            )
        s = jax.lax.axis_index(pipe_axis)
        return jax.lax.switch(s, branches, params, bus_val, m)

    def wrap_first(first_fn):
        def first(params, mb):
            out = first_fn(params, mb)
            got = _aval(out)
            if got.shape != edges[0].shape or got.dtype != edges[0].dtype:
                raise ValueError(
                    f"first_fn produced {got.shape}/{got.dtype}, expected "
                    f"edges[0] = {edges[0].shape}/{edges[0].dtype}"
                )
            return bus_pack(out, bus)

        return first

    def wrap_last(last_fn):
        def last(params, bus_val, tgt):
            return last_fn(params, bus_unpack(bus_val, edges[-1]), tgt)

        return last

    return wrap_first, stage_fn, wrap_last
