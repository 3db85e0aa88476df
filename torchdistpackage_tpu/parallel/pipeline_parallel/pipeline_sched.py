"""SPMD pipeline schedule — analogue of the reference's 1F1B scheduler +
p2p comm layer (``pipeline_parallel/pipeline_sched.py`` 269 LoC,
``pipeline_parallel/comm.py`` 595 LoC).

The reference drives warmup -> steady 1F1B -> cooldown from Python, moving
activations with batched NCCL isend/irecv guarded by a shape-meta handshake
(comm.py:26-105) and a defensive ``cuda.synchronize`` (comm.py:326-327).
Under XLA the whole schedule is **one compiled collective program**:

- microbatches advance through stages inside a ``lax.scan`` over
  ``M + P - 1`` ticks (fill -> steady -> drain);
- inter-stage transfer is a single ``ppermute`` per tick over the ``pipe``
  axis — shapes are static at trace time, so the reference's entire meta
  protocol and race guard vanish by construction;
- backward is JAX AD through the scan: the transpose of ``ppermute`` is the
  reverse ``ppermute``, which *is* the backward pipeline, microbatch grads
  accumulating in the scan-carry — the reference's grad-accumulate-then-
  reduce-once behavior (naive_ddp.py:108-110) falls out;
- peak memory is governed by ``jax.checkpoint`` around the stage body
  (1F1B's raison d'être — bounded live activations — achieved by remat
  rather than schedule order, which XLA controls anyway);
- the pipeline bubble is the same (P-1)/(M+P-1) as the reference's 1F1B.

Non-linear stage graphs (the reference supports CLIP-style fwd_fn/bwd_fn
pairs, Intro.md:54-66) are supported the same way: ``stage_fn`` is arbitrary
user code — it sees (stage_params, activation, per-tick aux) and can branch on
``stage_index``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax

from jax.lax import axis_size
import jax.numpy as jnp

from ...dist.topology import PIPE_AXIS
from ..tensor_parallel.layers import RematMode, checkpoint_block

PyTree = Any


def _stage_probe(stage_params, microbatches, stage_fn, pipe_axis):
    """(zero_state, want_vma): the stage activation's shape/dtype and the
    varying-axis set the scan carry must hold.

    The carry's vma is a fixed point: the tick computes
    ``shift_right(stage_fn(params, where(first, mb, state)))``, so the state
    must vary over exactly ``vma(stage_fn output) | vma(mb) | {pipe}`` — which
    itself depends on the state's vma.  Iterate ``jax.eval_shape`` (whose
    results carry vma) until stable; this handles both under-marking (output
    picks up axes from sharded params) and over-marking (output drops axes via
    an internal psum) for any TP/SP/PP composition."""
    from ..data_parallel import _mark_varying, _vma

    mb_vma = _vma(microbatches)
    want_vma = mb_vma | {pipe_axis}
    probe0 = microbatches[0]
    out_shape = None
    for _ in range(8):  # bounded by the number of mesh axes
        probe = probe0
        missing = tuple(a for a in want_vma if a not in _vma(probe))
        if missing:
            probe = _mark_varying(probe, missing)
        out_shape = jax.eval_shape(stage_fn, stage_params, probe)
        new_want = frozenset(getattr(out_shape, "vma", frozenset())) | mb_vma | {pipe_axis}
        if new_want == want_vma:
            break
        want_vma = new_want
    zero_state = jnp.zeros(out_shape.shape, out_shape.dtype)
    missing = tuple(a for a in want_vma if a not in _vma(zero_state))
    if missing:
        zero_state = _mark_varying(zero_state, missing)
    return zero_state, want_vma


def _zeros_like_shapes(shapes):
    """Zero pytree matching ShapeDtypeStructs (or values), reproducing vma."""
    from ..data_parallel import _mark_varying

    def z(a):
        from jax import typeof

        aval = a if isinstance(a, jax.ShapeDtypeStruct) else typeof(a)
        x = jnp.zeros(aval.shape, aval.dtype)
        vm = tuple(getattr(aval, "vma", ()))
        return _mark_varying(x, vm) if vm else x

    return jax.tree.map(
        z, shapes, is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct)
    )


def _normalized_first_fn(first_fn, x_shape, want_vma):
    """``(first_v, first_missing)``: ``first_v`` wraps ``first_fn`` to emit
    the scan-carry vma; ``first_missing`` (static) lists the axes the
    normalization must ADD.  If it contains the pipe axis, the added pvary's
    transpose is a pipe psum — illegal inside a stage-gated cond, so callers
    then run ``first_v`` unconditionally + select instead."""
    from ..data_parallel import _mark_varying, _vma

    first_missing = tuple(
        a for a in want_vma if a not in frozenset(getattr(x_shape, "vma", frozenset()))
    )

    def first_v(p, mb):
        o = first_fn(p, mb)
        miss = tuple(a for a in want_vma if a not in _vma(o))
        return _mark_varying(o, miss) if miss else o

    return first_v, first_missing


def stage_index(pipe_axis: str = PIPE_AXIS):
    return jax.lax.axis_index(pipe_axis)


def is_first_stage(pipe_axis: str = PIPE_AXIS):
    return jax.lax.axis_index(pipe_axis) == 0


def is_last_stage(pipe_axis: str = PIPE_AXIS):
    return jax.lax.axis_index(pipe_axis) == axis_size(pipe_axis) - 1


def last_stage_value(x, pipe_axis: str = PIPE_AXIS):
    """Cheaply broadcast a (small) per-stage value from the last stage to all
    stages: mask + psum.  The scalar analogue of the reference's loss returned
    by the final stage."""
    return jax.lax.psum(jnp.where(is_last_stage(pipe_axis), x, jnp.zeros_like(x)), pipe_axis)


def shift_right(x, pipe_axis: str = PIPE_AXIS, circular: bool = False):
    """Send to the next stage: stage s's value arrives at s+1.  Non-circular
    (default): stage 0 receives zeros — the ppermute analogue of
    send_forward/recv_forward (comm.py:362-435).  ``circular``: stage 0
    receives stage P-1's value — the wrap edge of the interleaved (virtual
    chunk) schedule, carrying a finished chunk's activation back to stage 0
    as the next chunk's input."""
    n = axis_size(pipe_axis)
    last_edge = [(n - 1, 0)] if circular else []
    return jax.lax.ppermute(
        x, pipe_axis, [(i, i + 1) for i in range(n - 1)] + last_edge
    )


def shift_left(x, pipe_axis: str = PIPE_AXIS, circular: bool = False):
    """Send to the previous stage: stage s's value arrives at s-1.  The
    cotangent channel of the 1F1B schedule — analogue of
    send_backward/recv_backward (comm.py:362-435).  ``circular``: stage P-1
    receives stage 0's value (the wrap cotangent from chunk v+1 back to
    chunk v under the interleaved schedule)."""
    n = axis_size(pipe_axis)
    wrap_edge = [(0, n - 1)] if circular else []
    return jax.lax.ppermute(
        x, pipe_axis, [(i, i - 1) for i in range(1, n)] + wrap_edge
    )


def _transfer_dim(shape, n: int) -> int:
    """The dim sliced by sharded inter-stage transfers: first one divisible
    by the axis size (batch/seq dims come first, leaving the minor-most lane
    dim intact when possible); -1 = leaf transfers unsliced."""
    for d, s in enumerate(shape):
        if s % n == 0 and s >= n:
            return d
    return -1


def _slice_state(x, tdims, axis: str):
    """Each ``axis`` rank keeps its 1/n slice of every leaf's transfer dim."""
    i = jax.lax.axis_index(axis)
    n = axis_size(axis)

    def one(a, d):
        if d < 0:
            return a
        sz = a.shape[d] // n
        return jax.lax.dynamic_slice_in_dim(a, i * sz, sz, axis=d)

    return jax.tree.map(one, x, tdims)


def _gather_state(x, tdims, axis: str):
    """Reassemble the full state from the per-rank slices (transpose:
    psum_scatter — AD keeps replicated-param grads exact through this)."""

    def one(a, d):
        if d < 0:
            return a
        return jax.lax.all_gather(a, axis, axis=d, tiled=True)

    return jax.tree.map(one, x, tdims)


def _pipeline_scan(
    stage_params: PyTree,
    microbatches: jnp.ndarray,
    stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    num_microbatches: int,
    pipe_axis: str,
    remat: RematMode,
    make_acc: Callable,
    consume: Callable,
    first_fn: Callable = None,
    params: PyTree = None,
):
    """Shared fill -> steady -> drain scan driver for the pipelined schedules.

    Each tick: stage 0 consumes microbatch ``min(t, M-1)`` (clamped in the
    drain phase — those results never reach a consumer), other stages consume
    what ``shift_right`` delivered; the stage output is both shifted onward
    and handed to ``consume``.

    - ``make_acc(zero_state, want_vma) -> acc0`` builds the scan's accumulator
      (output buffer / loss sum / None).
    - ``consume(acc, y, m_idx, steady) -> acc`` folds in the stage output for
      completed microbatch ``m_idx``; ``steady`` is the traced ``t >= P-1``
      validity predicate.
    - ``first_fn(params, mb) -> x`` (optional): stage-0 preprocessing (e.g.
      token embedding) applied PER TICK inside the scan, so raw microbatch
      inputs — not M pre-embedded activations — are what stays resident.
      ``params`` is pipe-pvaried here so the embed cond-gates to stage 0 only
      (its grad psum over pipe sits at the pvary transpose, outside the scan).
      ``microbatches`` is then the raw-input pytree ``[M, ...]``.
    """
    from ..data_parallel import pvary_params

    M = num_microbatches
    P_ = axis_size(pipe_axis)
    ticks = M + P_ - 1
    first = is_first_stage(pipe_axis)
    # prevent_cse=False: body_fn executes inside the tick lax.scan below,
    # whose loop structure already blocks CSE (same rationale as scan_blocks)
    body_fn = checkpoint_block(stage_fn, remat, prevent_cse=False)

    if first_fn is None:
        zero_state, want_vma = _stage_probe(
            stage_params, microbatches, stage_fn, pipe_axis
        )
        first_v, first_missing = None, ()
    else:
        # pipe-pvary so first_fn's output is pipe-varying -> stage-gated cond
        # below is legal AND only stage 0 pays the embed FLOPs
        params = pvary_params(params, (pipe_axis,))
        mb0 = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, 0, axis=0, keepdims=False),
            microbatches,
        )
        x_shape = jax.eval_shape(first_fn, params, mb0)
        zero_state, want_vma = _stage_probe(
            stage_params, _zeros_like_shapes(x_shape)[None], stage_fn, pipe_axis
        )
        first_v, first_missing = _normalized_first_fn(first_fn, x_shape, want_vma)

    acc0 = make_acc(zero_state, want_vma)

    def tick(carry, t):
        state, acc = carry
        mb = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.minimum(t, M - 1), axis=0, keepdims=False
            ),
            microbatches,
        )
        if first_fn is None:
            x = jnp.where(first, mb, state)
        elif pipe_axis not in first_missing:
            x = jax.lax.cond(
                first, lambda op: first_v(params, op[0]), lambda op: op[1], (mb, state)
            )
        else:
            x = jnp.where(first, first_v(params, mb), state)
        y = body_fn(stage_params, x)
        nxt = shift_right(y, pipe_axis)
        m_idx = jnp.maximum(t - (P_ - 1), 0)
        acc = consume(acc, y, m_idx, t >= P_ - 1)
        return (nxt, acc), None

    (_, acc), _ = jax.lax.scan(tick, (zero_state, acc0), jnp.arange(ticks))
    return acc


def pipeline_forward(
    stage_params: PyTree,
    microbatches: jnp.ndarray,
    stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    num_microbatches: int,
    pipe_axis: str = PIPE_AXIS,
    remat: RematMode = True,
    collect_outputs: bool = True,
    first_fn: Callable = None,
    params: PyTree = None,
):
    """Run the pipelined forward inside shard_map.

    - ``stage_params``: this stage's local params (e.g. its slab of stacked
      layers, ``[L_local, ...]`` leaves).
    - ``microbatches``: ``[M, mbs, ...]`` local microbatch inputs (only read
      on stage 0; pass the same array everywhere).
    - ``stage_fn(stage_params, x) -> y``: one stage's compute; activations
      must keep shape/dtype across stages (classic linear pipeline).

    Returns ``outputs`` of shape ``[M, mbs, ...]`` — valid on the **last**
    stage (garbage elsewhere; combine with :func:`last_stage_value` or mask).
    When ``collect_outputs=False`` returns None (use the scanning loss variant
    in :func:`pipeline_loss` instead to avoid materializing outputs).
    """
    from ..data_parallel import _mark_varying, _vma

    M = num_microbatches

    def make_acc(zero_state, want_vma):
        if not collect_outputs:
            return None
        outputs = jnp.zeros((M,) + zero_state.shape, zero_state.dtype)
        missing = tuple(a for a in want_vma if a not in _vma(outputs))
        return _mark_varying(outputs, missing) if missing else outputs

    def consume(outputs, y, m_idx, steady):
        if outputs is None:
            return None
        return jax.lax.cond(
            steady,
            lambda o: jax.lax.dynamic_update_index_in_dim(o, y, m_idx, axis=0),
            lambda o: o,
            outputs,
        )

    return _pipeline_scan(
        stage_params, microbatches, stage_fn, M, pipe_axis, remat, make_acc, consume,
        first_fn=first_fn, params=params,
    )


def pipeline_loss(
    stage_params: PyTree,
    microbatches: jnp.ndarray,
    targets: jnp.ndarray,
    stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    loss_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    num_microbatches: int,
    pipe_axis: str = PIPE_AXIS,
    remat: RematMode = True,
    first_fn: Callable = None,
    params: PyTree = None,
) -> jnp.ndarray:
    """Pipelined forward + per-microbatch loss on the last stage, without
    materializing the output buffer.  Returns the mean loss, valid on every
    stage (masked psum broadcast).

    ``targets``: ``[M, mbs, ...]`` — read on the last stage only.
    ``loss_fn(y, target) -> scalar`` (mean over the microbatch).
    ``first_fn(params, mb) -> x`` (optional): per-tick stage-0 preprocessing;
    ``microbatches`` is then the raw input pytree (see ``_pipeline_scan``).
    """
    from ..data_parallel import _mark_varying, _vma

    M = num_microbatches
    last = is_last_stage(pipe_axis)

    def make_acc(zero_state, want_vma):
        loss0 = jnp.zeros(())
        missing = tuple(a for a in (want_vma | _vma(targets)) if a not in _vma(loss0))
        return _mark_varying(loss0, missing) if missing else loss0

    def consume(loss_sum, y, m_idx, steady):
        tgt = jax.lax.dynamic_index_in_dim(targets, m_idx, axis=0, keepdims=False)
        mb_loss = loss_fn(y, tgt)
        valid = jnp.logical_and(last, steady)
        return loss_sum + jnp.where(valid, mb_loss, 0.0)

    loss_sum = _pipeline_scan(
        stage_params, microbatches, stage_fn, M, pipe_axis, remat, make_acc, consume,
        first_fn=first_fn, params=params,
    )
    # broadcast from the last stage; grads flow back through the mask
    return jax.lax.psum(loss_sum, pipe_axis) / M


# --------------------------------------------------------------------- 1F1B


def ring_slots(num_microbatches: int, pipe_size: int, num_chunks: int = 1) -> int:
    """Stage-input slots the 1F1B schedule keeps live:
    ``min(V*M, 2*P*V - 1)`` (``V = num_chunks``; classic ``min(M, 2P-1)`` at
    V=1).

    This is the schedule's memory guarantee — peak in-flight activations are
    bounded by the pipeline depth (x the chunk count under interleaving),
    NOT the microbatch count (the property the reference's steady-state 1F1B
    interleave exists for, pipeline_parallel/pipeline_sched.py:163-211).
    Derivation: unit k's slot may be overwritten only after unit ``k - R``'s
    backward, and ``t_f(k) - t_b(k-R)`` >= 0 for every (stage, chunk) iff
    ``R >= (P-1-2s) + (V-1-2v)P + PV``, maximized at s=0, v=0 as
    ``2PV - 1``."""
    return min(
        num_microbatches * num_chunks, 2 * pipe_size * num_chunks - 1
    )


def pipeline_1f1b(
    params: PyTree,
    inputs: PyTree,
    targets: PyTree,
    first_fn: Callable[[PyTree, PyTree], jnp.ndarray],
    stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    last_fn: Callable[[PyTree, jnp.ndarray, PyTree], jnp.ndarray],
    num_microbatches: int,
    pipe_axis: str = PIPE_AXIS,
    stage_takes_mb: bool = False,
    stage_returns_aux: bool = False,
    num_chunks: int = 1,
    transfer_shard_axis: Optional[str] = None,
):
    """One-forward-one-backward pipeline schedule: returns ``(loss, grads)``
    directly (do NOT wrap in ``jax.grad`` — the backward pipeline runs inside).

    The match for the reference's steady-state 1F1B interleave
    (pipeline_parallel/pipeline_sched.py:163-211), rebuilt for SPMD/XLA: one
    ``lax.scan`` over ``M + 2P - 2`` ticks where **every tick carries one
    forward and one backward unit of work** —

    - fwd: stage ``s`` runs microbatch ``m_f = t - s`` (fill wavefront), stage
      0 sourcing it from ``first_fn(params, inputs[m_f])`` (embed), others
      from the activation ``ppermute``-d in last tick; the stage INPUT is
      saved in a ring buffer of :func:`ring_slots` slots.
    - bwd: stage ``s`` runs microbatch ``m_b = t - 2(P-1) + s``: recompute the
      stage from its saved input under ``jax.vjp`` (the remat), pull the
      output cotangent from the next stage's ``shift_left`` (or, on the last
      stage, from the vjp of ``last_fn``'s per-microbatch loss), accumulate
      param grads, and send the input cotangent upstream.

    Peak live activations are O(P) — independent of M — versus O(M) for AD
    through :func:`pipeline_loss`'s forward scan (which must keep every tick's
    carry for the reverse pass).  Total FLOPs are the same as remat-AD: fwd +
    recompute + bwd per microbatch.

    ``first_fn``/``last_fn`` take the FULL ``params`` pytree, so embedding and
    head weights get their gradients here too (on their owning stage, then
    psum-ed over ``pipe`` for every param leaf that is replicated across
    stages — the explicit form of shard_map's transpose).

    ``inputs``/``targets``: pytrees with leading dim ``M`` (raw microbatches;
    read on the first / last stage respectively).  ``last_fn(params, y, tgt)``
    returns the microbatch's mean loss.  Returns the mean loss over all M
    (identical on every stage) and a grads pytree matching ``params``.

    ``stage_returns_aux``: ``stage_fn`` returns ``(y, aux)`` where ``aux`` is
    a scalar **auxiliary loss term produced mid-pipeline** (e.g. the MoE
    load-balance loss, which arises on every stage that holds expert blocks
    — it cannot be computed in ``last_fn``, which only sees the final
    activation).  The schedule adds each microbatch's aux to the loss once
    (forward unit, masked to real microbatches) and backpropagates it with a
    unit cotangent through the stage's vjp (backward unit) — so aux
    gradients flow into the stage's params AND upstream through ``dx``
    exactly as if ``total = last_fn_loss + sum_stages aux`` had been
    differentiated as one expression.  ``aux`` must already carry whatever
    weight the caller wants (the returned loss is ``mean_m [CE_m +
    sum_stages aux_{s,m}]``).

    ``num_chunks`` (V > 1): the **interleaved schedule** (virtual pipeline
    stages, the Megatron-style bubble reduction): each physical stage holds
    V model chunks — chunk v of stage s is global layer-slab ``v*P + s``
    (round-robin) — and ``stage_fn(params, x, m, v)`` additionally receives
    the chunk index to select its slab.  Forward unit order per stage is
    ``sigma(v, m) = (m // P)*P*V + v*P + (m % P)`` (groups of P microbatches
    sweep all chunks before the next group — requires ``M % P == 0``, as
    Megatron's interleaved schedule does); the backward mirrors it with the
    chunk order reversed.  Inter-stage transfer becomes a CIRCULAR ppermute:
    the P-1 -> 0 wrap edge carries a finished chunk's activation back as the
    next chunk's input (and stage 0's cotangent back to stage P-1), and the
    schedule arithmetic guarantees each wrap payload arrives exactly one
    tick before its consumer.  Total ticks ``VM + PV + P - 2`` of 1/V-sized
    units vs ``V(M + 2P - 2)`` chunk-equivalents non-interleaved — the
    fill/drain bubble shrinks whenever ``P + 2V - 2 < PV`` (any P >= 3); the
    price is the deeper ring buffer, ``min(VM, 2PV-1)`` slots of 1 chunk's
    activation each (:func:`ring_slots`).  At V=1 every formula reduces to
    the classic schedule above.

    ``transfer_shard_axis``: shard the inter-stage state over this (tensor)
    axis — the analogue of the reference's ``scatter_gather_tensors``
    (pipeline_parallel/comm.py:108-155), which splits the p2p payload 1/tp
    before send and all-gathers after receive.  Here the state stays SLICED
    through the whole schedule (each TP rank carries slice ``i`` of the
    first divisible dim): stage entry all-gathers over the axis, stage exit
    slices — both INSIDE the differentiated stage fn, so AD's
    all_gather <-> psum_scatter transposition keeps every gradient exact
    (the Megatron SP conjugate pair).  The pipe ``ppermute`` payload AND the
    activation ring buffer shrink by 1/tp (beyond the reference, which only
    shards the wire bytes).  Pointless under SP, where the state is already
    sequence-sharded — meant for the non-SP TP pipeline.
    """
    from ..data_parallel import _mark_varying, _vma, pvary_params

    M = num_microbatches
    V = num_chunks
    P_ = axis_size(pipe_axis)
    if V < 1:
        raise ValueError(f"num_chunks must be >= 1, got {V}")
    if V > 1 and M % P_ != 0:
        raise ValueError(
            f"the interleaved schedule requires num_microbatches ({M}) "
            f"divisible by pipe size ({P_}): the last microbatch group would "
            f"otherwise break the sigma(v, m) dependency spacing"
        )
    R = ring_slots(M, P_, V)
    T = V * M + P_ * V + P_ - 2  # == M + 2(P-1) at V=1
    s = jax.lax.axis_index(pipe_axis)
    first = is_first_stage(pipe_axis)
    last = is_last_stage(pipe_axis)
    circular = V > 1

    # Mark params pipe-varying so every vjp below yields LOCAL per-stage
    # grads (no implicit psum inside the scan's conds, where a pipe
    # collective would be illegal); the single explicit psum for
    # pipe-replicated leaves happens once at the end (see ``sync``).
    orig_params = params
    params = pvary_params(params, (pipe_axis,))

    # ``stage_takes_mb``: stage_fn(params, x, m) also receives the microbatch
    # index m (int32, < M) — for per-microbatch stage behavior such as
    # dropout keys.  The bwd recompute replays the same m, so key-derived
    # masks are identical between forward and recompute.  With V > 1 the
    # stage fn must take (p, x, m, v) — v selects the chunk's param slab.
    if V > 1:
        # fail the CONTRACT loudly: a stage_fn(p, x) or (p, x, m) would
        # otherwise surface as an opaque arity TypeError from inside tracing
        # when the scheduler calls it with four arguments
        try:
            import inspect

            sig_params = inspect.signature(stage_fn).parameters.values()
        except (TypeError, ValueError):
            sig_params = None  # unintrospectable callable: let it through
        if sig_params is not None and not any(
            p.kind is inspect.Parameter.VAR_POSITIONAL for p in sig_params
        ):
            n_pos = sum(
                p.kind in (inspect.Parameter.POSITIONAL_ONLY,
                           inspect.Parameter.POSITIONAL_OR_KEYWORD)
                for p in sig_params
            )
            if n_pos < 4:
                raise ValueError(
                    f"num_chunks > 1 (interleaved schedule) requires a "
                    f"stage_fn with signature (params, x, microbatch_idx, "
                    f"chunk_idx); got a callable taking {n_pos} positional "
                    f"args. The scheduler passes m to replay per-microbatch "
                    f"behavior in the backward recompute and v to select "
                    f"the chunk's param slab."
                )
        call_stage = stage_fn  # (p, x, m, v)
    elif stage_takes_mb:
        call_stage = lambda p, x, m, v: stage_fn(p, x, m)
    else:
        call_stage = lambda p, x, m, v: stage_fn(p, x)

    take_mb = lambda tree, i: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False), tree
    )
    mb0_in = take_mb(inputs, jnp.zeros((), jnp.int32))
    mb0_tgt = take_mb(targets, jnp.zeros((), jnp.int32))

    if transfer_shard_axis is not None:
        # Sharded inter-stage state (see docstring): slice at every stage
        # exit, gather at every entry — INSIDE the differentiated fns, so
        # the schedule below (carry, ring buffer, ppermutes, cotangents)
        # only ever sees 1/tp-sized state and AD stays exact.
        tax = transfer_shard_axis
        tsz = axis_size(tax)
        full_state = jax.eval_shape(first_fn, params, mb0_in)
        tdims = jax.tree.map(lambda a: _transfer_dim(a.shape, tsz), full_state)
        _first0, _stage0, _last0 = first_fn, call_stage, last_fn

        def _close_scalar(v):
            # A scalar that ESCAPES the slice/gather conjugate pair (aux
            # losses, a last_fn that doesn't psum over tax internally) is
            # computed from gathered — tax-varying-TYPED but value-equal —
            # state.  Left varying, its vjp transpose-psums a FULL
            # per-rank grad contribution tp times (overcount), while the
            # sliced-state path's grads are exact shares — no global
            # rescale can fix both.  pmean is exact on the equal values,
            # restores invariance, and seeds each rank with the correct
            # 1/tp cotangent so the transpose-psum sums to exactly 1x.
            return jax.lax.pmean(v, tax) if tax in _vma(v) else v

        def first_fn(p, mb):
            return _slice_state(_first0(p, mb), tdims, tax)

        def call_stage(p, x, m, v):
            out = _stage0(p, _gather_state(x, tdims, tax), m, v)
            if stage_returns_aux:
                y, aux = out
                return _slice_state(y, tdims, tax), _close_scalar(aux)
            return _slice_state(out, tdims, tax)

        def last_fn(p, y, tgt):
            return _close_scalar(_last0(p, _gather_state(y, tdims, tax), tgt))

    # ---- state aval fixed point (stage in/out shape + varying axes)
    x_shape = jax.eval_shape(first_fn, params, mb0_in)
    want_vma = frozenset(getattr(x_shape, "vma", frozenset())) | {pipe_axis}
    zero_state = None
    aux_shape = None
    for _ in range(8):  # bounded by the number of mesh axes
        zero_state = _zeros_like_shapes(x_shape)
        missing = tuple(a for a in want_vma if a not in _vma(zero_state))
        if missing:
            zero_state = _mark_varying(zero_state, missing)
        out_shape = jax.eval_shape(
            call_stage, params, zero_state,
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
        )
        y_shape, aux_shape = out_shape if stage_returns_aux else (out_shape, None)
        new_want = frozenset(getattr(y_shape, "vma", frozenset())) | want_vma
        if new_want == want_vma:
            break
        want_vma = new_want
    if y_shape.shape != x_shape.shape or y_shape.dtype != x_shape.dtype:
        raise ValueError(
            f"stage_fn must preserve activation shape/dtype for pipelining: "
            f"{x_shape.shape}/{x_shape.dtype} -> {y_shape.shape}/{y_shape.dtype}"
        )

    # first_v normalizes first_fn's output vma; if that adds a PIPE marking
    # (degenerate first_fn that ignores params, e.g. identity), its vjp
    # contains a pipe psum and must run unconditionally each tick rather than
    # inside the stage-gated cond.  Static, trace-time choice.
    first_v, _first_missing = _normalized_first_fn(first_fn, x_shape, want_vma)
    first_vjp_in_cond = pipe_axis not in _first_missing

    # ---- one backward unit of work (runs under lax.cond when bwd is active)
    def run_bwd(opers):
        x_saved, cot_in, mb_tgt, mb_in, m_b, v_b = opers
        if stage_returns_aux:
            (y_, aux_), vjp_stage = jax.vjp(
                lambda p, xx: call_stage(p, xx, m_b, v_b), params, x_saved
            )
        else:
            y_, vjp_stage = jax.vjp(
                lambda p, xx: call_stage(p, xx, m_b, v_b), params, x_saved
            )

        def last_branch(op):
            y_, mb_tgt, _ = op
            loss_m, vjp_last = jax.vjp(
                lambda p, yy: last_fn(p, yy, mb_tgt), params, y_
            )
            one = jnp.ones(jnp.shape(loss_m), jnp.result_type(loss_m))
            miss = tuple(a for a in _vma(loss_m) if a not in _vma(one))
            dp_last, g = vjp_last(_mark_varying(one, miss) if miss else one)
            return loss_m, dp_last, g

        last_shapes = jax.eval_shape(last_branch, (y_, mb_tgt, cot_in))

        def mid_branch(op):
            _, _, cot_in = op
            zl, zp, _ = _zeros_like_shapes(last_shapes)
            return zl, zp, cot_in

        # the loss seed lives on the LAST chunk of the last stage (chunk
        # V-1 is the model's tail under the round-robin slab assignment)
        loss_m, dp_last, g = jax.lax.cond(
            jnp.logical_and(last, v_b == V - 1),
            last_branch, mid_branch, (y_, mb_tgt, cot_in)
        )

        if stage_returns_aux:
            # unit cotangent on the stage's aux loss term: total loss holds
            # +aux per (stage, microbatch), so d total / d aux = 1 (the
            # schedule's b_active mask zeroes fill/drain ticks afterwards)
            one_aux = jnp.ones(jnp.shape(aux_), jnp.result_type(aux_))
            miss = tuple(a for a in _vma(aux_) if a not in _vma(one_aux))
            dp_stage, dx = vjp_stage(
                (g, _mark_varying(one_aux, miss) if miss else one_aux)
            )
        else:
            dp_stage, dx = vjp_stage(g)

        if first_vjp_in_cond:
            def first_branch(op):
                mb_in, dx = op
                _, vjp_first = jax.vjp(lambda p: first_v(p, mb_in), params)
                (dp_first,) = vjp_first(dx)
                return dp_first

            first_shapes = jax.eval_shape(first_branch, (mb_in, dx))
            # the embed's vjp belongs to stage 0's CHUNK-0 units only (the
            # model's head-end slab); wrap units (v > 0) pass dx upstream
            dp_first = jax.lax.cond(
                jnp.logical_and(first, v_b == 0),
                first_branch,
                lambda op: _zeros_like_shapes(first_shapes),
                (mb_in, dx),
            )
            dp = jax.tree.map(lambda a, b, c: a + b + c, dp_stage, dp_last, dp_first)
        else:
            dp = jax.tree.map(lambda a, b: a + b, dp_stage, dp_last)
        return loss_m, dp, dx

    # ---- carry init (zeros with the right vma, via abstract eval)
    _zvma = _vma(zero_state)

    def _stacked_struct(a):
        if _zvma:
            return jax.ShapeDtypeStruct((R,) + a.shape, a.dtype, vma=_zvma)
        return jax.ShapeDtypeStruct((R,) + a.shape, a.dtype)

    saved0 = _zeros_like_shapes(
        jax.tree.map(_stacked_struct, jax.eval_shape(lambda z: z, zero_state))
    )
    cot0 = zero_state
    bwd_shapes = jax.eval_shape(
        run_bwd,
        (zero_state, cot0, mb0_tgt, mb0_in,
         jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)),
    )
    # the loss accumulator inherits the TRUE loss aval's varying axes (e.g. a
    # vocab-parallel CE has already psum-ed over 'tensor', so the loss must
    # NOT be marked tensor-varying — downstream model-axis normalization keys
    # on the loss vma)
    loss0, grads0, _ = _zeros_like_shapes(bwd_shapes)
    if stage_returns_aux:
        # the fwd units also add per-stage aux terms into the accumulator
        aux_vma = frozenset(getattr(aux_shape, "vma", frozenset()))
        miss = tuple(a for a in aux_vma if a not in _vma(loss0))
        if miss:
            loss0 = _mark_varying(loss0, miss)

    def tick(carry, t):
        state, cot_state, saved_x, grads_acc, loss_sum = carry

        # -------- forward unit: stage s runs its k-th fwd unit at tick s+k,
        # with (chunk, microbatch) = sigma^-1(k); V=1 degenerates to the
        # classic wavefront m_f = t - s
        k_f = t - s
        f_active = (k_f >= 0) & (k_f < V * M)
        k_f_c = jnp.clip(k_f, 0, V * M - 1)
        r_f = jnp.remainder(k_f_c, P_ * V)
        v_f = r_f // P_
        m_f_c = (k_f_c // (P_ * V)) * P_ + jnp.remainder(r_f, P_)
        mb_in = take_mb(inputs, m_f_c)
        x = jax.lax.cond(
            jnp.logical_and(first, v_f == 0),
            lambda op: first_v(params, op[0]), lambda op: op[1], (mb_in, state)
        )
        if stage_returns_aux:
            y, aux_f = call_stage(params, x, m_f_c, v_f)
        else:
            y, aux_f = call_stage(params, x, m_f_c, v_f), None
        slot_f = jnp.remainder(k_f_c, R)
        saved_x = jax.lax.cond(
            f_active,
            lambda b: jax.lax.dynamic_update_index_in_dim(b, x, slot_f, axis=0),
            lambda b: b,
            saved_x,
        )

        # -------- backward unit: mirrored order (chunks reversed), delayed
        # by the first microbatch's full-model forward (PV - 1 ticks)
        k_b = t - (P_ - 1 - s) - (P_ * V - 1)
        b_active = (k_b >= 0) & (k_b < V * M)
        k_b_c = jnp.clip(k_b, 0, V * M - 1)
        r_b = jnp.remainder(k_b_c, P_ * V)
        v_b = (V - 1) - r_b // P_
        m_b_c = (k_b_c // (P_ * V)) * P_ + jnp.remainder(r_b, P_)
        # the unit's own fwd counter locates its ring-buffer slot
        k_unit = (k_b_c // (P_ * V)) * (P_ * V) + v_b * P_ + jnp.remainder(r_b, P_)
        x_saved = jax.lax.dynamic_index_in_dim(
            saved_x, jnp.remainder(k_unit, R), axis=0, keepdims=False
        )
        mb_in_b = take_mb(inputs, m_b_c)
        opers = (x_saved, cot_state, take_mb(targets, m_b_c), mb_in_b, m_b_c, v_b)
        # Run the bwd unit UNCONDITIONALLY and mask the accumulation, the
        # same uniform-body rule the forward follows (line `y = stage_fn`
        # above): ``b_active`` is pipe-varying, and a collective inside a
        # branch-divergent cond is undefined — XLA's collective-permute in
        # particular is a FULL-mesh rendezvous, so a ring-attention stage
        # (ppermute over 'context') inside ``cond(b_active, ...)`` deadlocks
        # or silently corrupts.  The extra recompute+bwd FLOPs are paid only
        # on the PV+P-2 fill/drain ticks (2(P-1) at V=1) where b_active is
        # false anyway.
        loss_m, dp, dx = run_bwd(opers)
        mask_b = lambda g: jnp.where(b_active, g, jnp.zeros((), g.dtype))
        loss_m = mask_b(loss_m)
        dp = jax.tree.map(mask_b, dp)
        dx = jax.tree.map(mask_b, dx)

        if not first_vjp_in_cond:
            # degenerate first_fn (ignores params): its vjp contains a pipe
            # psum (transpose of the vma normalization), so it must run
            # unconditionally.  Mask the cotangent to stage 0's bwd window
            # before, and the (pipe-replicated) grad after, so the final sync
            # psum yields exactly stage 0's contribution.
            gate = jnp.logical_and(jnp.logical_and(first, v_b == 0), b_active)
            dxm = jax.tree.map(
                lambda a: jnp.where(gate, a, jnp.zeros((), a.dtype)), dx
            )
            _, vjp_first = jax.vjp(lambda p: first_v(p, mb_in_b), params)
            (dp_first,) = vjp_first(dxm)
            dp_first = jax.tree.map(
                lambda g: g * gate.astype(jnp.result_type(g)), dp_first
            )
            dp = jax.tree.map(jnp.add, dp, dp_first)

        grads_acc = jax.tree.map(jnp.add, grads_acc, dp)
        loss_sum = loss_sum + loss_m
        if aux_f is not None:
            # each real microbatch's per-stage aux counts once, at its fwd
            # unit (the bwd recompute only carries its gradient)
            loss_sum = loss_sum + jnp.where(
                f_active, aux_f.astype(loss_sum.dtype), jnp.zeros((), loss_sum.dtype)
            )
        return (
            shift_right(y, pipe_axis, circular=circular),
            shift_left(dx, pipe_axis, circular=circular),
            saved_x, grads_acc, loss_sum,
        ), None

    (_, _, _, grads, loss_sum), _ = jax.lax.scan(
        tick, (zero_state, cot0, saved0, grads0, loss0), jnp.arange(T)
    )

    # mean over microbatches; broadcast the last stage's loss everywhere
    loss = jax.lax.psum(loss_sum, pipe_axis) / M
    inv = 1.0 / M

    # replicated-across-stages params (embed/head, anything not pipe-sharded)
    # get contributions from their owning stage only — make every stage agree,
    # the explicit form of shard_map's transpose-psum.
    def sync(g, p):
        g = g * inv if not isinstance(g, jax.ShapeDtypeStruct) else g
        if pipe_axis in _vma(p):
            return g
        if pipe_axis in _vma(g):
            return jax.lax.psum(g, pipe_axis)
        return g

    grads = jax.tree.map(lambda g, p: sync(g, p), grads, orig_params)
    return loss, grads
