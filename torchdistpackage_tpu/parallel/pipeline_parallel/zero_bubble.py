"""Zero-bubble (ZB-H1-shaped) pipeline schedule — split backward into
dgrad/wgrad and excise the cooldown's wasted weight-gradient work.

The classic SPMD 1F1B (``pipeline_sched.pipeline_1f1b``) runs one
``lax.scan`` over ``M + 2(P-1)`` ticks whose body carries one forward unit
AND one full backward unit (recompute + grad-input + grad-weight fused in
one ``jax.vjp``).  Under the uniform-body SPMD rule every tick executes
every slot, so the ``2(P-1)`` fill/drain ticks pay the FULL fused backward
on masked garbage — including the weight-gradient (wgrad) matmuls, which
have no cross-stage dependency at all and never needed a wavefront.

The zero-bubble family (MPMD Pipeline Parallelism, arXiv 2412.14374; the
ZB-H1 schedule of Qi et al.) decouples the two halves of the backward:

- **dgrad** (grad-input): ``dx`` must flow upstream on the 1F1B wavefront
  — it IS the backward pipeline's critical path;
- **wgrad** (grad-weight): ``dp`` is a per-(stage, microbatch) leaf
  computation consumed only by the end-of-step accumulator — it can run
  ANY time after its dgrad.

The MPMD papers fill each stage's idle cooldown gaps with the deferred
wgrad work.  An SPMD scan has no per-stage idle gaps to fill — it has
*wasted slot executions* — so the equivalent transformation is to remove
the wgrad ops from the wavefront scan entirely and run them in a dedicated
drain with zero idle slots:

1. **main scan** (``M + 2(P-1)`` ticks): forward unit + dgrad unit.  The
   dgrad differentiates the stage w.r.t. its INPUT only
   (``jax.vjp(lambda x: stage_fn(params, x), x)``) so the wgrad matmuls
   are never traced into this scan's body; each completed unit queues its
   wgrad work item ``(x, g, dx)`` — saved stage input, output cotangent,
   input cotangent — at queue slot ``m`` (the trace-time analogue of the
   reference schedulers' host-side wgrad queue);
2. **wgrad drain scan** (exactly ``M`` ticks): every stage pops its own
   unit ``m`` per tick — all stages busy every tick, no wavefront, no
   bubble — and computes ``dp`` by differentiating w.r.t. PARAMS only
   (``jax.vjp(lambda p: stage_fn(p, x), params)``; the dx ops are never
   traced here).

Slot accounting (the number :func:`~...obs.aggregate.
pipeline_bubble_fraction` reports for ``schedule='zb'``): fwd and dgrad
slots each run ``M + 2(P-1)`` times for M useful, the wgrad slot runs
exactly M times — idle/total = ``4(P-1) / (3M + 4(P-1))``, vs 1F1B's
``2(P-1) / (M + 2(P-1))``: strictly lower at every (P >= 2, M), -> 2/3 of
the 1F1B bubble as M grows, and ~half of it in the deep-pipeline
small-M regime the cooldown bubble actually hurts.

Honest costs (docs/parallelism.md spells these out):

- **extra recompute**: splitting the vjp re-runs the stage forward once in
  the dgrad pass and once in the wgrad pass (the fused 1F1B backward runs
  it once).  In wall-clock units (fwd = dgrad = wgrad = recompute = 1) the
  schedule totals ``3(M + 2P - 2) + 2M`` vs 1F1B's ``4(M + 2P - 2)`` — a
  net win exactly when ``M < 2(P-1)``, the regime where the bubble
  dominates; at large M the 1F1B bubble is already small and ZB's tick
  accounting win is paid for by recompute.
- **memory**: the wgrad queue keeps ``(x, g, dx)`` per microbatch — 3M
  activation-sized buffers vs 1F1B's ``min(M, 2P-1)`` ring.  ZB trades
  1F1B's O(P) activation bound for O(M); pick the schedule per config.

TP x PP synergy (Synergistic Tensor and Pipeline Parallelism, arXiv
2510.27257): the main-scan tick issues the forward boundary ``ppermute``
BETWEEN the forward compute and the dgrad compute — its payload is only
consumed by the next tick's carry, so the whole dgrad unit (including its
SP all-gather/reduce-scatter pairs when the stage runs TP) is independent
work the latency-hiding scheduler can run under the p2p transfer; the
cotangent ``ppermute`` likewise issues after the dgrad with the next
tick's forward as its slack.  ``obs.comm_ledger.tp_pp_overlap`` reads the
achieved overlap back out of the compiled step's HLO (async
collective-permute windows containing tensor-axis collectives).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from jax.lax import axis_size
from ...dist.topology import PIPE_AXIS
from .pipeline_sched import (
    _gather_state,
    _normalized_first_fn,
    _slice_state,
    _transfer_dim,
    _zeros_like_shapes,
    is_first_stage,
    is_last_stage,
    shift_left,
    shift_right,
)

PyTree = Any


def zb_schedule_ticks(num_microbatches: int, pipe_size: int):
    """``(main_ticks, wgrad_ticks)`` of the zero-bubble schedule:
    ``M + 2(P-1)`` wavefront ticks (fwd + dgrad slots) plus exactly ``M``
    drain ticks (wgrad slot, every stage busy every tick)."""
    M, P_ = int(num_microbatches), int(pipe_size)
    return M + 2 * (P_ - 1), M


def pipeline_zb_1f1b(
    params: PyTree,
    inputs: PyTree,
    targets: PyTree,
    first_fn: Callable[[PyTree, PyTree], jnp.ndarray],
    stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    last_fn: Callable[[PyTree, jnp.ndarray, PyTree], jnp.ndarray],
    num_microbatches: int,
    pipe_axis: str = PIPE_AXIS,
    stage_takes_mb: bool = False,
    transfer_shard_axis: Optional[str] = None,
):
    """Zero-bubble 1F1B: returns ``(loss, grads)`` directly, same contract
    as :func:`~.pipeline_sched.pipeline_1f1b` (do NOT wrap in ``jax.grad``)
    and bit-compatible loss/grads with it — the dgrad and wgrad passes
    replay the exact vjp subgraphs the fused backward runs, just in two
    scans instead of one.

    Signature subset of ``pipeline_1f1b``: ``first_fn``/``stage_fn``/
    ``last_fn`` take the same arguments (``stage_takes_mb`` hands
    ``stage_fn(params, x, m)`` the microbatch index — dropout keys replay
    identically in the forward, dgrad recompute, and wgrad recompute);
    ``transfer_shard_axis`` slices the inter-stage state 1/tp exactly as
    the classic schedule does.  Not supported here: ``num_chunks > 1``
    (interleaving composes with the split but is a separate schedule) and
    ``stage_returns_aux`` — both raise in ``pipeline_1f1b`` terms by not
    existing in this signature.

    Emits ``zb_wgrad_deferred`` + ``zb_cooldown_filled`` events at trace
    time with the schedule's tick accounting (the RUNREPORT pipeline
    section and the repo-lint kind registry read these).
    """
    from ...obs.aggregate import pipeline_bubble_fraction
    from ...obs.events import emit_event
    from ..data_parallel import _mark_varying, _vma, pvary_params

    M = num_microbatches
    P_ = axis_size(pipe_axis)
    T1, T2 = zb_schedule_ticks(M, P_)
    s = jax.lax.axis_index(pipe_axis)
    first = is_first_stage(pipe_axis)
    last = is_last_stage(pipe_axis)

    emit_event(
        "zb_wgrad_deferred",
        units=M, pipe_size=P_, queue_slots=M,
        note="wgrad work items (x, g, dx) queued per microbatch at trace "
             "time; executed in the drain scan",
    )
    emit_event(
        "zb_cooldown_filled",
        main_ticks=T1, wgrad_ticks=T2, pipe_size=P_, num_microbatches=M,
        bubble_fraction=pipeline_bubble_fraction(M, P_, schedule="zb"),
        bubble_fraction_1f1b=pipeline_bubble_fraction(M, P_, schedule="1f1b"),
    )

    # pipe-pvaried params: every vjp below yields LOCAL per-stage grads;
    # the one explicit psum for pipe-replicated leaves happens in ``sync``.
    orig_params = params
    params = pvary_params(params, (pipe_axis,))

    if stage_takes_mb:
        call_stage = stage_fn  # (p, x, m)
    else:
        call_stage = lambda p, x, m: stage_fn(p, x)

    take_mb = lambda tree, i: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False),
        tree,
    )
    mb0_in = take_mb(inputs, jnp.zeros((), jnp.int32))
    mb0_tgt = take_mb(targets, jnp.zeros((), jnp.int32))

    if transfer_shard_axis is not None:
        # Sharded inter-stage state (pipeline_1f1b docstring): slice at
        # every stage exit, gather at every entry — inside the
        # differentiated fns, so the wgrad queue and both ppermute
        # channels carry 1/tp-sized state and AD stays exact.
        tax = transfer_shard_axis
        tsz = axis_size(tax)
        full_state = jax.eval_shape(first_fn, params, mb0_in)
        tdims = jax.tree.map(lambda a: _transfer_dim(a.shape, tsz), full_state)
        _first0, _stage0, _last0 = first_fn, call_stage, last_fn

        def _close_scalar(v):
            # same rationale as pipeline_1f1b: a scalar escaping the
            # slice/gather conjugate pair is tax-varying-typed but
            # value-equal; pmean restores invariance and seeds the
            # transpose with the exact 1/tp share
            return jax.lax.pmean(v, tax) if tax in _vma(v) else v

        def first_fn(p, mb):
            return _slice_state(_first0(p, mb), tdims, tax)

        def call_stage(p, x, m):
            return _slice_state(_stage0(p, _gather_state(x, tdims, tax), m),
                                tdims, tax)

        def last_fn(p, y, tgt):
            return _close_scalar(_last0(p, _gather_state(y, tdims, tax), tgt))

    # ---- state aval fixed point (same iteration as pipeline_1f1b)
    x_shape = jax.eval_shape(first_fn, params, mb0_in)
    want_vma = frozenset(getattr(x_shape, "vma", frozenset())) | {pipe_axis}
    zero_state = None
    for _ in range(8):  # bounded by the number of mesh axes
        zero_state = _zeros_like_shapes(x_shape)
        missing = tuple(a for a in want_vma if a not in _vma(zero_state))
        if missing:
            zero_state = _mark_varying(zero_state, missing)
        y_shape = jax.eval_shape(
            call_stage, params, zero_state, jnp.zeros((), jnp.int32))
        new_want = frozenset(getattr(y_shape, "vma", frozenset())) | want_vma
        if new_want == want_vma:
            break
        want_vma = new_want
    if y_shape.shape != x_shape.shape or y_shape.dtype != x_shape.dtype:
        raise ValueError(
            f"stage_fn must preserve activation shape/dtype for pipelining: "
            f"{x_shape.shape}/{x_shape.dtype} -> {y_shape.shape}/{y_shape.dtype}"
        )

    first_v, _first_missing = _normalized_first_fn(first_fn, x_shape, want_vma)
    first_vjp_in_cond = pipe_axis not in _first_missing

    def _ones_seed(v):
        one = jnp.ones(jnp.shape(v), jnp.result_type(v))
        miss = tuple(a for a in _vma(v) if a not in _vma(one))
        return _mark_varying(one, miss) if miss else one

    # ---- one dgrad unit: recompute + vjp w.r.t. the INPUT only — the
    # wgrad (param-cotangent) ops are never traced into the main scan.
    def run_dgrad(opers):
        x_saved, cot_in, mb_tgt, m_b = opers
        y_, vjp_x = jax.vjp(lambda xx: call_stage(params, xx, m_b), x_saved)

        def last_branch(op):
            y_, mb_tgt, _ = op
            # loss seed lives on the last stage; differentiate last_fn
            # w.r.t. the ACTIVATION only (its param grads are wgrad work)
            loss_m, vjp_y = jax.vjp(
                lambda yy: last_fn(params, yy, mb_tgt), y_)
            (g,) = vjp_y(_ones_seed(loss_m))
            return loss_m, g

        last_shapes = jax.eval_shape(last_branch, (y_, mb_tgt, cot_in))

        def mid_branch(op):
            _, _, cot_in = op
            zl, _ = _zeros_like_shapes(last_shapes)
            return zl, cot_in

        loss_m, g = jax.lax.cond(last, last_branch, mid_branch,
                                 (y_, mb_tgt, cot_in))
        (dx,) = vjp_x(g)
        return loss_m, g, dx

    # ---- carry init
    _zvma = _vma(zero_state)

    def _stacked(n):
        def one(a):
            if _zvma:
                return jax.ShapeDtypeStruct((n,) + a.shape, a.dtype, vma=_zvma)
            return jax.ShapeDtypeStruct((n,) + a.shape, a.dtype)

        return _zeros_like_shapes(
            jax.tree.map(one, jax.eval_shape(lambda z: z, zero_state)))

    # the wgrad queue IS the activation ring: slot m holds microbatch m's
    # stage input (written by the fwd unit), output cotangent g and input
    # cotangent dx (written by the dgrad unit) — O(M), not O(P); see the
    # module docstring's memory note
    qx0, qg0, qdx0 = _stacked(M), _stacked(M), _stacked(M)
    cot0 = zero_state
    dgrad_shapes = jax.eval_shape(
        run_dgrad, (zero_state, cot0, mb0_tgt, jnp.zeros((), jnp.int32)))
    loss0, _, _ = _zeros_like_shapes(dgrad_shapes)

    def tick(carry, t):
        state, cot_state, qx, qg, qdx, loss_sum = carry

        # -------- forward unit: wavefront m_f = t - s
        k_f = t - s
        f_active = (k_f >= 0) & (k_f < M)
        m_f = jnp.clip(k_f, 0, M - 1)
        mb_in = take_mb(inputs, m_f)
        x = jax.lax.cond(
            first, lambda op: first_v(params, op[0]), lambda op: op[1],
            (mb_in, state))
        y = call_stage(params, x, m_f)
        qx = jax.lax.cond(
            f_active,
            lambda b: jax.tree.map(
                lambda buf, v: jax.lax.dynamic_update_index_in_dim(
                    buf, v, m_f, axis=0), b, x),
            lambda b: b,
            qx,
        )

        # Issue the forward boundary ppermute HERE, between the forward
        # and dgrad computes: its payload is consumed only by the next
        # tick's carry, so the whole dgrad unit below — including the SP
        # all-gather/reduce-scatter pairs of a TP stage — is independent
        # work the latency-hiding scheduler can hide the transfer behind
        # (the synergy-paper ordering, arXiv 2510.27257).
        nxt = shift_right(y, pipe_axis)

        # -------- dgrad unit: wavefront m_b = t - 2(P-1) + s; runs
        # unconditionally (uniform-body rule — a collective inside a
        # branch-divergent cond is undefined), accumulation masked
        k_b = t - (P_ - 1 - s) - (P_ - 1)
        b_active = (k_b >= 0) & (k_b < M)
        m_b = jnp.clip(k_b, 0, M - 1)
        x_saved = jax.tree.map(
            lambda buf: jax.lax.dynamic_index_in_dim(
                buf, m_b, axis=0, keepdims=False), qx)
        loss_m, g, dx = run_dgrad(
            (x_saved, cot_state, take_mb(targets, m_b), m_b))
        mask_b = lambda v: jnp.where(b_active, v, jnp.zeros((), v.dtype))
        loss_m = mask_b(loss_m)
        dx = jax.tree.map(mask_b, dx)
        # queue the wgrad work item (g, dx) at slot m_b for the drain
        qg, qdx = jax.lax.cond(
            b_active,
            lambda b: tuple(
                jax.tree.map(
                    lambda buf, v: jax.lax.dynamic_update_index_in_dim(
                        buf, v, m_b, axis=0), bi, vi)
                for bi, vi in zip(b, (g, dx))),
            lambda b: b,
            (qg, qdx),
        )
        loss_sum = loss_sum + loss_m
        cot_nxt = shift_left(dx, pipe_axis)
        return (nxt, cot_nxt, qx, qg, qdx, loss_sum), None

    (_, _, qx, qg, qdx, loss_sum), _ = jax.lax.scan(
        tick, (zero_state, cot0, qx0, qg0, qdx0, loss0), jnp.arange(T1))

    # ---- wgrad drain: M ticks, every stage pops its own unit m = j per
    # tick — no wavefront, no idle slots.  Differentiates w.r.t. PARAMS
    # only; the dx ops are never traced here.
    def first_branch(op):
        mb_in, dxm = op
        _, vjp_fp = jax.vjp(lambda p: first_v(p, mb_in), params)
        (dp_first,) = vjp_fp(dxm)
        return dp_first

    def run_wgrad(opers):
        """One deferred wgrad unit: total dp = dp_stage + dp_last +
        dp_first for queued microbatch ``m`` — exactly the param-cotangent
        half the fused 1F1B backward computes, replayed from the queue."""
        x_q, g_q, dx_q, mb_in, mb_tgt, m = opers

        # stage wgrad (the deferred work): recompute + vjp w.r.t. params
        y2, vjp_p = jax.vjp(lambda p: call_stage(p, x_q, m), params)
        (dp_stage,) = vjp_p(g_q)

        # last_fn's param grads (head/loss-side weights), y held fixed —
        # the dp_last partial the fused backward's last_branch computes
        def last_p_branch(op):
            y2, mb_tgt = op
            loss2, vjp_lp = jax.vjp(
                lambda p: last_fn(p, y2, mb_tgt), params)
            (dp_last,) = vjp_lp(_ones_seed(loss2))
            return dp_last

        last_p_shapes = jax.eval_shape(last_p_branch, (y2, mb_tgt))
        dp_last = jax.lax.cond(
            last, last_p_branch,
            lambda op: _zeros_like_shapes(last_p_shapes), (y2, mb_tgt))

        # first_fn's param grads (embed), seeded with the queued dx
        if first_vjp_in_cond:
            first_shapes = jax.eval_shape(first_branch, (mb_in, dx_q))
            dp_first = jax.lax.cond(
                first, first_branch,
                lambda op: _zeros_like_shapes(first_shapes), (mb_in, dx_q))
        else:
            # degenerate first_fn (ignores params): its vjp contains a
            # pipe psum and must run unconditionally — mask cotangent in,
            # (pipe-replicated) grad out, as pipeline_1f1b does
            dxm = jax.tree.map(
                lambda a: jnp.where(first, a, jnp.zeros((), a.dtype)), dx_q)
            dp_first = first_branch((mb_in, dxm))
            dp_first = jax.tree.map(
                lambda gr: gr * first.astype(jnp.result_type(gr)), dp_first)
        return jax.tree.map(
            lambda a, b, c: a + b + c, dp_stage, dp_last, dp_first)

    grads0 = _zeros_like_shapes(jax.eval_shape(
        run_wgrad,
        (zero_state, zero_state, zero_state, mb0_in, mb0_tgt,
         jnp.zeros((), jnp.int32))))

    def wtick(grads_acc, j):
        pop = lambda q: jax.tree.map(
            lambda buf: jax.lax.dynamic_index_in_dim(
                buf, j, axis=0, keepdims=False), q)
        dp = run_wgrad((pop(qx), pop(qg), pop(qdx),
                        take_mb(inputs, j), take_mb(targets, j), j))
        return jax.tree.map(jnp.add, grads_acc, dp), None

    grads, _ = jax.lax.scan(wtick, grads0, jnp.arange(T2))

    # mean over microbatches; broadcast the last stage's loss everywhere
    loss = jax.lax.psum(loss_sum, pipe_axis) / M
    inv = 1.0 / M

    def sync(g, p):
        g = g * inv
        if pipe_axis in _vma(p):
            return g
        if pipe_axis in _vma(g):
            return jax.lax.psum(g, pipe_axis)
        return g

    grads = jax.tree.map(lambda g, p: sync(g, p), grads, orig_params)
    return loss, grads
