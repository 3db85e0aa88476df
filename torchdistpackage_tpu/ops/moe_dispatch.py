"""Fused MoE expert dispatch as a Pallas TPU kernel.

Both training-side dispatch materializations in ``parallel/moe.py`` pay an
HBM round trip the expert matmul never needed: the dense path builds
[T, E, C] one-hot dispatch/combine tensors, and the index ('sorted') path
scatter-adds every kept token row into an [E, C, D] slot view, runs the
expert FFN over it, and gathers the slots back per token — O(E·C·D) HBM
written AND re-read per layer, whatever the actual expert load.  The
serving ragged path (``moe_serve_forward``) still materializes the
[T·k, D] expert-grouped row gather before its grouped GEMMs.

This kernel removes the round trip, the same treatment the attention path
got in ``ops/paged_attention.py``: the ``_top_k_route`` decision is
compressed into two tiny maps — ``idx`` [E, C] (the token occupying each
capacity slot) and ``comb`` [E, C] (its renormalized gate weight, 0 for
empty or capacity-dropped slots) — and ``idx`` rides scalar prefetch into
SMEM exactly like the paged block table, pointed at token slots instead of
KV blocks.  The grid runs (expert, capacity-tile); each program DMAs its
expert's weights into VMEM once per tile row, gathers its C_TILE token
rows from HBM by dynamic index, runs the expert FFN (w1/w3/w2 — SwiGLU
and 2-weight experts via the same ``w1.ndim`` structural dispatch the
package uses everywhere), and scatter-adds the gate-weighted outputs back
into the [T, D] output in-register.  No [T, E, C] dispatch tensor and no
gathered [E, C, D] slot view ever exists in HBM.  A capacity tile whose
``comb`` row is all zero (padding, or an underloaded expert) skips its
gather AND its matmuls entirely — the ragged path's "pay only for real
rows" property at tile granularity, which is what lets serving run this
kernel at the no-drop capacity bound without the E/top_k padded-compute
tax.

int8 expert weights ((q8, scale) leaf pairs from
:func:`quantize_moe_experts`) are dequantized in-register next to the
matmul that consumes them — the EQuARX thesis (PAPERS.md 2506.17615)
extended from wire collectives and the KV pool to the expert weights.

Numerics: gather, matmuls, and combine run in f32 (matching the oracle);
the per-token accumulation ORDER differs from the jnp paths (slot-major
scatter-add vs choice-major gather-sum), so outputs agree to float
tolerance and greedy decode tokens bit-match the gather arms
(tests/test_moe_dispatch.py locks dense, EP-sharded, SwiGLU, and int8).
:func:`moe_ffn_oracle` — the pure-JAX gather → FFN → scatter-add that
DOES materialize the [E, C, D] slot view — stays in-tree as the parity
oracle and as the backward: :func:`fused_moe_ffn` is a ``jax.custom_vjp``
whose bwd differentiates the oracle (same math, so grads are exact to the
oracle's own tolerance; the int routing args get float0 cotangents).

The kernel runs in the Pallas interpreter only (on CPU, automatically: the
``_interpret`` switch shared with ops/flash_attention.py).  It does not
lower for TPU as written — the ``comb`` BlockSpec ``(1, c_tile)`` over
``[E, Cp]`` breaks the (8, 128) rule, it reads and writes rows of
``ANY``-space refs directly where Mosaic wants a DMA, and it asks for a
whole expert's ``w1``/``w2`` as one VMEM block — so
:func:`resolve_moe_dispatch` never picks it and it has no chip number.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret, _out_struct

PyTree = Any

#: Capacity slots per grid step.  8 sublanes is the f32 tile floor; 128
#: keeps the gather loop short while the per-tile matmul stays MXU-sized.
_C_TILE_MAX = 128
#: Output rows zeroed per store in the first-step init loop.
_ZERO_TILE = 8


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _is_q(w) -> bool:
    return isinstance(w, tuple)


def _dequant(w) -> jnp.ndarray:
    """(q8, scale) -> f32; float leaves upcast to f32 (oracle numerics)."""
    if _is_q(w):
        q, s = w
        return q.astype(jnp.float32) * s[..., None, :]
    return w.astype(jnp.float32)


def quantize_moe_experts(experts: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
    """Per-expert, per-output-feature symmetric int8 for the matmul
    weights (w1/w2 -> ``(q8, scale)`` pairs; biases stay float) — the
    same leaf convention as the int8 KV pool, consumed fused by both the
    kernel and the oracle."""

    def q(w):
        s = jnp.max(jnp.abs(w), axis=-2) / 127.0  # reduce the contracted dim
        s = jnp.maximum(s, 1e-8)
        q8 = jnp.clip(jnp.round(w / s[..., None, :]), -127, 127).astype(jnp.int8)
        return q8, s.astype(jnp.float32)

    return {
        "w1": q(experts["w1"]),
        "b1": experts["b1"],
        "w2": q(experts["w2"]),
        "b2": experts["b2"],
    }


def modeled_slot_view_bytes(num_experts: int, capacity: int, dim: int,
                            itemsize: int = 4) -> int:
    """HBM bytes of the [E, C, D] gathered slot view the jnp dispatch
    paths materialize (written by dispatch, re-read by combine — hence
    2x) and the fused kernel never allocates.  The static-ledger evidence
    test checks the compiled programs against exactly this shape."""
    return 2 * num_experts * capacity * dim * itemsize


def slot_maps(
    gate_vals: jnp.ndarray,
    gate_idx: jnp.ndarray,
    slot: jnp.ndarray,
    keep: jnp.ndarray,
    capacity: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compress a ``_top_k_route`` decision into the kernel's two [E, C]
    maps: ``idx`` (token occupying each slot; 0 where empty — harmless,
    its weight is 0) and ``comb`` (the renormalized gate weight of that
    (token, choice), 0 for empty or dropped slots).  The ``comb`` build is
    a linear scatter of ``gate_vals``, so gradients flow through it — the
    oracle (hence the fused bwd) differentiates the router through these
    maps."""
    T, k = gate_idx.shape
    E = keep.shape[-1]
    kept = jnp.sum(keep, axis=-1)  # [T, k] 1 iff the choice fit capacity
    dest = jnp.where(
        kept > 0, gate_idx * capacity + slot, E * capacity
    ).reshape(-1)  # dropped choices land on a dumpster entry, sliced off
    tok = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[:, None], (T, k)).reshape(-1)
    idx = (
        jnp.zeros((E * capacity + 1,), jnp.int32).at[dest].set(tok)
    )[: E * capacity].reshape(E, capacity)
    comb = (
        jnp.zeros((E * capacity + 1,), jnp.float32)
        .at[dest]
        .set((gate_vals * kept).astype(jnp.float32).reshape(-1))
    )[: E * capacity].reshape(E, capacity)
    return idx, comb


def _ffn_rows(xs, w1, b1, w2, b2):
    """Expert FFN on [G, D] rows against ONE expert's dequantized f32
    weights — the math both the kernel tile and the oracle slot view run;
    a 3-dim ``w1`` ([2, D, F]) is the stacked gate/up SwiGLU expert."""
    if w1.ndim == 3:
        g = jnp.dot(xs, w1[0], preferred_element_type=jnp.float32) + b1[0]
        u = jnp.dot(xs, w1[1], preferred_element_type=jnp.float32) + b1[1]
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(
            jnp.dot(xs, w1, preferred_element_type=jnp.float32) + b1)
    return jnp.dot(h, w2, preferred_element_type=jnp.float32) + b2


def moe_ffn_oracle(
    experts: Dict[str, Any],
    tokens: jnp.ndarray,
    gate_vals: jnp.ndarray,
    gate_idx: jnp.ndarray,
    slot: jnp.ndarray,
    keep: jnp.ndarray,
    capacity: int,
) -> jnp.ndarray:
    """Pure-JAX parity oracle AND the fused kernel's backward: gather the
    [E, C, D] slot view (the HBM buffer the kernel exists to eliminate —
    its presence in THIS path's compiled program is the static-ledger
    evidence), run the expert FFN, weighted-scatter-add per token.
    Differentiable in ``experts`` / ``tokens`` / ``gate_vals``."""
    T, D = tokens.shape
    E = keep.shape[-1]
    idx, comb = slot_maps(gate_vals, gate_idx, slot, keep, capacity)
    filled = (comb != 0.0)[..., None]
    slot_view = jnp.where(
        filled, tokens.astype(jnp.float32)[idx], 0.0)  # [E, C, D]
    w1 = _dequant(experts["w1"])
    w2 = _dequant(experts["w2"])
    b1 = experts["b1"].astype(jnp.float32)
    b2 = experts["b2"].astype(jnp.float32)
    out = jax.vmap(
        lambda xs, a, c, d, e: _ffn_rows(xs, a, c, d, e)
    )(slot_view, w1, b1, w2, b2)  # [E, C, D]
    y = jnp.zeros((T, D), jnp.float32).at[idx.reshape(-1)].add(
        comb.reshape(-1, 1) * out.reshape(E * capacity, D))
    return y.astype(tokens.dtype)


# ------------------------------------------------------------------ kernel


def _kernel(idx_ref, comb_ref, x_ref, *refs, Cp, c_tile, Tp, D, swiglu,
            quantized):
    """Grid ``(expert e, capacity-tile c)``.  ``refs``: the per-expert
    weight blocks (w1[, w1_scale], b1, w2[, w2_scale], b2), then the
    [Tp, D] output ref (ANY memory, read-modify-write — safe because the
    TPU grid executes sequentially) and the [c_tile, D] gather scratch."""
    pos = 0
    w1_ref = refs[pos]; pos += 1
    if quantized:
        w1s_ref = refs[pos]; pos += 1
    b1_ref = refs[pos]; pos += 1
    w2_ref = refs[pos]; pos += 1
    if quantized:
        w2s_ref = refs[pos]; pos += 1
    b2_ref = refs[pos]; pos += 1
    o_ref, xs_ref = refs[pos], refs[pos + 1]

    e = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when((e == 0) & (c == 0))
    def _zero_out():
        def body(i, _):
            o_ref[pl.ds(i * _ZERO_TILE, _ZERO_TILE), :] = jnp.zeros(
                (_ZERO_TILE, D), jnp.float32)
            return 0

        jax.lax.fori_loop(0, Tp // _ZERO_TILE, body, 0)

    comb = comb_ref[0]  # [c_tile]

    # an all-empty tile (padding, or an underloaded expert at the no-drop
    # serving capacity bound) skips gather AND matmuls — compute tracks
    # the tokens actually routed, not the static capacity
    @pl.when(jnp.any(comb != 0.0))
    def _compute():
        base = e * Cp + c * c_tile

        def gather(i, _):
            t = idx_ref[base + i]
            row = x_ref[pl.ds(t, 1), :]
            row = jnp.where(comb[i] != 0.0, row.astype(jnp.float32), 0.0)
            xs_ref[pl.ds(i, 1), :] = row
            return 0

        jax.lax.fori_loop(0, c_tile, gather, 0)

        xs = xs_ref[...]  # [c_tile, D] f32
        if quantized:
            if swiglu:
                w1 = w1_ref[0].astype(jnp.float32) * w1s_ref[0][:, None, :]
            else:
                w1 = w1_ref[0].astype(jnp.float32) * w1s_ref[0][None, :]
            w2 = w2_ref[0].astype(jnp.float32) * w2s_ref[0][None, :]
        else:
            w1 = w1_ref[0].astype(jnp.float32)
            w2 = w2_ref[0].astype(jnp.float32)
        out = _ffn_rows(
            xs, w1, b1_ref[0].astype(jnp.float32), w2,
            b2_ref[0].astype(jnp.float32))  # [c_tile, D]

        def scatter(i, _):
            t = idx_ref[base + i]

            @pl.when(comb[i] != 0.0)
            def _add():
                upd = comb[i] * jax.lax.dynamic_slice_in_dim(out, i, 1, 0)
                o_ref[pl.ds(t, 1), :] = o_ref[pl.ds(t, 1), :] + upd

            return 0

        jax.lax.fori_loop(0, c_tile, scatter, 0)


def _compiler_params():
    if _interpret():
        return None
    # the output is accumulated read-modify-write across grid steps, so
    # every dimension must execute sequentially
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))


def _pallas_moe_ffn(
    experts: Dict[str, Any],
    tokens: jnp.ndarray,
    idx: jnp.ndarray,
    comb: jnp.ndarray,
) -> jnp.ndarray:
    """Run the fused kernel for one layer.  ``idx``/``comb``: the [E, C]
    slot maps from :func:`slot_maps`.  Returns [T, D] f32."""
    T, D = tokens.shape
    E, C = idx.shape
    quantized = _is_q(experts["w1"])
    w1 = experts["w1"][0] if quantized else experts["w1"]
    swiglu = w1.ndim == 4

    c_tile = min(_C_TILE_MAX, _round_up(C, 8))
    Cp = _round_up(C, c_tile)
    Tp = _round_up(T, _ZERO_TILE)
    if Cp != C:
        idx = jnp.pad(idx, ((0, 0), (0, Cp - C)))
        comb = jnp.pad(comb, ((0, 0), (0, Cp - C)))
    x = tokens
    if Tp != T:
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))

    operands = []
    in_specs = [
        pl.BlockSpec((1, c_tile), lambda e, c, i: (e, c)),  # comb
        pl.BlockSpec(memory_space=pl.ANY),               # tokens
    ]
    operands.extend([comb, x])

    def add_w(wname):
        w = experts[wname]
        if _is_q(w):
            q, s = w
            operands.append(q)
            in_specs.append(pl.BlockSpec(
                (1,) + q.shape[1:], lambda e, c, i, n=q.ndim: (e,) + (0,) * (n - 1)))
            operands.append(s)
            in_specs.append(pl.BlockSpec(
                (1,) + s.shape[1:], lambda e, c, i, n=s.ndim: (e,) + (0,) * (n - 1)))
        else:
            operands.append(w)
            in_specs.append(pl.BlockSpec(
                (1,) + w.shape[1:], lambda e, c, i, n=w.ndim: (e,) + (0,) * (n - 1)))

    for name in ("w1", "b1", "w2", "b2"):
        add_w(name)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E, Cp // c_tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((c_tile, D), jnp.float32)],
    )
    kernel = functools.partial(
        _kernel, Cp=Cp, c_tile=c_tile, Tp=Tp, D=D, swiglu=swiglu,
        quantized=quantized)
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_out_struct((Tp, D), jnp.float32, tokens),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="moe_fused_ffn",
    )(idx.reshape(-1), *operands)
    return y[:T]


# ------------------------------------------------------------- entry points


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_diff(capacity, experts, tokens, gate_vals, gate_idx, slot, keep):
    idx, comb = slot_maps(gate_vals, gate_idx, slot, keep, capacity)
    return _pallas_moe_ffn(experts, tokens, idx, comb).astype(tokens.dtype)


def _fused_fwd(capacity, experts, tokens, gate_vals, gate_idx, slot, keep):
    y = _fused_diff(capacity, experts, tokens, gate_vals, gate_idx, slot, keep)
    return y, (experts, tokens, gate_vals, gate_idx, slot, keep)


def _fused_bwd(capacity, res, g):
    experts, tokens, gate_vals, gate_idx, slot, keep = res
    _, vjp = jax.vjp(
        lambda e, t, gv, kp: moe_ffn_oracle(
            e, t, gv, gate_idx, slot, kp, capacity),
        experts, tokens, gate_vals, keep,
    )
    de, dt, dgv, dkp = vjp(g)

    def f0(a):
        return np.zeros(a.shape, jax.dtypes.float0)

    return de, dt, dgv, f0(gate_idx), f0(slot), dkp


_fused_diff.defvjp(_fused_fwd, _fused_bwd)


def fused_moe_ffn(
    experts: Dict[str, Any],
    tokens: jnp.ndarray,
    gate_vals: jnp.ndarray,
    gate_idx: jnp.ndarray,
    slot: jnp.ndarray,
    keep: jnp.ndarray,
    capacity: int,
) -> jnp.ndarray:
    """Fused gather -> expert FFN -> weighted scatter-add over a
    ``_top_k_route`` decision.  tokens [T, D] -> [T, D] in tokens.dtype;
    no [T, E, C] dispatch tensor or [E, C, D] slot view in HBM.

    Differentiable (``jax.custom_vjp``: forward = the Pallas kernel,
    backward = ``jax.vjp`` through :func:`moe_ffn_oracle` — identical
    math, so train-step goldens hold at float tolerance).  int8
    ``(q8, scale)`` expert weights (:func:`quantize_moe_experts`) are
    consumed forward-only with in-register dequant."""
    if _is_q(experts["w1"]) or _is_q(experts["w2"]):
        idx, comb = slot_maps(gate_vals, gate_idx, slot, keep, capacity)
        return _pallas_moe_ffn(experts, tokens, idx, comb).astype(tokens.dtype)
    return _fused_diff(
        int(capacity), experts, tokens, gate_vals, gate_idx, slot, keep)


# ------------------------------------------- EP-sharded expert FFN kernel


def _ep_kernel(x_ref, *refs, swiglu, quantized):
    """Grid ``(local expert, group-tile)``: the expert-FFN matmul leg of
    the fused path for EP-sharded layers — the all_to_all exchange needs
    the [e_loc, G, D] grouped layout in HBM (it IS the wire payload), so
    only the FFN fuses; dispatch/combine stay with the exchange."""
    pos = 0
    w1_ref = refs[pos]; pos += 1
    if quantized:
        w1s_ref = refs[pos]; pos += 1
    b1_ref = refs[pos]; pos += 1
    w2_ref = refs[pos]; pos += 1
    if quantized:
        w2s_ref = refs[pos]; pos += 1
    b2_ref = refs[pos]; pos += 1
    o_ref = refs[pos]
    xs = x_ref[0].astype(jnp.float32)  # [g_tile, D]
    if quantized:
        if swiglu:
            w1 = w1_ref[0].astype(jnp.float32) * w1s_ref[0][:, None, :]
        else:
            w1 = w1_ref[0].astype(jnp.float32) * w1s_ref[0][None, :]
        w2 = w2_ref[0].astype(jnp.float32) * w2s_ref[0][None, :]
    else:
        w1 = w1_ref[0].astype(jnp.float32)
        w2 = w2_ref[0].astype(jnp.float32)
    out = _ffn_rows(
        xs, w1, b1_ref[0].astype(jnp.float32), w2,
        b2_ref[0].astype(jnp.float32))
    o_ref[0] = out.astype(o_ref.dtype)


def _ep_ffn_reference(experts, x):
    """jnp reference/backward for :func:`fused_expert_ffn` (f32)."""
    w1 = _dequant(experts["w1"])
    w2 = _dequant(experts["w2"])
    b1 = experts["b1"].astype(jnp.float32)
    b2 = experts["b2"].astype(jnp.float32)
    out = jax.vmap(
        lambda xs, a, c, d, e: _ffn_rows(xs.astype(jnp.float32), a, c, d, e)
    )(x, w1, b1, w2, b2)
    return out.astype(x.dtype)


@jax.custom_vjp
def _ep_diff(experts, x):
    return _pallas_expert_ffn(experts, x)


def _ep_fwd(experts, x):
    return _ep_diff(experts, x), (experts, x)


def _ep_bwd(res, g):
    experts, x = res
    _, vjp = jax.vjp(_ep_ffn_reference, *res)
    return vjp(g)


_ep_diff.defvjp(_ep_fwd, _ep_bwd)


def _pallas_expert_ffn(experts, x):
    e_loc, G, D = x.shape
    quantized = _is_q(experts["w1"])
    w1 = experts["w1"][0] if quantized else experts["w1"]
    swiglu = w1.ndim == 4

    g_tile = min(_C_TILE_MAX, _round_up(G, 8))
    Gp = _round_up(G, g_tile)
    if Gp != G:
        x = jnp.pad(x, ((0, 0), (0, Gp - G), (0, 0)))

    operands = [x]
    in_specs = [pl.BlockSpec((1, g_tile, D), lambda e, g: (e, g, 0))]

    def add_w(wname):
        w = experts[wname]
        leaves = w if _is_q(w) else (w,)
        for leaf in leaves:
            operands.append(leaf)
            in_specs.append(pl.BlockSpec(
                (1,) + leaf.shape[1:],
                lambda e, g, n=leaf.ndim: (e,) + (0,) * (n - 1)))

    for name in ("w1", "b1", "w2", "b2"):
        add_w(name)

    kernel = functools.partial(_ep_kernel, swiglu=swiglu, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid=(e_loc, Gp // g_tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g_tile, D), lambda e, g: (e, g, 0)),
        out_shape=_out_struct((e_loc, Gp, D), x.dtype, x),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="moe_expert_ffn",
    )(*operands)
    return out[:, :G]


def fused_expert_ffn(experts: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
    """Pallas expert FFN on EP-exchanged groups: x [e_loc, G, D] ->
    [e_loc, G, D] (``moe_forward``'s drop-in for ``_expert_ffn`` under
    ``dispatch='pallas'`` + ``ep_axis``).  Differentiable for float
    weights; int8 pairs run forward-only with fused dequant."""
    if _is_q(experts["w1"]) or _is_q(experts["w2"]):
        return _pallas_expert_ffn(experts, x)
    return _ep_diff(experts, x)


# ---------------------------------------------------------------- resolve


def resolve_moe_dispatch(dispatch: Optional[str]) -> str:
    """``'auto'``/None -> ``'sorted'`` on TPU (the one dispatch with a chip
    record: it beat ``'dense'`` there), ``'auto'`` (the size-based
    dense/sorted selection) elsewhere.  ``'auto'`` never picks ``'pallas'``:
    the fused kernel runs in the Pallas interpreter only — its BlockSpecs
    and its row accesses to ``ANY``-space refs do not lower for TPU — so an
    explicit ``'pallas'`` on a TPU raises at lowering.  Explicit values
    pass through validated.  The choice is recorded on the event timeline
    (``moe_dispatch_selected``)."""
    if dispatch in (None, "auto"):
        chosen = "sorted" if jax.default_backend() == "tpu" else "auto"
        from ..obs.events import emit_event

        emit_event("moe_dispatch_selected", requested="auto", chosen=chosen,
                   backend=jax.default_backend())
        return chosen
    if dispatch not in ("dense", "sorted", "pallas"):
        raise ValueError(
            "moe dispatch must be 'dense', 'sorted', 'pallas' or 'auto', "
            f"got {dispatch!r}")
    return dispatch
