"""Paged decode attention as a Pallas TPU kernel (vLLM PagedAttention
lineage): walk the per-slot block table *inside* the kernel.

The serving engine's gather path (serving/paged_cache.py ``gather_kv``)
materializes every slot's blocks into a contiguous ``[B, Hkv,
max_blocks*bs, hd]`` view before the dense ``_cached_attention`` — O(max
context) HBM read AND written per decode tick, whatever the slot's actual
length, plus an f32 upcast temp of the same size on the int8 pool.  This
kernel removes that round trip: the grid runs ``(slot, kv_head,
kv-block-step)`` and each program DMAs ONE pool block into VMEM through a
scalar-prefetched block table (``PrefetchScalarGridSpec`` — the table IS
the index map), runs online-softmax flash accumulation against it with
per-row position masking, and stops issuing fresh fetches past the slot's
live length (the index map clamps dead steps onto the last live block, so
Mosaic's block-revisit elision skips the re-fetch).  Per-tick attention
HBM traffic scales with the tokens a slot actually holds, VMEM per
program is O(block) — which is what opens 32k+ serving contexts
(docs/long_context.md) on the same pool.

One entry point covers every serving shape:

- ``S_in = 1`` ordinary decode, ``S_in = K+1`` the speculative verify
  step, ``S_in = chunk`` chunked prefill — all the same kernel, so both
  compiled engine programs ride it;
- scalar or ``[B]``-vector offsets (each slot at its own depth);
- GQA: q heads grouped per KV head OUTSIDE the kernel (a reshape, not a
  repeat) — a KV block is fetched once per group;
- sliding-window masking (Mistral semantics, matching
  ``_cached_attention``);
- int8 pools: ``(q8, scale)`` block pairs are dequantized IN-REGISTER —
  the scale folds into the scores (k) / probabilities (v) exactly as the
  gather path folds it, but the f32 gathered view is never materialized,
  extending the EQuARX thesis (PAPERS.md 2506.17615 — keep quantized
  bytes quantized until the compute that consumes them) from wire
  collectives to the KV-cache read path.

Numerics: scores and the online softmax run in f32 (matching the gather
path's f32 softmax); the accumulation ORDER differs (blockwise online
rescale vs one full-row softmax), so logits agree to float tolerance and
greedy tokens bit-match the gather goldens (tests/test_paged_attention.py
locks dense, GQA, sliding-window, vector offsets, and the K+1 verify
shape).  The gather path stays in-tree as the parity oracle.

On CPU the kernel runs in Pallas interpreter mode automatically (same
``_interpret`` switch as ops/flash_attention.py), so every test exercises
the identical code path the TPU compiles.

Tuning: ``fetch_width`` (pool blocks streamed per grid step — each is an
independent BlockSpec input, so Mosaic pipelines the DMAs) and
``q_pad_to`` (pad the in-kernel q rows to a tile-friendly multiple; the
K+1 verify shape lands at awkward row counts like G*(K+1)) come from the
per-chip table :data:`_PAGED_PARAMS` (tools/flash_tune.py ``--paged``
measures candidates for a row).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret, _out_struct

NEG_INF = -1e30  # finite "minus infinity": avoids (-inf) - (-inf) NaNs

_LANES = 128  # m/l scratch keeps a full lane dim for layout friendliness

#: Kernel parameters by device_kind substring.  ``fetch_width`` = pool
#: blocks streamed per grid step; ``q_pad_to`` = q-row padding multiple (the
#: K+1 verify shape's G*(K+1) rows are rarely tile-aligned).  The v5e row
#: compiles and matches the gather oracle on the chip but was never TUNED
#: there: tools/flash_tune.py ``--paged`` has not run on a v5e, so read it as
#: "works", not "fastest".  The cpu row is the Pallas interpreter's.  A chip
#: with no row is an error.
_PAGED_PARAMS = (
    ("v5 lite", {"fetch_width": 4, "q_pad_to": 8}),
    ("v5e", {"fetch_width": 4, "q_pad_to": 8}),
    ("cpu", {"fetch_width": 1, "q_pad_to": 8}),
)


def paged_params_for(device_kind: str) -> dict:
    dk = device_kind.lower()
    for sub, params in _PAGED_PARAMS:
        if sub in dk:
            return dict(params)
    raise ValueError(
        f"paged_attention: no parameter row for device_kind={device_kind!r}; "
        "run tools/flash_tune.py --paged on this chip and add a "
        "_PAGED_PARAMS row")


def default_paged_params() -> dict:
    """``{fetch_width, q_pad_to}`` for the attached chip, from
    :data:`_PAGED_PARAMS`."""
    return paged_params_for(jax.devices()[0].device_kind)


def resolve_attn_impl(impl: Optional[str]) -> str:
    """``'auto'``/None -> ``'pallas'`` on TPU, ``'gather'`` elsewhere (the
    interpreter-mode kernel is correct on CPU but slow — tests opt in
    explicitly).  Explicit values pass through validated."""
    if impl in (None, "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "gather"
    if impl not in ("pallas", "gather"):
        raise ValueError(
            f"attn_impl must be 'pallas', 'gather' or 'auto', got {impl!r}")
    return impl


def _compiler_params():
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def _kernel(
    tab_ref, off_ref, lay_ref, q_ref, *refs,
    S_in, bs, window, sm_scale, quantized, fetch_width, rows,
):
    """Grid ``(slot b, kv-head h, kv-step j)``; ``lay_ref`` (the layer of
    the stacked pool) is read by the index maps alone; ``refs`` carries the
    ``fetch_width`` per-step KV blocks ((k, v) dense or (k8, ks, v8, vs)
    quantized, sub-block-major), then the output ref and the (acc, m, l)
    online-softmax VMEM scratch carried across j steps."""
    per = 4 if quantized else 2
    kv_refs = refs[:fetch_width * per]
    o_ref = refs[fetch_width * per]
    acc_ref, m_ref, l_ref = refs[fetch_width * per + 1:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    off = off_ref[b]
    hi = (off + S_in + bs - 1) // bs  # live KV blocks for this slot

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # [rows, hd]
    # row r covers query position off + (r % S_in) (group-major rows);
    # padded rows past the real R mask everything and are sliced off
    qpos = off + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) % S_in

    for i in range(fetch_width):
        blk = j * fetch_width + i  # absolute pool-block step

        @pl.when(blk < hi)
        def _compute(i=i, blk=blk):
            if quantized:
                k8 = kv_refs[4 * i][0, 0, 0]
                ks = kv_refs[4 * i + 1][0, 0, 0]  # [1, bs]
                v8 = kv_refs[4 * i + 2][0, 0, 0]
                vs = kv_refs[4 * i + 3][0, 0, 0]  # [1, bs]
                kblk = k8.astype(jnp.float32)
                s = jnp.dot(q.astype(jnp.float32), kblk.T,
                            preferred_element_type=jnp.float32)
                s = s * ks
            else:
                kblk = kv_refs[2 * i][0, 0, 0]
                s = jnp.dot(q, kblk.T,
                            preferred_element_type=jnp.float32)
            s = s * sm_scale
            kpos = blk * bs + jax.lax.broadcasted_iota(
                jnp.int32, (rows, bs), 1)
            keep = kpos <= qpos
            if window is not None:  # Mistral: key in (qpos - window, qpos]
                keep = keep & (kpos > qpos - window)
            s = jnp.where(keep, s, NEG_INF)
            m = m_ref[:, :1]
            l = l_ref[:, :1]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_ref[...] = jnp.broadcast_to(
                l * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
            if quantized:
                pv = p * vs
                upd = jnp.dot(pv, v8.astype(jnp.float32),
                              preferred_element_type=jnp.float32)
            else:
                vblk = kv_refs[2 * i + 1][0, 0, 0]
                upd = jnp.dot(p.astype(vblk.dtype), vblk,
                              preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * corr + upd
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == (hi - 1) // fetch_width)
    def _write():
        # l > 0 for every real row (a query always attends its own
        # position); padded rows divide garbage that is sliced away
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: Any,
    v_pool: Any,
    tables: jnp.ndarray,
    offsets,
    *,
    layer=None,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    fetch_width: Optional[int] = None,
    q_pad_to: Optional[int] = None,
) -> jnp.ndarray:
    """Attention of ``q`` [B, H, S_in, hd] against each slot's paged
    context, walking the block table in-kernel.

    ``k_pool``/``v_pool``: the WHOLE pool ``[L, num_blocks, Hkv, bs, hd]``
    (or its int8 ``(q8 [..., hd], scale [...])`` pair) and ``layer`` (an
    int, traced or not) naming the layer to read: the layer is one more
    scalar-prefetch operand and the index map's leading coordinate, so no
    ``pool[layer]`` is ever materialised.  ``layer=None``: the pools are
    ONE layer's ``[num_blocks, Hkv, bs, hd]``, taken as the one-layer
    stack.  ``tables`` [B, max_blocks] int32 block tables; ``offsets``
    scalar or [B] — slot b's rows sit at positions ``offsets[b] +
    arange(S_in)`` and attend keys at ``kpos <= qpos`` (``window``
    additionally bounds below).  Returns [B, H, S_in, hd] in ``q.dtype``
    — drop-in for the gather path's ``_cached_attention`` output
    (float-tolerance equal; the engine goldens assert token bit parity).
    """
    B, H, S_in, hd = q.shape
    k_pool, v_pool, lay = _stacked(k_pool, v_pool, layer)
    quantized = isinstance(k_pool, tuple)
    k_arr = k_pool[0] if quantized else k_pool
    _L, nb, Hkv, bs, _hd = k_arr.shape
    groups, rem = divmod(H, Hkv)
    if rem:
        raise ValueError(
            f"GQA needs q heads divisible by kv heads, got {H} vs {Hkv}")
    mb = tables.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    params = default_paged_params()
    fw = int(fetch_width if fetch_width is not None else
             params["fetch_width"])
    fw = max(1, min(fw, mb))
    pad_to = int(q_pad_to if q_pad_to is not None else params["q_pad_to"])

    offs = jnp.asarray(offsets, jnp.int32)
    if offs.ndim == 0:
        offs = jnp.broadcast_to(offs, (B,))
    # group-major rows: row r = g*S_in + s covers position off + s
    R = groups * S_in
    rows = -(-R // pad_to) * pad_to
    qr = q.reshape(B, Hkv, R, hd)
    if rows != R:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - R), (0, 0)))

    def qidx(b, h, j, tab, off, lay):
        return (b, h, 0, 0)

    def kvidx(b, h, j, tab, off, lay, i=0, own_layer=False):
        # clamp dead steps onto the last live block: consecutive grid
        # steps then revisit the same index and Mosaic skips the re-fetch
        # — attention HBM traffic scales with the slot's ACTUAL length
        hi1 = (off[b] + S_in + bs - 1) // bs - 1
        blk = jnp.minimum(jnp.minimum(j * fw + i, hi1), mb - 1)
        return (0 if own_layer else lay[0], tab[b, blk], h, 0, 0)

    in_specs = [pl.BlockSpec((1, 1, rows, hd), qidx)]
    operands = [qr]
    for pool in (k_pool, v_pool):
        scales = _scale_rows(pool[1], lay) if quantized else None
        for i in range(fw):
            if quantized:
                in_specs.append(pl.BlockSpec(
                    (1, 1, 1, bs, hd), functools.partial(kvidx, i=i)))
                operands.append(pool[0])
                in_specs.append(pl.BlockSpec(
                    (1, 1, 1, 1, bs),
                    functools.partial(kvidx, i=i, own_layer=True)))
                operands.append(scales)
            else:
                in_specs.append(pl.BlockSpec(
                    (1, 1, 1, bs, hd), functools.partial(kvidx, i=i)))
                operands.append(pool)
    # interleave per sub-block: kernel expects (k, v) / (k8, ks, v8, vs)
    # pairs sub-block-major — reorder the flat k-then-v lists
    per = 2 if quantized else 1
    k_ops, v_ops = operands[1:1 + fw * per], operands[1 + fw * per:]
    k_specs, v_specs = in_specs[1:1 + fw * per], in_specs[1 + fw * per:]
    ordered_ops, ordered_specs = [operands[0]], [in_specs[0]]
    for i in range(fw):
        ordered_ops.extend(k_ops[per * i:per * (i + 1)])
        ordered_ops.extend(v_ops[per * i:per * (i + 1)])
        ordered_specs.extend(k_specs[per * i:per * (i + 1)])
        ordered_specs.extend(v_specs[per * i:per * (i + 1)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, -(-mb // fw)),
        in_specs=ordered_specs,
        out_specs=pl.BlockSpec((1, 1, rows, hd), qidx),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),     # acc
            pltpu.VMEM((rows, _LANES), jnp.float32),  # m
            pltpu.VMEM((rows, _LANES), jnp.float32),  # l
        ],
    )
    kernel = functools.partial(
        _kernel, S_in=S_in, bs=bs, window=window, sm_scale=float(sm_scale),
        quantized=quantized, fetch_width=fw, rows=rows)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_out_struct((B, Hkv, rows, hd), q.dtype, q),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="paged_decode" if S_in == 1 else "paged_chunk",
    )(tables.astype(jnp.int32), offs, lay, *ordered_ops)
    return out[:, :, :R].reshape(B, H, S_in, hd)


def _stacked(k_pool: Any, v_pool: Any, layer) -> Tuple[Any, Any, jnp.ndarray]:
    """The pools as the kernels take them: stacked ``[L, nb, Hkv, bs, hd]``
    beside the layer as an int32 ``[1]`` scalar-prefetch operand.  One
    layer's pool (``layer`` None) is the one-layer stack: a reshape."""
    if layer is None:
        lift = lambda c: jax.tree.map(lambda a: a[None], c)
        return lift(k_pool), lift(v_pool), jnp.zeros((1,), jnp.int32)
    return k_pool, v_pool, jnp.asarray(layer, jnp.int32).reshape(1)


def _scale_rows(scale: jnp.ndarray, lay: jnp.ndarray) -> jnp.ndarray:
    """An int8 pool's scales ``[L, nb, Hkv, bs]`` as the kernel reads them,
    ``[1, nb, Hkv, 1, bs]`` of layer ``lay``: a (1, bs) block over a
    (1, bs) minor pair is legal on TPU, (1, bs) over (Hkv, bs) is not.
    That view is a relayout (a real copy) of what it covers, so it covers
    ONE layer's scales (1/32 of the layer's int8 bytes), which the index
    map then reaches at layer 0; the int8 values themselves are read
    where they lie."""
    one = jax.lax.dynamic_index_in_dim(scale, lay[0], 0, keepdims=True)
    return one[:, :, :, None, :]


# ------------------------------------------------ CP ring carry entry point


def _cp_kernel(
    tab_ref, off_ref, lay_ref, q_ref, *refs,
    S_in, bs, window, sm_scale, fetch_width, rows, nb, has_carry,
):
    """Ring-hop variant of :func:`_kernel` for context-parallel prefill
    (ops/ring_paged.py): the pool operand is ONE rank's slice
    [nb, Hkv, bs, hd] reached through a RE-BASED table (global id minus
    the source rank's slice base), so entries outside ``[0, nb)`` mean
    "another rank owns this block" — the index map clamps them onto a
    valid fetch and the in-kernel ownership test masks them out of the
    scores.  Instead of normalizing, the kernel RETURNS the raw online
    -softmax carry (acc, m, l); the ring accumulates it across hops
    (``has_carry`` seeds the scratch from the previous hop's output) and
    normalizes once after the last hop."""
    n_c = 3 if has_carry else 0
    carry_refs = refs[:n_c]
    kv_refs = refs[n_c:n_c + fetch_width * 2]
    acc_o, m_o, l_o = refs[n_c + fetch_width * 2:n_c + fetch_width * 2 + 3]
    acc_ref, m_ref, l_ref = refs[n_c + fetch_width * 2 + 3:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    off = off_ref[b]
    hi = (off + S_in + bs - 1) // bs  # live KV blocks for this slot

    @pl.when(j == 0)
    def _init():
        if has_carry:
            acc_ref[...] = carry_refs[0][0, 0]
            m_ref[...] = carry_refs[1][0, 0]
            l_ref[...] = carry_refs[2][0, 0]
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # [rows, hd]
    qpos = off + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) % S_in

    for i in range(fetch_width):
        blk = j * fetch_width + i

        @pl.when(blk < hi)
        def _compute(i=i, blk=blk):
            raw = tab_ref[b, blk]  # re-based id; out of [0, nb) = remote
            owned = (raw >= 0) & (raw < nb)
            kblk = kv_refs[2 * i][0, 0, 0]
            s = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32)
            s = s * sm_scale
            kpos = blk * bs + jax.lax.broadcasted_iota(
                jnp.int32, (rows, bs), 1)
            keep = (kpos <= qpos) & owned
            if window is not None:
                keep = keep & (kpos > qpos - window)
            s = jnp.where(keep, s, NEG_INF)
            m = m_ref[:, :1]
            l = l_ref[:, :1]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_ref[...] = jnp.broadcast_to(
                l * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
            vblk = kv_refs[2 * i + 1][0, 0, 0]
            upd = jnp.dot(p.astype(vblk.dtype), vblk,
                          preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * corr + upd
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == (hi - 1) // fetch_width)
    def _write():
        acc_o[0, 0] = acc_ref[...]
        m_o[0, 0] = m_ref[...]
        l_o[0, 0] = l_ref[...]


def paged_carry_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables_local: jnp.ndarray,
    offsets,
    *,
    layer=None,
    carry: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    fetch_width: Optional[int] = None,
    q_pad_to: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One ring hop of CP paged prefill: accumulate ``q`` [B, H, S_in,
    hd] against layer ``layer`` of ONE rank's pool slice ``[L, nb, Hkv,
    bs, hd]`` (``layer=None``: one layer's ``[nb, Hkv, bs, hd]``, as in
    :func:`paged_decode_attention`) reached through
    ``tables_local`` (= global tables minus that rank's slice
    base; out-of-slice entries are masked in-kernel), returning the
    UN-normalized online-softmax carry ``(acc [B, Hkv, rows, hd] f32,
    m [B, Hkv, rows, 128] f32, l [B, Hkv, rows, 128] f32)``.

    ``offsets`` must already include the rank's sub-chunk base (the q
    rows sit at ``offsets[b] + arange(S_in)`` globally), so the existing
    live-length walk (``hi``), dead-step clamping and position masking
    carry over from :func:`paged_decode_attention` unchanged.  Pass the
    previous hop's return as ``carry`` to continue accumulation; finish
    with :func:`finalize_paged_carry`.  ``l`` may be zero mid-ring (no
    owned key seen yet) — only the final carry's ``l`` must be positive,
    guaranteed because each row's own position is pool-resident on
    exactly one rank.  Int8 pools are not supported (the engine rejects
    ``kv_quant`` under ``cp_axis``)."""
    if isinstance(k_pool, tuple):
        raise NotImplementedError(
            "paged_carry_attention does not support int8 pools")
    B, H, S_in, hd = q.shape
    k_pool, v_pool, lay = _stacked(k_pool, v_pool, layer)
    _L, nb, Hkv, bs, _hd = k_pool.shape
    groups, rem = divmod(H, Hkv)
    if rem:
        raise ValueError(
            f"GQA needs q heads divisible by kv heads, got {H} vs {Hkv}")
    mb = tables_local.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    params = default_paged_params()
    fw = int(fetch_width if fetch_width is not None else
             params["fetch_width"])
    fw = max(1, min(fw, mb))
    pad_to = int(q_pad_to if q_pad_to is not None else params["q_pad_to"])

    offs = jnp.asarray(offsets, jnp.int32)
    if offs.ndim == 0:
        offs = jnp.broadcast_to(offs, (B,))
    R = groups * S_in
    rows = -(-R // pad_to) * pad_to
    qr = q.reshape(B, Hkv, R, hd)
    if rows != R:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - R), (0, 0)))

    def qidx(b, h, j, tab, off, lay):
        return (b, h, 0, 0)

    def kvidx(b, h, j, tab, off, lay, i=0):
        # same dead-step clamp as the decode kernel, plus a clamp of the
        # re-based table entry into the slice (remote blocks fetch SOME
        # valid block; the in-kernel ownership test masks the scores)
        hi1 = (off[b] + S_in + bs - 1) // bs - 1
        blk = jnp.minimum(jnp.minimum(j * fw + i, hi1), mb - 1)
        idx = jnp.clip(tab[b, blk], 0, nb - 1)
        return (lay[0], idx, h, 0, 0)

    has_carry = carry is not None
    in_specs = [pl.BlockSpec((1, 1, rows, hd), qidx)]
    operands = [qr]
    if has_carry:
        for c, lanes in zip(carry, (hd, _LANES, _LANES)):
            in_specs.append(pl.BlockSpec((1, 1, rows, lanes), qidx))
            operands.append(c)
    for i in range(fw):
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, bs, hd), functools.partial(kvidx, i=i)))
        operands.append(k_pool)
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, bs, hd), functools.partial(kvidx, i=i)))
        operands.append(v_pool)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, -(-mb // fw)),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, rows, hd), qidx),
            pl.BlockSpec((1, 1, rows, _LANES), qidx),
            pl.BlockSpec((1, 1, rows, _LANES), qidx),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),      # acc
            pltpu.VMEM((rows, _LANES), jnp.float32),  # m
            pltpu.VMEM((rows, _LANES), jnp.float32),  # l
        ],
    )
    kernel = functools.partial(
        _cp_kernel, S_in=S_in, bs=bs, window=window,
        sm_scale=float(sm_scale), fetch_width=fw, rows=rows, nb=nb,
        has_carry=has_carry)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            _out_struct((B, Hkv, rows, hd), jnp.float32, q),
            _out_struct((B, Hkv, rows, _LANES), jnp.float32, q),
            _out_struct((B, Hkv, rows, _LANES), jnp.float32, q),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="paged_carry",
    )(tables_local.astype(jnp.int32), offs, lay, *operands)
    return acc, m, l


def finalize_paged_carry(carry, B: int, H: int, S_in: int, hd: int,
                         dtype) -> jnp.ndarray:
    """Normalize the last ring hop's carry and restore the public
    [B, H, S_in, hd] layout (undo group-major packing + row padding)."""
    acc, _m, l = carry
    Hkv = acc.shape[1]
    R = (H // Hkv) * S_in
    out = acc / l[..., :1]
    return out[:, :, :R].reshape(B, H, S_in, hd).astype(dtype)


# --------------------------------------------------- modeled HBM footprint


def modeled_attend_temp_bytes(
    impl: str, *, batch: int, kv_heads: int, max_blocks: int,
    block_size: int, head_dim: int, s_in: int = 1, groups: int = 1,
    itemsize: int = 4, fetch_width: Optional[int] = None,
) -> int:
    """Modeled per-layer attention working-set bytes for one decode step —
    the MemoryModel-style no-compile estimate the 32k serving test (and a
    capacity planner) judges against ``obs.mem_ledger.headroom_verdict``.

    ``gather``: the dense per-slot view ``[B, Hkv, max_blocks*bs, hd]``
    materialized for k AND v (the int8 pool additionally upcasts both to
    f32 in the einsum, so ``itemsize=4`` models that case too) — O(max
    context) whatever the slot holds.  ``pallas``: q/out rows plus
    ``fetch_width`` double-buffered KV blocks per program — O(block),
    independent of context."""
    if impl == "gather":
        return 2 * batch * kv_heads * max_blocks * block_size * head_dim * itemsize
    if impl == "pallas":
        fw = int(fetch_width or paged_params_for("cpu")["fetch_width"])
        rows = groups * s_in
        blocks = 2 * 2 * fw * block_size * head_dim * itemsize  # k+v, 2-buf
        return batch * kv_heads * (2 * rows * head_dim * itemsize + blocks)
    raise ValueError(f"impl must be 'gather' or 'pallas', got {impl!r}")
