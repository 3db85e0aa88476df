"""Ring paged prefill: context-parallel chunked prefill over the paged pool.

PR-12 opened 32k single-replica serving (the fused paged kernel bounds
per-tick attention HBM by live context), but at 128k+ PREFILL becomes the
wall: a single replica grinds through ``ctx / chunk`` sequential chunk
ticks while decode needs one chip's FLOPs.  This module shards the
*prefill* of one long prompt across a ``context`` mesh axis:

- the **pool is sequence-sharded by blocks**: dim 1 of every pool leaf
  (``[L, num_blocks, Hkv, bs, hd]``) carries the cp axis, so rank ``r``
  physically owns global blocks ``[r*nb_local, (r+1)*nb_local)`` and host
  code (allocator, tables, router) keeps seeing ONE global pool;
- each chunk's rows split into ``cp`` sub-chunks — rank ``r`` embeds and
  projects only rows ``[r*Csub, (r+1)*Csub)`` of the chunk, so per-rank
  activation work divides by cp;
- a **python-unrolled ppermute ring** (the PR-3/PR-8 idiom: every hop is
  its own HLO ``collective-permute``, so the comm ledger prices each hop
  instead of under-counting a while body) does double duty per layer:

  1. *write ring*: the fresh sub-chunk (K, V) rotates ``cp-1`` hops and
     every rank scatters the rows that land in ITS blocks (out-of-slice
     writes drop — ``mode='drop'``), completing the chunk's pool write
     collectively;
  2. *attend ring*: the per-layer pool SLICES rotate ``cp-1`` hops and
     each rank's sub-chunk q accumulates online-softmax partials against
     every slice (``impl='gather'`` = the dense masked-view oracle;
     ``impl='pallas'`` = the carry entry point of
     :func:`..ops.paged_attention.paged_carry_attention`, which walks
     only the slice's live blocks in VMEM).  XLA's async collectives let
     hop ``i+1``'s permute overlap hop ``i``'s flash accumulation — the
     ``obs.comm_ledger.cp_ring_overlap`` summary is the evidence.

Decode on a CP engine stays ONE compiled program (S_in=1): every rank
attends its local slice and the per-rank partials combine exactly via a
``pmax``/``psum`` logsumexp reduction — deterministic and identical on
every rank, so ``decode_signatures`` stays 1.

Numerics: partials accumulate in f32 with the same online-softmax update
as the flash/ring lineage; the association order differs from the gather
oracle's single full-row softmax, so logits agree to float tolerance and
greedy tokens bit-match (tests/test_cp_prefill.py locks dense, GQA,
sliding-window, single-device and the cp mesh, plus the prefill-tier →
decode-replica handoff).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.lax import axis_size
from .flash_attention import NEG_INF

__all__ = [
    "ring_paged_write",
    "ring_paged_attend",
    "ring_hops_per_chunk",
    "ring_chunk_bytes",
    "modeled_cp_working_set_bytes",
]


def _ring_perm(cp: int):
    """The one-step rotation ``i -> i+1`` — each hop is one ppermute."""
    return [(i, (i + 1) % cp) for i in range(cp)]


def _scatter_local(c, val, pos, tables, rank_base, nb_local: int, layer):
    """Scatter ``val`` [B, Hkv, S, hd] at absolute positions ``pos``
    [B, S] into layer ``layer`` of the LOCAL pool slice ``c``
    [L, nb_local, Hkv, bs, hd] (returned whole, written where it lies):
    global block ids resolve through ``tables`` and re-base by
    ``rank_base``; rows landing outside this rank's slice get the
    sentinel index ``nb_local`` — NOT -1, which ``.at[...]`` would wrap
    python-style into the last local block before ``mode='drop'`` could
    reject it — so the scatter drops them (another rank owns those
    blocks and performs the same scatter when the payload reaches it).
    Overshoot positions clamp to the table tail exactly like the global
    ``paged_write`` (NULL entries re-base to rank 0's local NULL; on
    other ranks they drop — never read either way)."""
    B, Hkv, S, hd = val.shape
    bs = c.shape[3]
    mb = tables.shape[1]
    blk = jnp.take_along_axis(
        tables, jnp.clip(pos // bs, 0, mb - 1), axis=1).reshape(-1)
    idx = (pos % bs).reshape(-1)
    loc = blk - rank_base
    loc = jnp.where((loc >= 0) & (loc < nb_local), loc, nb_local)
    vals = val.transpose(0, 2, 1, 3).reshape(B * S, Hkv, hd)
    # one index row a head, so that the update's window is ``hd`` alone: a
    # ``[Hkv, hd]`` window straddles ``bs`` and XLA:TPU then wants the pool
    # in another layout than the kernel reads it in (``paged_write``)
    return c.at[layer, loc[:, None], jnp.arange(Hkv)[None, :],
                idx[:, None]].set(vals.astype(c.dtype), mode="drop")


def ring_paged_write(c, val: jnp.ndarray, offset, *, tables: jnp.ndarray,
                     layer: int, cp_axis: str, prefill: bool = False):
    """CP analogue of ``paged_write`` for layer ``layer`` of a pool slice
    ``[L, nb_local, Hkv, bs, hd]`` sharded over
    ``cp_axis``: ``val`` [B, Hkv, S, hd] holds THIS rank's fresh rows —
    its sub-chunk (rows at ``offset + rank*S .. +S``) when ``prefill``,
    or the replicated decode row (identical on every rank) otherwise.
    ``prefill`` is an explicit trace-time flag, NOT inferred from S: at
    ``chunk == cp`` a prefill sub-chunk is one row too.  Prefill rotates
    the payload around the ring so every rank scatters the rows that map
    into its slice; decode needs no hop (all ranks already hold the
    value).  Int8 pools are not supported under CP (the engine validates
    this up front)."""
    if isinstance(c, tuple):
        raise NotImplementedError("cp_axis does not support kv_quant pools")
    cp = axis_size(cp_axis)
    r = jax.lax.axis_index(cp_axis)
    B, Hkv, S, hd = val.shape
    nb_local = c.shape[1]
    base = r * nb_local
    if not prefill or cp == 1:
        pos = jnp.asarray(offset)[:, None] + jnp.arange(S)[None, :]
        return _scatter_local(c, val, pos, tables, base, nb_local, layer)
    perm = _ring_perm(cp)
    cur = val
    for hop in range(cp):  # python-unrolled: one HLO permute per hop
        src = jnp.mod(r - hop, cp)
        pos = (jnp.asarray(offset)[:, None] + src * S
               + jnp.arange(S)[None, :])
        c = _scatter_local(c, cur, pos, tables, base, nb_local, layer)
        if hop < cp - 1:
            cur = jax.lax.ppermute(cur, cp_axis, perm)
    return c


def _gather_slice(pool, tbl_local, layer):
    """Layer ``layer`` of a pool slice [L, nb_local, Hkv, bs, hd] -> dense
    per-slot view [B, Hkv, mb*bs, hd] through RE-BASED tables (one gather:
    the layer is not sliced out first); out-of-slice ids
    (negative or >= nb_local) gather zeros (``mode='fill'``) and are
    masked out of the scores by the caller."""
    nb_local = pool.shape[1]
    # the sentinel, as in _scatter_local: .at[...] would wrap a negative id
    ids = jnp.where((tbl_local >= 0) & (tbl_local < nb_local), tbl_local,
                    nb_local)
    g = pool.at[layer, ids].get(mode="fill", fill_value=0)
    B, mb, Hkv, bs, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, mb * bs, hd)


def _partial_update(q, kk, vv, valid, qpos, carry, sm_scale, window):
    """One online-softmax accumulation of grouped-query ``q`` [B, H, Sq,
    hd] against a dense per-slot view ``kk``/``vv`` [B, Hkv, W, hd] whose
    per-position validity is ``valid`` [B, W] (False = block not owned by
    the payload's source rank).  Causal + sliding-window masking matches
    ``_cached_attention``; carry is ``(m, l, acc)`` grouped
    [B, Hkv, g, Sq, 1|hd] f32."""
    B, H, Sq, hd = q.shape
    Hkv, W = kk.shape[1], kk.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, Sq, hd)
    s = jnp.einsum("bkgqh,bkth->bkgqt", qg,
                   kk.astype(qg.dtype)).astype(jnp.float32) * sm_scale
    kpos = jnp.arange(W)
    keep = valid[:, None, :] & (kpos[None, None, :] <= qpos[..., None])
    if window is not None:  # Mistral: key in (qpos - window, qpos]
        keep = keep & (kpos[None, None, :] > qpos[..., None] - window)
    s = jnp.where(keep[:, None, None], s, NEG_INF)
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * corr + jnp.einsum("bkgqt,bkth->bkgqh", p,
                                  vv.astype(jnp.float32))
    return m_new, l, acc


def _valid_positions(tables, rank_base, nb_local: int, bs: int):
    """[B, mb*bs] per-position ownership mask for the payload of the rank
    whose slice starts at ``rank_base``."""
    owned = (tables >= rank_base) & (tables < rank_base + nb_local)
    return jnp.repeat(owned, bs, axis=1)


def ring_paged_attend(
    q: jnp.ndarray,
    ck,
    cv,
    offset,
    *,
    tables: jnp.ndarray,
    layer: int,
    cp_axis: str,
    window: Optional[int] = None,
    impl: str = "gather",
    sm_scale: Optional[float] = None,
    prefill: bool = False,
) -> jnp.ndarray:
    """Attention of this rank's rows against layer ``layer`` of the
    cp-sharded pool ``ck`` / ``cv`` [L, nb_local, Hkv, bs, hd].  The rank's
    own slice is read where it lies, at ``layer``; what the ring rotates
    is that one layer (``[1, nb_local, ...]``: the payload of a hop is a
    copy by nature), read at layer 0.

    Prefill (``prefill=True`` — a trace-time flag, not inferred from the
    q length: at ``chunk == cp`` a sub-chunk is one row too): ``q``
    [B, H, Csub, hd] holds the rank's sub-chunk rows (global positions
    ``offset + rank*Csub + arange``); the per-layer pool slices rotate
    ``cp-1`` python-unrolled ppermute hops and the online-softmax carry
    accumulates across hops — the payload arriving at hop ``h`` came
    from rank ``(rank - h) mod cp`` and contributes exactly its owned
    blocks.  Decode (``prefill=False``, replicated q): each rank attends
    its LOCAL slice only and the partials combine across the axis via an
    exact pmax/psum logsumexp reduction — no hop, deterministic,
    identical on every rank.

    ``impl='gather'`` runs the dense masked-view oracle per payload;
    ``impl='pallas'`` runs the carry entry point of the fused paged
    kernel (:func:`.paged_attention.paged_carry_attention`)."""
    if isinstance(ck, tuple):
        raise NotImplementedError("cp_axis does not support kv_quant pools")
    cp = axis_size(cp_axis)
    r = jax.lax.axis_index(cp_axis)
    B, H, S_in, hd = q.shape
    _L, nb_local, Hkv, bs, _hd = ck.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    decode = (not prefill) and cp > 1
    qpos = (jnp.asarray(offset)[:, None]
            + (r * S_in if prefill else 0)
            + jnp.arange(S_in)[None, :])

    if impl == "pallas":
        from .paged_attention import finalize_paged_carry, paged_carry_attention

        offs_q = jnp.asarray(offset, jnp.int32) + (
            r * S_in if prefill else 0)
        carry = None
        kk, vv, at = ck, cv, layer
        perm = _ring_perm(cp)
        hops = 1 if decode else cp
        for hop in range(hops):
            src = jnp.mod(r - hop, cp)
            carry = paged_carry_attention(
                q, kk, vv, tables - src * nb_local, offs_q, layer=at,
                carry=carry, window=window, sm_scale=sm_scale)
            if hop < hops - 1:
                kk, vv, at = _rotate(kk, vv, at, cp_axis, perm)
        if decode:
            carry = _psum_combine_kernel_carry(carry, cp_axis)
        return finalize_paged_carry(carry, B, H, S_in, hd, q.dtype)

    g = H // Hkv
    shape = (B, Hkv, g, S_in)
    carry = (jnp.full(shape + (1,), NEG_INF, jnp.float32),
             jnp.zeros(shape + (1,), jnp.float32),
             jnp.zeros(shape + (hd,), jnp.float32))
    kk, vv, at = ck, cv, layer
    perm = _ring_perm(cp)
    hops = 1 if decode else cp
    for hop in range(hops):  # python-unrolled: every hop priced in HLO
        src = jnp.mod(r - hop, cp)
        base = src * nb_local
        valid = _valid_positions(tables, base, nb_local, bs)
        view_k = _gather_slice(kk, tables - base, at)
        view_v = _gather_slice(vv, tables - base, at)
        carry = _partial_update(q, view_k, view_v, valid, qpos, carry,
                                sm_scale, window)
        if hop < hops - 1:
            kk, vv, at = _rotate(kk, vv, at, cp_axis, perm)
    m, l, acc = carry
    if decode:
        m_g = jax.lax.pmax(m, cp_axis)
        w = jnp.exp(m - m_g)
        l = jax.lax.psum(l * w, cp_axis)
        acc = jax.lax.psum(acc * w, cp_axis)
    out = acc / l
    return out.reshape(B, H, S_in, hd).astype(q.dtype)


def _rotate(kk, vv, at: int, cp_axis: str, perm):
    """One hop of the attend ring: layer ``at`` of ``kk`` / ``vv`` moves to
    the next rank.  Returns the payloads as one-layer stacks and the layer
    to read them at (0), so a hop's payload is read as the pool is."""
    one = lambda a: jax.lax.ppermute(
        jax.lax.slice_in_dim(a, at, at + 1, axis=0), cp_axis, perm)
    return one(kk), one(vv), 0


def _psum_combine_kernel_carry(carry, cp_axis: str):
    """Exact cross-rank combine of the pallas carry ``(acc, m, l)`` —
    the decode-path analogue of the in-ring accumulation."""
    acc, m, l = carry
    m_g = jax.lax.pmax(m, cp_axis)
    w = jnp.exp(m - m_g)
    acc = jax.lax.psum(acc * w[..., :1], cp_axis)
    l = jax.lax.psum(l * w, cp_axis)
    return acc, m_g, l


# ----------------------------------------------------- host-side ring models


def ring_hops_per_chunk(nlayers: int, cp: int) -> int:
    """ppermute ops one prefill chunk issues: per layer, the k and v
    fresh payloads each rotate ``cp-1`` hops (write ring) and the k and v
    pool slices each rotate ``cp-1`` hops (attend ring)."""
    return 0 if cp <= 1 else 4 * (cp - 1) * nlayers


def ring_chunk_bytes(
    *, nlayers: int, cp: int, batch: int, kv_heads: int, head_dim: int,
    chunk: int, nb_local: int, block_size: int, itemsize: int,
) -> int:
    """Modeled wire bytes one prefill chunk puts on the cp ring (the
    quantity the engine accumulates as ``long_context.ring_bytes`` and
    ``plan_prefill_tier`` prices through the CommModel): per layer and
    per hop, two fresh sub-chunk payloads (k, v) plus two pool-slice
    payloads."""
    if cp <= 1:
        return 0
    fresh = batch * kv_heads * (chunk // cp) * head_dim * itemsize
    pool = nb_local * kv_heads * block_size * head_dim * itemsize
    return nlayers * (cp - 1) * 2 * (fresh + pool)


def modeled_cp_working_set_bytes(
    *, kv_heads: int, head_dim: int, block_size: int, nb_local: int,
    chunk: int, cp: int, batch: int = 1, itemsize: int = 4,
    attend_temp_bytes: int = 0,
) -> int:
    """Per-device CP prefill working set beyond the resident pool slice:
    the two in-flight rotating pool-slice buffers (k + v; send and
    receive sides of the ppermute double-buffer), the fresh sub-chunk
    (k, v) payload, and the chosen attention impl's per-call temp
    (``modeled_attend_temp_bytes`` — pass the pallas O(block) figure for
    the kernel path, the dense-view figure for the gather oracle).  The
    quantity the 128k/256k headroom verdicts add to ``pool_bytes / cp``
    per device (tests/test_cp_prefill.py::test_128k_cp_headroom_verdicts)."""
    pool_slice = 2 * nb_local * kv_heads * block_size * head_dim * itemsize
    fresh = 2 * batch * kv_heads * max(1, chunk // max(cp, 1)) \
        * head_dim * itemsize
    return 2 * pool_slice + fresh + int(attend_temp_bytes)
