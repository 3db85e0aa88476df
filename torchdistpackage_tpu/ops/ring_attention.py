"""Context parallelism for long sequences: ring attention + Ulysses.

The reference has NO context parallelism (SURVEY.md §5 "Long-context": its
only long-sequence mechanism is Megatron SP, and its only seed is the
single-device tiled-softmax study explore/flash-attn/tile_attn.py:100-212).
This module is the capability *extension* SURVEY.md §7 step 8 calls for,
built the TPU way:

- :func:`ring_attention` — sequence sharded over a ``'context'`` mesh axis;
  each device keeps its Q shard resident and the KV shards rotate around the
  ICI ring via ``lax.ppermute`` (one hop per step).  With ``use_flash=True``
  (default) each hop runs the Pallas flash kernel on the KV shard in hand
  (``flash_attention_with_lse``) and the per-hop partial outputs combine
  exactly through their logsumexps — so the inner loop is MXU-blocked VMEM
  compute, never an [S_loc, S_loc] score matrix in HBM.  Activation memory
  per device is O(S/cp) and each step's ppermute overlaps with the attention
  compute of the block in hand (XLA async collectives).  Differentiable: AD
  transposes ppermute to the reverse rotation automatically, and the flash
  kernel's lse output carries its own cotangent.
- :func:`ulysses_attention` — the all-to-all alternative: scatter heads /
  gather sequence over the axis, run full flash attention on H/cp local
  heads, scatter back.  Four all_to_alls per attention (q/k/v head-scatter
  + output gather) instead of cp-1 ppermute hops; better when H >= cp and
  S very long.

Both are for use inside ``shard_map`` with the sequence dim of q/k/v sharded
over ``axis``; both run serially when ``axis`` is None (golden path).
"""

from __future__ import annotations

import math
from typing import Optional

import jax

from jax.lax import axis_size
import jax.numpy as jnp

from .flash_attention import NEG_INF, flash_attention_with_lse, mha_reference


def _block_update(q, k, v, m, l, acc, qpos, kpos, causal, sm_scale):
    """One online-softmax accumulation step against a KV block.

    q: [B,H,Sq,D]; k,v: [B,H,Sk,D]; m,l: [B,H,Sq,1]; acc: [B,H,Sq,D];
    qpos: [Sq], kpos: [Sk] global token positions for causal masking."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        mask = kpos[None, :] <= qpos[:, None]  # [Sq, Sk]
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * corr + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
    )
    return m_new, l, acc


def _lse_combine(o, lse, o_j, lse_j):
    """Exactly combine two softmax partials given their logsumexps.

    ``o``/``o_j`` are each normalized over their own KV subset; the combined
    output weights them by exp(lse - lse_new) — the fraction of the total
    softmax mass each subset carries.  o/lse: [B,H,S,D] f32 / [B,H,S] f32."""
    lse_new = jnp.logaddexp(lse, lse_j)
    w = jnp.exp(lse - lse_new)[..., None]
    w_j = jnp.exp(lse_j - lse_new)[..., None]
    return o * w + o_j.astype(jnp.float32) * w_j, lse_new


def zigzag_positions(shard_idx, s_local: int, n: int):
    """Global token positions owned by ``shard_idx`` under the ZIGZAG layout:
    the sequence is split into 2n contiguous chunks and shard i owns chunks
    (i, 2n-1-i) — one early + one late, so every shard carries the same
    amount of causal-attention work (the striped/zigzag load-balancing trick;
    under the contiguous layout shard 0 skips almost every ring hop while
    shard n-1 computes them all).  Returns ([s_local] positions,
    (lo_start, hi_start))."""
    if s_local % 2 != 0:
        raise ValueError(
            f"zigzag needs an even local sequence length, got {s_local}"
        )
    c = s_local // 2
    lo = shard_idx * c
    hi = (2 * n - 1 - shard_idx) * c
    return jnp.concatenate([lo + jnp.arange(c), hi + jnp.arange(c)]), (lo, hi)


def _zigzag_index(S: int, n: int) -> jnp.ndarray:
    """The [S] gather index realizing the zigzag layout: position j of the
    permuted sequence holds original token idx[j] (shard i = chunks i and
    2n-1-i).  Single source of truth for permute/unpermute."""
    if S % (2 * n) != 0:
        raise ValueError(
            f"sequence length {S} not divisible by 2*n = {2 * n} — trailing "
            f"tokens would be silently dropped"
        )
    c = S // (2 * n)
    return jnp.concatenate(
        [jnp.concatenate([jnp.arange(i * c, (i + 1) * c),
                          jnp.arange((2 * n - 1 - i) * c, (2 * n - i) * c)])
         for i in range(n)]
    )


def zigzag_permute(x: jnp.ndarray, n: int, seq_dim: int = 1) -> jnp.ndarray:
    """Host-side layout change: reorder the sequence dim so that a contiguous
    n-way split yields the zigzag ownership (shard i = chunks i and 2n-1-i).
    Apply to tokens AND targets before sharding over the context axis; mean
    losses are permutation-invariant so training is unaffected."""
    return jnp.take(x, _zigzag_index(x.shape[seq_dim], n), axis=seq_dim)


def zigzag_unpermute(x: jnp.ndarray, n: int, seq_dim: int = 1) -> jnp.ndarray:
    """Inverse of :func:`zigzag_permute` (for inspecting outputs in natural
    order)."""
    inv = jnp.argsort(_zigzag_index(x.shape[seq_dim], n))
    return jnp.take(x, inv, axis=seq_dim)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis: Optional[str] = None,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    use_flash: bool = True,
    # per-hop flash tiles; None = the per-chip autotuned defaults
    # (ops/flash_attention.default_tiles, docs/FLASH_TUNE_v5e.json)
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    layout: str = "contiguous",
) -> jnp.ndarray:
    """Ring attention over the ``axis`` mesh ring.  [B, H, S_local, D] layout
    with the global sequence sharded over the axis either contiguously
    (shard i owns positions [i*S_local, (i+1)*S_local)) or in the ZIGZAG
    layout (``layout='zigzag'``: shard i owns chunks i and 2n-1-i of 2n —
    see :func:`zigzag_positions`; prepare inputs with
    :func:`zigzag_permute`).  Zigzag balances the causal FLOPs across the
    ring: per hop every shard computes the same past/diagonal mix, so the
    critical path is ~half the contiguous layout's at large cp.

    ``use_flash=True`` runs the Pallas flash kernel per ring hop and combines
    hops via logsumexp (:func:`_lse_combine`); shard alignment means each
    hop (each half-pair under zigzag) is either the diagonal (causal flash),
    entirely in the past (non-causal flash), or entirely in the future
    (skipped).  ``use_flash=False`` keeps the XLA einsum online-softmax
    update (golden / debug path — materializes [S_loc, S_loc] scores per
    hop).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring layout {layout!r}")
    if axis is None:
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    if not use_flash and k.shape[1] != q.shape[1]:
        # the einsum online-softmax (golden/debug) path assumes equal head
        # counts — materialize the GQA broadcast here; the flash paths
        # serve shared KV blocks via the kernel's index maps instead
        g, rem = divmod(q.shape[1], k.shape[1])
        if rem:
            raise ValueError(
                f"GQA needs q heads divisible by kv heads "
                f"({q.shape[1]} vs {k.shape[1]})")
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    if layout == "zigzag":
        if not causal:
            # zigzag only rebalances the causal triangle; non-causal work is
            # already uniform
            return ring_attention(
                q, k, v, axis, causal=False, sm_scale=sm_scale,
                use_flash=use_flash, block_q=block_q, block_k=block_k,
            )
        if use_flash:
            return _ring_attention_zigzag_flash(
                q, k, v, axis, sm_scale, block_q, block_k
            )
        return _ring_attention_zigzag_einsum(q, k, v, axis, sm_scale)
    if use_flash:
        return _ring_attention_flash(q, k, v, axis, causal, sm_scale, block_q, block_k)

    n = axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B, H, S, D = q.shape
    qpos = idx * S + jnp.arange(S)

    # accumulators are per-shard values: mark them varying over the ring axis
    # AND every axis the inputs vary over (e.g. 'data' under a DP mesh), so
    # the scan carry type matches the block-update outputs
    from ..parallel.data_parallel import _mark_varying, _vma

    vary = tuple(_vma(q) | _vma(k) | _vma(v) | {axis})
    m0 = _mark_varying(jnp.full((B, H, S, 1), NEG_INF, jnp.float32), vary)
    l0 = _mark_varying(jnp.zeros((B, H, S, 1), jnp.float32), vary)
    acc0 = _mark_varying(jnp.zeros((B, H, S, D), jnp.float32), vary)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        m, l, acc, kc, vc = carry
        src = (idx - t) % n  # original owner of the KV block in hand
        kpos = src * S + jnp.arange(S)

        def update(opers):
            m, l, acc = opers
            return _block_update(q, kc, vc, m, l, acc, qpos, kpos, causal, sm_scale)

        if causal:
            # KV shards entirely in the future are fully masked — skip their
            # FLOPs (~half the steps across the ring); cond keeps the scan
            # body uniform so the ppermute below still overlaps compute
            m, l, acc = jax.lax.cond(src <= idx, update, lambda o: o, (m, l, acc))
        else:
            m, l, acc = update((m, l, acc))
        # rotate KV to the next ring neighbor (skippable on the last step,
        # but a uniform scan body lets XLA overlap the hop with compute)
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        return (m, l, acc, kc, vc), None

    (m, l, acc, _, _), _ = jax.lax.scan(step, (m0, l0, acc0, k, v), jnp.arange(n))
    return (acc / l).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis, causal, sm_scale, block_q, block_k):
    """Flash-kernel ring: per hop, one Pallas flash call over the KV shard in
    hand; hops combine exactly via logsumexp weights."""
    from ..parallel.data_parallel import _mark_varying, _vma

    n = axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B, H, S, D = q.shape

    # carry must vary over the ring axis AND everything the inputs vary over
    vary = tuple(_vma(q) | _vma(k) | _vma(v) | {axis})
    o0 = _mark_varying(jnp.zeros((B, H, S, D), jnp.float32), vary)
    lse0 = _mark_varying(jnp.full((B, H, S), NEG_INF, jnp.float32), vary)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def flash_hop(kc, vc, hop_causal):
        return flash_attention_with_lse(
            q, kc, vc, causal=hop_causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k,
        )

    def step(carry, t):
        o, lse, kc, vc = carry
        src = (idx - t) % n  # original owner of the KV shard in hand

        if causal:
            def skip(opers):
                # future shard: fully masked — zero mass keeps combine exact
                # (derive from q so the vma matches the flash branches)
                return q * 0, jnp.float32(NEG_INF) + (q[..., 0] * 0).astype(jnp.float32)

            def diag(opers):
                return flash_hop(*opers, hop_causal=True)

            def past(opers):
                return flash_hop(*opers, hop_causal=False)

            # src > idx -> 0 (skip), src == idx -> 1 (diag), src < idx -> 2 (past)
            branch = (src <= idx).astype(jnp.int32) + (src < idx).astype(jnp.int32)
            o_j, lse_j = jax.lax.switch(branch, [skip, diag, past], (kc, vc))
        else:
            o_j, lse_j = flash_hop(kc, vc, hop_causal=False)

        o, lse = _lse_combine(o, lse, o_j, lse_j)
        # rotate KV to the next ring neighbor (uniform scan body lets XLA
        # overlap the hop with the flash compute)
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        return (o, lse, kc, vc), None

    (o, lse, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v), jnp.arange(n))
    return o.astype(q.dtype)


def _ring_attention_zigzag_einsum(q, k, v, axis, sm_scale):
    """Zigzag golden path: the online-softmax update takes ARBITRARY global
    position arrays, so the only difference from the contiguous path is the
    qpos/kpos bookkeeping (and no hop skipping — every hop carries a
    balanced past/diagonal mix by construction)."""
    from ..parallel.data_parallel import _mark_varying, _vma

    n = axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B, H, S, D = q.shape
    qpos, _ = zigzag_positions(idx, S, n)

    vary = tuple(_vma(q) | _vma(k) | _vma(v) | {axis})
    m0 = _mark_varying(jnp.full((B, H, S, 1), NEG_INF, jnp.float32), vary)
    l0 = _mark_varying(jnp.zeros((B, H, S, 1), jnp.float32), vary)
    acc0 = _mark_varying(jnp.zeros((B, H, S, D), jnp.float32), vary)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        m, l, acc, kc, vc = carry
        src = (idx - t) % n
        kpos, _ = zigzag_positions(src, S, n)
        m, l, acc = _block_update(q, kc, vc, m, l, acc, qpos, kpos, True, sm_scale)
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        return (m, l, acc, kc, vc), None

    (m, l, acc, _, _), _ = jax.lax.scan(step, (m0, l0, acc0, k, v), jnp.arange(n))
    return (acc / l).astype(q.dtype)


def _ring_attention_zigzag_flash(q, k, v, axis, sm_scale, block_q, block_k):
    """Zigzag flash path: each shard's activation is two contiguous chunks
    (lo = chunk idx, hi = chunk 2n-1-idx), so every (q-half, kv-half) pair
    per hop is a pure relation — same chunk (diagonal causal flash), kv
    entirely past (non-causal flash), or kv entirely future (skipped with
    zero softmax mass) — and hops combine exactly via logsumexp.  Four
    half-sized flash calls per hop; per-shard work is UNIFORM across the
    ring (the point of zigzag)."""
    from ..parallel.data_parallel import _mark_varying, _vma

    n = axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B, H, S, D = q.shape
    if S % 2 != 0:
        raise ValueError(f"zigzag needs an even local sequence length, got {S}")
    c = S // 2

    vary = tuple(_vma(q) | _vma(k) | _vma(v) | {axis})
    halves_q = (q[:, :, :c], q[:, :, c:])
    q_starts = (idx * c, (2 * n - 1 - idx) * c)

    o0 = tuple(
        _mark_varying(jnp.zeros((B, H, c, D), jnp.float32), vary) for _ in range(2)
    )
    lse0 = tuple(
        _mark_varying(jnp.full((B, H, c), NEG_INF, jnp.float32), vary)
        for _ in range(2)
    )
    perm = [(i, (i + 1) % n) for i in range(n)]

    def pair(qh, kh, vh, q_start, k_start):
        """(o, lse) of one (q-half, kv-half) pair by chunk relation."""

        def skip(op):
            return qh * 0, jnp.float32(NEG_INF) + (qh[..., 0] * 0).astype(jnp.float32)

        def diag(op):
            return flash_attention_with_lse(
                qh, op[0], op[1], causal=True, sm_scale=sm_scale,
                block_q=block_q, block_k=block_k,
            )

        def past(op):
            return flash_attention_with_lse(
                qh, op[0], op[1], causal=False, sm_scale=sm_scale,
                block_q=block_q, block_k=block_k,
            )

        # k_start > q_start -> 0 (future: skip), == -> 1 (diag), < -> 2 (past)
        branch = (k_start <= q_start).astype(jnp.int32) + (
            k_start < q_start
        ).astype(jnp.int32)
        return jax.lax.switch(branch, [skip, diag, past], (kh, vh))

    def step(carry, t):
        o, lse, kc, vc = carry
        src = (idx - t) % n
        k_starts = (src * c, (2 * n - 1 - src) * c)
        o, lse = list(o), list(lse)
        for qi in range(2):
            for ki in range(2):
                o_j, lse_j = pair(
                    halves_q[qi], kc[:, :, ki * c:(ki + 1) * c],
                    vc[:, :, ki * c:(ki + 1) * c],
                    q_starts[qi], k_starts[ki],
                )
                o[qi], lse[qi] = _lse_combine(o[qi], lse[qi], o_j, lse_j)
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        return (tuple(o), tuple(lse), kc, vc), None

    (o, _, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v), jnp.arange(n))
    return jnp.concatenate([o[0], o[1]], axis=2).astype(q.dtype)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis: Optional[str] = None,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    use_flash: bool = True,
) -> jnp.ndarray:
    """Ulysses (DeepSpeed-style) sequence parallelism: all_to_all scatters
    heads and gathers sequence, attention runs on full sequences with H/cp
    local heads (through the Pallas flash kernel by default), then the
    inverse all_to_all restores [B, H, S_local, D]."""
    if axis is None:
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    n = axis_size(axis)
    B, H, S, D = q.shape

    def scatter_heads(x):
        # [B, Hx, S_loc, D] -> [B, n, Hx/n, S_loc, D] -> a2a (recv dim =
        # source rank, inserted *before* seq so the global order is
        # preserved).  Reads the head count off each tensor: under GQA the
        # kv tensors carry fewer heads, and BOTH counts must divide the
        # ring so every shard keeps whole (q-group, kv-head) pairs.
        Hx = x.shape[1]
        if Hx % n != 0:
            raise ValueError(
                f"heads {Hx} not divisible by context-parallel size {n}"
                + (" (GQA under Ulysses needs kv_heads % cp == 0)"
                   if Hx != H else ""))
        x = x.reshape(B, n, Hx // n, S, D)
        x = jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2)
        return x.reshape(B, Hx // n, n * S, D)

    def gather_heads(x):  # out is q-shaped
        x = x.reshape(B, H // n, n, S, D)
        x = jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1)
        return x.reshape(B, H, S, D)

    qf, kf, vf = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    if use_flash:
        from .flash_attention import flash_attention

        out = flash_attention(qf, kf, vf, causal=causal, sm_scale=sm_scale)
    else:
        out = mha_reference(qf, kf, vf, causal=causal, sm_scale=sm_scale)
    return gather_heads(out)
