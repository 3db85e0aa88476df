"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

The reference only *derives* this math in a single-device numpy study
(explore/flash-attn/tile_attn.py:100-212 — tiled online-softmax fwd+bwd); it
ships no kernel.  Here it is a first-class TPU kernel: blockwise online
softmax with f32 accumulators in VMEM, MXU matmuls via ``jnp.dot`` with
``preferred_element_type``, causal block skipping, and a standard flash
backward (recompute probabilities from the saved logsumexp; dq kernel loops
over KV blocks, dkv kernel loops over Q blocks).

**Blocked-KV 3D grid**: K/V are streamed through VMEM one ``block_k`` tile at
a time — the grid is ``(batch*heads, Sq/block_q, Sk/block_k)`` with the KV
dimension innermost ("arbitrary" semantics, executed sequentially per core)
and the online-softmax state ``(m, l, acc)`` carried in VMEM scratch across
KV steps.  VMEM per program is O(block), independent of sequence length, so
single-chip long-S is bounded by HBM, not VMEM; Mosaic double-buffers the KV
block DMAs against the MXU work.

The kernel also returns the per-row logsumexp **differentiably** (cotangents
on lse fold into the standard flash ``delta`` term), which is what lets ring
/ Ulysses context parallelism (ops/ring_attention.py) combine per-hop partial
outputs exactly.

On CPU (tests / CI sim) the kernels run in Pallas interpreter mode
automatically, so the same code path is exercised everywhere.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite "minus infinity": avoids (-inf) - (-inf) NaNs

_LANES = 128  # m/l scratch keeps a full lane dim for layout friendliness

# (block_q, block_k) by device_kind substring.  The v5e rows were measured
# by the autotuner (tools/flash_tune.py, docs/FLASH_TUNE_v5e.json); the cpu
# row is the Pallas interpreter's.  A chip with no row is an error: run
# tools/flash_tune.py on it and add one.
_TILES = (
    ("v5 lite", (1024, 1024)),
    ("v5e", (1024, 1024)),
    ("cpu", (256, 512)),
)


def tiles_for(device_kind: str) -> Tuple[int, int]:
    dk = device_kind.lower()
    for sub, tiles in _TILES:
        if sub in dk:
            return tiles
    raise ValueError(
        f"flash_attention: no tile row for device_kind={device_kind!r}; run "
        "tools/flash_tune.py on this chip and add a _TILES row")


def default_tiles() -> Tuple[int, int]:
    """(block_q, block_k) for the attached chip, from :data:`_TILES`."""
    return tiles_for(jax.devices()[0].device_kind)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _compiler_params():
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def mha_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Plain softmax(QK^T)V golden — [B, H, S, D] layout.  Grouped-query
    attention: ``k``/``v`` may carry fewer heads (H_q % H_kv == 0); each
    group of ``H_q // H_kv`` consecutive query heads attends to one shared
    KV head."""
    if k.shape[1] != q.shape[1]:
        g, rem = divmod(q.shape[1], k.shape[1])
        assert rem == 0, (q.shape, k.shape)
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), dtype=bool), k=Sk - Sq)
        if window is not None:
            # Mistral semantics: key in (qpos - window, qpos]
            mask = mask & jnp.triu(
                jnp.ones((Sq, Sk), dtype=bool), k=Sk - Sq - window + 1)
        s = jnp.where(mask, s, NEG_INF)
    elif window is not None:
        raise ValueError("sliding window requires causal attention")
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct carrying the vma of ``like`` — required for
    pallas_call under shard_map (check_vma=True)."""
    from jax import typeof

    vma = getattr(typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _causal_hi(qi, block_q, block_k, num_kv):
    """Number of KV blocks a causal row-block attends to (incl. diagonal)."""
    hi = jax.lax.div((qi + 1) * block_q + block_k - 1, block_k)
    return jnp.minimum(hi, num_kv)


def _window_lo(qi, block_q, block_k, window):
    """First KV block with any in-window key for q row-block ``qi``
    (lowest needed key position = qi*block_q - window + 1)."""
    return jnp.maximum(jax.lax.div(qi * block_q - window + 1, block_k), 0)


def _window_mask(s, qi, kj, block_q, block_k, window):
    """Causal + sliding-window in-block mask: key in (qpos-window, qpos]."""
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = kpos <= qpos
    if window is not None:
        keep = keep & (kpos > qpos - window)
    return jnp.where(keep, s, NEG_INF)


# ------------------------------------------------------------------- forward


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, sm_scale, causal, num_kv, window=None,
):
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    hi = _causal_hi(qi, block_q, block_k, num_kv) if causal else num_kv
    lo = _window_lo(qi, block_q, block_k, window) if window is not None else 0

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((kj >= lo) & (kj < hi))
    def _compute():
        q = q_ref[0]  # [Bq, D] storage dtype — MXU takes bf16 in, f32 out
        kblk = k_ref[0]
        vblk = v_ref[0]
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        s = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _window_mask(s, qi, kj, block_q, block_k, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(vblk.dtype), vblk, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == hi - 1)
    def _write():
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(l)  # [Bq, 1]


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, groups=1, window=None):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    num_kv = Sk // block_k
    grid = (BH, Sq // block_q, num_kv)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, num_kv=num_kv,
        window=window,
    )
    # GQA: q is flattened [B*Hq, ...] b-major with the G q-heads of a group
    # consecutive, kv is [B*Hkv, ...] — kv block for q-program b is b//G
    # (an index_map, not a materialized repeat)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // groups, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // groups, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((BH, Sq, D), q.dtype, q),
            _out_struct((BH, Sq, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),       # acc
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # l
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# ------------------------------------------------------------------ backward


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
    *, sm_scale, causal, num_kv, window=None,
):
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    hi = _causal_hi(qi, block_q, block_k, num_kv) if causal else num_kv
    lo = _window_lo(qi, block_q, block_k, window) if window is not None else 0

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    @pl.when((kj >= lo) & (kj < hi))
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # [Bq, 1]
        delta = delta_ref[0]
        kblk = k_ref[0]
        vblk = v_ref[0]
        s = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _window_mask(s, qi, kj, block_q, block_k, window)
        p = jnp.exp(s - lse)  # [Bq, Bk]
        dp = jnp.dot(do, vblk.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(kblk.dtype)
        dq_acc_ref[...] = dq_acc_ref[...] + jnp.dot(
            ds, kblk, preferred_element_type=jnp.float32
        )

    @pl.when(kj == hi - 1)
    def _write():
        dq_ref[0] = (dq_acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, sm_scale, causal, num_q, window=None,
):
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    # causal: only q blocks at or after this kv block contribute; a window
    # additionally bounds ABOVE (no q past kpos_max + window - 1 sees it)
    lo = jax.lax.div(ki * block_k, block_q) if causal else 0
    if window is not None:
        hi_q = jnp.minimum(
            jax.lax.div((ki + 1) * block_k - 1 + window - 1, block_q) + 1,
            num_q)
    else:
        hi_q = num_q

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    @pl.when((qi >= lo) & (qi < hi_q))
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # [Bq, 1]
        delta = delta_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale  # [Bq, Bk]
        if causal:
            s = _window_mask(s, qi, ki, block_q, block_k, window)
        p = jnp.exp(s - lse)
        dv_acc_ref[...] = dv_acc_ref[...] + jnp.dot(
            p.T.astype(do.dtype), do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc_ref[...] = dk_acc_ref[...] + jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32
        )

    @pl.when(qi == num_q - 1)
    def _write():
        dk_ref[0] = (dk_acc_ref[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, groups, window, res, cts):
    q, k, v, o, lse = res
    dout, dlse = cts
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    num_q = Sq // block_q
    num_kv = Sk // block_k
    # delta is the standard flash rowsum(do * o); a cotangent on lse folds in
    # exactly here: d lse_i / d s_ij = p_ij, so ds += dlse_i * p_ij, i.e.
    # delta' = delta - dlse.
    delta = jnp.sum(
        dout.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [BH, Sq, 1]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, num_kv=num_kv,
            window=window,
        ),
        grid=(BH, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // groups, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // groups, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, dout, lse, delta)

    # GQA: the dkv kernel stays per-Q-HEAD (grid dim 0 = B*Hq, kv blocks
    # read via b//G) — G programs writing one kv output block would race,
    # so each q head writes its own partial [B*Hq, Sk, D] (f32 when G > 1)
    # and the group-sum happens outside as a fused XLA reduction.
    dkv_dtype = k.dtype if groups == 1 else jnp.float32
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal, num_q=num_q,
            window=window,
        ),
        grid=(BH, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b // groups, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b // groups, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((BH, Sk, D), dkv_dtype, k),
            _out_struct((BH, Sk, D), dkv_dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, dout, lse, delta)
    if groups > 1:
        BHkv = BH // groups
        dk = dk.reshape(BHkv, groups, Sk, D).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(BHkv, groups, Sk, D).sum(axis=1).astype(v.dtype)
    return dq, dk, dv


# ------------------------------------------------------------------ public op


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, groups=1, window=None):
    return _fwd(q, k, v, sm_scale, causal, block_q, block_k, groups, window)


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, groups=1,
                    window=None):
    o, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, groups, window)
    # Name the kernel's residuals so rematerialization policies can elect to
    # save them: under jax.checkpoint with
    # save_only_these_names('flash_out', 'flash_lse') (scan_blocks
    # remat='flash') the backward reuses o/lse instead of re-running the
    # Pallas forward kernel — the recompute replays only the cheap qkv
    # einsum, cutting the remat recompute by the whole attention fwd at
    # [B, S, D] (+ lse) bf16 of extra saved bytes per block.  Without such a
    # policy the tags are inert identities.
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, groups, window,
                    res, cts):
    return _bwd(sm_scale, causal, block_q, block_k, groups, window, res, cts)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _prep(q, k, v, sm_scale, block_q, block_k):
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    Sk = k.shape[2]
    groups, rem = divmod(H, Hkv)
    if rem:
        raise ValueError(
            f"GQA needs q heads divisible by kv heads, got {H} vs {Hkv}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    if block_q is None or block_k is None:
        tq, tk = default_tiles()
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    # clamp to the sequence, then shrink to an exact divisor (gcd) so any
    # shard length works — e.g. ring shards of 384 with block_q=256 use 128
    block_q = math.gcd(min(block_q, Sq), Sq)
    block_k = math.gcd(min(block_k, Sk), Sk)
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, D)
    return qf, kf, vf, float(sm_scale), int(block_q), int(block_k), int(groups)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Blockwise (flash) attention.  [B, H, S, D] layout, differentiable.

    ``window``: sliding-window attention (Mistral semantics — query q
    attends keys in ``(q - window, q]``; requires ``causal``).  Both the
    in-block mask AND the KV block range are bounded (``_window_lo``), so
    compute drops to O(S*window) like the causal bound drops it to half.

    **Grouped-query attention**: ``k``/``v`` may carry fewer heads than
    ``q`` (``H_q % H_kv == 0`` — MQA is ``H_kv == 1``); each group of
    ``H_q // H_kv`` consecutive query heads shares one KV head.  The kv
    tiles are NEVER materialized per-group: the kernels' kv BlockSpecs
    index ``b // G``, so a KV block is DMA'd once per group, and the
    dk/dv group-sum is a fused XLA reduction outside the kernel.  Grads
    return in the kv heads' own shape.

    Block sizes are clamped to the sequence lengths and shrunk (gcd) to exact
    divisors of S, so any shard length traces; power-of-two S keeps the
    requested blocks.  Pad upstream if S is prime-ish and perf matters.

    ``block_q``/``block_k`` default to :func:`default_tiles` — the per-chip
    autotuned sizes (tools/flash_tune.py, docs/FLASH_TUNE_v5e.json): at the
    bench shape [8, 12, 2048, 64] on v5e, (1024, 1024) runs the fwd+bwd
    1.8x faster than the previous (256, 512) default — larger tiles
    amortize the per-grid-step scratch init/rescale overhead and keep the
    MXU busier; VMEM per program stays ~2 MB, well under budget at
    head_dim 64.
    """
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    B, H, Sq, D = q.shape
    qf, kf, vf, sm_scale, block_q, block_k, groups = _prep(
        q, k, v, sm_scale, block_q, block_k)
    o, _ = _flash(qf, kf, vf, sm_scale, bool(causal), block_q, block_k,
                  groups, None if window is None else int(window))
    return o.reshape(B, H, Sq, D)


def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    ``[B, H, S]`` (f32), differentiably.

    This is the composition point for ring / Ulysses context parallelism:
    per-hop partial outputs combine exactly via
    ``o = sum_i exp(lse_i - lse_total) * o_i`` with
    ``lse_total = logaddexp_i(lse_i)`` (ops/ring_attention.py).
    """
    B, H, Sq, D = q.shape
    qf, kf, vf, sm_scale, block_q, block_k, groups = _prep(
        q, k, v, sm_scale, block_q, block_k)
    o, lse = _flash(qf, kf, vf, sm_scale, bool(causal), block_q, block_k, groups)
    return o.reshape(B, H, Sq, D), lse.reshape(B, H, Sq)
