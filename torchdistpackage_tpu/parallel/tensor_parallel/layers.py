"""TP/SP transformer layers — analogue of the reference's
``tensor_parallel/mlp.py`` (77 LoC), ``attn.py`` (98 LoC) and
``transformer.py`` (99 LoC).

Design: **one implementation, serial and parallel.**  Parameters are plain
dict pytrees holding *global* arrays; tensor parallelism is expressed purely
as a ``PartitionSpec`` tree (:func:`transformer_param_specs`).  The forward
functions below run either

- serially (``axis=None``) on full weights, or
- inside ``shard_map`` over the TP axis, where each device sees its local
  weight shard and the functions insert the Megatron collectives:
  column-parallel QKV/W1 need no forward comm (tp_utils.py:176-216 semantics),
  row-parallel WO/W2 reduce via ``psum`` — or ``psum_scatter`` straight into
  sequence-parallel layout (tp_utils.py:218-248) — and SP block boundaries
  all-gather/reduce-scatter along the sequence dim (transformer.py:48-72).

Because the global param arrays are identical in both modes, the reference's
``init_weight_from_full*`` weight-slicing helpers (tp_utils.py:203,
transformer.py:74-85) are unnecessary: sharding *is* the slicing.  Head-safe
QKV sharding (attn.py:64) falls out of storing QKV stacked as ``(3, D, D)``
and sharding the last dim, so each shard owns whole heads.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple, Union

import jax

from jax.lax import axis_size
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...utils import profiling as prof
from .tp_utils import (
    gather_from_sp,
    reduce_from_tp,
    ring_ag_matmul,
    ring_matmul_rs,
    scatter_to_sp,
    split_to_sp,
)

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    dim: int
    nheads: int
    nlayers: int = 2
    ffn_mult: int = 4
    causal: bool = True
    dtype: Any = jnp.float32
    # 'naive' materializes the [S, S] score matrix; 'flash' uses the Pallas
    # blockwise kernel (ops/flash_attention.py) — preferred on TPU for long S;
    # 'ring' / 'ulysses' are the context-parallel impls (ops/ring_attention.py):
    # the sequence stays sharded over ``context_axis`` and KV shards rotate
    # around the ICI ring (ring) or heads scatter via all_to_all (ulysses).
    # Serial (context_axis=None) they fall back to the reference math, so one
    # config runs both the golden and the distributed path.
    attn_impl: str = "naive"
    # mesh axis the sequence is sharded over for 'ring'/'ulysses'; composes
    # orthogonally with TP(+SP): TP splits heads, SP shards the context-LOCAL
    # chunk over the tensor axis between blocks, CP shards the global
    # sequence over this axis inside the attention op itself
    context_axis: Optional[str] = None
    # 'contiguous' | 'zigzag' (ring only): zigzag balances the causal FLOPs
    # across the ring — shard i owns chunks i and 2n-1-i; prepare batches
    # with ops.ring_attention.zigzag_permute
    cp_layout: str = "contiguous"
    # residual dropout rate (after attention proj and after MLP); active only
    # when a dropout key is threaded into the forward — see ``dropout`` and
    # the per-axis key recipe in utils/random.py (axis_unique_key)
    dropout_rate: float = 0.0
    # Grouped-query attention: number of KV heads (None = nheads, plain
    # MHA; 1 = MQA).  nheads % kv_heads must be 0; under TP additionally
    # kv_heads % tp_size (each shard owns whole KV heads).  The flash
    # kernel serves the shared KV blocks via index maps — no repeat.
    kv_heads: "int | None" = None
    # Rotary position embeddings: rotate q/k by their GLOBAL token position
    # inside attention (applied pre-kernel, so flash/ring/ulysses and GQA
    # all compose; under CP each shard rotates its chunk at the chunk's
    # global offsets — contiguous or zigzag).  The model family drops the
    # learned pos_emb table when this is on.
    rope: bool = False
    rope_theta: float = 10000.0
    # optional rope-scaling dict ('linear' or 'llama3' — see
    # _scaled_inv_freq); carried verbatim from HF configs by
    # models/convert.py.  NB a dict field makes the (frozen) config
    # unhashable — nothing in the package hashes configs.
    rope_scaling: "dict | None" = None
    # 'layer' (LayerNorm, scale+bias) | 'rms' (RMSNorm, scale only — the
    # Llama-family norm).  The choice is carried STRUCTURALLY by the param
    # tree: rms norm params have no 'bias' leaf and :func:`layer_norm`
    # dispatches on that, so downstream code (heads, MoE blocks, pipeline
    # slabs) needs no norm plumbing.
    norm: str = "layer"
    # 'gelu' (w1 [D, F] -> gelu -> w2) | 'swiglu' (w1 [2, D, F] stacked
    # gate/up -> silu(gate) * up -> w2, the Llama FFN).  Also structural:
    # :func:`mlp_partial` dispatches on w1.ndim.
    act: str = "gelu"
    # explicit FFN hidden width; None = dim * ffn_mult.  Llama-style models
    # use non-integer multipliers (~8/3 d rounded), which ffn_mult can't
    # express.
    ffn_hidden: Optional[int] = None
    # norm epsilon — HF checkpoints carry 1e-5 or 1e-6 (rms_norm_eps) and
    # models/convert.py preserves whichever the checkpoint says
    norm_eps: float = 1e-5
    # sliding-window attention (Mistral): query q attends keys in
    # (q - window, q].  None = full causal.  Served by the flash kernel
    # (block-range bounded — O(S*window) compute), the naive reference and
    # the KV-cache decode mask; rejected for the CP impls (a ring shard
    # boundary would silently change the window's reach).
    sliding_window: "int | None" = None
    # Collective matmul (opt-in, SP mode only): decompose the SP
    # all-gather ⊕ column-parallel matmul and the row-parallel matmul ⊕
    # reduce-scatter at the attention/MLP boundaries into ppermute rings
    # (tp_utils.ring_ag_matmul / ring_matmul_rs) so each chunk transfer
    # overlaps the previous chunk's partial matmul — the manual
    # counterpart of XLA's windowed-einsum decomposition
    # (dist/overlap.py).  Falls back to the fused gather/scatter path
    # when the gathered activation is smaller than ``cm_min_bytes``
    # (ring latency — n-1 ppermute hops per boundary — beats the fused
    # collective only once the payload is bandwidth-bound), when sp is
    # off, or when the TP axis has size 1.
    collective_matmul: bool = False
    cm_min_bytes: int = 1 << 20
    # Quantized SP boundaries (opt-in, SP mode only): the block-boundary
    # activation all-gather AND the row-parallel close's reduce-scatter
    # ride the int8 rings (dist/compressed.py — 1 byte/elem + ~1.5% scale
    # sideband on the wire vs 4 for f32; the rings' custom VJPs quantize
    # the matching backward collectives too).  Falls back to the exact
    # collective when the gathered activation is smaller than
    # ``compress_min_bytes`` (scale sideband + ring latency dominate tiny
    # payloads), when sp is off, or when the TP axis has size 1.
    # Orthogonal to ``collective_matmul``: where the cm ring applies it
    # wins (the decomposed boundary has no fused collective to quantize).
    ag_compress: "str | None" = None
    compress_min_bytes: int = 1 << 16

    def __post_init__(self):
        if self.sliding_window is not None:
            if self.attn_impl in ("ring", "ulysses"):
                raise NotImplementedError(
                    "sliding_window is not supported with context-parallel "
                    "attention (ring/ulysses)")
            if not self.causal:
                raise ValueError("sliding_window requires causal attention")
            if self.sliding_window < 1:
                raise ValueError(
                    f"sliding_window must be >= 1, got {self.sliding_window}")
        if self.ag_compress not in (None, "int8"):
            raise ValueError(
                f"ag_compress must be None or 'int8', got {self.ag_compress!r}")
        if self.norm not in ("layer", "rms"):
            raise ValueError(f"norm must be 'layer' or 'rms', got {self.norm!r}")
        if self.act not in ("gelu", "swiglu"):
            raise ValueError(f"act must be 'gelu' or 'swiglu', got {self.act!r}")
        if self.rope_scaling is not None:
            kind = self.rope_scaling.get(
                "rope_type", self.rope_scaling.get("type"))
            if kind not in _ROPE_SCALING_TYPES:
                raise NotImplementedError(
                    f"rope_scaling type {kind!r}; supported: "
                    f"{_ROPE_SCALING_TYPES}")
            need = {
                "linear": ("factor",),
                "llama3": ("factor", "low_freq_factor", "high_freq_factor",
                           "original_max_position_embeddings"),
                "dynamic": ("factor", "original_max_position_embeddings"),
                "yarn": ("factor", "original_max_position_embeddings"),
            }[kind]
            missing = [k for k in need if k not in self.rope_scaling]
            if missing:
                raise ValueError(
                    f"rope_scaling type {kind!r} needs keys {missing} "
                    f"(models/convert.py injects them on HF import)")

    @property
    def head_dim(self) -> int:
        assert self.dim % self.nheads == 0
        return self.dim // self.nheads

    @property
    def kv_head_count(self) -> int:
        kv = self.nheads if self.kv_heads is None else self.kv_heads
        assert self.nheads % kv == 0, (self.nheads, kv)
        return kv

    @property
    def is_gqa(self) -> bool:
        return self.kv_head_count != self.nheads

    @property
    def ffn_dim(self) -> int:
        return self.ffn_hidden if self.ffn_hidden is not None else self.dim * self.ffn_mult


# ------------------------------------------------------------------ primitives


def layer_norm(x: jnp.ndarray, p: Dict[str, jnp.ndarray], eps: float = 1e-5) -> jnp.ndarray:
    """Statistics in f32 regardless of storage dtype: at bf16 the mean/var
    of ~1e3-element rows lose enough mantissa to visibly perturb the
    normalization (the standard TPU-stack practice is f32 LN statistics;
    the op is VPU-bound and XLA fuses the casts, so the cost is noise).
    f32 inputs are bit-identical to the plain formulation.

    Structural norm dispatch: params WITHOUT a 'bias' leaf are RMSNorm
    (``TransformerConfig.norm='rms'`` — see :func:`rms_norm`), so every call
    site (block norms, final heads, MoE blocks) serves both families with no
    cfg plumbing."""
    if "bias" not in p:
        return rms_norm(x, p, eps)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (
        y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    ).astype(x.dtype)


def rms_norm(x: jnp.ndarray, p: Dict[str, jnp.ndarray], eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm (Zhang & Sennrich): x / rms(x) * scale — no mean subtraction,
    no bias.  The Llama-family norm.  f32 statistics for the same mantissa
    reason as :func:`layer_norm`."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_norm_params(dim: int, dtype, norm: str = "layer") -> Dict[str, jnp.ndarray]:
    """Norm params whose STRUCTURE encodes the norm kind ('layer' carries a
    bias leaf, 'rms' does not) — the dispatch key :func:`layer_norm` reads."""
    out = {"scale": jnp.ones((dim,), dtype)}
    if norm == "layer":
        out["bias"] = jnp.zeros((dim,), dtype)
    return out


def norm_param_specs(norm: str = "layer") -> Dict[str, P]:
    """Spec tree matching :func:`init_norm_params` (norm params are always
    replicated)."""
    out = {"scale": P()}
    if norm == "layer":
        out["bias"] = P()
    return out


_ROPE_SCALING_TYPES = ("linear", "llama3", "dynamic", "yarn")


def _scaled_inv_freq(
    inv_freq: jnp.ndarray,
    scaling: dict,
    theta: float = 10000.0,
    pos: "jnp.ndarray | None" = None,
) -> Tuple[jnp.ndarray, float]:
    """Apply a rope-scaling recipe to the base inverse frequencies.
    Returns ``(inv_freq, attention_factor)`` — the factor multiplies the
    cos/sin tables (1.0 for every type but yarn).

    All four recipes match transformers' ``modeling_rope_utils`` exactly
    (verified by HF logits goldens in tests/test_convert.py):

    - 'linear' (position interpolation): every frequency / factor.
    - 'llama3' (Llama-3.1 long-context): frequencies whose wavelength
      exceeds ``original_max_position_embeddings / low_freq_factor`` divide
      by ``factor``, short wavelengths stay, the band between interpolates
      smoothly (``_compute_llama3_parameters``).
    - 'dynamic' (NTK-by-parts, /u/bloc97-style): the base theta grows with
      the CURRENT sequence length past
      ``original_max_position_embeddings`` —
      ``theta' = theta * ((f*s/orig) - (f-1))^(d/(d-2))``; at or below the
      original length it is exactly the unscaled rope
      (``_compute_dynamic_ntk_parameters``).  The current length is read
      from ``pos`` (max position + 1), TRACED — so one jitted decode loop
      reproduces HF's recompute-on-growth behavior with no retrace.
    - 'yarn': interpolated (freq/factor) below ``beta_slow`` rotations,
      extrapolated (unscaled) above ``beta_fast``, linear ramp between,
      plus the attention temperature ``0.1*ln(factor)+1`` returned as the
      attention_factor (``_compute_yarn_parameters``, incl. the
      mscale/mscale_all_dim variant used by Deepseek-style checkpoints).
    """
    kind = scaling.get("rope_type", scaling.get("type"))
    factor = float(scaling["factor"])
    if kind == "linear":
        return inv_freq / factor, 1.0
    if kind == "llama3":
        lo = float(scaling["low_freq_factor"])
        hi = float(scaling["high_freq_factor"])
        old_len = float(scaling["original_max_position_embeddings"])
        wavelen = 2.0 * math.pi / inv_freq
        scaled = jnp.where(wavelen > old_len / lo, inv_freq / factor, inv_freq)
        smooth = (old_len / wavelen - lo) / (hi - lo)
        smoothed = (1.0 - smooth) * scaled / factor + smooth * scaled
        medium = (wavelen >= old_len / hi) & (wavelen <= old_len / lo)
        return jnp.where(medium, smoothed, scaled), 1.0
    half = inv_freq.shape[0]
    dim = 2 * half
    if kind == "dynamic":
        orig = float(scaling["original_max_position_embeddings"])
        if pos is None:
            seq_len = jnp.float32(orig)
        else:
            seq_len = jnp.maximum(jnp.max(pos) + 1, orig).astype(jnp.float32)
        base = theta * ((factor * seq_len / orig) - (factor - 1.0)) ** (
            dim / (dim - 2.0))
        return base ** (-jnp.arange(0, half, dtype=jnp.float32) / half), 1.0
    if kind != "yarn":
        raise NotImplementedError(f"rope_scaling type {kind!r}")
    orig = float(scaling["original_max_position_embeddings"])
    beta_fast = float(scaling.get("beta_fast") or 32)
    beta_slow = float(scaling.get("beta_slow") or 1)

    def get_mscale(scale, m=1.0):
        return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0

    af = scaling.get("attention_factor")
    if af is None:
        ms, msad = scaling.get("mscale"), scaling.get("mscale_all_dim")
        af = (
            get_mscale(factor, ms) / get_mscale(factor, msad)
            if ms and msad
            else get_mscale(factor)
        )

    def correction_dim(n_rot):
        return dim * math.log(orig / (n_rot * 2 * math.pi)) / (2 * math.log(theta))

    low = correction_dim(beta_fast)
    high = correction_dim(beta_slow)
    if scaling.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001  # transformers' singularity guard
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    extrap_w = 1.0 - ramp
    inv = inv_freq / factor * (1.0 - extrap_w) + inv_freq * extrap_w
    return inv, float(af)


def rope_cache(
    pos: jnp.ndarray, head_dim: int, theta: float = 10000.0,
    scaling: "dict | None" = None,
):
    """(cos, sin) tables [1, 1, S, hd/2] for :func:`apply_rope` — compute
    once per forward (they are layer-invariant) and reuse across the block
    stack; ``scan_blocks`` hoists them out of the scan body as closed-over
    loop constants.  ``scaling``: optional rope-scaling dict
    (:func:`_scaled_inv_freq` — 'linear'/'llama3'/'dynamic'/'yarn'; yarn's
    attention temperature is folded into the tables, dynamic reads the
    current length from ``pos``)."""
    assert head_dim % 2 == 0, f"rope needs an even head_dim, got {head_dim}"
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    af = 1.0
    if scaling is not None:
        inv_freq, af = _scaled_inv_freq(inv_freq, scaling, theta=theta, pos=pos)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [S, half]
    return jnp.cos(ang)[None, None] * af, jnp.sin(ang)[None, None] * af


def apply_rope(
    x: jnp.ndarray, pos: jnp.ndarray = None, theta: float = 10000.0,
    cache=None,
) -> jnp.ndarray:
    """Rotary embedding, half-split convention: x [B, H, S, hd] (hd even),
    pos [S] global token positions.  Pairs (x_i, x_{i+hd/2}) rotate by
    pos * theta^(-2i/hd); f32 trig, result in x's dtype.  Pass ``cache``
    (from :func:`rope_cache`) to reuse precomputed tables."""
    if cache is None:
        cache = rope_cache(pos, x.shape[-1], theta)
    cos, sin = cache
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def _rope_positions(cfg: TransformerConfig, S: int) -> jnp.ndarray:
    """Global positions of the S sequence rows attention sees: arange
    serially and under SP (attention runs on the gathered full sequence);
    the chunk's global offsets under CP (contiguous or zigzag)."""
    if cfg.context_axis is None:
        return jnp.arange(S)
    idx = jax.lax.axis_index(cfg.context_axis)
    if cfg.cp_layout == "zigzag":
        from ...ops.ring_attention import zigzag_positions

        pos, _ = zigzag_positions(idx, S, axis_size(cfg.context_axis))
        return pos
    return idx * S + jnp.arange(S)


def block_rope_cache(
    cfg: TransformerConfig, s_local: int, axis: Optional[str] = None,
    sp: bool = False,
):
    """The layer-invariant (cos, sin) rope cache for a block stack whose
    activations have ``s_local`` sequence rows — or None when rope is off.
    Compute ONCE per forward and thread into every block (``scan_blocks``
    and the MoE families' heterogeneous loops both do); attention sees the
    SP-gathered full sequence, so under SP the table length is
    s_local * tp."""
    if not cfg.rope:
        return None
    s_attn = s_local
    if axis is not None and sp:
        s_attn = s_attn * axis_size(axis)
    return rope_cache(_rope_positions(cfg, s_attn), cfg.head_dim,
                      cfg.rope_theta, scaling=cfg.rope_scaling)


def dense(x: jnp.ndarray, w, spec: Optional[str] = None) -> jnp.ndarray:
    """``x @ w`` (or ``einsum(spec, x, w)`` for stacked weights) with
    structural int8 dispatch: a ``tools.surgery.QuantizedLinear`` leaf
    (attrs ``q``/``scale``) upcasts its int8 weight in-register on the way
    into the MXU and folds the per-channel scale into the epilogue — the
    weight-only-quantized serving path (HBM weight reads halve vs bf16).
    Dense array weights take the exact path, so one model implementation
    serves both; every matmul site of the model families funnels here.

    ``spec`` must contract the weight's -2 dim and emit its stack dims
    leading (the families' two forms: ``"bsd,tdh->tbsh"`` / and the plain
    2-D matmul) — that is what aligns the ``[*stack, 1, out]`` scale."""
    q = getattr(w, "q", None)
    if q is None:
        return jnp.einsum(spec, x, w) if spec else x @ w
    qc = q.astype(x.dtype)
    if spec:
        y = jnp.einsum(spec, x, qc, preferred_element_type=jnp.float32)
        # scale [t, 1, h] -> [t, 1, 1, h] against y [t, B, S, h]
        scale = w.scale.astype(jnp.float32)[:, None]
    else:
        y = jnp.dot(x, qc, preferred_element_type=jnp.float32)
        scale = w.scale.astype(jnp.float32)  # [1, h] or [h] broadcasts
    return (y * scale).astype(x.dtype)


def compute_qkv(
    p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: TransformerConfig,
    rope: "tuple | None" = None,
):
    """x [B, S, D] -> rope-rotated (q [B, H_loc, S, hd], k, v
    [B, Hkv_loc, S, hd]) from either the fused-QKV or the GQA param layout
    — the projection half of :func:`attention_partial`, shared with the
    KV-cache prefill (models/generate.py)."""
    B, S, D = x.shape
    hd = cfg.head_dim
    if "wqkv" in p:
        h_loc = p["wqkv"].shape[-1] // hd
        qkv = dense(x, p["wqkv"], "bsd,tdh->tbsh") + p["bqkv"][:, None, None, :]
        q, k, v = qkv[0], qkv[1], qkv[2]
        q = q.reshape(B, S, h_loc, hd).transpose(0, 2, 1, 3)  # [B,h,S,hd]
        k = k.reshape(B, S, h_loc, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, h_loc, hd).transpose(0, 2, 1, 3)
    else:
        # GQA params (cfg.kv_heads < nheads): separate q and stacked kv
        # projections — the attention op reads the head counts off the
        # shapes and serves shared KV blocks without materializing repeats
        h_loc = p["wq"].shape[-1] // hd
        hkv_loc, rem = divmod(p["wkv"].shape[-1], hd)
        if rem or hkv_loc == 0:
            # e.g. MQA (kv_heads=1) under TP=2: the byte count divides so
            # sharding succeeds, but the shard owns HALF a KV head — the
            # reshape would quietly produce 0 heads and zero attention
            raise ValueError(
                f"TP shard holds {p['wkv'].shape[-1]} kv columns = "
                f"{p['wkv'].shape[-1] / hd:g} heads of dim {hd}; GQA under "
                f"TP needs kv_heads % tp_size == 0 (whole heads per shard)"
            )
        q = (dense(x, p["wq"]) + p["bq"]).reshape(B, S, h_loc, hd).transpose(0, 2, 1, 3)
        kv = dense(x, p["wkv"], "bsd,tdh->tbsh") + p["bkv"][:, None, None, :]
        k = kv[0].reshape(B, S, hkv_loc, hd).transpose(0, 2, 1, 3)
        v = kv[1].reshape(B, S, hkv_loc, hd).transpose(0, 2, 1, 3)

    if cfg.rope:
        # ``rope`` is the precomputed (cos, sin) cache (layer-invariant —
        # scan_blocks hoists it); self-compute when called standalone
        cache = rope if rope is not None else rope_cache(
            _rope_positions(cfg, S), hd, cfg.rope_theta,
            scaling=cfg.rope_scaling)
        q = apply_rope(q, cache=cache)
        k = apply_rope(k, cache=cache)
    return q, k, v


def attention_partial(
    p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: TransformerConfig,
    rope: "tuple | None" = None,
) -> jnp.ndarray:
    """Core attention on the *local* heads; returns the (partial) output
    projection WITHOUT the TP reduction or output bias — the caller closes the
    row-parallel region.  Mirrors ``TpAttention`` (attn.py:53-91) where each
    rank computes ``num_heads // tp_size`` heads.

    x: [B, S, D] — the full sequence, or under context parallelism
    (attn_impl 'ring'/'ulysses') the context-LOCAL chunk [B, S/cp, D]: the
    CP op itself sees the rest of the sequence via ppermute/all_to_all over
    ``cfg.context_axis``.  p['wqkv']: [3, D, H_loc * hd]."""
    B, S, D = x.shape
    hd = cfg.head_dim
    q, k, v = compute_qkv(p, x, cfg, rope=rope)
    h_loc = q.shape[1]
    out = core_attention(q, k, v, cfg)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, h_loc * hd)
    return dense(out, p["wo"])  # [B,S,D] — partial sum across TP shards


@prof.scoped(prof.ATTEND)
def core_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, cfg: TransformerConfig
) -> jnp.ndarray:
    """(q, k, v) [B, H(kv), S, hd] -> out [B, H, S, hd] via the configured
    kernel — the ONE ``attn_impl`` dispatch switch, shared by
    :func:`attention_partial` and the KV-cache prefill
    (models/generate.py), so a new impl cannot be wired in one place and
    silently fall back in the other."""
    if cfg.attn_impl == "flash":
        from ...ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=cfg.causal,
                               window=cfg.sliding_window)
    if cfg.attn_impl == "ring":
        from ...ops.ring_attention import ring_attention

        return ring_attention(
            q, k, v, axis=cfg.context_axis, causal=cfg.causal,
            layout=cfg.cp_layout,
        )
    if cfg.attn_impl == "ulysses":
        from ...ops.ring_attention import ulysses_attention

        return ulysses_attention(q, k, v, axis=cfg.context_axis, causal=cfg.causal)
    from ...ops.flash_attention import mha_reference

    return mha_reference(q, k, v, causal=cfg.causal,
                         window=cfg.sliding_window)


def mlp_partial(p: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    """Col -> act -> Row without the closing reduction/bias (``TpMlp``,
    mlp.py:64-66).  Structural act dispatch: a 3-dim ``w1`` is the stacked
    [2, D, F] gate/up SwiGLU pair (``TransformerConfig.act='swiglu'``) —
    silu(gate) * up, the Llama FFN; 2-dim ``w1`` is the gelu MLP.  Stacking
    gate and up in one leaf keeps the col-parallel TP spec a single rule
    (shard the last dim) and the einsum one fused matmul."""
    if p["w1"].ndim == 3:
        gu = dense(x, p["w1"], "bsd,tdf->tbsf") + p["b1"][:, None, None, :]
        h = jax.nn.silu(gu[0]) * gu[1]
    else:
        h = jax.nn.gelu(dense(x, p["w1"]) + p["b1"])
    return dense(h, p["w2"])  # partial


def _close_row_parallel(
    y: jnp.ndarray, bias: jnp.ndarray, axis: Optional[str], sp: bool,
    compress: Optional[str] = None,
) -> jnp.ndarray:
    """Finish a row-parallel layer: reduce partial sums over TP (into SP
    layout if requested) and add the output bias exactly once.
    ``compress='int8'`` quantizes the SP reduce-scatter's wire (the non-SP
    psum stays exact — its invariance typing has no ring analogue
    cheaper than the pmean decomposition, and activations in non-SP mode
    are replicated anyway)."""
    if axis is not None:
        y = (scatter_to_sp(y, axis, compress=compress) if sp
             else reduce_from_tp(y, axis))
    return y + bias


def _sp_compress(cfg: TransformerConfig, x: jnp.ndarray,
                 axis: Optional[str], sp: bool) -> Optional[str]:
    """Static (trace-time) decision for a quantized SP boundary: 'int8'
    when opted in, SP is on over a real TP axis, and the FULL (gathered)
    activation clears ``compress_min_bytes`` — else None (exact
    collective).  ``x`` is the boundary's sequence-sharded view."""
    if cfg.ag_compress != "int8" or axis is None or not sp:
        return None
    n = axis_size(axis)
    if n <= 1:
        return None
    full_bytes = x.size * n * jnp.dtype(x.dtype).itemsize
    return "int8" if full_bytes >= cfg.compress_min_bytes else None


# ------------------------------------------------- collective-matmul paths
# The SP block boundaries rewritten as ppermute rings
# (tp_utils.ring_ag_matmul / ring_matmul_rs): the entering all-gather is
# fused with the column-parallel projection (each chunk transfer overlaps
# the previous chunk's partial matmul) and the closing psum_scatter is
# fused with the row-parallel matmul.  Opt-in via
# ``TransformerConfig.collective_matmul``; numerics match the fused path
# up to summation order (fp32-level reassociation).


def _use_cm(cfg: TransformerConfig, x: jnp.ndarray,
            axis: Optional[str], sp: bool) -> bool:
    """Static (trace-time) decision: collective matmul only in SP mode on
    a real TP axis, and only when the gathered activation is big enough
    that the ring's n-1 extra latency hops pay for themselves."""
    if not (cfg.collective_matmul and axis is not None and sp):
        return False
    n = axis_size(axis)
    if n <= 1:
        return False
    gathered_bytes = x.size * n * jnp.dtype(x.dtype).itemsize
    return gathered_bytes >= cfg.cm_min_bytes


def attention_partial_cm(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    cfg: TransformerConfig,
    axis: str,
    rope: "tuple | None" = None,
) -> jnp.ndarray:
    """Collective-matmul attention on an SP-sharded input.

    x: [B, s_local, D] sequence shard -> [B, s_local, D] FINAL output
    (TP-reduced into SP layout), WITHOUT the output bias — the ring
    already performs the row-parallel reduction, so the caller must NOT
    apply :func:`_close_row_parallel` (only add ``bo``).

    The QKV projection runs inside :func:`ring_ag_matmul` (per-chunk
    projection overlapped with the next chunk's transfer); attention
    itself sees the assembled full sequence exactly as the fused path
    does; the output projection closes through :func:`ring_matmul_rs`.
    """
    B, s, D = x.shape
    hd = cfg.head_dim
    n = axis_size(axis)
    S = s * n

    def proj(xc):
        # chunk [B, sc, D] -> {'q','k','v'}: [B, h, sc, hd] (seq dim 2) —
        # the head split/transpose is per-sequence-row, so folding it into
        # the ring mm keeps the assembled output identical to compute_qkv
        sc = xc.shape[1]
        if "wqkv" in p:
            h_loc = p["wqkv"].shape[-1] // hd
            qkv = dense(xc, p["wqkv"], "bsd,tdh->tbsh") + p["bqkv"][:, None, None, :]
            f = lambda t: t.reshape(B, sc, h_loc, hd).transpose(0, 2, 1, 3)
            return {"q": f(qkv[0]), "k": f(qkv[1]), "v": f(qkv[2])}
        h_loc = p["wq"].shape[-1] // hd
        hkv_loc, rem = divmod(p["wkv"].shape[-1], hd)
        if rem or hkv_loc == 0:
            raise ValueError(
                f"TP shard holds {p['wkv'].shape[-1]} kv columns = "
                f"{p['wkv'].shape[-1] / hd:g} heads of dim {hd}; GQA under "
                f"TP needs kv_heads % tp_size == 0 (whole heads per shard)"
            )
        q = (dense(xc, p["wq"]) + p["bq"]).reshape(B, sc, h_loc, hd).transpose(0, 2, 1, 3)
        kv = dense(xc, p["wkv"], "bsd,tdh->tbsh") + p["bkv"][:, None, None, :]
        k = kv[0].reshape(B, sc, hkv_loc, hd).transpose(0, 2, 1, 3)
        v = kv[1].reshape(B, sc, hkv_loc, hd).transpose(0, 2, 1, 3)
        return {"q": q, "k": k, "v": v}

    qkv = ring_ag_matmul(x, proj, axis, out_seq_dim=2)
    q, k, v = qkv["q"], qkv["k"], qkv["v"]
    if cfg.rope:
        cache = rope if rope is not None else rope_cache(
            _rope_positions(cfg, S), hd, cfg.rope_theta,
            scaling=cfg.rope_scaling)
        q = apply_rope(q, cache=cache)
        k = apply_rope(k, cache=cache)
    out = core_attention(q, k, v, cfg)
    h_loc = q.shape[1]
    out = out.transpose(0, 2, 1, 3).reshape(B, S, h_loc * hd)
    return ring_matmul_rs(out, lambda oc: dense(oc, p["wo"]), axis)


def mlp_partial_cm(
    p: Dict[str, jnp.ndarray], x: jnp.ndarray, axis: str
) -> jnp.ndarray:
    """Collective-matmul MLP on an SP-sharded input: [B, s_local, D] ->
    [B, s_local, D] FINAL (TP-reduced into SP layout) WITHOUT ``b2`` —
    the ring performs the reduction, the caller only adds the bias.  The
    activation is pointwise per sequence row, so it folds into the ring's
    chunk function and the hidden [B, S, F] never materializes whole."""
    if p["w1"].ndim == 3:
        def mm1(xc):
            gu = dense(xc, p["w1"], "bsd,tdf->tbsf") + p["b1"][:, None, None, :]
            return jax.nn.silu(gu[0]) * gu[1]
    else:
        def mm1(xc):
            return jax.nn.gelu(dense(xc, p["w1"]) + p["b1"])
    h = ring_ag_matmul(x, mm1, axis, out_seq_dim=1)
    return ring_matmul_rs(h, lambda hc: dense(hc, p["w2"]), axis)


def dropout(
    x: jnp.ndarray, rate: float, key: Optional[jax.Array]
) -> jnp.ndarray:
    """Inverted dropout; identity when ``key`` is None or ``rate`` is 0.

    Sharding semantics under SPMD (the reference never had to solve this —
    eager per-rank torch RNG diverges for free): the caller derives ``key``
    with ``axis_unique_key`` (utils/random.py) so data shards draw different
    masks while TP shards (which hold replicated activations in non-SP mode)
    draw the SAME mask and stay consistent.  Under SP the activation is
    seq-sharded, so each shard masking its own tokens IS the globally
    consistent behavior (Megatron's sharded dropout states)."""
    if key is None or rate == 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, jnp.shape(x))
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros((), x.dtype))


#: Valid ``remat`` values everywhere the package threads one: False/None
#: (no checkpointing), True (full-block), 'flash' (block checkpoint whose
#: policy saves the flash kernel's named (o, lse) residuals — tagged in
#: ops/flash_attention._flash_fwd_rule — so the backward skips the Pallas
#: fwd re-run and recomputes only LN/einsum/MLP), and 'flash_offload' ('flash'
#: whose saved o residual lives in ``pinned_host`` memory instead of HBM —
#: XLA schedules the device->host DMA behind the remaining forward and the
#: host->device prefetch behind the backward, so the HBM cost of the
#: policy drops to ~one block's o in flight plus the small on-device lse;
#: the long-context / big-batch lever).
RematMode = Union[bool, None, str]
_REMAT_MODES = (False, None, True, "flash", "flash_offload")
_FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")
# flash_offload partition of the same names (renames must update the tuple,
# and these views follow): o offloads to pinned_host; lse stays saved in
# HBM — offloading it crashes XLA's HostOffloader on current TPU compilers
# (see checkpoint_block)
_OFFLOADED_RESIDUAL_NAMES = _FLASH_RESIDUAL_NAMES[:1]  # ("flash_out",)
_HBM_SAVED_RESIDUAL_NAMES = _FLASH_RESIDUAL_NAMES[1:]  # ("flash_lse",)


def _device_hbm_bytes() -> Optional[int]:
    """Per-device memory capacity, or None when the backend doesn't report
    one (the CPU sim).  Reads through ``obs.mem_ledger.device_capacity``
    — the one ``memory_stats()`` call site (lint-enforced)."""
    try:
        from ...obs.mem_ledger import device_capacity

        return device_capacity()
    except Exception:
        return None


def offload_advice(
    cfg: "TransformerConfig",
    x_shape: Tuple[int, ...],
    nlayers: int,
    hbm_bytes: Optional[int] = None,
) -> Optional[str]:
    """Guard-rail for ``remat='flash_offload'``: the offload trades HBM for
    a host round trip of every block's saved o, which short and medium
    sequences cannot hide behind compute (no benchmark cell sets it: not
    measured in the ledger) — so flag configs where the flash-resident
    footprint comfortably fits HBM and the flag is pure loss.

    Returns a human-readable warning string, or None when the offload is
    plausibly load-bearing (footprint >= half of HBM, or HBM unknown).
    The estimate is the per-chip bytes the 'flash' policy keeps resident
    across the scan: per block one boundary carry [B, S_local, D] in
    ``cfg.dtype``, the saved o (same shape/dtype) and the f32 lse
    [B, H, S_local].  Params/optimizer/temps are NOT modeled — hence the
    conservative 50% threshold rather than a tight fit."""
    if hbm_bytes is None:
        hbm_bytes = _device_hbm_bytes()
    if not hbm_bytes:
        return None
    B, S_local, D = x_shape
    dt = jnp.dtype(cfg.dtype).itemsize
    per_block = 2 * B * S_local * D * dt + B * cfg.nheads * S_local * 4
    total = nlayers * per_block
    if total >= 0.5 * hbm_bytes:
        return None
    return (
        f"remat='flash_offload': the 'flash' policy's resident activations "
        f"are ~{total / 1e9:.2f} GB for this config vs ~{hbm_bytes / 1e9:.1f} GB "
        f"HBM — plain remat='flash' should fit and skips the host round "
        f"trip of every block's saved output, which short and medium "
        f"sequences cannot hide. "
        f"Use 'flash_offload' only when 'flash' actually OOMs."
    )


def checkpoint_block(fn, remat: RematMode, prevent_cse: bool = True):
    """``jax.checkpoint`` with the package's validated remat modes.

    Every ``remat=`` kwarg in the package funnels here, so a misspelled
    policy string raises instead of silently degrading to plain block remat
    (which would leave the caller believing the faster policy is active).
    ``prevent_cse=False`` is correct under ``lax.scan`` (the loop structure
    already blocks CSE — the default barriers would only cost performance).
    """
    if remat not in _REMAT_MODES:
        raise ValueError(
            f"remat must be one of {_REMAT_MODES}, got {remat!r}")
    if not remat:
        return fn
    if remat == "flash":
        policy = jax.checkpoint_policies.save_only_these_names(
            *_FLASH_RESIDUAL_NAMES)
    elif remat == "flash_offload":
        # offload the BIG residual (o, [B, S, D] bf16) only; lse
        # ([B, H, S] f32, ~1/32 of o at head_dim 64) stays saved in HBM.
        # Offloading lse too crashes XLA's HostOffloader on current TPU
        # compilers — its consumer path reaches a variadic (2-operand)
        # reduce the pass can't walk (host_offload_utils.cc:225, observed
        # on v5e 2026-07-31 on every GPT config tried); keeping lse
        # on-device costs ~3% of the HBM win and compiles everywhere.
        policy = jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=list(_HBM_SAVED_RESIDUAL_NAMES),
            names_which_can_be_offloaded=list(_OFFLOADED_RESIDUAL_NAMES),
            offload_src="device",
            offload_dst="pinned_host",
        )
    else:
        policy = None
    return jax.checkpoint(fn, prevent_cse=prevent_cse, policy=policy)


# ---------------------------------------------------------------------- blocks


def block_forward(
    p: Dict[str, PyTree],
    x: jnp.ndarray,
    cfg: TransformerConfig,
    axis: Optional[str] = None,
    sp: bool = False,
    dropout_key: Optional[jax.Array] = None,
    rope: "tuple | None" = None,
) -> jnp.ndarray:
    """Pre-LN transformer block (``ParallelBlock``, transformer.py:48-72):
    LN kept replicated and applied on the sequence shard; SP enters/leaves at
    the attention/MLP boundaries.  ``dropout_key`` activates residual dropout
    at ``cfg.dropout_rate`` (distinct subkeys for the two sites).

    x: [B, S_local, D] when ``sp`` else [B, S, D]."""
    k_attn = k_mlp = None
    if dropout_key is not None and cfg.dropout_rate > 0.0:
        k_attn, k_mlp = jax.random.split(dropout_key)
    use_cm = _use_cm(cfg, x, axis, sp)
    with jax.named_scope(prof.MIXER):
        h = layer_norm(x, p["ln1"], cfg.norm_eps)
        # quantized SP boundaries (cfg.ag_compress): the entering all-gather
        # and the closing reduce-scatter carry int8 payloads; their custom
        # VJPs quantize the backward's mirror collectives too
        qc = _sp_compress(cfg, h, axis, sp)
        if use_cm:
            # ring path: gather⊕QKV-matmul and WO-matmul⊕scatter decomposed;
            # the ring already reduced over TP, so only the bias remains
            y = attention_partial_cm(p["attn"], h, cfg, axis, rope=rope)
            y = y + p["attn"]["bo"]
        else:
            full = (gather_from_sp(h, axis, compress=qc) if (axis and sp)
                    else h)
            y = attention_partial(p["attn"], full, cfg, rope=rope)
            y = _close_row_parallel(y, p["attn"]["bo"], axis, sp,
                                    compress=qc)
        x = x + dropout(y, cfg.dropout_rate, k_attn)

    with jax.named_scope(prof.FFN):
        h = layer_norm(x, p["ln2"], cfg.norm_eps)
        qc = _sp_compress(cfg, h, axis, sp)
        if use_cm:
            z = mlp_partial_cm(p["mlp"], h, axis) + p["mlp"]["b2"]
        else:
            full = (gather_from_sp(h, axis, compress=qc) if (axis and sp)
                    else h)
            z = mlp_partial(p["mlp"], full)
            z = _close_row_parallel(z, p["mlp"]["b2"], axis, sp,
                                    compress=qc)
        return x + dropout(z, cfg.dropout_rate, k_mlp)


def transformer_forward(
    params: Dict[str, PyTree],
    x: jnp.ndarray,
    cfg: TransformerConfig,
    axis: Optional[str] = None,
    sp: bool = False,
    gather_output: bool = True,
) -> jnp.ndarray:
    """Block stack with SP split/gather at the ends (``Transformer``,
    transformer.py:88-100).  x: [B, S, D] full activation in.

    With ``sp`` and ``gather_output=False`` the output stays sequence-sharded
    ([B, S/tp, D] per shard) — pair it with an ``out_specs`` of
    ``P(None, axis, None)`` so shard_map reassembles the full array without
    spending the final all-gather the reference performs
    (transformer.py:98-99); XLA's output layout does the job for free."""
    if axis and sp:
        x = split_to_sp(x, axis)
    for bp in params["blocks"]:
        x = block_forward(bp, x, cfg, axis=axis, sp=sp)
    x = layer_norm(x, params["ln_f"], cfg.norm_eps)
    if axis and sp and gather_output:
        x = gather_from_sp(x, axis)
    return x


def scan_blocks(
    stacked: PyTree,
    x: jnp.ndarray,
    cfg: TransformerConfig,
    axis: Optional[str] = None,
    sp: bool = False,
    remat: RematMode = False,
    dropout_key: Optional[jax.Array] = None,
    layer_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Run ``x`` through a layer-stacked block tree with ``lax.scan`` (one
    compiled block body for L layers).  Shared by the GPT and ViT model
    families and pipeline stage slabs.

    ``remat`` checkpoints each block: only block boundaries are saved and the
    backward recomputes the block, trading ~1 extra fwd for O(L) less
    activation HBM — enables 2-4x larger per-chip batch (place selectively
    via tools/profiler.py MB/ms ranking).  ``remat='flash'`` also saves the
    flash-attention kernel's (o, lse) residuals so the backward recompute
    skips the Pallas fwd kernel — faster than ``True`` for ~[B, S, D] more
    saved bytes per block (requires ``cfg.attn_impl`` 'flash'/'ring'/
    'ulysses'; with 'naive' attention no tags exist and it degrades to
    exactly ``True``).  ``remat='flash_offload'`` parks those saved
    residuals in pinned_host memory instead of HBM (the long-context /
    big-batch lever — see :data:`RematMode`).

    ``dropout_key`` enables residual dropout (``cfg.dropout_rate``); each
    layer folds its index into the key so layers draw distinct masks.

    ``layer_mask`` ([L] floats, 1=real 0=padding) supports UNEQUAL pipeline
    stage loads via padded slabs (``pipeline_helper.balanced_stage_stack``):
    padding layers are masked out with ``jnp.where`` — they contribute zero
    grads, so zero-initialized padding params stay zero under any optimizer.
    """
    from ..data_parallel import _mark_varying, _vma

    # the carry's varying axes must cover every value entering the block body:
    # the params' (e.g. pipe-sharded stacks make the block output pipe-varying
    # even when x starts replicated) AND the dropout key's (an
    # axis_unique_key-derived key makes the masks — hence the output —
    # data-varying, and lax.scan requires a fixed carry type across steps)
    want = _vma(x)
    for leaf in jax.tree.leaves(stacked):
        want = want | _vma(leaf)
    if dropout_key is not None:
        want = want | _vma(dropout_key)
    if layer_mask is not None:
        want = want | _vma(layer_mask)
    x = _mark_varying(x, tuple(want))  # idempotent: only missing axes added

    # layer-invariant (cos, sin): computed ONCE and closed over by the scan
    # body (a loop constant), instead of re-deriving the trig per layer
    rope = block_rope_cache(cfg, x.shape[1], axis, sp)

    def blk(lp, h, i):
        k = (
            jax.random.fold_in(dropout_key, i)
            if dropout_key is not None
            else None
        )
        return block_forward(
            lp, h, cfg, axis=axis, sp=sp, dropout_key=k, rope=rope)

    L = jax.tree.leaves(stacked)[0].shape[0]

    if remat == "flash_offload":
        # trace-time advisory (shapes are static): offloading when 'flash'
        # fits is a measured ~2.4x loss — never let that happen silently
        advice = offload_advice(cfg, x.shape, L)
        if advice:
            import warnings

            warnings.warn(advice, stacklevel=2)
    if remat:
        blk = checkpoint_block(blk, remat, prevent_cse=False)

    if layer_mask is None:
        def body(h, xs):
            lp, i = xs
            return blk(lp, h, i), None

        x, _ = jax.lax.scan(body, x, (stacked, jnp.arange(L)))
    else:
        # jnp.where, NOT lax.cond: the mask differs across pipe stages, and a
        # collective inside a branch-divergent cond is undefined (ppermute is
        # a full-mesh rendezvous — see pipeline_1f1b's backward unit).  The
        # padding layers' FLOPs are paid, but their params still get exactly
        # zero grads (where's transpose routes the cotangent to the taken
        # branch only), so zero-initialized padding stays zero.
        def body(h, xs):
            lp, i, m = xs
            return jnp.where(m > 0, blk(lp, h, i), h), None

        x, _ = jax.lax.scan(
            body, x, (stacked, jnp.arange(L), layer_mask)
        )
    return x


def stacked_block_specs(
    tp_axis: Optional[str] = None, stack_axis: Optional[str] = None,
    gqa: bool = False, norm: str = "layer", act: str = "gelu",
) -> Dict[str, PyTree]:
    """Per-block TP specs with a leading entry for the layer-stack dim —
    ``stack_axis`` shards the stack (pipeline stages), None replicates it.
    Shared by gpt_param_specs / vit_param_specs."""
    bspecs = block_param_specs(tp_axis, gqa=gqa, norm=norm, act=act)
    is_spec = lambda x: isinstance(x, P)
    return jax.tree.map(lambda s: P(stack_axis, *tuple(s)), bspecs, is_leaf=is_spec)


# ------------------------------------------------------------------------ init


def init_block_params(key, cfg: TransformerConfig, mlp: bool = True) -> Dict[str, PyTree]:
    """``mlp=False`` skips the dense FFN weights (the largest leaves) — for
    callers that replace the FFN, e.g. MoE expert blocks."""
    kq, ko, k1, k2 = jax.random.split(key, 4)
    D, F = cfg.dim, cfg.ffn_dim
    s = 1.0 / math.sqrt(D)
    dt = cfg.dtype
    if cfg.is_gqa:
        Dkv = cfg.kv_head_count * cfg.head_dim
        attn = {
            "wq": (jax.random.normal(kq, (D, D)) * s).astype(dt),
            "bq": jnp.zeros((D,), dt),
            "wkv": (jax.random.normal(
                jax.random.fold_in(kq, 1), (2, D, Dkv)) * s).astype(dt),
            "bkv": jnp.zeros((2, Dkv), dt),
            "wo": (jax.random.normal(ko, (D, D)) * s).astype(dt),
            "bo": jnp.zeros((D,), dt),
        }
    else:
        attn = {
            "wqkv": (jax.random.normal(kq, (3, D, D)) * s).astype(dt),
            "bqkv": jnp.zeros((3, D), dt),
            "wo": (jax.random.normal(ko, (D, D)) * s).astype(dt),
            "bo": jnp.zeros((D,), dt),
        }
    out = {
        "ln1": init_norm_params(D, dt, cfg.norm),
        "attn": attn,
        "ln2": init_norm_params(D, dt, cfg.norm),
    }
    if mlp:
        if cfg.act == "swiglu":
            out["mlp"] = {
                "w1": (jax.random.normal(k1, (2, D, F)) * s).astype(dt),
                "b1": jnp.zeros((2, F), dt),
                "w2": (jax.random.normal(k2, (F, D)) * (1.0 / math.sqrt(F))).astype(dt),
                "b2": jnp.zeros((D,), dt),
            }
        else:
            out["mlp"] = {
                "w1": (jax.random.normal(k1, (D, F)) * s).astype(dt),
                "b1": jnp.zeros((F,), dt),
                "w2": (jax.random.normal(k2, (F, D)) * (1.0 / math.sqrt(F))).astype(dt),
                "b2": jnp.zeros((D,), dt),
            }
    return out


def init_transformer_params(key, cfg: TransformerConfig) -> Dict[str, PyTree]:
    keys = jax.random.split(key, cfg.nlayers)
    return {
        "blocks": [init_block_params(k, cfg) for k in keys],
        "ln_f": init_norm_params(cfg.dim, cfg.dtype, cfg.norm),
    }


# ----------------------------------------------------------------------- specs


def block_param_specs(
    axis: str = "tensor", gqa: bool = False, norm: str = "layer",
    act: str = "gelu",
) -> Dict[str, PyTree]:
    """PartitionSpec tree for one block under TP.  Column-parallel weights
    shard their output dim, row-parallel their input dim; LN and row biases
    replicated (added post-reduction exactly once).  ``gqa`` selects the
    grouped-query leaf set (separate wq / stacked wkv; requires
    kv_heads % tp_size == 0 so shards own whole KV heads); ``norm``/``act``
    select the rms (biasless) norm leaves and the stacked [2, D, F] SwiGLU
    w1 — match the block's TransformerConfig."""
    attn = (
        {
            "wq": P(None, axis),
            "bq": P(axis),
            "wkv": P(None, None, axis),
            "bkv": P(None, axis),
            "wo": P(axis, None),
            "bo": P(),
        }
        if gqa
        else {
            "wqkv": P(None, None, axis),  # heads contiguous on last dim
            "bqkv": P(None, axis),
            "wo": P(axis, None),
            "bo": P(),
        }
    )
    mlp = (
        {
            "w1": P(None, None, axis),  # [2, D, F]: gate/up both col-parallel
            "b1": P(None, axis),
            "w2": P(axis, None),
            "b2": P(),
        }
        if act == "swiglu"
        else {
            "w1": P(None, axis),
            "b1": P(axis),
            "w2": P(axis, None),
            "b2": P(),
        }
    )
    return {
        "ln1": norm_param_specs(norm),
        "attn": attn,
        "ln2": norm_param_specs(norm),
        "mlp": mlp,
    }


def transformer_param_specs(cfg: TransformerConfig, axis: str = "tensor") -> Dict[str, PyTree]:
    return {
        "blocks": [
            block_param_specs(axis, gqa=cfg.is_gqa, norm=cfg.norm, act=cfg.act)
            for _ in range(cfg.nlayers)
        ],
        "ln_f": norm_param_specs(cfg.norm),
    }
