"""The plain reference: a decoder-only transformer in straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision, no kernels, no
cache, no batching tricks.  It imports nothing of the program.  It reads the
seeded weight tree (benchmarks/weights.py) one layer at a time, upcasting
that layer only, so it fits on the chip once the program's state is gone.

Two block types, chosen by ``Arch``: GPT-2 (LayerNorm, learned positions,
fused MHA, tanh-GELU MLP) and Mistral (RMSNorm, rotary half-split, GQA,
sliding-window causal mask, SwiGLU).

``quant="fp8"`` computes the same mathematics with every matmul's operands
rounded to e4m3, scaled per row of the contraction: the control that the
benchmark's limits must reject.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.arch import Arch

F32 = jnp.float32


def _round_to(x, kind: str, axis: int):
    if kind != "fp8":
        raise ValueError(f"unknown precision {kind!r}")
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s


def _q(x, kind: Optional[str], axis: int):
    if kind is None:
        return x
    # straight-through: the rounding shapes the value, not the gradient
    return x + jax.lax.stop_gradient(_round_to(x, kind, axis) - x)


def mm(a, b, quant: Optional[str] = None):
    """``a [..., K] @ b [K, N]`` in float32."""
    return jnp.matmul(_q(a, quant, -1), _q(b, quant, 0),
                      precision=jax.lax.Precision.HIGHEST)


def norm(x, p: Dict[str, Any], a: Arch):
    if a.norm == "rms":
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + a.eps) * p["scale"].astype(F32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + a.eps) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rope(x, a: Arch):
    """x [H, S, hd]; pairs (i, i + hd/2) turn by pos * theta^(-2i/hd)."""
    S, half = x.shape[-2], a.head_dim // 2
    inv = a.rope_theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, a: Arch, quant: Optional[str] = None):
    """q [H, S, hd], k/v [Hkv, S, hd] -> [H, S, hd]; causal, windowed."""
    H, S, hd = q.shape
    rep = H // k.shape[0]
    k = jnp.repeat(k, rep, axis=0)
    v = jnp.repeat(v, rep, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", _q(q, quant, -1), _q(k, quant, -1),
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    keep = j <= i
    if a.window is not None:
        keep &= (i - j) < a.window
    s = jnp.where(keep[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", _q(p, quant, -1), _q(v, quant, -2),
                      precision=jax.lax.Precision.HIGHEST)


def layer(p: Dict[str, Any], x, a: Arch, quant: Optional[str] = None):
    """One block on one sequence: x [S, D] float32 -> [S, D]."""
    p = jax.tree.map(lambda w: w.astype(F32), p)
    S, hd = x.shape[0], a.head_dim
    h = norm(x, p["ln1"], a)
    at = p["attn"]
    if "wqkv" in at:
        q, k, v = (mm(h, at["wqkv"][i], quant) + at["bqkv"][i] for i in range(3))
    else:
        q = mm(h, at["wq"], quant) + at["bq"]
        k, v = (mm(h, at["wkv"][i], quant) + at["bkv"][i] for i in range(2))
    q = q.reshape(S, a.heads, hd).transpose(1, 0, 2)
    k = k.reshape(S, a.kv_heads, hd).transpose(1, 0, 2)
    v = v.reshape(S, a.kv_heads, hd).transpose(1, 0, 2)
    if a.pos == "rope":
        q, k = rope(q, a), rope(k, a)
    o = attention(q, k, v, a, quant).transpose(1, 0, 2).reshape(S, a.dim)
    x = x + mm(o, at["wo"], quant) + at["bo"]
    h = norm(x, p["ln2"], a)
    m = p["mlp"]
    if a.act == "swiglu":
        gate = mm(h, m["w1"][0], quant) + m["b1"][0]
        up = mm(h, m["w1"][1], quant) + m["b1"][1]
        h = gate * jax.nn.sigmoid(gate) * up
    else:
        h = gelu_tanh(mm(h, m["w1"], quant) + m["b1"])
    return x + mm(h, m["w2"], quant) + m["b2"]


def embed(params, tokens, a: Arch):
    """tokens [S] -> [S, D] float32."""
    x = params["tok_emb"].astype(F32)[tokens]
    if a.pos == "learned":
        x = x + params["pos_emb"].astype(F32)[: tokens.shape[0]]
    return x


def head_logits(head_p, x, a: Arch, quant: Optional[str] = None):
    """Final norm and output head: x [S, D] -> [S, V] float32."""
    return mm(norm(x, head_p["ln_f"], a), head_p["head"].astype(F32), quant)


def head_loss(head_p, x, targets, a: Arch, quant: Optional[str] = None):
    """Mean next-token cross-entropy of one sequence."""
    logits = head_logits(head_p, x, a, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0])


def layer_slice(blocks, l: int):
    return jax.tree.map(lambda w: w[l], blocks)


@functools.lru_cache(maxsize=None)
def _jitted(a: Arch, quant: Optional[str]):
    """The reference's few programs for one (Arch, precision)."""
    lay = jax.jit(lambda p, x: layer(p, x, a, quant))
    emb = jax.jit(lambda params, t: embed(params, t, a))
    logits = jax.jit(lambda hp, x: head_logits(hp, x, a, quant))
    return lay, emb, logits


def forward_logits(params, tokens, a: Arch, quant: Optional[str] = None):
    """One sequence, tokens [S] -> logits [S, V] float32, layer by layer."""
    lay, emb, logits = _jitted(a, quant)
    x = emb({k: params[k] for k in ("tok_emb", "pos_emb") if k in params},
            jnp.asarray(tokens, jnp.int32))
    for l in range(a.layers):
        x = lay(layer_slice(params["blocks"], l), x)
    return logits({"ln_f": params["ln_f"], "head": params["head"]}, x)
