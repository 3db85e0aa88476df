"""The plain reference of the ``granitemoehybrid`` family as Granite-4.0-H-Micro
publishes it (no experts: ``num_local_experts`` 0, so the shared MLP is the
whole feed-forward part): straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision, one sequence, no kernels, no cache, no chunking.  It
imports nothing of the program (``mm`` and ``_q`` are the reference's own,
benchmarks/reference/model.py, for the fp8 control).

The model, block by block (``GraniteMoeHybridDecoderLayer``)::

    h_0    = E[token] * embedding_multiplier
    h     <- h + residual_multiplier * mixer_i(RMSNorm(h))
    h     <- h + residual_multiplier * mlp(RMSNorm(h))
    logits = (RMSNorm(h_last) E^T) / logits_scaling          (tied head)

- ``mlp(x) = (silu(a) * b) W_out``, ``[a, b] = x W_in``, no bias.
- ``layer_types[i] == "mamba"``: the Mamba-2 mixer.  ``[z, xBC, dt] = x
  W_in``; a depthwise causal convolution with bias, then silu, over ``xBC``,
  from ZERO rows before the first position; ``dt = softplus(dt + dt_bias)``;
  the recurrence STEP BY STEP from a zero state, ``S_t = exp(dt_t A) S_{t-1}
  + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``, every head reading the B
  and C of its group; the gate BEFORE the norm, ``y <- RMSNorm(y * silu(z))``
  within each of ``mamba_n_groups`` groups of ``d_inner`` (one group here:
  the whole of it); ``y W_out``.
- ``"attention"``: grouped-query attention, causal, NO positions
  (``position_embedding_type`` "nope"), the softmax scale
  ``attention_multiplier`` (1/64 = 1/head_dim here, NOT 1/sqrt(head_dim)).

It reads the seeded tree the program reads (benchmarks/weights_granite_hybrid.py):
``params["layers"]`` lists ONE-mixer layers, so block ``i`` is layers ``2i``
(its mixer, under the block's first norm) and ``2i + 1`` (its MLP, under the
second); the tree has no ``head`` leaf.  One layer is upcast at a time.

Departures from the published block, each also in the configuration file:
the recurrent state is float32 (the published kernels keep it in the
activations' dtype unless told otherwise); ``dt`` is not clamped
(``time_step_limit`` (0, inf), the published default); seeded weights.

``quant="fp8"`` rounds every matmul's operands to e4m3: the control that the
cell's limit must refuse."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.model import F32, _q, mm

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes and constants of one ``granitemoehybrid`` stack without
    experts, as it is run here."""

    dim: int
    #: a block's mixer, in order: 'mamba' | 'attention'
    layer_types: Tuple[str, ...]
    vocab: int
    ffn: int
    # attention
    heads: int
    kv_heads: int
    head_dim: int
    attn_scale: float
    # Mamba-2
    m_heads: int
    m_head_dim: int
    state: int
    groups: int
    conv_kernel: int
    chunk: int
    # the four multipliers' other three
    embed_scale: float
    residual_scale: float
    logits_scale: float
    eps: float

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    @property
    def pattern(self) -> str:
        """The stack as the program's one-mixer layers: a block is its
        mixer (``M`` | ``*``) and then its MLP (``D``)."""
        return "".join({"mamba": "M", "attention": "*"}[k] + "D"
                       for k in self.layer_types)


def rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def mamba(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None):
    """x [S, D] (normed) -> [S, D]."""
    S = x.shape[0]
    H, P, N, G, K, di = (s.m_heads, s.m_head_dim, s.state, s.groups,
                         s.conv_kernel, s.d_inner)
    zxbcdt = mm(x, p["in_proj"], quant)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + s.conv_channels],
                  zxbcdt[:, di + s.conv_channels:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    xbc = silu(p["conv_b"] + sum(padded[k:k + S] * p["conv_w"][k]
                                 for k in range(K)))
    xs = xbc[:, :di].reshape(S, H, P)
    # head h reads the B and C of its group h // (H / G)
    Bh = jnp.repeat(xbc[:, di:di + G * N].reshape(S, G, N), H // G, axis=1)
    Ch = jnp.repeat(xbc[:, di + G * N:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])               # [S, H]
    A = -jnp.exp(p["A_log"])                              # [H]

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return state, jnp.sum(state * C_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (xs, dt, Bh, Ch))
    y = (y + p["D"][:, None] * xs).reshape(S, di)
    yg = (y * silu(z)).reshape(S, G, di // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + s.eps)
    return mm(yg.reshape(S, di) * p["gate_norm"]["scale"], p["out_proj"],
              quant)


def attention(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None):
    """x [S, D] (normed) -> [S, D]: causal GQA, no positions, the scores
    times ``attn_scale``."""
    S, hd = x.shape[0], s.head_dim
    q = mm(x, p["wq"], quant).reshape(S, s.heads, hd).transpose(1, 0, 2)
    k = mm(x, p["wkv"][0], quant).reshape(S, s.kv_heads, hd).transpose(1, 0, 2)
    v = mm(x, p["wkv"][1], quant).reshape(S, s.kv_heads, hd).transpose(1, 0, 2)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    sc = jnp.einsum("hqd,hkd->hqk", _q(q, quant, -1), _q(k, quant, -1),
                    precision=_HI) * s.attn_scale
    keep = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    pr = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", _q(pr, quant, -1), _q(v, quant, -2),
                   precision=_HI)
    return mm(o.transpose(1, 0, 2).reshape(S, s.heads * hd), p["wo"], quant)


def mlp(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None):
    ab = mm(x, p["w1"], quant)
    return mm(silu(ab[:, :s.ffn]) * ab[:, s.ffn:], p["w2"], quant)


def block(kind: str, mixer: Dict[str, Any], ffn: Dict[str, Any], h, *,
          s: Shape, quant: Optional[str] = None):
    """One published block on one sequence: h [S, D] float32 -> [S, D]."""
    mixer, ffn = (jax.tree.map(lambda w: w.astype(F32), p)
                  for p in (mixer, ffn))
    mix = mamba if kind == "mamba" else attention
    h = h + s.residual_scale * mix(
        mixer, rms(h, mixer["norm"]["scale"], s.eps), s, quant)
    return h + s.residual_scale * mlp(
        ffn, rms(h, ffn["norm"]["scale"], s.eps), s, quant)


@functools.lru_cache(maxsize=None)
def _jitted(s: Shape, quant: Optional[str]):
    """The reference's few programs for one (Shape, precision): one a kind
    of block, the embedding, the tied head."""
    blocks = {kind: jax.jit(functools.partial(block, kind, s=s, quant=quant))
              for kind in set(s.layer_types)}
    emb = jax.jit(lambda table, t: table.astype(F32)[t] * s.embed_scale)
    head = jax.jit(lambda hp, x: mm(
        rms(x, hp["ln_f"]["scale"].astype(F32), s.eps),
        hp["tok_emb"].astype(F32).T, quant) * s.logits_scale)
    return blocks, emb, head


def forward_logits(params, tokens, s: Shape, quant: Optional[str] = None):
    """One sequence, tokens [S] -> logits [S, V] float32."""
    blocks, emb, head = _jitted(s, quant)
    layers = params["layers"]
    if "head" in params or len(layers) != 2 * len(s.layer_types):
        raise ValueError("a tied tree of two one-mixer layers a block")
    h = emb(params["tok_emb"], jnp.asarray(tokens, jnp.int32))
    for i, kind in enumerate(s.layer_types):
        h = blocks[kind](layers[2 * i], layers[2 * i + 1], h)
    return head({"ln_f": params["ln_f"], "tok_emb": params["tok_emb"]}, h)


def forward_following(params, tokens, s: Shape, quant: Optional[str] = None,
                      follow=None) -> Dict[str, Any]:
    """``runners/serve_family.py``'s form of the call: the model chooses
    nothing (no experts, no kept positions), so there is no routing to
    follow and no deficit."""
    return {"logits": forward_logits(params, tokens, s, quant),
            "routing": None, "deficit": None}
