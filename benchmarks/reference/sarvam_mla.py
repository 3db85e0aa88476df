"""The plain reference of the ``sarvam_mla`` family: pre-norm blocks of
latent attention (MLA) and a feed-forward part that is dense in the leading
layer and a sigmoid-routed mixture of gated experts with one shared expert
after it, in straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision, no kernels, no cache, no chunking of positions.  It imports
nothing of the program.  One sequence at a time; one layer upcast at a time
(one EXPERT, one HEAD at a time inside a layer), so it fits on the chip once
the program's pool is gone.

A layer of the stack is ONE mixer under a residual, ``x <- x + f(rms(x))``,
by a pattern string: ``*`` latent attention, ``D`` the dense gated MLP,
``E`` the expert layer.  A published block is two of them (``*D``, ``*E``).

- Attention, UNABSORBED (the published form; the program runs the absorbed
  one, a different route to the same numbers): ``q = x W_q`` split by head
  into ``nope`` and ``rope`` parts, each head normed over its whole width
  (a learned RMSNorm, ``use_qk_norm``) before its rope part is rotated;
  ``x W_kva`` gives the latent ``c`` (normed) and one ``k_rope`` (rotated,
  not normed) for all heads; per head ``k_nope = c W_uk[h]^T`` and ``v = c
  W_uv[h]`` are MATERIALISED; scores ``(q_nope . k_nope + q_rope . k_rope)
  x scale``, causal softmax, ``W_o``.  Rope turns half-split pairs of the
  rope dims by yarn's blended frequencies; ``scale = (nope + rope)^-0.5 x
  (0.1 mscale_all_dim ln factor + 1)^2``; with ``mscale == mscale_all_dim``
  the cos/sin tables carry no factor of their own.
- Experts: sigmoid scores over ALL experts in float32, the top k of score +
  bias, weights = score / (sum of the chosen + 1e-20) x scale; the experts
  this share HOLDS in a plain loop, every token through every held expert,
  weighted by its gate or by zero; what the absent experts would add is
  left out, as in the program.  Gated (SwiGLU) experts, ``w1`` holding gate
  and up side by side, no biases; the shared expert likewise, every token.

``quant="fp8"`` rounds every matmul's operands to e4m3: the control that
the cell's limits must reject.  ``forward_following`` takes someone else's
choice of experts, as benchmarks/reference/nemotron_h.py explains.

Departures from a checkpoint's layout, none of which changes a number at
seeded weights: ``W_kvb`` is kept as its two per-head halves ``wuk`` [H,
nope, latent] and ``wuv`` [H, latent, v]; rope pairs are half-split, not
interleaved (a permutation of ``W_q`` / ``W_kva`` columns)."""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.model import F32, _q, mm
# the sigmoid router with a selection bias and a followed choice, and the
# RMSNorm, are the nemotron_h reference's, sizes read off this Shape
from benchmarks.reference.nemotron_h import gates, rms

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one ``sarvam_mla`` stack as it is run here."""

    dim: int
    #: one mixer a layer: '*' latent attention | 'D' dense MLP | 'E' experts
    pattern: str
    vocab: int
    # latent attention
    heads: int
    nope: int
    rope: int
    v_dim: int
    latent: int
    # rope (deepseek_yarn)
    rope_theta: float
    yarn_factor: float
    yarn_orig: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    # feed-forward
    dense_ffn: int
    experts: int
    held_first: int
    held: int
    top_k: int
    moe_ffn: int
    shared_ffn: int
    routed_scale: float
    eps: float

    @property
    def q_dim(self) -> int:
        return self.nope + self.rope

    @property
    def cached(self) -> int:
        """What a position caches: the latent and the shared rope key."""
        return self.latent + self.rope

    @property
    def scale(self) -> float:
        m = 1.0
        if self.yarn_factor > 1.0 and self.mscale_all_dim:
            m = 0.1 * self.mscale_all_dim * math.log(self.yarn_factor) + 1.0
        return self.q_dim ** -0.5 * m * m


def swiglu(h):
    """``h`` = gate and up side by side -> silu(gate) * up."""
    F = h.shape[-1] // 2
    return (h[..., :F] * jax.nn.sigmoid(h[..., :F])) * h[..., F:]


def yarn_inv_freq(s: Shape):
    """The rope dims' inverse frequencies, yarn's blend (closed form):
    frequency i of ``rope / 2`` is ``theta^(-2i / rope)``; those that turn
    fewer than ``beta_slow`` times over the original context are divided by
    ``factor`` (interpolated), those that turn more than ``beta_fast`` times
    are kept, a linear ramp between."""
    half = s.rope // 2
    base = s.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    if s.yarn_factor <= 1.0:
        return jnp.asarray(base, F32)

    def dim_of(turns):   # the (fractional) frequency index that makes them
        return (s.rope * math.log(s.yarn_orig / (turns * 2 * math.pi))
                / (2 * math.log(s.rope_theta)))

    low = max(math.floor(dim_of(s.beta_fast)), 0)
    high = min(math.ceil(dim_of(s.beta_slow)), s.rope - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(base / s.yarn_factor * ramp + base * (1.0 - ramp), F32)


def rope(x, s: Shape):
    """x [..., S, rope]: pairs (i, i + rope/2) turn by pos x inv_freq[i].
    ``mscale / mscale_all_dim`` scales the tables (1 as published)."""
    S, half = x.shape[-2], s.rope // 2
    ang = jnp.arange(S, dtype=F32)[:, None] * yarn_inv_freq(s)[None, :]
    af = 1.0
    if s.yarn_factor > 1.0 and s.mscale and s.mscale_all_dim:
        get = lambda m: 0.1 * m * math.log(s.yarn_factor) + 1.0
        af = get(s.mscale) / get(s.mscale_all_dim)
    cos, sin = jnp.cos(ang) * af, jnp.sin(ang) * af
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None):
    """x [S, D] (normed) -> [S, D], one head at a time."""
    S = x.shape[0]
    q = mm(x, p["wq"], quant).reshape(S, s.heads, s.q_dim)
    q = rms(q, p["q_norm"]["scale"], s.eps).transpose(1, 0, 2)   # [H, S, q]
    q_nope, q_rope = q[..., :s.nope], rope(q[..., s.nope:], s)
    kva = mm(x, p["wkva"], quant)
    c = rms(kva[:, :s.latent], p["kv_norm"]["scale"], s.eps)     # [S, latent]
    k_rope = rope(kva[:, s.latent:], s)                          # [S, rope]
    keep = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def head(h):
        qn, qr, wuk, wuv = h
        k_nope = mm(c, wuk.T, quant)                             # [S, nope]
        v = mm(c, wuv, quant)                                    # [S, v]
        qk = jnp.concatenate([qn, qr], -1)
        k = jnp.concatenate([k_nope, k_rope], -1)
        sc = jnp.matmul(_q(qk, quant, -1), _q(k, quant, -1).T,
                        precision=_HI) * s.scale
        pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return jnp.matmul(_q(pr, quant, -1), _q(v, quant, -2), precision=_HI)

    o = jax.lax.map(head, (q_nope, q_rope, p["wuk"], p["wuv"]))  # [H, S, v]
    return mm(o.transpose(1, 0, 2).reshape(S, s.heads * s.v_dim), p["wo"],
              quant)


def dense_mlp(p: Dict[str, Any], x, quant: Optional[str] = None):
    return mm(swiglu(mm(x, p["w1"], quant)), p["w2"], quant)


def moe(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None,
        follow=None):
    """x [S, D] (normed) -> ([S, D], experts chosen, deficit): this share's
    routed part plus the shared expert.  ``p['experts']`` keeps its stored
    precision: each expert is upcast inside the loop."""
    gate, idx, deficit = gates(p["router"], x, s, quant, follow)
    gate = gate[:, s.held_first:s.held_first + s.held]

    def one(acc, e):
        w1, w2, g = e
        r = mm(swiglu(mm(x, w1.astype(F32), quant)), w2.astype(F32), quant)
        return acc + g[:, None] * r, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             (p["experts"]["w1"], p["experts"]["w2"], gate.T))
    shared = dense_mlp(jax.tree.map(lambda w: w.astype(F32), p["shared"]), x,
                       quant)
    return routed + shared, idx, deficit


def layer(kind: str, p: Dict[str, Any], x, follow=None, *, s: Shape,
          quant: Optional[str] = None):
    """One layer on one sequence: x [S, D] float32 -> [S, D]; an ``E``
    layer also gives the experts chosen [S, k] and the deficit [S]."""
    h = rms(x, p["norm"]["scale"].astype(F32), s.eps)
    if kind == "E":
        y, idx, deficit = moe(p, h, s, quant, follow)
        return x + y, idx, deficit
    p = jax.tree.map(lambda w: w.astype(F32), p)
    if kind == "D":
        return x + dense_mlp(p, h, quant)
    return x + attention(p, h, s, quant)


@functools.lru_cache(maxsize=None)
def _jitted(s: Shape, quant: Optional[str]):
    """The reference's few programs for one (Shape, precision): one a kind
    of layer, the embedding, the head."""
    lay = {kind: jax.jit(functools.partial(layer, kind, s=s, quant=quant))
           for kind in set(s.pattern)}
    emb = jax.jit(lambda table, t: table.astype(F32)[t])
    head = jax.jit(lambda hp, x: mm(
        rms(x, hp["ln_f"]["scale"].astype(F32), s.eps),
        hp["head"].astype(F32), quant))
    return lay, emb, head


def forward_following(params, tokens, s: Shape, quant: Optional[str] = None,
                      follow=None) -> Dict[str, Any]:
    """One sequence, tokens [S], layer by layer.  ``follow`` [n, E-layers,
    k] (n <= S): the experts to take at the first n positions in each
    expert layer; past them, and with None, the reference's own choice.
    Returns ``logits`` [S, V] float32, ``routing`` [S, E-layers, k] (what
    was taken) and ``deficit`` [S, E-layers]."""
    lay, emb, head = _jitted(s, quant)
    S = len(tokens)
    x = emb(params["tok_emb"], jnp.asarray(tokens, jnp.int32))
    given = None
    if follow is not None:
        follow = np.asarray(follow, np.int32)
        pad = np.zeros((S,) + follow.shape[1:], np.int32)
        pad[:len(follow)] = follow
        follow, given = jnp.asarray(pad), jnp.arange(S) < len(follow)
    routing, deficit = [], []
    for kind, p in zip(s.pattern, params["layers"]):
        if kind == "E":
            e = len(routing)
            x, idx, d = lay[kind](
                p, x, None if follow is None else (follow[:, e], given))
            routing.append(idx)
            deficit.append(d)
        else:
            x = lay[kind](p, x)
    stack = lambda a: jnp.stack(a, axis=1) if a else None
    return {"logits": head({"ln_f": params["ln_f"], "head": params["head"]}, x),
            "routing": stack(routing), "deficit": stack(deficit)}


def forward_logits(params, tokens, s: Shape, quant: Optional[str] = None):
    """One sequence, tokens [S] -> logits [S, V] float32, the reference's
    own choices throughout."""
    return forward_following(params, tokens, s, quant)["logits"]
