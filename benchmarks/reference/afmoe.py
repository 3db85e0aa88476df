"""The plain reference of the ``afmoe`` family (Arcee's AFMoE block): blocks
of gated grouped-query attention, three with a sliding window to one that
reads every position, and a feed-forward part that is dense in the leading
blocks and a sigmoid-routed mixture of gated experts with one shared expert
after them, in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision, no kernels, no cache, no chunking of the sequence.
It imports nothing of the program.  One sequence at a time; one layer upcast
at a time (one EXPERT, one HEAD at a time inside a layer), so it fits on the
chip once the program's pools are gone.

A layer of the stack is ONE mixer under a residual, normed before AND after
the mixer: ``x <- x + rms_post(f(rms(x)))``, by a pattern string: ``W``
window attention, ``*`` global attention, ``D`` the dense gated MLP, ``E``
the expert layer.  A published block (four norms) is two of them (``WD``,
``WE``, ``*E``).  ``h_0 = E[token] x embed_scale`` (``sqrt(dim)`` where the
config says ``mup_enabled``).

- Attention: ``q = x W_q`` [heads x head_dim], ``k, v = x W_kv`` [kv_heads x
  head_dim], a gate ``g = x W_g`` [heads x head_dim]; a learned RMSNorm over
  each query head and each key head; a ``W`` layer rotates q and k over the
  whole head (half-split pairs, ``theta``) and a query at t reads the keys
  in ``(t - window, t]``; a ``*`` layer does NOT rotate (no positional
  encoding) and reads every key ``<= t``.  Scores at ``head_dim^-0.5`` under
  a BOOLEAN mask (causal; banded in a window layer), one softmax over the
  whole row, ``y = (o * sigmoid(g)) W_o``.  One head at a time, and a head's
  rows in blocks of :data:`ROWS` queries (each against all S keys under the
  mask: no running maximum, nothing merged), so that 14,336 positions fit.
- Experts and the dense MLP: benchmarks/reference/sarvam_mla.py's, to the
  letter (sigmoid scores over ALL experts in float32, the top k of score +
  bias, weights = score / (sum of the chosen + 1e-20) x scale, the held
  range in a plain loop, the shared expert once, SwiGLU with gate and up
  side by side).

``quant="fp8"`` rounds every matmul's operands to e4m3: the control that the
cell's limits must reject.  ``forward_following`` takes someone else's
choice of experts, as benchmarks/reference/nemotron_h.py explains.
``window=None`` in the Shape reads every ``W`` layer as global AND rotated
(what a window at least as long as the context is)."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.model import F32, _q, mm
from benchmarks.reference.nemotron_h import rms
from benchmarks.reference.sarvam_mla import dense_mlp, moe

_HI = jax.lax.Precision.HIGHEST

#: query rows of one head scored at a time (against every key)
ROWS = 1024


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one ``afmoe`` stack as it is run here."""

    dim: int
    #: one mixer a layer: 'W' window attention | '*' global attention |
    #: 'D' dense MLP | 'E' experts
    pattern: str
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    #: keys a 'W' layer's query reads, itself included (None: all)
    window: Optional[int]
    rope_theta: float
    embed_scale: float
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


def rope(x, s: Shape):
    """x [..., S, head_dim]: pairs (i, i + head_dim/2) turn by pos x
    theta^(-2i / head_dim)."""
    S, half = x.shape[-2], s.head_dim // 2
    inv = s.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p: Dict[str, Any], x, s: Shape, windowed: bool,
              quant: Optional[str] = None):
    """x [S, D] (normed) -> [S, D].  ``windowed``: a 'W' layer."""
    S, H, G, hd = x.shape[0], s.heads, s.heads // s.kv_heads, s.head_dim
    q = mm(x, p["wq"], quant).reshape(S, H, hd)
    k = mm(x, p["wkv"][0], quant).reshape(S, s.kv_heads, hd)
    v = mm(x, p["wkv"][1], quant).reshape(S, s.kv_heads, hd)
    q = rms(q, p["q_norm"]["scale"], s.eps).transpose(1, 0, 2)   # [H, S, hd]
    k = rms(k, p["k_norm"]["scale"], s.eps).transpose(1, 0, 2)
    v = v.transpose(1, 0, 2)
    if windowed:
        q, k = rope(q, s), rope(k, s)
    rows = min(ROWS, S)
    if S % rows:
        raise ValueError(f"{S} positions do not divide into blocks of {rows}")
    kpos = jnp.arange(S)[None, :]

    def head(h):
        qh, kh, vh = h                                           # [S, hd]

        def block(b):
            qb, t = b                              # [rows, hd], [rows] positions
            keep = kpos <= t[:, None]
            if windowed and s.window is not None:
                keep = keep & (kpos > t[:, None] - s.window)
            sc = jnp.matmul(_q(qb, quant, -1), _q(kh, quant, -1).T,
                            precision=_HI) * hd ** -0.5
            pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
            return jnp.matmul(_q(pr, quant, -1), _q(vh, quant, -2),
                              precision=_HI)

        return jax.lax.map(block, (
            qh.reshape(S // rows, rows, hd),
            jnp.arange(S).reshape(S // rows, rows))).reshape(S, hd)

    o = jax.lax.map(head, (q, jnp.repeat(k, G, axis=0),
                           jnp.repeat(v, G, axis=0)))            # [H, S, hd]
    o = o.transpose(1, 0, 2).reshape(S, H * hd)
    return mm(o * jax.nn.sigmoid(mm(x, p["wg"], quant)), p["wo"], quant)


def layer(kind: str, p: Dict[str, Any], x, follow=None, *, s: Shape,
          quant: Optional[str] = None):
    """One layer on one sequence: x [S, D] float32 -> [S, D]; an ``E``
    layer also gives the experts chosen [S, k] and the deficit [S]."""
    h = rms(x, p["norm"]["scale"].astype(F32), s.eps)
    post = p["post_norm"]["scale"].astype(F32)
    if kind == "E":
        y, idx, deficit = moe(p, h, s, quant, follow)
        return x + rms(y, post, s.eps), idx, deficit
    p = jax.tree.map(lambda w: w.astype(F32), p)
    if kind == "D":
        y = dense_mlp(p, h, quant)
    else:
        y = attention(p, h, s, kind == "W", quant)
    return x + rms(y, post, s.eps)


@functools.lru_cache(maxsize=None)
def _jitted(s: Shape, quant: Optional[str]):
    """The reference's few programs for one (Shape, precision): one a kind
    of layer, the embedding, the head."""
    lay = {kind: jax.jit(functools.partial(layer, kind, s=s, quant=quant))
           for kind in set(s.pattern)}
    emb = jax.jit(lambda table, t: table[t].astype(F32) * s.embed_scale)
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
