"""The plain reference of the ``keye_vl2`` family (the language model of
Keye-VL-2.0-30B-A3B): pre-norm blocks of grouped-query attention behind a
learned top-k INDEXER (DeepSeek-V3.2-Exp's "lightning indexer": report and
reference ``inference/model.py``) and a softmax top-k mixture of gated
experts, in straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision, no kernels, no cache, no chunking of positions.  It imports
nothing of the program.  One sequence at a time; one layer upcast at a time
(one EXPERT, one HEAD at a time inside a layer), so it fits on the chip once
the program's pool is gone.

A layer of the stack is ONE mixer under a residual, ``x <- x + f(rms(x))``,
by a pattern string: ``*`` indexed attention, ``E`` the expert layer.  A
published block is ``*E``.

- Attention.  ``q = x W_q`` (heads x head_dim), ``k = x W_k``, ``v = x W_v``
  (kv_heads x head_dim); a learned RMSNorm over each query head and each key
  head; rope by THREE position rows (``positions`` [3, S]: temporal, height,
  width): of a head's ``head_dim / 2`` frequency pairs (half-split, ``(i, i
  + head_dim / 2)``, frequency ``theta^(-2i / head_dim)``) the first
  ``mrope_section[0]`` turn by the temporal position, the next by the
  height, the last by the width; a text token has the three equal.
- The indexer.  ``qI = x W_qI`` (idx_heads x idx_dim); ONE key a position,
  ``kI = LayerNorm(x W_kI)`` (scale and bias); both rotated on their leading
  ``idx_rope`` dims (pairs ``(i, i + idx_rope / 2)``, frequency ``theta^(-2i
  / idx_rope)``) by the temporal position; head weights ``w = (x W_w)
  idx_heads^-0.5 idx_dim^-0.5``; the FULL ``[S, S]`` matrix of index scores
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; per row
  ``lax.top_k`` over the causal scores (equal scores: the lower position
  first) keeps ``min(idx_topk, t + 1)`` positions: a boolean mask ``[S,
  S]``, the same for every head.
- Causal grouped-query attention at ``head_dim^-0.5`` under that mask (a
  position outside it is never a key or a value), then ``W_o``.
- Experts: ``p = softmax(x W_r)`` over ALL experts in float32, the top k,
  their weights renormalised to sum 1 (``norm_topk_prob``); the experts this
  share HOLDS in a plain loop, every token through every held expert,
  weighted by its gate or by zero; what the absent experts would add is left
  out, as in the program.  Gated (SwiGLU) experts, ``w1`` holding gate and
  up side by side, no biases, no shared expert.

``quant="fp8"`` rounds every matmul's operands to e4m3 (the indexer's
products too): the control that the cell's limits must reject.
``forward_following`` takes someone else's CHOICES, as
benchmarks/reference/nemotron_h.py explains: the experts of every position
(``deficit``: how far a followed expert's probability lay below the
reference's own k-th, on the probability scale) and the positions every row
KEPT (:func:`selection_deficit`: how far a kept position's index score lay
below the reference's own ``idx_topk``-th, or a dropped one's above it, on
the scale of the layer's scores).
A score that rounding puts on the other side of the ``idx_topk``-th is
another key read, as a flipped expert is another function: a row keeps 2,048
of up to 14,336 positions, a few dozen of them sit within bfloat16's rounding
of the 2,048th score, and with seeded weights a row's attention is a sum of
2,048 unrelated values in which those are a fifth of the norm.  A selection
travels as bits, sixteen positions an int16 (:func:`pack_mask`, the program's
``ops.dsa_attention.selection_words``)."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.model import F32, _q, mm
from benchmarks.reference.nemotron_h import rms
from benchmarks.reference.sarvam_mla import swiglu

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one ``keye_vl2`` stack as it is run here."""

    dim: int
    #: one mixer a layer: '*' indexed attention | 'E' experts
    pattern: str
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    #: a head's frequency pairs dealt to (temporal, height, width)
    mrope_section: Tuple[int, int, int]
    rope_theta: float
    # the indexer
    idx_heads: int
    idx_dim: int
    idx_topk: int
    idx_rope: int
    # experts
    experts: int
    held_first: int
    held: int
    top_k: int
    moe_ffn: int
    eps: float


def text_positions(S: int):
    """A text token's three position rows: its index, three times."""
    return jnp.broadcast_to(jnp.arange(S), (3, S))


def mrope(x, positions, section, theta: float):
    """x [..., S, d]: pair ``(i, i + d / 2)`` turns by ``positions[axis(i)]
    x theta^(-2i / d)``, the pairs dealt to the three rows of ``positions``
    by ``section`` (None: all to row 0)."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    axis = np.repeat(np.arange(3), (half, 0, 0) if section is None else section)
    ang = positions.astype(F32)[axis].T * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)                     # [S, half]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_norm(x, p, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def index_scores(p: Dict[str, Any], x, positions, s: Shape,
                 quant: Optional[str] = None):
    """x [S, D] (normed) -> I [S, S] float32, ``-inf`` behind the query."""
    S, J, di, dr = x.shape[0], s.idx_heads, s.idx_dim, s.idx_rope

    def turned(a):   # [..., S, di]: the leading ``dr`` dims by the temporal row
        return jnp.concatenate(
            [mrope(a[..., :dr], positions, None, s.rope_theta), a[..., dr:]],
            axis=-1)

    qi = turned(mm(x, p["wq_idx"], quant).reshape(S, J, di).transpose(1, 0, 2))
    ki = turned(layer_norm(mm(x, p["wk_idx"], quant), p["k_idx_norm"], s.eps))
    w = mm(x, p["w_idx"], quant) * (J ** -0.5 * di ** -0.5)      # [S, J]

    def head(acc, h):
        qh, wh = h
        sc = jnp.matmul(_q(qh, quant, -1), _q(ki, quant, -1).T, precision=_HI)
        return acc + wh[:, None] * jnp.maximum(sc, 0.0), None

    scores, _ = jax.lax.scan(head, jnp.zeros((S, S), F32), (qi, w.T))
    keep = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    return jnp.where(keep, scores, -jnp.inf)


def selection(scores, topk: int):
    """I [S, S] -> the mask [S, S] of each row's ``min(topk, t + 1)`` best
    positions: ``lax.top_k`` a row (equal scores: the lower position
    first), cut to the causal ones."""
    S = scores.shape[0]
    _, idx = jax.lax.top_k(scores, min(topk, S))
    sel = jnp.zeros((S, S), bool).at[jnp.arange(S)[:, None], idx].set(True)
    return sel & (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])


def selection_deficit(scores, own, sel):
    """How far the kept positions ``sel`` [S, S] stray from the reference's
    own choice, per row, on the scale of the layer's scores (their root mean
    square over the causal pairs): the further of (a kept position's score
    below the own ``topk``-th) and (a dropped causal position's score above
    it).  0 where the sets are equal; a score's rounding error where a
    choice flipped on rounding; the scores' whole spread where the choice
    was made from something else."""
    causal = scores > -jnp.inf
    kth = jnp.min(jnp.where(own, scores, jnp.inf), axis=-1)
    low = jnp.min(jnp.where(sel, scores, jnp.inf), axis=-1)
    high = jnp.max(jnp.where(causal & ~sel, scores, -jnp.inf), axis=-1)
    scale = jnp.sqrt(jnp.sum(jnp.where(causal, scores, 0.0) ** 2)
                     / jnp.sum(causal))
    return jnp.maximum(jnp.maximum(kth - low, high - kth), 0.0) / scale


def pack_mask(mask):
    """bool [S, P] -> int16 [S, ceil(P / 16)]: bit i of word j is position
    ``16 j + i``."""
    S, P = mask.shape
    bits = jnp.pad(mask, ((0, 0), (0, -P % 16))).reshape(S, -1, 16)
    words = jnp.sum(bits.astype(jnp.int32) << jnp.arange(16), axis=-1)
    return jax.lax.bitcast_convert_type(words.astype(jnp.uint16), jnp.int16)


def unpack_mask(words, P: int):
    """int16 [S, W] -> bool [S, P] (positions past ``16 W``: not kept)."""
    S, W = words.shape
    bits = (words.astype(jnp.int32)[:, :, None] >> jnp.arange(16)) & 1
    mask = bits.reshape(S, W * 16).astype(bool)[:, :P]
    return jnp.pad(mask, ((0, 0), (0, P - mask.shape[1])))


def attention(p: Dict[str, Any], x, positions, s: Shape,
              quant: Optional[str] = None, follow=None):
    """x [S, D] (normed) -> ([S, D], the selection taken as
    :func:`pack_mask` words, its deficit [S]).  ``follow`` = (words [S, W],
    given [S] bool): where ``given``, those positions are kept in place of
    the own ``idx_topk`` best."""
    S, H, Hkv, hd = x.shape[0], s.heads, s.kv_heads, s.head_dim
    R = H // Hkv
    scores = index_scores(p, x, positions, s, quant)
    sel = own = selection(scores, s.idx_topk)
    if follow is not None:
        sel = jnp.where(follow[1][:, None], unpack_mask(follow[0], S), own)
    deficit = selection_deficit(scores, own, sel)

    def heads_of(w, n, norm):
        a = mm(x, w, quant).reshape(S, n, hd)
        if norm is not None:
            a = rms(a, norm["scale"], s.eps)
        return a.transpose(1, 0, 2)                           # [n, S, hd]

    q = mrope(heads_of(p["wq"], H, p["q_norm"]), positions, s.mrope_section,
              s.rope_theta).reshape(Hkv, R, S, hd)
    k = mrope(heads_of(p["wkv"][0], Hkv, p["k_norm"]), positions,
              s.mrope_section, s.rope_theta)
    v = heads_of(p["wkv"][1], Hkv, None)

    def kv_head(h):
        qs, kh, vh = h

        def q_head(qh):
            sc = jnp.matmul(_q(qh, quant, -1), _q(kh, quant, -1).T,
                            precision=_HI) * hd ** -0.5
            pr = jax.nn.softmax(jnp.where(sel, sc, -jnp.inf), axis=-1)
            return jnp.matmul(_q(pr, quant, -1), _q(vh, quant, -2),
                              precision=_HI)

        return jax.lax.map(q_head, qs)                        # [R, S, hd]

    o = jax.lax.map(kv_head, (q, k, v)).reshape(H, S, hd)
    y = mm(o.transpose(1, 0, 2).reshape(S, H * hd), p["wo"], quant)
    return y, pack_mask(sel), deficit


def gates(router: Dict[str, Any], x, s: Shape, quant: Optional[str] = None,
          follow=None):
    """x [S, D] -> (the weight of every expert for every token [S, experts],
    zero where the expert was not chosen; the experts chosen [S, k]; the
    deficit [S]).  ``follow`` = (idx [S, k], given [S] bool): where
    ``given``, those experts are taken in place of the own top k."""
    probs = jax.nn.softmax(mm(x, router["w"].astype(F32), quant), axis=-1)
    best, idx = jax.lax.top_k(probs, s.top_k)
    if follow is not None:
        idx = jnp.where(follow[1][:, None], follow[0], idx)
    chosen = jnp.take_along_axis(probs, idx, axis=-1)
    deficit = jnp.max(jnp.maximum(best[:, -1:] - chosen, 0.0), axis=-1)
    w = chosen / jnp.maximum(jnp.sum(chosen, axis=-1, keepdims=True), 1e-9)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w)
    return gate, idx, deficit


def moe(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None,
        follow=None):
    """x [S, D] (normed) -> ([S, D], experts chosen, deficit): this share's
    routed part.  ``p['experts']`` keeps its stored precision: each expert
    is upcast inside the loop."""
    gate, idx, deficit = gates(p["router"], x, s, quant, follow)
    gate = gate[:, s.held_first:s.held_first + s.held]

    def one(acc, e):
        w1, w2, g = e
        r = mm(swiglu(mm(x, w1.astype(F32), quant)), w2.astype(F32), quant)
        return acc + g[:, None] * r, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["experts"]["w1"], p["experts"]["w2"], gate.T))
    return y, idx, deficit


def layer(kind: str, p: Dict[str, Any], x, positions=None, follow=None, *,
          s: Shape, quant: Optional[str] = None):
    """One layer on one sequence: x [S, D] float32 -> ([S, D], the choice
    taken, its deficit [S]): an ``E`` layer's experts [S, k], an attention
    layer's kept positions as words [S, W] (it alone reads ``positions`` [3,
    S]).  ``follow``: the choice to take in place of the own, as
    :func:`gates` / :func:`attention` say."""
    h = rms(x, p["norm"]["scale"].astype(F32), s.eps)
    if kind == "E":
        y, chose, deficit = moe(p, h, s, quant, follow)
    else:
        p = jax.tree.map(lambda w: w.astype(F32), p)
        y, chose, deficit = attention(p, h, positions, s, quant, follow)
    return x + y, chose, deficit


@functools.lru_cache(maxsize=None)
def _jitted(s: Shape, quant: Optional[str]):
    """The reference's few programs for one (Shape, precision): one a kind
    of layer, the embedding, the head."""
    lay = {kind: jax.jit(functools.partial(layer, kind, s=s, quant=quant))
           for kind in set(s.pattern)}
    emb = jax.jit(lambda table, t: table[t].astype(F32))
    head = jax.jit(lambda hp, x: mm(
        rms(x, hp["ln_f"]["scale"].astype(F32), s.eps),
        hp["head"].astype(F32), quant))
    return lay, emb, head


def forward_following(params, tokens, s: Shape, quant: Optional[str] = None,
                      follow=None, positions=None,
                      follow_selection=None) -> Dict[str, Any]:
    """One sequence, tokens [S], layer by layer.  ``follow`` [n, E-layers,
    k] (n <= S): the experts to take at the first n positions in each
    expert layer; ``follow_selection`` [n, attention layers, W] int16: the
    positions those rows keep in each attention layer, as bits; past the
    n, and with None, the reference's own choice.  ``positions`` [3, S]:
    the three position rows (None: a text's).  Returns ``logits`` [S, V]
    float32, ``routing`` [S, E-layers, k] and ``selection`` [S, attention
    layers, ceil(S / 16)] (what was taken), ``deficit`` [S, E-layers] and
    ``selection_deficit`` [S, attention layers]."""
    lay, emb, head = _jitted(s, quant)
    S = len(tokens)
    x = emb(params["tok_emb"], jnp.asarray(tokens, jnp.int32))
    positions = (text_positions(S) if positions is None
                 else jnp.asarray(positions, jnp.int32))

    def padded(a):   # [n, ...] -> ([S, ...] on the device, given [S])
        a = np.asarray(a)
        pad = np.zeros((S,) + a.shape[1:], a.dtype)
        pad[:len(a)] = a
        return jnp.asarray(pad), jnp.arange(S) < len(a)

    follow = {"E": None if follow is None else padded(
                  np.asarray(follow, np.int32)),
              "*": None if follow_selection is None else padded(
                  follow_selection)}
    chose = {"E": [], "*": []}
    deficit = {"E": [], "*": []}
    for kind, p in zip(s.pattern, params["layers"]):
        mine = follow[kind] and (follow[kind][0][:, len(chose[kind])],
                                 follow[kind][1])
        x, c, d = lay[kind](p, x, positions, mine)
        chose[kind].append(c)
        deficit[kind].append(d)
    stack = lambda rows: jnp.stack(rows, axis=1)
    return {"logits": head({"ln_f": params["ln_f"], "head": params["head"]}, x),
            "routing": stack(chose["E"]), "deficit": stack(deficit["E"]),
            "selection": stack(chose["*"]),
            "selection_deficit": stack(deficit["*"])}


def forward_logits(params, tokens, s: Shape, quant: Optional[str] = None,
                   positions=None):
    """One sequence, tokens [S] -> logits [S, V] float32, the reference's
    own choices throughout."""
    return forward_following(params, tokens, s, quant,
                             positions=positions)["logits"]
