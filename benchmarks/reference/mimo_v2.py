"""The plain reference of the ``mimo_v2`` family (Xiaomi's MiMo-V2 block):
pre-norm blocks of grouped-query attention, five with a window of 128 and a
learned sink to one that reads every position, and a feed-forward part that
is dense in the first block and a sigmoid-routed mixture of gated experts
after it, in straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision, no kernels, no cache, no chunking of the sequence.  It imports
nothing of the program.  One sequence at a time; one layer upcast at a time
(one EXPERT, one HEAD at a time inside a layer), so it fits on the chip once
the program's pools are gone.

A layer of the stack is ONE mixer under a residual, ``x <- x + f(rms(x))``,
by a pattern string: ``W`` window attention, ``*`` global attention, ``D``
the dense gated MLP, ``E`` the expert layer.  A published block is two of
them (``*D``, ``WE``, ``*E``).

- Attention, both kinds: ``[q | k | v] = x W_qkv`` (one fused projection, no
  bias): ``heads`` query heads and ``H_kv`` key heads of ``head_dim`` (192),
  ``H_kv`` value heads of ``v_head_dim`` (128); ``H_kv`` is ``kv_heads`` in
  a ``*`` layer and ``window_kv_heads`` in a ``W`` layer.  Rope turns the
  LEADING ``rope_dims`` (64) of every query and key head, half-split pairs
  ``(i, i + rope_dims / 2)`` within them, by the layer's own theta
  (``rope_theta`` in a ``*`` layer, ``window_rope_theta`` in a ``W`` layer);
  the other dims stay as they lie.  ``v <- value_scale x v``.  Scores at
  ``head_dim^-0.5`` under a BOOLEAN mask: causal, and in a ``W`` layer
  banded, ``0 <= t - j < window`` (the query's own key inside).  A layer
  with a ``sink`` leaf (a scalar a query head, float32) puts it into the
  softmax as one more column, which takes its share of the mass and is
  dropped: ``p_j = exp(s_j) / (exp(sink_h) + sum_i exp(s_i))``.  ``o =
  p v`` is ``heads x v_head_dim`` wide, ``y = o W_o``.  One head at a time,
  a head's rows in blocks of :data:`ROWS` queries, each block in ONE
  softmax under the mask (no running maximum, nothing merged), so that
  26,624 positions fit: against all S keys in a ``*`` layer, and in a ``W``
  layer against the ``window + ROWS`` keys that end with the block's last
  row, which are all that its band lets through.
- Experts: benchmarks/reference/nemotron_h.py's router to the letter
  (sigmoid scores over ALL experts in float32, the top k of score + bias,
  weights = score / (sum of the chosen + 1e-20) x scale), the held range in
  a plain loop of SwiGLU experts (gate and up side by side), NO shared
  expert.  The dense MLP: benchmarks/reference/sarvam_mla.py's.

Departures from the published model: the three multi-token-prediction
modules and the vision and audio towers are left out (the configuration
file's ``departures``); the experts are this chip's share and the head the
vocabulary's slice, in the program alike.

``quant="fp8"`` rounds every matmul's operands to e4m3: the control that the
cell's limits must reject.  ``forward_following`` takes someone else's
choice of experts, as benchmarks/reference/nemotron_h.py explains.
``window=None`` in the Shape reads every ``W`` layer as global (what a
window at least as long as the context is)."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.model import F32, _q, mm
from benchmarks.reference.nemotron_h import gates, rms
from benchmarks.reference.sarvam_mla import dense_mlp, swiglu

_HI = jax.lax.Precision.HIGHEST

#: query rows of one head scored at a time (against every key)
ROWS = 1024


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one ``mimo_v2`` stack as it is run here."""

    dim: int
    #: one mixer a layer: 'W' window attention | '*' global attention |
    #: 'D' dense MLP | 'E' experts
    pattern: str
    vocab: int
    heads: int
    #: KV heads of a '*' layer, and of a 'W' layer
    kv_heads: int
    window_kv_heads: int
    #: a query / key head's width, and a value head's
    head_dim: int
    v_head_dim: int
    #: the leading dims of a query / key head that rope turns
    rope_dims: int
    #: keys a 'W' layer's query reads, itself included (None: all)
    window: Optional[int]
    #: a '*' layer's theta, and a 'W' layer's
    rope_theta: float
    window_rope_theta: float
    value_scale: float
    #: whether a 'W' / a '*' layer's tree carries a ``sink`` leaf
    window_sink: bool
    global_sink: bool
    # feed-forward
    dense_ffn: int
    experts: int
    held_first: int
    held: int
    top_k: int
    moe_ffn: int
    routed_scale: float
    eps: float


def rope(x, dims: int, theta: float):
    """x [..., S, head_dim]: of the leading ``dims``, pairs (i, i + dims/2)
    turn by pos x theta^(-2i / dims); the dims behind them stay."""
    S, half = x.shape[-2], dims // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:dims]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., dims:]], -1)


def attention(p: Dict[str, Any], x, s: Shape, windowed: bool,
              quant: Optional[str] = None):
    """x [S, D] (normed) -> [S, D].  ``windowed``: a 'W' layer."""
    S, H, hd, hv = x.shape[0], s.heads, s.head_dim, s.v_head_dim
    Hkv = s.window_kv_heads if windowed else s.kv_heads
    qkv = mm(x, p["wqkv"], quant)
    dq, dk = H * hd, Hkv * hd
    q = qkv[:, :dq].reshape(S, H, hd).transpose(1, 0, 2)         # [H, S, hd]
    k = qkv[:, dq:dq + dk].reshape(S, Hkv, hd).transpose(1, 0, 2)
    v = qkv[:, dq + dk:].reshape(S, Hkv, hv).transpose(1, 0, 2)
    theta = s.window_rope_theta if windowed else s.rope_theta
    q, k = rope(q, s.rope_dims, theta), rope(k, s.rope_dims, theta)
    v = v * s.value_scale
    sink = p.get("sink")
    rows = min(ROWS, S)
    if S % rows:
        raise ValueError(f"{S} positions do not divide into blocks of {rows}")
    # a window layer's block of rows [r, r + rows) is scored against the
    # keys [r - window, r + rows) alone, under the same mask: every key
    # before them is one the band leaves out (exp(-inf) = 0 exactly), and
    # scoring all 26,624 in 9 of 11 layers took most of the check's time
    band = windowed and s.window is not None and s.window + rows < S
    span, lead = (s.window + rows, s.window) if band else (S, 0)

    def head(h):
        qh, i = h                                  # [S, hd], the head's index
        kh, vh = (jnp.pad(a[i // (H // Hkv)], ((lead, 0), (0, 0)))
                  for a in (k, v))

        def block(b):
            qb, t = b                              # [rows, hd], [rows] positions
            first = t[0] if band else 0            # in the padded rows' count
            kb, vb = (jax.lax.dynamic_slice_in_dim(a, first, span)
                      for a in (kh, vh))
            kpos = (first - lead + jnp.arange(span))[None, :]
            keep = (kpos <= t[:, None]) & (kpos >= 0)
            if windowed and s.window is not None:
                keep = keep & (kpos > t[:, None] - s.window)
            sc = jnp.matmul(_q(qb, quant, -1), _q(kb, quant, -1).T,
                            precision=_HI) * hd ** -0.5
            sc = jnp.where(keep, sc, -jnp.inf)
            if sink is not None:   # one more column, dropped behind the softmax
                sc = jnp.concatenate(
                    [sc, jnp.full((rows, 1), sink[i], F32)], axis=-1)
            pr = jax.nn.softmax(sc, axis=-1)[:, :span]
            return jnp.matmul(_q(pr, quant, -1), _q(vb, quant, -2),
                              precision=_HI)

        return jax.lax.map(block, (
            qh.reshape(S // rows, rows, hd),
            jnp.arange(S).reshape(S // rows, rows))).reshape(S, hv)

    o = jax.lax.map(head, (q, jnp.arange(H)))                    # [H, S, hv]
    return mm(o.transpose(1, 0, 2).reshape(S, H * hv), p["wo"], quant)


def moe(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None,
        follow=None):
    """x [S, D] (normed) -> ([S, D], experts chosen, deficit): this share's
    routed part, nothing shared.  ``p['experts']`` keeps its stored
    precision: each expert is upcast inside the loop."""
    gate, idx, deficit = gates(p["router"], x, s, quant, follow)
    gate = gate[:, s.held_first:s.held_first + s.held]

    def one(acc, e):
        w1, w2, g = e
        r = mm(swiglu(mm(x, w1.astype(F32), quant)), w2.astype(F32), quant)
        return acc + g[:, None] * r, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             (p["experts"]["w1"], p["experts"]["w2"], gate.T))
    return routed, idx, deficit


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
    return x + attention(p, h, s, kind == "W", quant)


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
