"""The plain reference of the ``zaya`` family (ZAYA1, arXiv:2511.17127):
blocks of compressed convolutional attention (CCA, arXiv:2510.04476) and a
top-1 mixture of gated experts behind a router that is a small network
with a stream of its own through the depth, each sublayer under a residual
scaled by four learned vectors, a tied head; in straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision, no kernels, no
cache, no chunking of positions (a convolution is a sum of shifted copies
of the whole sequence).  It imports nothing of the program.  One sequence at
a time; one layer upcast at a time (one EXPERT at a time inside a layer, one
block of the table's rows at a time in the head), so it fits on the chip
once the program's pool is gone.

A layer of the stack is ONE mixer, ``x <- a_h x + b_h + a_y f(rms(x)) +
b_y``, by a pattern string: ``*`` attention, ``E`` experts.  A published
block is ``*E``.

- Attention: ``z = x W_z`` is ``[q~ ; k~]``, ``heads + kv_heads`` heads of
  ``head_dim``, the latent in which attention runs.  Two causal convolutions
  over the sequence: ``c0_t = sum_j w0[j] z_{t-K0+1+j} + b0`` (depthwise),
  then ``c1_t[g] = sum_j c0_{t-K1+1+j}[g] W1[j, g] + b1[g]`` (one group a
  head), the pair padded ONCE on the left by ``K0 + K1 - 2`` zero rows of
  ``z`` (so ``c0`` before position 0 is ``b0``, not 0).  The q-k mean ``m_i =
  (q~_i + k~_j) / 2`` (query head i with its key head j) is added back: ``q_i
  = c1[q head i] + m_i``, ``k_j = c1[k head j] + mean over i of m_i``.  Each
  head is scaled to length ``sqrt(head_dim)``, the key times a learned
  temperature a KV head; rope turns half-split pairs of the leading
  ``rope_dims`` of a head.  The first half of the value heads are ``x_t
  W_v``, the second half ``x_{t-1} W_v`` (zero at position 0).  Causal
  grouped-query attention at ``head_dim^-0.5``, then ``W_o``.
- Experts: ``u = x W_d + b_d``; from the second expert layer on ``u <- u +
  gamma * r``, ``r`` the ``u`` of the expert layer before; ``p = softmax(W_3
  gelu(W_2 gelu(W_1 rms(u) + b_1) + b_2))`` in float32 (gelu by erf); the
  top k of ``p + bias`` are chosen, each weighs by its ``p`` as it is (no
  renormalising, the bias stays out); gated (SwiGLU) experts, ``w1`` holding
  gate and up side by side, no biases, in a plain loop: every token through
  every expert, weighted by its gate or by zero.  No shared expert.
- Head: ``rms(x) E^T`` with ``E`` the embedding table.

``quant="fp8"`` rounds every matmul's operands to e4m3 (the grouped
convolution's too: it is a matmul a head): the control that the cell's
limits must reject.  ``forward_following`` takes someone else's choice of
experts, as benchmarks/reference/nemotron_h.py explains; ``deficit`` is how
far the followed expert's ``p + bias`` lay below the reference's own best,
on the probability scale."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.model import F32, _q, mm
from benchmarks.reference.nemotron_h import rms
from benchmarks.reference.sarvam_mla import swiglu

_HI = jax.lax.Precision.HIGHEST
#: rows of the table that the head upcasts and multiplies at a time
HEAD_ROWS = 8192


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one ``zaya`` stack as it is run here."""

    dim: int
    #: one mixer a layer: '*' attention | 'E' experts
    pattern: str
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    #: the two convolutions' kernel sizes over the sequence
    time0: int
    time1: int
    #: the leading dims of a head that rope turns
    rope_dims: int
    rope_theta: float
    experts: int
    top_k: int
    moe_ffn: int
    router_hidden: int
    eps: float

    @property
    def channels(self) -> int:
        """What the convolutions mix: every query and key head."""
        return (self.heads + self.kv_heads) * self.head_dim

    @property
    def shifted(self) -> int:
        """The width of the value heads that are the position before's."""
        return self.kv_heads // 2 * self.head_dim

    @property
    def tail(self) -> int:
        """What a cache keeps of a sequence beside keys and values: the
        rows of ``z`` that the next position's convolutions reach back to,
        and the last position's shifted value heads."""
        return (self.time0 + self.time1 - 2) * self.channels + self.shifted


def delayed(a, n: int):
    """``a`` [S, ...] moved ``n`` positions later, zeros in front."""
    if n == 0:
        return a
    return jnp.concatenate([jnp.zeros((n,) + a.shape[1:], a.dtype), a[:-n]])


def rope(x, s: Shape):
    """x [..., S, head_dim]: pairs (i, i + rope_dims / 2) of the leading
    ``rope_dims`` turn by pos x theta^(-2i / rope_dims); the rest pass."""
    S, half = x.shape[-2], s.rope_dims // 2
    inv = s.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


def to_length(a, n: int):
    """Each vector of the last axis scaled to length sqrt(n)."""
    norm = jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True))
    return a / jnp.maximum(norm, 1e-12) * np.sqrt(n)


def attention(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None):
    """x [S, D] (normed) -> [S, D]."""
    S, H, Hkv, hd = x.shape[0], s.heads, s.kv_heads, s.head_dim
    G, R = H + Hkv, H // Hkv
    z = mm(x, p["wz"], quant)                                # [S, C]
    # c0 at position t - d is the delayed sum of z; the pair's one padding
    # makes every delayed row of z zero and so every early c0 its bias
    c0 = p["conv0_b"] + sum(
        delayed(z, s.time0 - 1 - j) * p["conv0_w"][j] for j in range(s.time0))

    def before(d):   # c0 at position t - d; b0 where t - d < 0
        early = (jnp.arange(S) < d)[:, None]
        return jnp.where(early, p["conv0_b"], delayed(c0, d))

    c1 = p["conv1_b"].reshape(G, hd) + sum(
        jnp.einsum("sgd,gde->sge",
                   _q(before(s.time1 - 1 - j).reshape(S, G, hd), quant, -1),
                   _q(p["conv1_w"][j], quant, -2), precision=_HI)
        for j in range(s.time1))                             # [S, G, hd]
    zq = z[:, :H * hd].reshape(S, Hkv, R, hd)
    zk = z[:, H * hd:].reshape(S, Hkv, 1, hd)
    m = (zq + zk) / 2
    q = to_length(c1[:, :H] + m.reshape(S, H, hd), hd)
    k = to_length(c1[:, H:] + jnp.mean(m, axis=2), hd) * p["k_temp"][:, None]
    q = rope(q.transpose(1, 0, 2), s).reshape(Hkv, R, S, hd)
    k = rope(k.transpose(1, 0, 2), s)                        # [Hkv, S, hd]
    v = mm(x, p["wv"], quant)
    now = Hkv * hd - s.shifted
    v = jnp.concatenate([v[:, :now], delayed(v[:, now:], 1)], axis=-1)
    v = v.reshape(S, Hkv, hd).transpose(1, 0, 2)
    keep = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def kv_head(h):
        qs, kh, vh = h

        def q_head(qh):
            sc = jnp.matmul(_q(qh, quant, -1), _q(kh, quant, -1).T,
                            precision=_HI) * hd ** -0.5
            pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
            return jnp.matmul(_q(pr, quant, -1), _q(vh, quant, -2),
                              precision=_HI)

        return jax.lax.map(q_head, qs)                       # [R, S, hd]

    o = jax.lax.map(kv_head, (q, k, v)).reshape(H, S, hd)
    return mm(o.transpose(1, 0, 2).reshape(S, H * hd), p["wo"], quant)


def gates(router: Dict[str, Any], x, s: Shape, quant: Optional[str] = None,
          depth=None, follow=None):
    """x [S, D] -> (the weight of every expert for every token [S, experts],
    zero where the expert was not chosen; the experts chosen [S, k]; the
    deficit [S]; the router's stream ``u`` [S, R], the next expert layer's
    ``depth``).  ``follow`` = (idx [S, k], given [S] bool): where ``given``,
    those experts are taken in place of the own top k."""
    r = jax.tree.map(lambda w: w.astype(F32), router)
    u = mm(x, r["down"]["w"], quant) + r["down"]["b"]
    if depth is not None:
        u = u + r["gamma"] * depth
    h = rms(u, r["norm"]["scale"], s.eps)
    for w, b in (("w1", "b1"), ("w2", "b2")):
        h = jax.nn.gelu(mm(h, r[w], quant) + r[b], approximate=False)
    probs = jax.nn.softmax(mm(h, r["w3"], quant), axis=-1)
    sel = probs + r["bias"]
    best, idx = jax.lax.top_k(sel, s.top_k)
    if follow is not None:
        idx = jnp.where(follow[1][:, None], follow[0], idx)
    deficit = jnp.max(jnp.maximum(
        best[:, -1:] - jnp.take_along_axis(sel, idx, axis=-1), 0.0), axis=-1)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(
            jnp.take_along_axis(probs, idx, axis=-1))
    return gate, idx, deficit, u


def moe(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None,
        depth=None, follow=None):
    """x [S, D] (normed) -> ([S, D], experts chosen, deficit, the router's
    stream).  ``p['experts']`` keeps its stored precision: each expert is
    upcast inside the loop."""
    gate, idx, deficit, u = gates(p["router"], x, s, quant, depth, follow)

    def one(acc, e):
        w1, w2, g = e
        r = mm(swiglu(mm(x, w1.astype(F32), quant)), w2.astype(F32), quant)
        return acc + g[:, None] * r, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["experts"]["w1"], p["experts"]["w2"], gate.T))
    return y, idx, deficit, u


def layer(kind: str, p: Dict[str, Any], x, depth=None, follow=None, *,
          s: Shape, quant: Optional[str] = None):
    """One layer on one sequence: x [S, D] float32 -> [S, D]; an ``E``
    layer also gives the experts chosen [S, k], the deficit [S] and the
    router's stream [S, R]."""
    h = rms(x, p["norm"]["scale"].astype(F32), s.eps)
    res = jax.tree.map(lambda w: w.astype(F32), p["res"])

    def mixed(y):
        return res["a_h"] * x + res["b_h"] + res["a_y"] * y + res["b_y"]

    if kind == "E":
        y, idx, deficit, u = moe(p, h, s, quant, depth, follow)
        return mixed(y), idx, deficit, u
    return mixed(attention(jax.tree.map(lambda w: w.astype(F32), p), h, s,
                           quant))


def head(ln_f, table, x, *, s: Shape, quant: Optional[str] = None):
    """x [S, D] -> logits [S, V] = rms(x) E^T, ``HEAD_ROWS`` rows of the
    table at a time, each block written where it belongs in ONE [S, V]
    buffer (the table in float32 would be 2 GB beside it; the last block
    is moved back to end with the table, and rewrites some columns)."""
    h = rms(x, ln_f["scale"].astype(F32), s.eps)
    rows = min(HEAD_ROWS, s.vocab)

    def block(i, out):
        at = jnp.minimum(i * rows, s.vocab - rows)
        part = jax.lax.dynamic_slice_in_dim(table, at, rows, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, mm(h, part.astype(F32).T, quant), at, axis=1)

    return jax.lax.fori_loop(0, -(-s.vocab // rows), block,
                             jnp.zeros((x.shape[0], s.vocab), F32))


@functools.lru_cache(maxsize=None)
def _jitted(s: Shape, quant: Optional[str]):
    """The reference's few programs for one (Shape, precision): one a kind
    of layer, the embedding, the head."""
    lay = {kind: jax.jit(functools.partial(layer, kind, s=s, quant=quant))
           for kind in set(s.pattern)}
    emb = jax.jit(lambda table, t: table[t].astype(F32))
    return lay, emb, jax.jit(functools.partial(head, s=s, quant=quant))


def forward_following(params, tokens, s: Shape, quant: Optional[str] = None,
                      follow=None) -> Dict[str, Any]:
    """One sequence, tokens [S], layer by layer.  ``follow`` [n, E-layers,
    k] (n <= S): the experts to take at the first n positions in each
    expert layer; past them, and with None, the reference's own choice.
    Returns ``logits`` [S, V] float32, ``routing`` [S, E-layers, k] (what
    was taken) and ``deficit`` [S, E-layers]."""
    lay, emb, head_of = _jitted(s, quant)
    S = len(tokens)
    x = emb(params["tok_emb"], jnp.asarray(tokens, jnp.int32))
    given = None
    if follow is not None:
        follow = np.asarray(follow, np.int32)
        pad = np.zeros((S,) + follow.shape[1:], np.int32)
        pad[:len(follow)] = follow
        follow, given = jnp.asarray(pad), jnp.arange(S) < len(follow)
    routing, deficit, depth = [], [], None
    for kind, p in zip(s.pattern, params["layers"]):
        if kind == "E":
            e = len(routing)
            x, idx, d, depth = lay[kind](
                p, x, depth, None if follow is None else (follow[:, e], given))
            routing.append(idx)
            deficit.append(d)
        else:
            x = lay[kind](p, x)
    return {"logits": head_of(params["ln_f"], params["tok_emb"], x),
            "routing": jnp.stack(routing, axis=1),
            "deficit": jnp.stack(deficit, axis=1)}


def forward_logits(params, tokens, s: Shape, quant: Optional[str] = None):
    """One sequence, tokens [S] -> logits [S, V] float32, the reference's
    own choices throughout."""
    return forward_following(params, tokens, s, quant)["logits"]
