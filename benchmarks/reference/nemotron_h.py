"""The plain reference of the ``nemotron_h`` family: a stack of single-mixer
layers (``M`` Mamba-2, ``*`` attention without positions, ``E`` latent
mixture of experts) in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision, no kernels, no cache, no chunking.  It imports
nothing of the program.  One sequence at a time; one layer upcast at a time
(one EXPERT at a time in an ``E`` layer), so it fits on the chip once the
program's state is gone.

- Mamba-2: the recurrence step by step, ``S_t = exp(dt_t A) S_{t-1} + dt_t
  x_t B_t^T``, ``y_t = S_t C_t + D x_t``, from a zero state; the causal
  convolution from zero rows.
- Attention: GQA, causal, no window, NO positional encoding.
- Latent MoE: sigmoid scores over ALL experts in float32, the top k of
  score + bias, weights = score / (sum of the chosen + 1e-20) x scale; the
  experts this share HOLDS (``held_first .. held_first + held``) in a plain
  loop, every token through every held expert, weighted by its gate or by
  zero; what the absent experts would add is left out, as in the program.

``quant="fp8"`` rounds every matmul's operands to e4m3 (benchmarks/
reference/model.py's ``mm``): the control that the cell's limit must reject.

**Following a choice.**  A top-k choice is discontinuous: where two experts'
scores lie within rounding of each other, bfloat16 and float32 choose
differently, and from there on the two are different functions of the same
weights (the layer's output, the recurrent state, every later position).
``forward_following`` therefore takes the experts that someone else chose
(the program, position by position) and FOLLOWS them: the weights are still
the reference's own scores at the followed experts, and it reports how far
below its own k-th best each followed expert lay (``deficit``: 0 where it
would have chosen the same; rounding's width at a near-tie; large where
the choice was wrong)."""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.model import F32, _q, mm

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one ``nemotron_h`` stack as it is run here."""

    dim: int
    pattern: str
    vocab: int
    # attention
    heads: int
    kv_heads: int
    head_dim: int
    # Mamba-2
    m_heads: int
    m_head_dim: int
    state: int
    groups: int
    conv_kernel: int
    # latent MoE: ``experts`` router outputs, ``held`` of them here
    experts: int
    held_first: int
    held: int
    top_k: int
    latent: int
    moe_ffn: int
    shared_ffn: int
    routed_scale: float
    eps: float

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.groups * self.state


def rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None):
    """x [S, D] (normed) -> [S, D]."""
    S = x.shape[0]
    H, P, N, G, K, di = (s.m_heads, s.m_head_dim, s.state, s.groups,
                         s.conv_kernel, s.d_inner)
    zxbcdt = mm(x, p["in_proj"], quant)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + s.conv_channels],
                  zxbcdt[:, di + s.conv_channels:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = p["conv_b"] + sum(padded[k:k + S] * p["conv_w"][k]
                             for k in range(K))
    xbc = conv * jax.nn.sigmoid(conv)
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
    y = y * (z * jax.nn.sigmoid(z))
    yg = y.reshape(S, G, di // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + s.eps)
    return mm(yg.reshape(S, di) * p["gate_norm"]["scale"], p["out_proj"],
              quant)


def attention(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None):
    S, hd = x.shape[0], s.head_dim
    q = mm(x, p["wq"], quant).reshape(S, s.heads, hd).transpose(1, 0, 2)
    k = mm(x, p["wkv"][0], quant).reshape(S, s.kv_heads, hd).transpose(1, 0, 2)
    v = mm(x, p["wkv"][1], quant).reshape(S, s.kv_heads, hd).transpose(1, 0, 2)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    sc = jnp.einsum("hqd,hkd->hqk", _q(q, quant, -1), _q(k, quant, -1),
                    precision=_HI) / math.sqrt(hd)
    keep = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    pr = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", _q(pr, quant, -1), _q(v, quant, -2),
                   precision=_HI)
    return mm(o.transpose(1, 0, 2).reshape(S, s.heads * hd), p["wo"], quant)


def gates(router: Dict[str, Any], x, s: Shape, quant: Optional[str] = None,
          follow=None):
    """x [S, D] -> (the weight of every expert for every token, [S,
    experts], zero where the expert was not chosen; the experts chosen
    [S, k]; the deficit [S]).  ``follow`` = (idx [S, k], given [S] bool):
    where ``given``, those experts are taken in place of the own top k."""
    scores = jax.nn.sigmoid(mm(x, router["w"].astype(F32), quant))
    sel = scores + router["bias"].astype(F32)
    best, idx = jax.lax.top_k(sel, s.top_k)
    if follow is not None:
        idx = jnp.where(follow[1][:, None], follow[0], idx)
    deficit = jnp.max(jnp.maximum(
        best[:, -1:] - jnp.take_along_axis(sel, idx, axis=-1), 0.0), axis=-1)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    w = w * s.routed_scale
    gate = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w)
    return gate, idx, deficit


def routed_latent(experts, u, gate, quant: Optional[str] = None):
    """The held experts' part in the latent: u [S, latent], gate [S, held]
    -> [S, latent].  A plain loop, one expert upcast at a time."""
    def one(acc, e):
        w1, w2, g = e
        r = mm(relu2(mm(u, w1.astype(F32), quant)), w2.astype(F32), quant)
        return acc + g[:, None] * r, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (experts["w1"], experts["w2"], gate.T))
    return acc


def moe(p: Dict[str, Any], x, s: Shape, quant: Optional[str] = None,
        follow=None, with_choice: bool = False):
    """x [S, D] (normed) -> [S, D]: this share's routed part through the
    up-projection, plus the shared expert.  ``p['experts']`` keeps its
    stored precision (each expert is upcast inside the loop).
    ``with_choice``: also the experts chosen and the deficit."""
    up = {k: jax.tree.map(lambda w: w.astype(F32), p[k])
          for k in ("latent", "shared")}
    gate, idx, deficit = gates(p["router"], x, s, quant, follow)
    gate = gate[:, s.held_first:s.held_first + s.held]
    u = mm(x, up["latent"]["down"], quant)
    r = routed_latent(p["experts"], u, gate, quant)
    shared = mm(relu2(mm(x, up["shared"]["w1"], quant)), up["shared"]["w2"],
                quant)
    y = mm(r, up["latent"]["up"], quant) + shared
    return (y, idx, deficit) if with_choice else y


def layer(kind: str, p: Dict[str, Any], x, follow=None, *, s: Shape,
          quant: Optional[str] = None):
    """One layer on one sequence: x [S, D] float32 -> [S, D]; an ``E``
    layer also gives the experts chosen [S, k] and the deficit [S]."""
    h = rms(x, p["norm"]["scale"].astype(F32), s.eps)
    if kind == "E":
        y, idx, deficit = moe(p, h, s, quant, follow, with_choice=True)
        return x + y, idx, deficit
    p = jax.tree.map(lambda w: w.astype(F32), p)
    return x + (mamba if kind == "M" else attention)(p, h, s, quant)


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
