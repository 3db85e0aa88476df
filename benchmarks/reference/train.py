"""The reference's training steps: loss and gradients layer by layer and row
by row (so that float32 at ``highest`` fits on the chip), then AdamW as optax
documents it, arithmetic in float32, parameters and moments stored in the
configuration's storage type between steps.  Imports nothing of the program.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.arch import Arch
from benchmarks.reference import model as M

F32 = jnp.float32


#: the weight tree's fused leaves (benchmarks/weights.py) and their parts
#: along the axis after the layer axis.  Norms are taken part by part: the
#: key bias has a mathematically zero gradient (softmax ignores a constant
#: added to a row of scores), and inside a fused leaf it would hide there.
FUSED = {"wqkv": ("q", "k", "v"), "bqkv": ("q", "k", "v"),
         "wkv": ("k", "v"), "bkv": ("k", "v")}


def _sumsq(tree, stacked: bool = True) -> Dict[str, Any]:
    return {k: jnp.sum(jnp.square(v.astype(F32)))
            for k, v in flatten(tree, stacked=stacked).items()}


def flatten(tree, prefix: str = "", stacked: bool = True) -> Dict[str, Any]:
    """Leaves by dotted name, e.g. ``blocks.mlp.w2``; a fused leaf part by
    part, e.g. ``blocks.attn.bqkv.k``.  ``stacked``: block leaves carry the
    layer axis first (the weight tree), or not (one layer's gradients)."""
    out: Dict[str, Any] = {}
    for k in sorted(tree):
        v = tree[k]
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name + ".", stacked))
        elif k in FUSED:
            for i, part in enumerate(FUSED[k]):
                out[f"{name}.{part}"] = v[:, i] if stacked else v[i]
        else:
            out[name] = v
    return out


@functools.lru_cache(maxsize=None)
def _programs(a: Arch, quant: Optional[str], opt_key: tuple):
    lr, b1, b2, eps, wd = opt_key

    def lay_vjp(p, x, g):
        y, vjp = jax.vjp(lambda p, x: M.layer(p, x, a, quant), p, x)
        gp, gx = vjp(g)
        return jax.tree.map(lambda t: t.astype(F32), gp), gx

    def head_vg(hp, x, targets):
        loss, (ghp, gx) = jax.value_and_grad(
            lambda hp, x: M.head_loss(hp, x, targets, a, quant),
            argnums=(0, 1))(hp, x)
        return loss, jax.tree.map(lambda t: t.astype(F32), ghp), gx

    def adam(p, mu, nu, g, t):
        """One leaf (or tree of leaves): float32 arithmetic, stored types."""
        def one(p, mu, nu, g):
            pf, m, v = p.astype(F32), mu.astype(F32), nu.astype(F32)
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * g * g
            mhat = m / (1.0 - b1 ** t)
            vhat = v / (1.0 - b2 ** t)
            pf = pf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
            return pf.astype(p.dtype), m.astype(mu.dtype), v.astype(nu.dtype)
        out = jax.tree.map(one, p, mu, nu, g)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    def adam_layer(blocks, mu, nu, g, l, t):
        sl = lambda tr: jax.tree.map(lambda w: w[l], tr)
        p1, m1, v1 = adam(sl(blocks), sl(mu), sl(nu), g, t)
        put = lambda tr, new: jax.tree.map(
            lambda w, n: w.at[l].set(n), tr, new)
        return put(blocks, p1), put(mu, m1), put(nu, v1)

    return (jax.jit(lay_vjp), jax.jit(head_vg),
            jax.jit(adam, donate_argnums=(0, 1, 2)),
            jax.jit(adam_layer, donate_argnums=(0, 1, 2)))


class TrainReference:
    """Follows the program's first steps from the same seeded weights."""

    def __init__(self, params, a: Arch, opt: Dict[str, float],
                 quant: Optional[str] = None):
        self.a, self.quant = a, quant
        self.opt_key = (opt["learning_rate"], opt["b1"], opt["b2"],
                        opt["eps"], opt["weight_decay"])
        self.params = params
        self.mu = jax.tree.map(jnp.zeros_like, params)
        self.nu = jax.tree.map(jnp.zeros_like, params)
        self.t = 0
        self.losses: List[float] = []
        self.grad_norms: List[Dict[str, float]] = []

    def step(self, tokens: np.ndarray, targets: np.ndarray) -> float:
        """One step on a [B, S] batch: mean loss over rows, AdamW update."""
        a, quant = self.a, self.quant
        lay, emb, _ = M._jitted(a, quant)
        lay_vjp, head_vg, adam, adam_layer = _programs(a, quant, self.opt_key)
        B = tokens.shape[0]
        self.t += 1
        p = self.params
        emb_p = {k: p[k] for k in ("tok_emb", "pos_emb") if k in p}
        toks = [jnp.asarray(tokens[b], jnp.int32) for b in range(B)]
        xs = [[emb(emb_p, toks[b]) for b in range(B)]]
        for l in range(a.layers):
            pl = M.layer_slice(p["blocks"], l)
            xs.append([lay(pl, x) for x in xs[-1]])
        head_p = {"ln_f": p["ln_f"], "head": p["head"]}
        loss, g_head, gx = 0.0, None, []
        for b in range(B):
            lb, gh, g = head_vg(head_p, xs[-1][b],
                                jnp.asarray(targets[b], jnp.int32))
            loss = loss + lb / B
            g_head = gh if g_head is None else jax.tree.map(jnp.add, g_head, gh)
            gx.append(g / B)
        g_head = jax.tree.map(lambda t: t / B, g_head)
        sumsq: Dict[str, Any] = {}

        def note(prefix: str, g) -> None:
            for k, v in _sumsq(g, stacked=False).items():
                sumsq[prefix + k] = sumsq.get(prefix + k, 0.0) + v

        note("", g_head)
        t = jnp.asarray(self.t, F32)
        for l in reversed(range(a.layers)):
            pl = M.layer_slice(p["blocks"], l)
            gl = None
            for b in range(B):
                gp, gx[b] = lay_vjp(pl, xs[l][b], gx[b])
                gl = gp if gl is None else jax.tree.map(jnp.add, gl, gp)
            xs[l + 1] = None
            note("blocks.", gl)
            p["blocks"], self.mu["blocks"], self.nu["blocks"] = adam_layer(
                p["blocks"], self.mu["blocks"], self.nu["blocks"], gl, l, t)
        g_emb = {"tok_emb": jnp.zeros(p["tok_emb"].shape, F32)}
        for b in range(B):
            g_emb["tok_emb"] = g_emb["tok_emb"].at[toks[b]].add(gx[b])
        if "pos_emb" in p:
            S = tokens.shape[1]
            g_emb["pos_emb"] = jnp.zeros(p["pos_emb"].shape, F32).at[:S].add(
                sum(gx))
        note("", g_emb)
        g_rest = {**g_head, **g_emb}   # every leaf outside the blocks
        sub = lambda tr: {k: tr[k] for k in g_rest}
        new_p, new_mu, new_nu = adam(sub(p), sub(self.mu), sub(self.nu),
                                     g_rest, t)
        p.update(new_p), self.mu.update(new_mu), self.nu.update(new_nu)
        self.losses.append(float(loss))
        self.grad_norms.append(
            {k: float(np.sqrt(v)) for k, v in sumsq.items()})
        return self.losses[-1]


@jax.jit
def leaf_norms(tree):
    """Per-leaf L2 norms of a weight-shaped tree, by dotted name."""
    return {k: jnp.sqrt(v) for k, v in _sumsq(tree).items()}


@jax.jit
def delta_norms(new, old):
    """Per-leaf norm of the change between two weight trees."""
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), new, old))


#: a leaf whose reference gradient is under this share of the median leaf's
#: has no gradient at all (the key bias: 1e-9 of the median in float32)
NO_GRADIENT = 1e-6


def without_gradient(ref_grad_norms: Dict[str, float]) -> List[str]:
    """The leaves whose reference gradient is mathematically zero.  Adam
    divides a gradient by its own size, so their update is rounding noise at
    full size in the program's precision and nothing in float32: their
    update norm says nothing, and ``worst_gap`` skips it."""
    med = float(np.median(list(ref_grad_norms.values())))
    return sorted(k for k, r in ref_grad_norms.items() if r < NO_GRADIENT * med)


def worst_gap(got: Dict[str, float], ref: Dict[str, float],
              skip: Sequence[str] = ()) -> Dict[str, Any]:
    """The worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(got[k] - r) / max(r, med, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, k
    return {"gap": worst, "leaf": where}
