"""Seeded weights of the ``keye_vl2`` family, made on the device in one
jitted call, in bfloat16, in the tree the program's hybrid family reads
(``torchdistpackage_tpu/models/hybrid.py``: a list of per-layer dicts, one
mixer a layer, kind ``S`` then ``E``).  The program and the reference are
handed this same tree; neither makes weights.  An ``E`` layer holds the
experts of this share only; its router has every output.

Nothing here is at a value that hides a fault: the norms' scales (the
layers', the query and key heads', the indexer key's LayerNorm) are drawn
around 1 and not AT 1, the LayerNorm's bias small and NOT zero.  The
indexer's head weights ``x W_w`` come out of both signs (a normal draw), so
index scores are not all of one sign and a dropped ``relu`` or ``w`` moves
the selection.  The router's matrix is drawn LARGE (its logits spread ~3,
not ~1): the eight chosen probabilities then stand clear of the ninth, as a
trained router's do, so that rounding flips few choices."""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference.keye_vl2 import Shape

F32 = jnp.float32
#: the spread of the router's logits (its input is a normed row of unit
#: mean square)
ROUTER_LOGIT_SPREAD = 3.0


def _layer(kind: str, key, s: Shape, dt) -> Dict[str, Any]:
    D, hd = s.dim, s.head_dim
    ks = jax.random.split(key, 14)

    def normal(k, shape, fan_in, scale=1.0):
        return (jax.random.normal(k, shape, F32) * scale
                / math.sqrt(fan_in)).astype(dt)

    def around(k, n, centre, spread):
        return (centre + spread * jax.random.normal(k, (n,), F32)).astype(dt)

    out: Dict[str, Any] = {"norm": {"scale": around(ks[13], D, 1.0, 0.1)}}
    if kind == "*":
        dq, dkv = s.heads * hd, s.kv_heads * hd
        out.update(
            wq=normal(ks[0], (D, dq), D),
            wkv=normal(ks[1], (2, D, dkv), D),
            q_norm={"scale": around(ks[2], hd, 1.0, 0.1)},
            k_norm={"scale": around(ks[3], hd, 1.0, 0.1)},
            wo=normal(ks[4], (dq, D), dq),
            wq_idx=normal(ks[5], (D, s.idx_heads * s.idx_dim), D),
            wk_idx=normal(ks[6], (D, s.idx_dim), D),
            k_idx_norm={"scale": around(ks[7], s.idx_dim, 1.0, 0.1),
                        "bias": around(ks[8], s.idx_dim, 0.0, 0.1)},
            w_idx=normal(ks[9], (D, s.idx_heads), D))
        return out
    F = s.moe_ffn

    def expert(k):   # one expert at a time: its float32 draw is 19 MB
        k1, k2 = jax.random.split(k)
        return {"w1": normal(k1, (D, 2 * F), D),
                "w2": normal(k2, (F, D), F)}

    out.update(
        router={"w": normal(ks[0], (D, s.experts), D, ROUTER_LOGIT_SPREAD)},
        experts=jax.lax.map(expert, jax.random.split(ks[1], s.held)))
    return out


def make_weights(s: Shape, seed: int, dtype=jnp.bfloat16):
    def build(key):
        ke, kh, kl, kn = jax.random.split(key, 4)
        return {
            "tok_emb": (jax.random.normal(ke, (s.vocab, s.dim), F32)
                        * 0.02).astype(dtype),
            "layers": [_layer(kind, k, s, dtype) for kind, k in zip(
                s.pattern, jax.random.split(kl, len(s.pattern)))],
            "ln_f": {"scale": (1.0 + 0.1 * jax.random.normal(
                kn, (s.dim,), F32)).astype(dtype)},
            "head": (jax.random.normal(kh, (s.dim, s.vocab), F32)
                     / math.sqrt(s.dim)).astype(dtype),
        }

    # the seed may exceed 32 signed bits: fold it into a key in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(build)(key)
