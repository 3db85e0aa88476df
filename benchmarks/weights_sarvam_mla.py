"""Seeded weights of the ``sarvam_mla`` family, made on the device in one
jitted call, in bfloat16 (the router's selection bias in float32), in the
tree the program's hybrid family reads (``torchdistpackage_tpu/models/
hybrid.py``: a list of per-layer dicts, one mixer a layer).  The program and
the reference are handed this same tree; neither makes weights.  An ``E``
layer holds the experts of this share only; its router has every output.

Nothing here is at a value that hides a fault: the norms' scales are drawn
around 1 and not AT 1 (a dropped norm weight then shows), and the router's
selection bias is drawn wide enough to move two or three of a token's eight
experts (a dropped bias then chooses others)."""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference.sarvam_mla import Shape

F32 = jnp.float32


def _layer(kind: str, key, s: Shape, dt) -> Dict[str, Any]:
    D = s.dim
    ks = jax.random.split(key, 10)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32)
                / math.sqrt(fan_in)).astype(dt)

    def scale(k, n):
        return {"scale": (1.0 + 0.1 * jax.random.normal(k, (n,), F32)
                          ).astype(dt)}

    out: Dict[str, Any] = {"norm": scale(ks[9], D)}
    if kind == "*":
        H = s.heads
        out.update(
            wq=normal(ks[0], (D, H * s.q_dim), D),
            q_norm=scale(ks[1], s.q_dim),
            wkva=normal(ks[2], (D, s.cached), D),
            kv_norm=scale(ks[3], s.latent),
            wuk=normal(ks[4], (H, s.nope, s.latent), s.latent),
            wuv=normal(ks[5], (H, s.latent, s.v_dim), s.latent),
            wo=normal(ks[6], (H * s.v_dim, D), H * s.v_dim))
    elif kind == "D":
        out.update(w1=normal(ks[0], (D, 2 * s.dense_ffn), D),
                   w2=normal(ks[1], (s.dense_ffn, D), s.dense_ffn))
    else:
        F = s.moe_ffn

        def expert(k):   # one expert at a time: its float32 draw is 100 MB
            k1, k2 = jax.random.split(k)
            return {"w1": normal(k1, (D, 2 * F), D),
                    "w2": normal(k2, (F, D), F)}

        out.update(
            router={"w": normal(ks[0], (D, s.experts), D),
                    # sigmoid scores of a unit-variance logit spread ~0.2
                    # around 0.5: a bias of 0.05 reorders the last few of
                    # the top k and no more
                    "bias": jax.random.normal(ks[1], (s.experts,), F32) * 0.05},
            experts=jax.lax.map(expert, jax.random.split(ks[2], s.held)),
            shared={"w1": normal(ks[3], (D, 2 * s.shared_ffn), D),
                    "w2": normal(ks[4], (s.shared_ffn, D), s.shared_ffn)})
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
