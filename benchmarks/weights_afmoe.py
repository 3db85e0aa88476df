"""Seeded weights of the ``afmoe`` family, made on the device in one jitted
call, in bfloat16 (the router's selection bias in float32), in the tree the
program's hybrid family reads (``torchdistpackage_tpu/models/hybrid.py``: a
list of per-layer dicts, one mixer a layer, each with a norm before and a
norm after its mixer).  The program and the reference are handed this same
tree; neither makes weights.  An ``E`` layer holds the experts of this share
only; its router has every output.

Nothing here is at a value that hides a fault: every norm's scale (the two
of a layer, the two of an attention layer's heads, the last) is drawn around
1 and not AT 1 (a dropped norm then shows), the output gate's projection is
drawn like any other (a dropped gate then doubles the layer, about), and the
router's selection bias is drawn wide enough to move two or three of a
token's eight experts (a dropped bias then chooses others)."""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe import Shape

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

    out: Dict[str, Any] = {"norm": scale(ks[9], D),
                           "post_norm": scale(ks[8], D)}
    if kind in "W*":
        dq, dkv = s.heads * s.head_dim, s.kv_heads * s.head_dim
        out.update(wq=normal(ks[0], (D, dq), D),
                   wkv=normal(ks[1], (2, D, dkv), D),
                   wg=normal(ks[2], (D, dq), D),
                   q_norm=scale(ks[3], s.head_dim),
                   k_norm=scale(ks[4], s.head_dim),
                   wo=normal(ks[5], (dq, D), dq))
    elif kind == "D":
        out.update(w1=normal(ks[0], (D, 2 * s.dense_ffn), D),
                   w2=normal(ks[1], (s.dense_ffn, D), s.dense_ffn))
    else:
        F = s.moe_ffn

        def expert(k):   # one expert at a time: its float32 draw is 25 MB
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


@functools.lru_cache(maxsize=None)
def _builder(s: Shape, dtype):
    """The jitted maker of one shape's tree from a key: compiled once a
    process, whatever the seeds (the control reads three, the tests a
    dozen)."""
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

    return jax.jit(build)


def make_weights(s: Shape, seed: int, dtype=jnp.bfloat16):
    # the seed may exceed 32 signed bits: fold it into a key in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _builder(s, dtype)(key)
