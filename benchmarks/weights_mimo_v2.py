"""Seeded weights of the ``mimo_v2`` family, made on the device in one jitted
call, in bfloat16 (the router's selection bias and the attention sinks in
float32), in the tree the program's hybrid family reads
(``torchdistpackage_tpu/models/hybrid.py``: a list of per-layer dicts, one
mixer a layer, a norm before it; an attention layer's three projections ONE
leaf ``wqkv``, ``[q | k | v]`` side by side).  The program and the reference
are handed this same tree; neither makes weights.  An ``E`` layer holds the
experts of this share only; its router has every output.

Nothing here is at a value that hides a fault: every norm's scale is drawn
around 1 and not AT 1 (a dropped norm then shows), the router's selection
bias is drawn wide enough to move two or three of a token's eight experts (a
dropped bias then chooses others), and the SINK of a query head is drawn
around :data:`SINK_MEAN`, where it takes a real share of a row's mass: a
window row's 128 scores are ~N(0, 1) at seeded weights (unit-variance
queries and keys over ``sqrt(head_dim)``), their exponentials sum to ~128 x
e^0.5 = 211, and a sink of 3.5 +- 0.5 is e^3.5 = 33 beside them: read on
one window layer at the published widths, 13.9-14.9% of a full window row's
mass on two seeds, 5.5-43% by head (a row's first positions, with fewer
keys, more: 64-66% at position 10).  A sink drawn around 0 would be 0.5% of
a row and a dropped one within rounding."""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference.mimo_v2 import Shape

F32 = jnp.float32

#: the sinks' draw: mean and spread over the query heads
SINK_MEAN, SINK_SPREAD = 3.5, 0.5


def _layer(kind: str, key, s: Shape, dt) -> Dict[str, Any]:
    D = s.dim
    ks = jax.random.split(key, 6)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32)
                / math.sqrt(fan_in)).astype(dt)

    out: Dict[str, Any] = {"norm": {"scale": (
        1.0 + 0.1 * jax.random.normal(ks[5], (D,), F32)).astype(dt)}}
    if kind in "W*":
        hkv, sink = ((s.window_kv_heads, s.window_sink) if kind == "W"
                     else (s.kv_heads, s.global_sink))
        wide = s.heads * s.head_dim + hkv * (s.head_dim + s.v_head_dim)
        out.update(wqkv=normal(ks[0], (D, wide), D),
                   wo=normal(ks[1], (s.heads * s.v_head_dim, D),
                             s.heads * s.v_head_dim))
        if sink:
            out["sink"] = SINK_MEAN + SINK_SPREAD * jax.random.normal(
                ks[2], (s.heads,), F32)
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
            experts=jax.lax.map(expert, jax.random.split(ks[2], s.held)))
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
