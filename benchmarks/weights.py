"""Seeded weights, made on the device in one jitted call, in bfloat16, in the
tree layout the program's model functions read (its checkpoint format:
stacked ``[L, ...]`` block leaves; fused ``wqkv`` for MHA, ``wq``/``wkv`` for
GQA; a 3-d ``w1`` for SwiGLU; a ``bias`` leaf marks LayerNorm).  The program
and the reference are handed this same tree; neither makes weights."""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.arch import Arch


def _layer(key, a: Arch, dt) -> Dict[str, Any]:
    D, F, hd = a.dim, a.ffn, a.head_dim
    s = 1.0 / math.sqrt(D)
    ks = jax.random.split(key, 5)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def norm_p():
        p = {"scale": jnp.ones((D,), dt)}
        if a.norm == "layer":
            p["bias"] = jnp.zeros((D,), dt)
        return p

    if a.gqa:
        dkv = a.kv_heads * hd
        attn = {"wq": normal(ks[0], (D, D), s), "bq": jnp.zeros((D,), dt),
                "wkv": normal(ks[1], (2, D, dkv), s),
                "bkv": jnp.zeros((2, dkv), dt)}
    else:
        attn = {"wqkv": normal(ks[0], (3, D, D), s),
                "bqkv": jnp.zeros((3, D), dt)}
    attn.update(wo=normal(ks[2], (D, D), s), bo=jnp.zeros((D,), dt))
    if a.act == "swiglu":
        mlp = {"w1": normal(ks[3], (2, D, F), s), "b1": jnp.zeros((2, F), dt)}
    else:
        mlp = {"w1": normal(ks[3], (D, F), s), "b1": jnp.zeros((F,), dt)}
    mlp.update(w2=normal(ks[4], (F, D), 1.0 / math.sqrt(F)),
               b2=jnp.zeros((D,), dt))
    return {"ln1": norm_p(), "attn": attn, "ln2": norm_p(), "mlp": mlp}


def make_weights(a: Arch, seed: int, dtype=jnp.bfloat16, sharding=None):
    """One jitted call; layers are drawn one at a time inside it, so the
    float32 draw of the widest leaf is one layer's and not the stack's."""

    def build(key):
        ke, kp, kh, kb = jax.random.split(key, 4)
        out = {
            "tok_emb": (jax.random.normal(ke, (a.vocab, a.dim), jnp.float32)
                        * 0.02).astype(dtype),
            "blocks": jax.lax.map(lambda k: _layer(k, a, dtype),
                                  jax.random.split(kb, a.layers)),
            "ln_f": {"scale": jnp.ones((a.dim,), dtype)},
            "head": (jax.random.normal(kh, (a.dim, a.vocab), jnp.float32)
                     / math.sqrt(a.dim)).astype(dtype),
        }
        if a.norm == "layer":
            out["ln_f"]["bias"] = jnp.zeros((a.dim,), dtype)
        if a.pos == "learned":
            out["pos_emb"] = (jax.random.normal(
                kp, (a.max_pos, a.dim), jnp.float32) * 0.02).astype(dtype)
        return out

    # the seed may exceed 32 signed bits: fold it into a key in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(build, out_shardings=sharding)(key)
