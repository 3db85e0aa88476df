"""Seeded weights of the ``nemotron_h`` family, made on the device in one
jitted call, in bfloat16 (the three per-head Mamba vectors ``dt_bias``,
``A_log`` and ``D`` in float32), in the tree the program's hybrid family
reads (``torchdistpackage_tpu/models/hybrid.py``: a list of per-layer dicts,
the kind of layer ``i`` being ``pattern[i]``).  The program and the
reference are handed this same tree; neither makes weights.  An ``E`` layer
holds the experts of this share only; its router has every output."""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference.nemotron_h import Shape

F32 = jnp.float32


def _layer(kind: str, key, s: Shape, dt) -> Dict[str, Any]:
    D = s.dim
    ks = jax.random.split(key, 8)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32)
                / math.sqrt(fan_in)).astype(dt)

    out: Dict[str, Any] = {"norm": {"scale": jnp.ones((D,), dt)}}
    if kind == "M":
        di, C, H, K = s.d_inner, s.conv_channels, s.m_heads, s.conv_kernel
        out.update(
            in_proj=normal(ks[0], (D, di + C + H), D),
            conv_w=normal(ks[1], (K, C), K),
            conv_b=(jax.random.normal(ks[2], (C,), F32) * 0.1).astype(dt),
            # the published initialisation's ranges: dt in [time_step_min,
            # time_step_max] = [1e-3, 1e-1] through the softplus, A in
            # [-16, -1], D = 1
            dt_bias=jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                ks[3], (H,), F32, math.log(1e-3), math.log(1e-1))))),
            A_log=jnp.log(jax.random.uniform(ks[4], (H,), F32, 1.0, 16.0)),
            D=jnp.ones((H,), F32),
            gate_norm={"scale": jnp.ones((di,), dt)},
            out_proj=normal(ks[5], (di, D), di))
    elif kind == "*":
        dkv = s.kv_heads * s.head_dim
        out.update(wq=normal(ks[0], (D, s.heads * s.head_dim), D),
                   wkv=normal(ks[1], (2, D, dkv), D),
                   wo=normal(ks[2], (s.heads * s.head_dim, D), D))
    else:
        lat, F = s.latent, s.moe_ffn

        def expert(k):   # one expert at a time: its float32 draw is 11 MB
            k1, k2 = jax.random.split(k)
            return {"w1": normal(k1, (lat, F), lat),
                    "w2": normal(k2, (F, lat), F)}

        out.update(
            router={"w": normal(ks[0], (D, s.experts), D),
                    # the selection bias: small, so that it moves the
                    # choice of the last few of the top k and no more
                    "bias": jax.random.normal(ks[1], (s.experts,), F32) * 0.01},
            latent={"down": normal(ks[2], (D, lat), D),
                    "up": normal(ks[3], (lat, D), lat)},
            experts=jax.lax.map(expert, jax.random.split(ks[4], s.held)),
            shared={"w1": normal(ks[5], (D, s.shared_ffn), D),
                    "w2": normal(ks[6], (s.shared_ffn, D), s.shared_ffn)})
    return out


def make_weights(s: Shape, seed: int, dtype=jnp.bfloat16):
    def build(key):
        ke, kh, kl = jax.random.split(key, 3)
        return {
            "tok_emb": (jax.random.normal(ke, (s.vocab, s.dim), F32)
                        * 0.02).astype(dtype),
            "layers": [_layer(kind, k, s, dtype) for kind, k in zip(
                s.pattern, jax.random.split(kl, len(s.pattern)))],
            "ln_f": {"scale": jnp.ones((s.dim,), dtype)},
            "head": (jax.random.normal(kh, (s.dim, s.vocab), F32)
                     / math.sqrt(s.dim)).astype(dtype),
        }

    # the seed may exceed 32 signed bits: fold it into a key in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(build)(key)
