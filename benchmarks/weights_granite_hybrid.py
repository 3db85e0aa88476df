"""Seeded weights of the ``granite_hybrid`` family, made on the device, in
bfloat16 (the three per-head Mamba vectors ``dt_bias``, ``A_log`` and ``D``
in float32), in the tree the program's hybrid family reads
(``torchdistpackage_tpu/models/hybrid.py``: a list of ONE-mixer layers, the
kind of layer ``i`` being ``pattern[i]``; a published block is its mixer's
layer, ``M`` or ``*``, and then its MLP's, ``D``, each under the block's
norm for that half).  The program and the reference are handed this same
tree; neither makes weights.  The head is TIED: the tree has no ``head``
leaf.  One jitted program a kind of layer (three), called once a layer: a
stack of 80 layers in one program would compile for minutes.

Every learned vector that a fault could drop lies off its neutral value:
norm scales around 1, the convolution's bias and ``D`` drawn, so that a
dropped bias or a norm in the wrong place moves the logits.

The tied table is drawn at ``2 / (embedding_multiplier x sqrt(hidden))``
(0.0037 at the published sizes), not at the usual 0.02.  ``h_0 = 12 E[token]``
stays in the residual stream to the end and the head multiplies by ``E``
again, so a token's OWN logit carries ``12 |E[token]|^2``, which grows with
the width where every other logit grows with its root: at 0.02 it stands 5
sigma over the rest, every position's best token is the token it was fed
(a trained model's layers learn to take that out; seeded ones cannot), and a
check of served tokens would pass whatever the 80 layers computed: at toy
width the fp8 control and four planted faults read a gap of 0.0.  At this
scale the own logit is about as large as the others' spread (2 / the
stream's rms), and the logits are what the layers made them."""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference.granite_hybrid import Shape

F32 = jnp.float32


def _layer(kind: str, key, *, s: Shape, dt) -> Dict[str, Any]:
    D = s.dim
    ks = jax.random.split(key, 10)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32)
                / math.sqrt(fan_in)).astype(dt)

    def around(k, n, mean, spread, dtype=dt):
        return (mean + spread * jax.random.normal(k, (n,), F32)).astype(dtype)

    out: Dict[str, Any] = {"norm": {"scale": around(ks[9], D, 1.0, 0.1)}}
    if kind == "M":
        di, C, H, K = s.d_inner, s.conv_channels, s.m_heads, s.conv_kernel
        out.update(
            in_proj=normal(ks[0], (D, di + C + H), D),
            conv_w=normal(ks[1], (K, C), K),
            conv_b=around(ks[2], C, 0.0, 0.1),
            # the published initialisation's ranges: dt in [time_step_min,
            # time_step_max] = [1e-3, 1e-1] through the softplus, A in
            # [-16, -1]
            dt_bias=jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                ks[3], (H,), F32, math.log(1e-3), math.log(1e-1))))),
            A_log=jnp.log(jax.random.uniform(ks[4], (H,), F32, 1.0, 16.0)),
            D=around(ks[5], H, 1.0, 0.1, F32),
            gate_norm={"scale": around(ks[6], di, 1.0, 0.1)},
            out_proj=normal(ks[7], (di, D), di))
    elif kind == "*":
        dq, dkv = s.heads * s.head_dim, s.kv_heads * s.head_dim
        out.update(wq=normal(ks[0], (D, dq), D),
                   wkv=normal(ks[1], (2, D, dkv), D),
                   wo=normal(ks[2], (dq, D), dq))
    else:   # the MLP: gate and up side by side
        out.update(w1=normal(ks[0], (D, 2 * s.ffn), D),
                   w2=normal(ks[1], (s.ffn, D), s.ffn))
    return out


@functools.lru_cache(maxsize=None)
def _makers(s: Shape, dtype):
    """The jitted programs of one (Shape, dtype), kept for the process: a
    run that reads several seeds compiles them once."""
    def ends(ke, kn):
        std = 2.0 / (s.embed_scale * math.sqrt(s.dim))
        return ((jax.random.normal(ke, (s.vocab, s.dim), F32)
                 * std).astype(dtype),
                {"scale": (1.0 + 0.1 * jax.random.normal(
                    kn, (s.dim,), F32)).astype(dtype)})

    return jax.jit(ends), {
        kind: jax.jit(functools.partial(_layer, kind, s=s, dt=dtype))
        for kind in set(s.pattern)}


def make_weights(s: Shape, seed: int, dtype=jnp.bfloat16):
    # the seed may exceed 32 signed bits: fold it into a key in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    ke, kl, kn = jax.random.split(key, 3)
    ends, make = _makers(s, jnp.dtype(dtype))
    table, ln_f = ends(ke, kn)
    return {"tok_emb": table,
            "layers": [make[kind](k) for kind, k in zip(
                s.pattern, jax.random.split(kl, len(s.pattern)))],
            "ln_f": ln_f}
