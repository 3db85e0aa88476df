"""Seeded weights of the ``zaya`` family, made on the device in one jitted
call, in bfloat16 (the router's balance bias in float32), in the tree the
program's hybrid family reads (``torchdistpackage_tpu/models/hybrid.py``: a
list of per-layer dicts, one mixer a layer, a ``res`` leaf of four vectors on
each, NO ``head`` leaf: the head is the table).  The program and the
reference are handed this same tree; neither makes weights.

Nothing here is at a value that hides a fault: the norms' scales, the keys'
temperatures, the router's ``gamma`` and the residual's ``a`` vectors are
drawn around 1 and not AT 1; the residual's ``b`` vectors, every bias of the
convolutions and of the router, and the balance bias are drawn small and NOT
zero (leaving one out then shows).  The router's last matrix is drawn LARGE
(its logits spread ~2.5, not ~1): the chosen probability of a token then lies
mostly in 0.3-0.9, as a trained top-1 router's does, so the experts' part of
the stream is not a sixteenth of what it is in the model and an error in an
expert shows in the logits."""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference.zaya import Shape

F32 = jnp.float32
#: the spread of the router's logits (W_3's rows are drawn at this over
#: sqrt(fan-in); its input has unit-order entries after the gelu)
ROUTER_LOGIT_SPREAD = 6.0


def _layer(kind: str, key, s: Shape, dt, first_expert_layer: bool) -> Dict[str, Any]:
    D, hd = s.dim, s.head_dim
    ks = jax.random.split(key, 16)

    def normal(k, shape, fan_in, scale=1.0):
        return (jax.random.normal(k, shape, F32) * scale
                / math.sqrt(fan_in)).astype(dt)

    def around(k, n, centre, spread):
        return (centre + spread * jax.random.normal(k, (n,), F32)).astype(dt)

    out: Dict[str, Any] = {
        "norm": {"scale": around(ks[15], D, 1.0, 0.1)},
        "res": {"a_h": around(ks[14], D, 1.0, 0.05),
                "b_h": around(ks[13], D, 0.0, 0.02),
                "a_y": around(ks[12], D, 1.0, 0.1),
                "b_y": around(ks[11], D, 0.0, 0.02)}}
    if kind == "*":
        G, C = s.heads + s.kv_heads, s.channels
        out.update(
            wz=normal(ks[0], (D, C), D),
            wv=normal(ks[1], (D, s.kv_heads * hd), D),
            conv0_w=normal(ks[2], (s.time0, C), s.time0),
            conv0_b=around(ks[3], C, 0.0, 0.1),
            conv1_w=normal(ks[4], (s.time1, G, hd, hd), s.time1 * hd),
            conv1_b=around(ks[5], C, 0.0, 0.1),
            k_temp=around(ks[6], s.kv_heads, 1.0, 0.2),
            wo=normal(ks[7], (s.heads * hd, D), s.heads * hd))
        return out
    F, R = s.moe_ffn, s.router_hidden

    def expert(k):   # one expert at a time: its float32 draw is 50 MB
        k1, k2 = jax.random.split(k)
        return {"w1": normal(k1, (D, 2 * F), D),
                "w2": normal(k2, (F, D), F)}

    router = {
        "down": {"w": normal(ks[0], (D, R), D), "b": around(ks[1], R, 0.0, 0.1)},
        "norm": {"scale": around(ks[2], R, 1.0, 0.1)},
        "w1": normal(ks[3], (R, R), R), "b1": around(ks[4], R, 0.0, 0.1),
        "w2": normal(ks[5], (R, R), R), "b2": around(ks[6], R, 0.0, 0.1),
        "w3": normal(ks[7], (R, s.experts), R, ROUTER_LOGIT_SPREAD),
        # a chosen probability of 0.3-0.9 stands well clear of the rest: a
        # bias of 0.05 moves the choice at the near-ties only, as a
        # balancing bias does
        "bias": jax.random.normal(ks[8], (s.experts,), F32) * 0.05}
    if not first_expert_layer:
        router["gamma"] = around(ks[9], R, 1.0, 0.1)
    out.update(router=router,
               experts=jax.lax.map(expert, jax.random.split(ks[10], s.experts)))
    return out


def make_weights(s: Shape, seed: int, dtype=jnp.bfloat16):
    def build(key):
        ke, kl, kn = jax.random.split(key, 3)
        first = s.pattern.index("E")
        return {
            "tok_emb": (jax.random.normal(ke, (s.vocab, s.dim), F32)
                        * 0.02).astype(dtype),
            "layers": [_layer(kind, k, s, dtype, i == first)
                       for i, (kind, k) in enumerate(zip(
                           s.pattern, jax.random.split(kl, len(s.pattern))))],
            "ln_f": {"scale": (1.0 + 0.1 * jax.random.normal(
                kn, (s.dim,), F32)).astype(dtype)},
        }

    # the seed may exceed 32 signed bits: fold it into a key in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(build)(key)
