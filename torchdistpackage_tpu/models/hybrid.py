"""The hybrid family: a layer stack given by a pattern string, in which a
layer is ONE mixer and not attention + MLP (the ``nemotron_h`` shape):

- ``M``  a Mamba-2 mixer (:func:`mamba2_mixer`): a recurrent state of
  ``[heads, head_dim, state]`` floats and the last ``conv_kernel - 1`` rows
  of the convolution's input per sequence, and no keys at all;
- ``*``  grouped-query attention with NO positional encoding (the Mamba
  layers carry order), through the same cache ops (block pool, paged
  kernel) as every other family;
- ``E``  the expert layer (:func:`~..parallel.moe.moe_serve_forward` with
  ``score='sigmoid'``, a shared expert and a held range of experts;
  ``moe_act`` 'relu2' experts in a latent width, or gated 'swiglu' ones);
- ``L``  latent attention (:func:`latent_attention_mixer`): every head's
  keys and values are up-projections of ONE cached row a position, the
  normed latent and a rotated key shared by all heads, and attention runs
  in that latent (the absorbed form), over a block pool of its own shape;
- ``D``  a dense gated MLP (SwiGLU, gate and up side by side).

Every layer is ``x <- x + mixer(RMSNorm(x))``, so a pre-norm block of
attention + FFN is two layers here (``"LD"``, ``"LE"``); a final RMSNorm,
then an untied head.  No biases except the convolution's.  ``params["layers"]`` is a
list of per-layer dicts (as ``gpt_moe.py`` lists its blocks), each
``{"norm": ..., <the mixer's leaves>}``; the kind of layer ``i`` is
``cfg.pattern[i]``.

This is the SERVING path (:func:`hybrid_paged_forward`, driven by
``ServingEngine``): the engine keeps the recurrent state beside its paged
KV pool, one array a Mamba layer with one row a slot.  Training this family
(a chunked scan with a backward, the router's auxiliary loss) is ROADMAP
queue 2 A1.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.moe import MoEConfig, _unbiased_act, moe_serve_forward
from ..parallel.tensor_parallel import TransformerConfig, dense
from ..parallel.tensor_parallel.layers import (
    apply_rope,
    rms_norm,
    rope_cache,
)

PyTree = Any
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    dim: int
    #: one character a layer: 'M' Mamba-2 | '*' attention | 'E' experts |
    #: 'L' latent attention | 'D' dense gated MLP
    pattern: str
    max_seq: int
    # attention: nheads x head_dim == dim (the engine's pool derives it so)
    nheads: int
    kv_heads: int
    # Mamba-2 ('M'): d_inner = mamba_heads x mamba_head_dim
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    conv_kernel: int = 4
    #: prefill computes the recurrence in chunks of this many positions
    ssm_chunk: int = 128
    # latent MoE: ``moe_experts`` router outputs, of which ``moe_held``
    # ``(first, count)`` live here (None = all)
    moe_experts: int = 0
    moe_held: Optional[Tuple[int, int]] = None
    moe_top_k: int = 2
    moe_latent: Optional[int] = None
    moe_ffn: int = 0
    moe_shared_ffn: int = 0
    moe_routed_scale: float = 1.0
    #: the experts' (and the shared expert's) activation: 'relu2' | 'swiglu'
    moe_act: str = "relu2"
    # latent attention ('L'): a query head is ``mla_nope + mla_rope`` wide,
    # a value head ``mla_v``; a position caches ``mla_latent + mla_rope``
    mla_latent: int = 0
    mla_nope: int = 0
    mla_rope: int = 0
    mla_v: int = 0
    rope_theta: float = 10000.0
    #: a rope-scaling dict as ``rope_cache`` takes it (yarn), or None
    rope_scaling: Optional[Dict[str, Any]] = None
    #: the dense gated MLP's width ('D')
    dense_ffn: int = 0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    #: the recurrent state's precision (the convolution's rows keep ``dtype``)
    state_dtype: Any = jnp.float32
    # what ``ServingEngine`` reads off every config: constants here, not
    # fields (no ring attention, no learned positions, the backend's dispatch)
    attn_impl = "flash"
    pos = "none"
    moe_dispatch = "auto"

    def __post_init__(self):
        bad = set(self.pattern) - set("M*ELD")
        if bad or not self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: one of 'M', '*', 'E', 'L', 'D' "
                f"a layer")
        if "*" in self.pattern and "L" in self.pattern:
            raise ValueError("one kind of block pool a model: '*' or 'L'")
        if "L" in self.pattern and not (
                self.mla_latent and self.mla_nope and self.mla_rope
                and self.mla_v):
            raise ValueError("an 'L' layer needs the four mla_* widths")
        if "D" in self.pattern and not self.dense_ffn:
            raise ValueError("a 'D' layer needs dense_ffn")
        if self.nheads * (self.dim // self.nheads) != self.dim:
            raise ValueError("dim must divide by nheads")
        if "M" in self.pattern and not (
                self.mamba_heads and self.mamba_head_dim and self.ssm_state):
            raise ValueError("an 'M' layer needs the Mamba-2 sizes")
        if self.mamba_heads % self.ssm_groups:
            raise ValueError("mamba_heads must divide by ssm_groups")
        if "E" in self.pattern and not self.moe_experts:
            raise ValueError("an 'E' layer needs moe_experts")

    # ---- layer counts: the engine sizes its pool and its state by these
    @property
    def nlayers(self) -> int:
        return len(self.pattern)

    @property
    def kv_layers(self) -> int:
        """Layers that keep keys and values, or the latent they are made
        from (the block pool's depth)."""
        return self.pattern.count("*") + self.pattern.count("L")

    @property
    def latent_width(self) -> int:
        """What one position caches in an 'L' layer (0: a K/V pool)."""
        return (self.mla_latent + self.mla_rope) if "L" in self.pattern else 0

    @property
    def mla_scale(self) -> float:
        """The softmax scale of latent attention: the query head's width,
        times yarn's ``mscale`` squared where the rope is stretched (the
        cos/sin tables themselves stay unscaled when ``mscale ==
        mscale_all_dim``, which ``rope_cache`` works out)."""
        scale = (self.mla_nope + self.mla_rope) ** -0.5
        rs = self.rope_scaling or {}
        if rs.get("mscale_all_dim") and float(rs.get("factor", 1.0)) > 1.0:
            m = 0.1 * float(rs["mscale_all_dim"]) * math.log(
                float(rs["factor"])) + 1.0
            scale *= m * m
        return scale

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state instead."""
        return self.pattern.count("M")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def block(self) -> TransformerConfig:
        """The attention layers' shape, as the pool and the paged ops read
        it (head counts and head size; nothing positional)."""
        return TransformerConfig(
            dim=self.dim, nheads=self.nheads, nlayers=max(self.kv_layers, 1),
            kv_heads=self.kv_heads, dtype=self.dtype, norm="rms",
            norm_eps=self.norm_eps, rope=False)

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(
            dim=self.dim, ffn_dim=self.moe_ffn, num_experts=self.moe_experts,
            top_k=self.moe_top_k, dtype=self.dtype, act=self.moe_act,
            score="sigmoid", routed_scale=self.moe_routed_scale,
            latent_dim=self.moe_latent, shared_ffn=self.moe_shared_ffn,
            held=self.moe_held, dispatch=self.moe_dispatch)

    def state_shapes(self, rows: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """One Mamba layer's state for ``rows`` sequences: name ->
        (shape, dtype)."""
        return {
            "ssm": ((rows, self.mamba_heads, self.mamba_head_dim,
                     self.ssm_state), self.state_dtype),
            "conv": ((rows, self.conv_kernel - 1, self.conv_channels),
                     self.dtype),
        }

    def state_bytes(self, rows: int) -> int:
        per = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                  for shape, dt in self.state_shapes(rows).values())
        return per * self.state_layers


def init_state(cfg: HybridConfig, rows: int) -> Dict[str, Tuple[jnp.ndarray, ...]]:
    """Zeroed recurrent state for ``rows`` sequences: ``{'ssm': (one
    [rows, H, P, N] array a Mamba layer), 'conv': (one [rows, K-1, C] a
    layer)}``.  One array a layer and not a stacked ``[L, ...]`` one: a
    step that is handed them as donated buffers then updates each in place
    (a stacked array would be rebuilt by a concatenate, and held twice)."""
    return {name: tuple(jnp.zeros(shape, dt)
                        for _ in range(cfg.state_layers))
            for name, (shape, dt) in cfg.state_shapes(rows).items()}


# ------------------------------------------------------------------ Mamba-2


def _ssd_chunk(x, dt, A, Bm, Cm, S0):
    """One chunk of the recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t`` in its matrix form (Dao & Gu 2024, "SSD").
    Grouped layout, heads = G groups x R heads each:

    x [b, Q, G, R, P], dt [b, Q, G, R] (0 where the position is padding:
    decay 1 and no input, so the state passes through), A [G, R] (< 0),
    Bm / Cm [b, Q, G, N], S0 [b, G, R, P, N].  All float32.  Returns
    (y [b, Q, G, R, P], S1)."""
    Q = x.shape[1]
    cum = jnp.cumsum(dt * A, axis=1)                      # [b, Q, G, R], <= 0
    t = jnp.arange(Q)
    tri = (t[:, None] >= t[None, :])[None, :, :, None, None]
    # decay from source s to target t (s <= t): exp(cum_t - cum_s) <= 1
    L = jnp.exp(jnp.where(tri, cum[:, :, None] - cum[:, None, :], -jnp.inf))
    CB = jnp.einsum("btgn,bsgn->btsg", Cm, Bm, precision=_HI)
    M = L * CB[..., None] * dt[:, None]                   # [b, t, s, G, R]
    y = jnp.einsum("btsgr,bsgrp->btgrp", M, x, precision=_HI)
    # what the carried state adds: exp(cum_t) S0 C_t
    y = y + jnp.einsum("btgn,bgrpn->btgrp", Cm, S0,
                       precision=_HI) * jnp.exp(cum)[..., None]
    to_end = jnp.exp(cum[:, -1:] - cum) * dt              # [b, Q, G, R]
    S1 = (jnp.exp(cum[:, -1])[..., None, None] * S0
          + jnp.einsum("bsgr,bsgrp,bsgn->bgrpn", to_end, x, Bm,
                       precision=_HI))
    return y, S1


def _ssd_step(x, dt, A, Bm, Cm, S0):
    """The one-step form, for a single position: x [b, G, R, P], dt
    [b, G, R], Bm / Cm [b, G, N], S0 [b, G, R, P, N]."""
    S1 = (jnp.exp(dt * A)[..., None, None] * S0
          + (dt[..., None] * x)[..., None] * Bm[:, :, None, None, :])
    y = jnp.sum(S1 * Cm[:, :, None, None, :], axis=-1)
    return y, S1


def mamba2_mixer(
    p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: HybridConfig,
    ssm: jnp.ndarray, conv: jnp.ndarray, n_valid: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x [B, S, D] (already normed) -> (y [B, S, D], ssm, conv).

    ``ssm`` [B, H, P, N] and ``conv`` [B, K-1, C] are each row's state
    BEFORE this call's positions; ``n_valid`` [B] says how many of the S
    positions are real.  The rest is padding (a prompt's last chunk, a
    slot the decode call masks): it advances neither the state nor the
    convolution's rows, so a row with ``n_valid == 0`` gets its state back
    bit for bit.  ``S == 1`` is the one-step recurrence; longer calls run
    the chunked form, ``cfg.ssm_chunk`` positions a chunk, carrying the
    state from chunk to chunk."""
    B, S, _ = x.shape
    H, P, N, G = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    R, K, di = H // G, cfg.conv_kernel, cfg.d_inner
    valid = jnp.arange(S)[None, :] < n_valid[:, None]     # [B, S]

    zxbcdt = dense(x, p["in_proj"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + cfg.conv_channels],
                  zxbcdt[..., di + cfg.conv_channels:])

    # depthwise causal convolution over (x, B, C): position t sees the
    # K-1 rows before it, the first of them from the carried tail
    cat = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=1)
    w = p["conv_w"].astype(F32)
    acc = p["conv_b"].astype(F32) + sum(
        cat[:, k:k + S].astype(F32) * w[k] for k in range(K))
    xbc_c = jax.nn.silu(acc)                              # float32 [B, S, C]
    # the tail after this call: the K-1 rows that end at the last REAL one
    conv = jax.vmap(
        lambda c, n: jax.lax.dynamic_slice_in_dim(c, n, K - 1, axis=0)
    )(cat, n_valid).astype(conv.dtype)

    xs = xbc_c[..., :di].reshape(B, S, G, R, P)
    Bm = xbc_c[..., di:di + G * N].reshape(B, S, G, N)
    Cm = xbc_c[..., di + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    dt = jnp.where(valid[..., None], dt, 0.0).reshape(B, S, G, R)
    A = -jnp.exp(p["A_log"].astype(F32)).reshape(G, R)
    S0 = ssm.astype(F32).reshape(B, G, R, P, N)

    if S == 1:
        y, S1 = _ssd_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], S0)
        y = y[:, None]
    else:
        Q = min(cfg.ssm_chunk, S)
        if S % Q:
            raise ValueError(
                f"{S} positions do not divide into chunks of {Q}")

        def chunks(a):   # [B, S, ...] -> [S/Q, B, Q, ...]
            return jnp.moveaxis(a.reshape((B, S // Q, Q) + a.shape[2:]), 1, 0)

        def body(Sc, c):
            yc, Sc = _ssd_chunk(*c[:2], A, *c[2:], Sc)
            return Sc, yc

        S1, ys = jax.lax.scan(
            body, S0, (chunks(xs), chunks(dt), chunks(Bm), chunks(Cm)))
        y = jnp.moveaxis(ys, 0, 1).reshape(B, S, G, R, P)
    y = y + p["D"].astype(F32).reshape(G, R)[..., None] * xs
    ssm = S1.reshape(B, H, P, N).astype(ssm.dtype)

    # gate, then RMSNorm within each of the G groups of d_inner / G
    y = y.reshape(B, S, di) * jax.nn.silu(z.astype(F32))
    yg = y.reshape(B, S, G, di // G)
    yg = yg * jax.lax.rsqrt(
        jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.norm_eps)
    y = (yg.reshape(B, S, di)
         * p["gate_norm"]["scale"].astype(F32)).astype(x.dtype)
    return dense(y, p["out_proj"]), ssm, conv


# ---------------------------------------------------------------- attention


def attention_mixer(p, x, cfg: HybridConfig, ck, cv, offset, cache_ops):
    """Position-free GQA on the block pool: x [B, S, D] (normed) -> (y, ck,
    cv).  ``cache_ops`` is the ``(write, attend)`` pair of
    ``serving/paged_cache.py``, as ``cached_block_forward`` takes it."""
    B, S, _ = x.shape
    hd = cfg.block.head_dim
    write, attend = cache_ops
    q = dense(x, p["wq"]).reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
    kv = dense(x, p["wkv"], "bsd,tdh->tbsh")
    k = kv[0].reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
    v = kv[1].reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
    ck = write(ck, k, offset)
    cv = write(cv, v, offset)
    out = attend(q, ck, cv, offset, window=None)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, q.shape[1] * hd)
    return dense(out, p["wo"]), ck, cv


def latent_attention_mixer(p, x, cfg: HybridConfig, pool, offset, cache_ops):
    """Latent attention in the absorbed form: x [B, S, D] (normed) -> (y,
    pool).  A position caches ONE row, ``[RMSNorm(c) | rope(k_rope)]``
    (``mla_latent + mla_rope`` wide), which is every head's key AND, in its
    first ``mla_latent`` columns, every head's value: head h's query goes
    into the latent through ``wuk[h]`` (its key up-projection, transposed),
    the heads attend to the shared rows, and what comes out of the latent
    goes through ``wuv[h]`` to the head's value width.  The keys and values
    of the published form, ``wuk[h] c`` and ``wuv[h] c``, are never made.
    Each query head is normed (a learned RMSNorm over its whole width)
    before its rope part is rotated; ``k_rope`` is not normed.
    ``cache_ops``: ``(write, attend)`` of ``serving/paged_cache.py`` for the
    latent pool."""
    B, S, _ = x.shape
    H, dn, dr, dc = cfg.nheads, cfg.mla_nope, cfg.mla_rope, cfg.mla_latent
    write, attend = cache_ops
    pos = offset[:, None] + jnp.arange(S)[None, :]
    cos, sin = rope_cache(pos.reshape(-1), dr, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    rope = (cos.reshape(B, 1, S, dr // 2), sin.reshape(B, 1, S, dr // 2))

    q = dense(x, p["wq"]).reshape(B, S, H, dn + dr).transpose(0, 2, 1, 3)
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q_lat = jnp.einsum("bhsn,hnc->bhsc", q[..., :dn], p["wuk"])
    q = jnp.concatenate(
        [q_lat, apply_rope(q[..., dn:], cache=rope)], axis=-1)
    kva = dense(x, p["wkva"])                              # [B, S, dc + dr]
    row = jnp.concatenate(
        [rms_norm(kva[..., :dc], p["kv_norm"], cfg.norm_eps),
         apply_rope(kva[:, None, :, dc:], cache=rope)[:, 0]], axis=-1)
    pool = write(pool, row, offset)
    o_lat = attend(q, pool, offset)                        # [B, H, S, dc]
    o = jnp.einsum("bhsc,hcv->bshv", o_lat, p["wuv"])
    return dense(o.reshape(B, S, H * cfg.mla_v), p["wo"]), pool


# ------------------------------------------------------------------ forward


def hybrid_paged_forward(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg: HybridConfig,
    cache: Dict[str, Any],
    state: Dict[str, Tuple[jnp.ndarray, ...]],
    n_valid: jnp.ndarray,
    cache_ops,
    offset: jnp.ndarray,
    last_idx=None,
):
    """``tokens`` [B, S] through the stack.  ``cache``: the block pool of
    the attention layers (``{'k','v': [kv_layers, ...]}``, or ``{'kv':
    ...}`` where they are latent), reached through
    ``cache_ops(layer)`` (the pool's ``(write, attend)`` pair for one of
    its layers; the pool itself is threaded whole through the attention
    layers); ``state``: :func:`init_state`'s arrays with one row a
    row of ``tokens``; ``n_valid`` [B]: the real positions of each row.
    Returns ``(cache, state, logits [B, V], moe_metrics)``: the logits of
    row ``last_idx`` (default: the last), and the expert layers' counters
    summed over the layers, with ``routing`` [B, S, E-layers, k]: the
    experts every position chose in every expert layer (None without an
    'E' layer)."""
    from ..serving.paged_cache import _select_row

    S = tokens.shape[1]
    valid = jnp.arange(S)[None, :] < n_valid[:, None]
    h = jnp.take(params["tok_emb"], tokens, axis=0)
    cache, kv_layer = dict(cache), 0
    ssm, conv, mets = [], [], []
    mcfg = cfg.moe if cfg.moe_experts else None
    for kind, lp in zip(cfg.pattern, params["layers"]):
        x = rms_norm(h, lp["norm"], cfg.norm_eps)
        if kind == "M":
            m = len(ssm)
            y, s_m, c_m = mamba2_mixer(
                lp, x, cfg, state["ssm"][m], state["conv"][m], n_valid)
            ssm.append(s_m)
            conv.append(c_m)
        elif kind == "*":
            y, cache["k"], cache["v"] = attention_mixer(
                lp, x, cfg, cache["k"], cache["v"], offset,
                cache_ops(kv_layer))
            kv_layer += 1
        elif kind == "L":
            y, cache["kv"] = latent_attention_mixer(
                lp, x, cfg, cache["kv"], offset, cache_ops(kv_layer))
            kv_layer += 1
        elif kind == "D":
            y = dense(_unbiased_act(dense(x, lp["w1"]), "swiglu"), lp["w2"])
        else:
            y, met = moe_serve_forward(
                lp, x, mcfg, return_metrics=True, valid=valid)
            mets.append(met)
        h = h + y
    state = {"ssm": tuple(ssm), "conv": tuple(conv)}
    metrics = None
    if mets:
        routing = jnp.stack([m.pop("gate_idx") for m in mets], axis=2)
        metrics = {k: sum(m[k] for m in mets) for k in mets[0]}
        metrics["routing"] = routing
    h = rms_norm(_select_row(h, last_idx), params["ln_f"], cfg.norm_eps)
    return cache, state, dense(h, params["head"])[:, 0, :], metrics


# --------------------------------------------------------------------- init


def init_hybrid_params(key, cfg: HybridConfig) -> Dict[str, PyTree]:
    """Seeded parameters in the layout :func:`hybrid_paged_forward` reads
    (tests and examples; a checkpoint converter is not written yet)."""
    dt, D = cfg.dtype, cfg.dim

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32)
                / math.sqrt(fan_in)).astype(dt)

    def norm():
        return {"scale": jnp.ones((D,), dt)}

    layers: List[Dict[str, Any]] = []
    keys = jax.random.split(key, len(cfg.pattern) + 2)
    hd = cfg.block.head_dim
    for kind, k in zip(cfg.pattern, keys):
        ks = jax.random.split(k, 8)
        if kind == "M":
            di, C, H = cfg.d_inner, cfg.conv_channels, cfg.mamba_heads
            lp = {
                "in_proj": normal(ks[0], (D, di + C + H), D),
                "conv_w": normal(ks[1], (cfg.conv_kernel, C), cfg.conv_kernel),
                "conv_b": jnp.zeros((C,), dt),
                # dt in [1e-3, 1e-1] through the softplus, A in [-16, -1]:
                # the published initialisation's ranges
                "dt_bias": jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                    ks[2], (H,), F32, math.log(1e-3), math.log(1e-1))))),
                "A_log": jnp.log(jax.random.uniform(ks[3], (H,), F32, 1., 16.)),
                "D": jnp.ones((H,), F32),
                "gate_norm": {"scale": jnp.ones((di,), dt)},
                "out_proj": normal(ks[4], (di, D), di),
            }
        elif kind == "*":
            dkv = cfg.kv_heads * hd
            lp = {"wq": normal(ks[0], (D, D), D),
                  "wkv": normal(ks[1], (2, D, dkv), D),
                  "wo": normal(ks[2], (D, D), D)}
        elif kind == "L":
            H, dn, dr, dc, dv = (cfg.nheads, cfg.mla_nope, cfg.mla_rope,
                                 cfg.mla_latent, cfg.mla_v)
            lp = {"wq": normal(ks[0], (D, H * (dn + dr)), D),
                  "q_norm": {"scale": jnp.ones((dn + dr,), dt)},
                  "wkva": normal(ks[1], (D, dc + dr), D),
                  "kv_norm": {"scale": jnp.ones((dc,), dt)},
                  "wuk": normal(ks[2], (H, dn, dc), dc),
                  "wuv": normal(ks[3], (H, dc, dv), dc),
                  "wo": normal(ks[4], (H * dv, D), H * dv)}
        elif kind == "D":
            lp = {"w1": normal(ks[0], (D, 2 * cfg.dense_ffn), D),
                  "w2": normal(ks[1], (cfg.dense_ffn, D), cfg.dense_ffn)}
        else:
            m = cfg.moe
            _, held = m.held_range
            lat = m.latent_dim or D
            # a gated expert's w1 is gate and up side by side
            wide = 2 if m.act == "swiglu" else 1
            lp = {"router": {"w": normal(ks[0], (D, m.num_experts), D),
                             "bias": jnp.zeros((m.num_experts,), F32)},
                  "experts": {"w1": normal(
                      ks[1], (held, lat, wide * m.ffn_dim), lat),
                              "w2": normal(ks[2], (held, m.ffn_dim, lat),
                                           m.ffn_dim)}}
            if m.latent_dim:
                lp["latent"] = {"down": normal(ks[3], (D, lat), D),
                                "up": normal(ks[4], (lat, D), lat)}
            if m.shared_ffn:
                lp["shared"] = {"w1": normal(
                    ks[5], (D, wide * m.shared_ffn), D),
                                "w2": normal(ks[6], (m.shared_ffn, D),
                                             m.shared_ffn)}
        layers.append({"norm": norm(), **lp})
    return {
        "tok_emb": (jax.random.normal(keys[-2], (cfg.vocab_size, D), F32)
                    * 0.02).astype(dt),
        "layers": layers,
        "ln_f": norm(),
        "head": normal(keys[-1], (D, cfg.vocab_size), D),
    }
