"""KV-cache autoregressive generation for the dense GPT/Llama families.

The reference is a training toolkit — it has no inference path at all.  A
complete framework needs one, and decode is where TPU-first design choices
differ most from training:

- **Static shapes end-to-end**: the KV cache is a fixed ``[L, B, Hkv,
  max_len, hd]`` buffer written with ``dynamic_update_slice``; attention
  always scores against the full buffer with a position mask (`key_pos <=
  query_pos`).  No growing tensors, so the whole decode loop is ONE
  ``lax.scan`` inside ONE jit — no per-token retrace, no host round-trips.
- **One cached-block implementation serves prefill AND decode**: prefill is
  the S_in=P case (offset 0), decode the S_in=1 case (offset t) of the same
  function — the reference-style "two code paths that drift" problem cannot
  exist.
- **TP composes exactly like training**: the same param specs shard q/kv
  heads and the vocab-parallel head; the per-shard last-position logits are
  psum-assembled into full [B, V] rows (tiny at S_in=1), sampling is
  replicated-deterministic across shards, and GQA serves grouped KV heads
  without materializing repeats.
- RoPE rotates at the true global positions (``offset + arange(S_in)``),
  traced, so the rotation is correct at every decode step inside the scan.

All families decode: dense GPT, ``llama_config`` models
(RMSNorm/SwiGLU/RoPE/GQA), and the MoE family — whose inference dispatch
is the NO-DROP limit of the training router (:func:`forward_cached_moe`:
capacity raised to >= E/top_k, so token t's routing never depends on what
other tokens routed — the property that makes incremental decode equal
the full forward).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax

from jax.lax import axis_size
import jax.numpy as jnp

from ..parallel.tensor_parallel.layers import (
    TransformerConfig,
    _close_row_parallel,
    compute_qkv,
    dense,
    layer_norm,
    mlp_partial,
    rope_cache,
)
from ..utils import profiling as prof
from .gpt import GPTConfig, gpt_head, vocab_parallel_embed

PyTree = Any


def init_kv_cache(
    cfg: GPTConfig, batch: int, max_len: int, axis_size: int = 1,
    quantized: bool = False,
) -> Dict[str, Any]:
    """Zeroed cache ``{'k','v': [L, B, Hkv_local, max_len, hd]}`` in
    ``cfg.dtype``.  ``axis_size`` divides the KV heads for TP (call inside
    shard_map with ``axis_size(axis)``, or build the global
    [L, B, Hkv, ...] array outside and shard dim 2 over the tensor axis).

    ``quantized=True``: int8 KV storage — each 'k'/'v' entry becomes a
    ``(q8, scale)`` pair (scale [L, B, Hkv, max_len] f32, one symmetric
    scale per written position-vector, computed at append time).  Decode
    reads the cache once per token, so at long context the KV bytes — not
    the weights — bound throughput; int8 halves them vs bf16.  Dequant happens in-register inside the attention
    einsums (:func:`_cached_attention` folds the k-scale into the score
    and the v-scale into the probabilities).  The pair is a pytree, so
    the decode scan slices/stacks it like any dense cache leaf."""
    hkv, rem = divmod(cfg.block.kv_head_count, axis_size)
    if rem or hkv == 0:
        raise ValueError(
            f"kv_heads {cfg.block.kv_head_count} not divisible by tp "
            f"{axis_size} (whole KV heads per shard)"
        )
    shape = (cfg.nlayers, batch, hkv, max_len, cfg.block.head_dim)
    if quantized:
        def entry():
            return (jnp.zeros(shape, jnp.int8),
                    jnp.ones(shape[:-1], jnp.float32))
        return {"k": entry(), "v": entry()}
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _kv_quant(x: jnp.ndarray):
    """[..., hd] -> (int8 [..., hd], scale [...]) — symmetric per-vector."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


@prof.scoped(prof.KV_WRITE)
def _cache_write(c, val: jnp.ndarray, offset):
    """Append ``val`` [B, Hkv, S_in, hd] at ``offset`` — dense array or
    quantized (q8, scale) pair, one code path for both."""
    if isinstance(c, tuple):
        q8, scale = c
        vq, vs = _kv_quant(val)
        return (
            jax.lax.dynamic_update_slice(q8, vq, (0, 0, offset, 0)),
            jax.lax.dynamic_update_slice(scale, vs, (0, 0, offset)),
        )
    return jax.lax.dynamic_update_slice(c, val.astype(c.dtype), (0, 0, offset, 0))


@prof.scoped(prof.ATTEND)
def _cached_attention(q: jnp.ndarray, ck, cv, offset, window=None,
                      sm_scale: Optional[float] = None,
                      sink: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Grouped-query attention of q [B, H, S_in, hd] against the full cache
    ck/cv [B, Hkv, T, hd], masked to ``key_pos <= offset + query_row``.
    f32 softmax, 1/sqrt(hd) scale (or ``sm_scale``) — the mha_reference
    conventions.

    ``offset`` is a scalar (every row at the same position — the
    ``generate()`` batch) OR a [B] vector of per-row positions — the
    serving engine's continuous batch, where every slot sits at its own
    depth.  The vector form broadcasts the mask per row and is otherwise
    the identical computation, so the two agree bitwise when the vector is
    constant.

    Quantized caches pass ``(q8, scale)`` pairs: the int8 payload is upcast
    in-register and the per-position scale folds into the scores (k) or
    the probabilities (v) — both exact because the scale is constant along
    the contracted hd dim, so HBM only ever moves int8 cache bytes.

    ``cv`` may be narrower than ``ck`` ([B, Hkv, T, hv]): the output rows
    are the values' width.  ``sink`` [H] float32: one more column of every
    row's softmax (the scalar of the row's query head), dropped before the
    probabilities meet the values."""
    B, H, S_in, hd = q.shape
    k_scale = v_scale = None
    if isinstance(ck, tuple):
        ck, k_scale = ck
    if isinstance(cv, tuple):
        cv, v_scale = cv
    Hkv, T = ck.shape[1], ck.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, S_in, hd)
    s = jnp.einsum(
        "bkgqh,bkth->bkgqt", qg.astype(jnp.float32) if k_scale is not None else qg,
        ck.astype(qg.dtype if k_scale is None else jnp.float32),
    ).astype(jnp.float32)
    if k_scale is not None:
        s = s * k_scale[:, :, None, None, :]
    s = s * (1.0 / math.sqrt(hd) if sm_scale is None else sm_scale)
    key_pos = jnp.arange(T)
    qpos = jnp.asarray(offset)[..., None] + jnp.arange(S_in)  # [S_in] | [B, S_in]
    mask = key_pos[None, :] <= qpos[..., None]
    if window is not None:  # Mistral: key in (qpos - window, qpos]
        mask = mask & (key_pos[None, :] > qpos[..., None] - window)
    if mask.ndim == 2:  # scalar offset: broadcast over the batch
        mask = mask[None]
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, Hkv, g, 1, 1),
            s.shape[:-1] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, col], axis=-1),
                           axis=-1)[..., :-1]
    if v_scale is not None:
        p = p * v_scale[:, :, None, None, :]
        out = jnp.einsum("bkgqt,bkth->bkgqh", p, cv.astype(jnp.float32))
        out = out.astype(q.dtype)
    else:
        p = p.astype(cv.dtype)
        out = jnp.einsum("bkgqt,bkth->bkgqh", p, cv)
    return out.reshape(B, H, S_in, cv.shape[-1])


def cached_block_forward(
    p: Dict[str, PyTree],
    x: jnp.ndarray,
    cfg: TransformerConfig,
    ck: jnp.ndarray,
    cv: jnp.ndarray,
    offset,
    axis: Optional[str] = None,
    rope: "tuple | None" = None,
    ffn=None,
    cache_ops: "tuple | None" = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One pre-LN block with KV caching: writes this call's k/v into the
    cache at ``[offset, offset + S_in)`` and attends against the whole
    buffer.  x: [B, S_in, D].  Returns ``(y, ck, cv)`` with the updated
    cache.  Prefill is S_in=P at offset 0; decode is S_in=1 at offset t —
    one implementation, both phases.

    ``ffn``: optional ``(p, h) -> z`` replacing the dense MLP half (h is
    the post-ln2 activation; z must be the COMPLETE ffn output — no
    pending TP partial sums) — how the MoE families plug their expert
    layer into the same cached block.

    ``cache_ops``: optional ``(write, attend)`` pair swapping the cache
    LAYOUT under the same block: ``write(c, val, offset) -> c`` and
    ``attend(q, ck, cv, offset, window=...) -> out``.  Default is the
    contiguous ``[B, Hkv, T, hd]`` buffer; ``serving/paged_cache.py``
    passes block-pool ops (and [B]-vector offsets) so the serving engine
    reuses this exact block — the transformer math cannot drift between
    the two layouts because there is only one copy of it."""
    B, S_in, D = x.shape
    write, attend = cache_ops if cache_ops is not None else (
        _cache_write, _cached_attention)
    with jax.named_scope(prof.MIXER):
        h = layer_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = compute_qkv(p["attn"], h, cfg, rope=rope)
        ck = write(ck, k, offset)
        cv = write(cv, v, offset)
        if (cache_ops is None and isinstance(offset, int) and offset == 0
                and S_in > 1):
            # prefill: every cached key IS this call's k, so causal
            # attention over (q, k, v) equals the cache-masked form — and
            # runs the model's own kernel via the shared core_attention
            # dispatch (flash on TPU) instead of materializing the [S_in,
            # total] masked score matrix
            from ..parallel.tensor_parallel.layers import core_attention

            out = core_attention(q, k, v, cfg)
        else:
            out = attend(q, ck, cv, offset, window=cfg.sliding_window)
        out = out.transpose(0, 2, 1, 3).reshape(
            B, S_in, q.shape[1] * cfg.head_dim)
        y = dense(out, p["attn"]["wo"])
        y = _close_row_parallel(y, p["attn"]["bo"], axis, False)
        x = x + y

    with jax.named_scope(prof.FFN):
        h = layer_norm(x, p["ln2"], cfg.norm_eps)
        if ffn is None:
            z = mlp_partial(p["mlp"], h)
            z = _close_row_parallel(z, p["mlp"]["b2"], axis, False)
        else:
            z = ffn(p, h)
        return x + z, ck, cv


@prof.scoped(prof.EMBED)
def _embed_at(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    axis: Optional[str],
) -> jnp.ndarray:
    """[B, S_in] ids at the given global positions -> [B, S_in, D]."""
    h = vocab_parallel_embed(params["tok_emb"], tokens, axis)
    if "pos_emb" in params:  # learned positions; rope models skip this
        h = h + jnp.take(params["pos_emb"], positions, axis=0)
    return h


def forward_cached(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg: GPTConfig,
    cache: Dict[str, jnp.ndarray],
    offset,
    axis: Optional[str] = None,
    all_logits: bool = False,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Run ``tokens`` [B, S_in] (occupying global positions
    ``offset + arange(S_in)``) through the cached stack.  Returns the
    updated cache and the LAST position's vocab-local logits [B, V_local].
    The layer dim rides a ``lax.scan`` over the stacked block params with
    the cache slices as per-layer carries-through (scan ys)."""
    bcfg = cfg.block
    S_in = tokens.shape[1]
    positions = offset + jnp.arange(S_in)
    h = _embed_at(params, tokens, positions, axis)
    rope = (
        rope_cache(positions, bcfg.head_dim, bcfg.rope_theta,
                   scaling=bcfg.rope_scaling)
        if bcfg.rope
        else None
    )

    def body(hc, xs):
        lp, ck, cv = xs
        y, ck, cv = cached_block_forward(
            lp, hc, bcfg, ck, cv, offset, axis=axis, rope=rope
        )
        return y, (ck, cv)

    h, (ck, cv) = jax.lax.scan(
        body, h, (params["blocks"], cache["k"], cache["v"])
    )
    if all_logits:
        # per-position logits [B, S_in, V_local] — the speculative-decode
        # verify pass needs the model's argmax at EVERY drafted position
        return {"k": ck, "v": cv}, gpt_head(
            params, h, axis, False, eps=cfg.norm_eps)
    logits = gpt_head(params, h[:, -1:, :], axis, False, eps=cfg.norm_eps)  # [B, 1, V_local]
    return {"k": ck, "v": cv}, logits[:, 0, :]


def forward_cached_moe(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg: GPTConfig,
    cache: Dict[str, jnp.ndarray],
    offset,
    axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """:func:`forward_cached` for the MoE family (heterogeneous block
    LIST, expert FFN every moe_every-th block).

    Inference-time dispatch is EXACT no-drop routing — every token reaches
    every expert it routed to, so token t's output never depends on what
    other tokens (batch rows, or the incremental history) routed.  This is
    what makes incremental decode == full forward: capacity-based drops
    are a training-batch interaction with no incremental equivalent.

    - ``ep_axis=None`` (single-host serving): the ragged route-then-group
      path (:func:`..parallel.moe.moe_serve_forward`) — ``jax.lax.
      ragged_dot`` grouped GEMMs over exactly ``T*top_k`` rows, no
      ``E/top_k`` capacity-padding tax at prefill.
    - ``ep_axis`` set (EP-sharded serving, inside shard_map on the moe
      mesh view): experts stay sharded over ``moe_ep`` at inference —
      each device holds ``E/ep`` experts and tokens ride the training
      all_to_all exchange, with capacity raised to the no-drop bound
      (``cf >= E/top_k`` ⇒ no token evicted).  Composes with TP decode
      (``axis``): attention heads/vocab shard over ``tensor``, experts
      over ``moe_ep``."""
    import dataclasses as _dc

    from ..parallel.moe import moe_forward, moe_serve_forward
    from .gpt_moe import moe_layer_config

    bcfg = cfg.block
    mcfg = moe_layer_config(cfg)
    mcfg = _dc.replace(
        mcfg,
        capacity_factor=max(
            mcfg.capacity_factor, mcfg.num_experts / mcfg.top_k
        ),
    )
    S_in = tokens.shape[1]
    positions = offset + jnp.arange(S_in)
    h = _embed_at(params, tokens, positions, axis)
    rope = (
        rope_cache(positions, bcfg.head_dim, bcfg.rope_theta,
                   scaling=bcfg.rope_scaling)
        if bcfg.rope
        else None
    )

    if ep_axis is None:
        def moe_ffn(p, hh):
            return moe_serve_forward(p["moe"], hh, mcfg)
    else:
        def moe_ffn(p, hh):
            z, _aux = moe_forward(
                p["moe"], hh, mcfg, ep_axis=ep_axis, causal=bcfg.causal)
            return z

    ks, vs = [], []
    layer = lambda c, i: jax.tree.map(lambda a: a[i], c)  # tuple-safe (int8)
    for i, bp in enumerate(params["blocks"]):
        h, ck, cv = cached_block_forward(
            bp, h, bcfg, layer(cache["k"], i), layer(cache["v"], i), offset,
            axis=axis, rope=rope, ffn=moe_ffn if "moe" in bp else None,
        )
        ks.append(ck)
        vs.append(cv)
    stack = lambda cs: jax.tree.map(lambda *xs: jnp.stack(xs), *cs)
    cache = {"k": stack(ks), "v": stack(vs)}
    logits = gpt_head(params, h[:, -1:, :], axis, False, eps=cfg.norm_eps)
    return cache, logits[:, 0, :]


@prof.scoped(prof.HEAD)
def _full_logits(logits: jnp.ndarray, cfg: GPTConfig, axis: Optional[str]):
    """Vocab-local [..., V_local] -> full [..., V] (psum-assembled shard
    slabs; tiny at a handful of positions per sequence).  Identity when
    serial.  Any leading shape: [B, V_local] for ordinary decode, [B,
    K+1, V_local] for the speculative multi-position verify step."""
    if axis is None:
        return logits
    n = axis_size(axis)
    i = jax.lax.axis_index(axis)
    full = jnp.zeros(logits.shape[:-1] + (cfg.vocab_size,), logits.dtype)
    start = (0,) * (logits.ndim - 1) + (i * logits.shape[-1],)
    full = jax.lax.dynamic_update_slice(full, logits, start)
    return jax.lax.psum(full, axis)


def _sample(
    logits: jnp.ndarray,
    key: Optional[jax.Array],
    temperature: float,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jnp.ndarray:
    """Greedy argmax when ``key`` is None, else temperature sampling with
    optional top-k and/or top-p (nucleus) filtering.  On full [B, V]
    logits, so TP shards make the identical choice.

    Filter order is the standard one: temperature -> top-k -> top-p.
    Masked logits become -inf (zero probability after softmax); top-p
    keeps the SMALLEST prefix of the probability-sorted vocab whose mass
    reaches ``top_p`` (the argmax always survives, so top_p -> 0 degrades
    to greedy rather than an empty support)."""
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    # temperature == 0 is the common shorthand for greedy — honor it instead
    # of dividing by zero (NaN logits -> undefined categorical draws)
    if key is None or temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    x = logits.astype(jnp.float32) / temperature
    V = x.shape[-1]
    neg = jnp.array(-jnp.inf, x.dtype)
    need_k = top_k is not None and top_k < V
    need_p = top_p is not None and top_p < 1.0
    if need_k and not need_p:
        # O(V·k) threshold; the full sort is only needed for the nucleus
        kth = jax.lax.top_k(x, top_k)[0][..., -1:]
        x = jnp.where(x < kth, neg, x)
    elif need_k or need_p:
        sorted_x = jnp.sort(x, axis=-1)[..., ::-1]  # ONE descending sort
        if need_k:
            x = jnp.where(x < sorted_x[..., top_k - 1][..., None], neg, x)
            # the filtered distribution's descending sort, for the nucleus
            sorted_x = jnp.where(jnp.arange(V) < top_k, sorted_x, neg)
        if need_p:
            probs = jax.nn.softmax(sorted_x, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep ranks whose PRECEDING mass is < top_p; rank 0 is kept
            # unconditionally so top_p -> 0 really is greedy (strict '<'
            # alone would empty the support at top_p == 0.0)
            keep = jnp.roll(cum, 1, axis=-1).at[..., 0].set(0.0) < top_p
            keep = keep.at[..., 0].set(True)
            cutoff = jnp.min(
                jnp.where(keep, sorted_x, jnp.inf), axis=-1, keepdims=True
            )
            x = jnp.where(x < cutoff, neg, x)
    return jax.random.categorical(key, x, axis=-1).astype(jnp.int32)


def generate(
    params: Dict[str, PyTree],
    prompt: jnp.ndarray,
    cfg: GPTConfig,
    max_new_tokens: int,
    axis: Optional[str] = None,
    key: Optional[jax.Array] = None,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    ep_axis: Optional[str] = None,
    kv_quant: bool = False,
) -> jnp.ndarray:
    """Autoregressively extend ``prompt`` [B, P] by ``max_new_tokens``.
    Greedy when ``key`` is None, else temperature sampling with optional
    ``top_k`` / ``top_p`` (nucleus) filtering (:func:`_sample`).  Returns
    [B, P + max_new_tokens] (prompt included).

    Serial when ``axis`` is None; under TP call inside shard_map with the
    training param specs (``gpt_param_specs(cfg, tp_axis=axis)``) — the
    returned tokens are psum/argmax-deterministic and identical on every
    shard.  Jit the whole call: prefill is one batched forward, then ONE
    ``lax.scan`` of single-token steps — no per-token recompilation.

    MoE configs decode through :func:`forward_cached_moe` — exact no-drop
    routing; ragged grouped GEMMs when ``ep_axis`` is None, EP-SHARDED
    experts (all_to_all over ``ep_axis``, e.g. the moe view's 'moe_ep')
    when set — its docstring has the semantics.  ``P + max_new_tokens <=
    cfg.max_seq`` for learned positions."""
    if ep_axis is not None and not cfg.moe_experts:
        raise ValueError("ep_axis is only meaningful for MoE configs")
    if cfg.attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            "context-parallel decode is not supported: the KV cache is not "
            "sequence-sharded. attn_impl is a runtime choice — decode a "
            "CP-trained checkpoint with dataclasses.replace(cfg, "
            "attn_impl='flash', context_axis=None)"
        )
    if cfg.moe_experts:
        fwd = functools.partial(forward_cached_moe, ep_axis=ep_axis)
    else:
        fwd = forward_cached
    B, P = prompt.shape
    if max_new_tokens < 1:
        # the prefill below would still sample one token and
        # dynamic_update_slice would CLAMP its out-of-bounds write onto the
        # last prompt position — silently corrupting the prompt
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    total = P + max_new_tokens
    if cfg.pos == "learned" and total > cfg.max_seq:
        raise ValueError(
            f"P + max_new_tokens = {total} exceeds the learned position "
            f"table ({cfg.max_seq})"
        )
    n_shards = 1 if axis is None else axis_size(axis)
    cache = init_kv_cache(cfg, B, total, axis_size=n_shards,
                          quantized=kv_quant)

    cache, logits = fwd(params, prompt, cfg, cache, 0, axis)
    k0 = None
    if key is not None:
        key, k0 = jax.random.split(key)
    first = _sample(
        _full_logits(logits, cfg, axis), k0, temperature, top_k, top_p)

    tokens = jnp.zeros((B, total), jnp.int32)
    tokens = jax.lax.dynamic_update_slice(tokens, prompt.astype(jnp.int32), (0, 0))
    tokens = jax.lax.dynamic_update_slice(tokens, first[:, None], (0, P))

    def step(carry, i):
        tokens, cache, key = carry
        pos = P + i  # position of the token being fed
        tok = jax.lax.dynamic_slice(tokens, (0, pos), (B, 1))
        cache, logits = fwd(params, tok, cfg, cache, pos, axis)
        sk = None
        if key is not None:
            key, sk = jax.random.split(key)
        nxt = _sample(
            _full_logits(logits, cfg, axis), sk, temperature, top_k, top_p)
        tokens = jax.lax.dynamic_update_slice(tokens, nxt[:, None], (0, pos + 1))
        return (tokens, cache, key), None

    if max_new_tokens > 1:
        (tokens, cache, key), _ = jax.lax.scan(
            step, (tokens, cache, key), jnp.arange(max_new_tokens - 1)
        )
    if axis is not None:
        # every shard computed the identical sequence; pmax re-types the
        # result as axis-invariant so callers can use out_specs P()
        tokens = jax.lax.pmax(tokens, axis)
    return tokens


def speculative_generate(
    params: Dict[str, PyTree],
    draft_params: Dict[str, PyTree],
    prompt: jnp.ndarray,
    cfg: GPTConfig,
    max_new_tokens: int,
    draft_cfg: Optional[GPTConfig] = None,
    num_draft: int = 4,
    kv_quant: bool = False,
) -> jnp.ndarray:
    """Greedy speculative decoding: a cheap DRAFT model proposes
    ``num_draft`` tokens per macro-step, the target model verifies them
    in ONE (K+1)-position cached forward, and the longest agreeing prefix
    plus the target's own correction token are emitted.

    **Lossless by construction**: every emitted token is the target
    model's greedy argmax on its certified prefix, whatever the draft
    proposes — a random draft only makes it slow, never wrong (the test
    asserts bit-equality with :func:`generate` for good, quantized AND
    adversarial drafts).  Decode is weight-bandwidth-bound, and a
    (K+1)-row verify forward reads the weights ONCE — so accepted drafts
    amortize the target's HBM traffic over up to K+1 tokens.  The natural
    self-speculative pairing is ``draft_params =
    tools.surgery.quantize_decode_params(params)``: the int8 draft reads
    half the weight bytes per token and near-always agrees.

    Static-shape design: both KV caches are fixed buffers; stale entries
    past the certified position are never attended (the position mask
    excludes them) and are overwritten when real tokens reach them, so
    rejected drafts need NO cache rollback.  The macro loop is a
    ``lax.while_loop`` on the certified position — data-dependent
    progress (1..K+1 tokens per macro-step) with zero retraces.

    Single-sequence (B == 1), serial (no TP axis) — the latency regime
    speculative decoding exists for.  ``draft_cfg`` defaults to ``cfg``
    (self-speculation); a distinct smaller model needs the same vocab.
    """
    if cfg.moe_experts:
        raise NotImplementedError(
            "speculative_generate supports the dense families")
    if cfg.attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            "context-parallel decode is not supported: the KV cache is not "
            "sequence-sharded. attn_impl is a runtime choice — decode a "
            "CP-trained checkpoint with dataclasses.replace(cfg, "
            "attn_impl='flash', context_axis=None)"
        )
    B, P = prompt.shape
    if B != 1:
        raise ValueError(f"speculative decode is B == 1 (got {B})")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    K = int(num_draft)
    if K < 1:
        raise ValueError(f"num_draft must be >= 1, got {K}")
    dcfg = draft_cfg or cfg
    if dcfg.vocab_size != cfg.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    total = P + max_new_tokens + K + 1  # slack for overshoot writes
    if cfg.pos == "learned" and total > cfg.max_seq:
        raise ValueError(
            f"P + max_new_tokens + num_draft + 1 = {total} exceeds the "
            f"learned position table ({cfg.max_seq})")
    cache_v = init_kv_cache(cfg, 1, total, quantized=kv_quant)
    cache_d = init_kv_cache(dcfg, 1, total, quantized=kv_quant)

    cache_v, logits = forward_cached(params, prompt, cfg, cache_v, 0)
    cache_d, _ = forward_cached(draft_params, prompt, dcfg, cache_d, 0)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [1]

    tokens = jnp.zeros((1, total), jnp.int32)
    tokens = jax.lax.dynamic_update_slice(tokens, prompt.astype(jnp.int32), (0, 0))
    tokens = jax.lax.dynamic_update_slice(tokens, first[:, None], (0, P))
    target_last = P + max_new_tokens - 1  # index of the final required token

    def macro(state):
        tokens, cache_v, cache_d, t = state

        # ---- draft K tokens after certified position t
        def dstep(carry, i):
            cache_d, tok = carry
            cache_d, lg = forward_cached(
                draft_params, tok, dcfg, cache_d, t + i)
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]  # [1,1]
            return (cache_d, nxt), nxt[0, 0]

        tok_t = jax.lax.dynamic_slice(tokens, (0, t), (1, 1))
        (cache_d, _), drafts = jax.lax.scan(
            dstep, (cache_d, tok_t), jnp.arange(K))  # drafts [K]

        # ---- verify: one (K+1)-position target forward over
        # [tokens[t], d_1..d_K] at offsets t..t+K
        cand = jnp.concatenate([tok_t[0], drafts])[None, :]  # [1, K+1]
        cache_v, all_lg = forward_cached(
            params, cand, cfg, cache_v, t, all_logits=True)  # [1, K+1, V]
        verify = jnp.argmax(all_lg[0], axis=-1).astype(jnp.int32)  # [K+1]
        # verify[i] = target's token for position t+i+1
        agree = (drafts == verify[:K]).astype(jnp.int32)
        n = jnp.sum(jnp.cumprod(agree))  # accepted draft prefix length

        # emit verify[0..n] at positions t+1..t+n+1: write ALL K+1 (the
        # tail past t+n+1 is uncertified overshoot — overwritten later,
        # never read: the final slice stops at the certified frontier)
        tokens = jax.lax.dynamic_update_slice(tokens, verify[None, :], (0, t + 1))
        return tokens, cache_v, cache_d, t + n + 1

    def cond(state):
        return state[3] < target_last

    tokens, cache_v, cache_d, t = jax.lax.while_loop(
        cond, macro, (tokens, cache_v, cache_d, P))
    return tokens[:, : P + max_new_tokens]


def beam_generate(
    params: Dict[str, PyTree],
    prompt: jnp.ndarray,
    cfg: GPTConfig,
    max_new_tokens: int,
    num_beams: int = 4,
    return_all: bool = False,
    kv_quant: bool = False,
) -> jnp.ndarray:
    """Fixed-length beam search (deterministic, log-prob scored).

    Standard beam semantics: at every step the ``num_beams * V``
    continuations of the live beams are scored by accumulated
    log-probability and the top ``num_beams`` survive (parent beams may
    be cloned or dropped — the KV caches are re-gathered along the batch
    dim accordingly, the textbook cost of beam search).  The
    best-scoring beam is returned (``return_all`` gives every beam,
    best first).  No ``length_penalty`` knob: every beam has the same
    length here, so a length normalization cannot change the ranking.

    The framework's generation API is fixed-length (no EOS machinery —
    the reference has no inference path at all, and stopping criteria
    are a serving-layer concern), so this is exhaustive-length beam
    search: parity with ``transformers.generate(num_beams=N,
    do_sample=False)`` holds when HF's early stopping is disabled
    (tests/test_generate.py::test_beam_matches_hf_and_greedy).  B == 1,
    serial.  ``kv_quant`` stores both caches int8 exactly as in
    :func:`generate` (the beam reorder gathers the (q8, scale) pytree
    unchanged).

    The whole search is one jit: prefill once, replicate the cache
    across beams, then ONE ``lax.scan`` of select-and-extend steps
    (static shapes throughout; beam reordering is a batch-dim gather).
    """
    B, P = prompt.shape
    if B != 1:
        raise ValueError(f"beam search is B == 1 (got {B})")
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if cfg.attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            "context-parallel decode is not supported (see generate)")
    total = P + max_new_tokens
    if cfg.pos == "learned" and total > cfg.max_seq:
        raise ValueError(
            f"P + max_new_tokens = {total} exceeds the learned position "
            f"table ({cfg.max_seq})")
    V = cfg.vocab_size
    nb = int(num_beams)
    fwd = forward_cached_moe if cfg.moe_experts else forward_cached

    # prefill every beam with the same prompt (identical rows; the first
    # expansion step de-duplicates by taking the top-nb of ONE row)
    cache = init_kv_cache(cfg, nb, total, quantized=kv_quant)
    tiled = jnp.broadcast_to(prompt.astype(jnp.int32), (nb, P))
    cache, logits = fwd(params, tiled, cfg, cache, 0)  # [nb, V]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    # beams start distinct: the nb best FIRST tokens of beam 0
    first_lp, first_tok = jax.lax.top_k(lp[0], nb)  # [nb]
    scores = first_lp
    tokens = jnp.zeros((nb, total), jnp.int32)
    tokens = jax.lax.dynamic_update_slice(tokens, tiled, (0, 0))
    tokens = jax.lax.dynamic_update_slice(
        tokens, first_tok.astype(jnp.int32)[:, None], (0, P))

    def step(carry, i):
        tokens, cache, scores = carry
        pos = P + i
        tok = jax.lax.dynamic_slice(tokens, (0, pos), (nb, 1))
        cache, logits = fwd(params, tok, cfg, cache, pos)  # [nb, V]
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        cand = scores[:, None] + lp  # [nb, V]
        top, flat_idx = jax.lax.top_k(cand.reshape(-1), nb)
        parent = flat_idx // V
        nxt = (flat_idx % V).astype(jnp.int32)
        tokens = tokens[parent]
        cache = jax.tree.map(lambda c: c[:, parent], cache)  # [L, nb, ...]
        tokens = jax.lax.dynamic_update_slice(
            tokens, nxt[:, None], (0, pos + 1))
        return (tokens, cache, top), None

    if max_new_tokens > 1:
        (tokens, cache, scores), _ = jax.lax.scan(
            step, (tokens, cache, scores), jnp.arange(max_new_tokens - 1))

    order = jnp.argsort(-scores)
    out = tokens[order][:, :total]
    return out if return_all else out[:1]
