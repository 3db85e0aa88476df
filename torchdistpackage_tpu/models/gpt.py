"""Flagship GPT-style model — the framework's end-to-end reference model,
playing the role of the reference's ``tensor_parallel/transformer.py`` test
model (transformer.py:88-100) scaled up to a *complete* LM: token + position
embeddings, a TP/SP block stack, final LN and LM head with cross-entropy.

TPU-first design decisions (vs the reference's torch modules):

- **Vocab-parallel embedding and LM head** (the Megatron pattern the reference
  never implements — its models start at the hidden layer): the token
  embedding is sharded over the vocab dim on the ``tensor`` axis; lookup masks
  out-of-shard ids and ``psum``-s partial one-hot gathers.  The LM head is
  column-parallel over vocab, and the cross-entropy is computed **on the
  sharded logits** (max/psum/log-sum-exp over the tensor axis) so full
  ``[B, S, V]`` logits are never materialized — the dominant activation of an
  LM trains at 1/tp of the memory.
- **Layer stack as a ``lax.scan`` over stacked params** ([L, ...] leaves) —
  one compiled block body regardless of depth; shard the leading dim over
  ``pipe`` for pipeline parallelism (see :func:`gpt_pipeline_loss`).
- One implementation serves serial, TP, TP+SP, and TP+SP+PP execution: the
  parallelism is carried entirely by ``axis=`` arguments and PartitionSpecs.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax

from jax.lax import axis_size
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.pipeline_parallel import pipeline_1f1b, pipeline_loss
from ..parallel.tensor_parallel import (
    RematMode,
    TransformerConfig,
    block_forward,
    block_param_specs,
    dense,
    scan_blocks,
    gather_from_sp,
    init_block_params,
    init_norm_params,
    layer_norm,
    norm_param_specs,
    split_to_sp,
)
from ..utils import profiling as prof

PyTree = Any


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int
    dim: int
    nheads: int
    nlayers: int
    max_seq: int
    ffn_mult: int = 4
    causal: bool = True
    dtype: Any = jnp.float32
    # 'naive' | 'flash' (Pallas kernel) | 'ring' | 'ulysses' (context
    # parallel — sequence sharded over ``context_axis``, see ops/ring_attention)
    attn_impl: str = "naive"
    context_axis: Optional[str] = None  # mesh axis for 'ring'/'ulysses'
    cp_layout: str = "contiguous"  # 'zigzag' balances causal ring FLOPs
    dropout_rate: float = 0.0  # residual dropout (needs a dropout_key)
    # grouped-query attention: KV head count (None = MHA, 1 = MQA);
    # see TransformerConfig.kv_heads
    kv_heads: Optional[int] = None
    # position encoding: 'learned' (table added at embed, the reference
    # style) | 'rope' (rotary: q/k rotated at their global positions inside
    # attention; no pos_emb table — see TransformerConfig.rope).  RoPE
    # composes with CP (chunk-offset/zigzag positions) and GQA.
    pos: str = "learned"
    rope_theta: float = 10000.0
    # optional 'linear'/'llama3' rope-scaling dict (long-context
    # checkpoints; see tensor_parallel.layers._scaled_inv_freq)
    rope_scaling: "dict | None" = None
    # 'layer' | 'rms' and 'gelu' | 'swiglu' — the Llama family is
    # norm='rms', act='swiglu', pos='rope' (see :func:`llama_config`);
    # both are carried structurally by the param tree
    # (TransformerConfig.norm/act), so every parallel path (TP/SP/PP/CP,
    # ZeRO, checkpointing) serves both families unchanged.
    norm: str = "layer"
    act: str = "gelu"
    # explicit FFN hidden width (overrides ffn_mult) — Llama-style ~8d/3
    # widths are not integer multiples of d
    ffn_hidden: Optional[int] = None
    # norm epsilon: preserved from HF checkpoints (rms_norm_eps is 1e-5 or
    # 1e-6 depending on the family) by models/convert.py
    norm_eps: float = 1e-5
    # sliding-window attention (Mistral family) — see
    # TransformerConfig.sliding_window
    sliding_window: Optional[int] = None
    # Mixture-of-Experts (0 = dense model).  With ``moe_experts > 0`` every
    # ``moe_every``-th block's FFN becomes an expert layer (Switch-style
    # alternation); use the gpt_moe_* family (models/gpt_moe.py) which
    # handles the heterogeneous block list and the aux load-balance loss.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    # 'topk' only for this family: GPT is autoregressive and
    # 'expert_choice' routing is non-causal (each expert ranks the whole
    # sequence -> future-token leak), so gpt_moe rejects it at trace time.
    # EC remains available through moe_forward(causal=False) for
    # encoder/non-AR models built from the same MoE layer.
    moe_router: str = "topk"
    moe_dispatch: str = "auto"  # 'dense' | 'sorted' | 'auto' (see MoEConfig)

    def __post_init__(self):
        from ..parallel.moe import check_moe_dispatch

        check_moe_dispatch(self.moe_dispatch)
        if self.context_axis is not None and self.attn_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"context_axis={self.context_axis!r} requires attn_impl "
                f"'ring' or 'ulysses' (got {self.attn_impl!r}): a chunk-local "
                f"attention with per-shard position offsets would be a "
                f"silently different model"
            )
        if self.cp_layout != "contiguous" and self.attn_impl != "ring":
            raise ValueError(
                f"cp_layout={self.cp_layout!r} applies to attn_impl='ring' "
                f"only (got {self.attn_impl!r})"
            )
        if self.pos not in ("learned", "rope"):
            raise ValueError(f"pos must be 'learned' or 'rope', got {self.pos!r}")

    @property
    def block(self) -> TransformerConfig:
        return TransformerConfig(
            dim=self.dim,
            nheads=self.nheads,
            nlayers=self.nlayers,
            ffn_mult=self.ffn_mult,
            causal=self.causal,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            context_axis=self.context_axis,
            cp_layout=self.cp_layout,
            dropout_rate=self.dropout_rate,
            kv_heads=self.kv_heads,
            rope=self.pos == "rope",
            rope_theta=self.rope_theta,
            rope_scaling=self.rope_scaling,
            norm=self.norm,
            act=self.act,
            ffn_hidden=self.ffn_hidden,
            norm_eps=self.norm_eps,
            sliding_window=self.sliding_window,
        )

    def num_params(self) -> int:
        D, V, L = self.dim, self.vocab_size, self.nlayers
        F = self.block.ffn_dim
        if self.kv_heads is not None and self.kv_heads != self.nheads:
            Dkv = self.kv_heads * (D // self.nheads)
            attn = (D * D + D) + (2 * D * Dkv + 2 * Dkv)  # wq/bq + wkv/bkv
        else:
            attn = 3 * D * D + 3 * D
        # swiglu stacks gate/up: one extra [D, F] + [F] vs the gelu MLP
        mlp = (3 * D * F + 2 * F + D) if self.act == "swiglu" else (2 * D * F + F + D)
        norm = D if self.norm == "rms" else 2 * D  # per norm site
        per_block = attn + D * D + D + mlp + 2 * norm
        pos = self.max_seq * D if self.pos == "learned" else 0
        return V * D + pos + L * per_block + norm + D * V


def llama_config(
    vocab_size: int,
    dim: int,
    nheads: int,
    nlayers: int,
    max_seq: int,
    kv_heads: Optional[int] = None,
    ffn_hidden: Optional[int] = None,
    rope_theta: float = 10000.0,
    rope_scaling: "dict | None" = None,
    dtype: Any = jnp.bfloat16,
    **kw,
) -> GPTConfig:
    """Llama-family preset: RMSNorm + SwiGLU + RoPE (+ GQA when ``kv_heads``
    is set) — the modern decoder recipe, composed entirely from existing
    framework levers, so every parallel path (TP/SP, PP incl. interleaved,
    CP ring/ulysses/zigzag, ZeRO/FSDP, remat incl. 'flash') serves it
    unchanged.  ``ffn_hidden`` defaults to the Llama width ceil(8d/3)
    rounded up to a multiple of 256 (TP- and MXU-friendly).

    One deliberate divergence: the framework keeps its (zero-initialized)
    bias leaves in attention/MLP where Llama is bias-free — structurally
    uniform with the GPT family, numerically inert at init."""
    if ffn_hidden is None:
        ffn_hidden = -(-8 * dim // 3)  # ceil
        ffn_hidden = -(-ffn_hidden // 256) * 256
    return GPTConfig(
        vocab_size=vocab_size,
        dim=dim,
        nheads=nheads,
        nlayers=nlayers,
        max_seq=max_seq,
        kv_heads=kv_heads,
        ffn_hidden=ffn_hidden,
        pos="rope",
        rope_theta=rope_theta,
        rope_scaling=rope_scaling,
        norm="rms",
        act="swiglu",
        dtype=dtype,
        **kw,
    )


# ------------------------------------------------------------------ embedding


def vocab_parallel_embed(
    tok_emb: jnp.ndarray, tokens: jnp.ndarray, axis: Optional[str] = None
) -> jnp.ndarray:
    """Token lookup from a vocab-sharded embedding table.

    ``tok_emb``: [V_local, D] (the local shard; V_local == V when serial).
    Out-of-shard ids contribute zeros; a ``psum`` over the tensor axis
    assembles the full embedding.  Backward is the transpose scatter-add into
    the local shard only — no gradient communication for the table."""
    if axis is None:
        return jnp.take(tok_emb, tokens, axis=0)
    v_loc = tok_emb.shape[0]
    offset = jax.lax.axis_index(axis) * v_loc
    local = tokens - offset
    valid = (local >= 0) & (local < v_loc)
    emb = jnp.take(tok_emb, jnp.where(valid, local, 0), axis=0)
    emb = jnp.where(valid[..., None], emb, jnp.zeros((), emb.dtype))
    return jax.lax.psum(emb, axis)


@prof.scoped(prof.LOSS)
def vocab_parallel_xent(
    logits: jnp.ndarray, targets: jnp.ndarray, axis: Optional[str] = None
) -> jnp.ndarray:
    """Mean token cross-entropy on vocab-sharded logits.

    ``logits``: [..., V_local]; ``targets``: int [...].  Log-sum-exp and the
    target-logit gather each close with one small collective over the tensor
    axis — the full softmax is never formed."""
    if axis is None:
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - tl)
    v_loc = logits.shape[-1]
    offset = jax.lax.axis_index(axis) * v_loc
    # the max shift is gradient-neutral (and pmax has no AD rule)
    m = jax.lax.pmax(jnp.max(jax.lax.stop_gradient(logits), axis=-1), axis)
    z = jax.lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axis)
    lse = jnp.log(z) + m
    local = targets - offset
    valid = (local >= 0) & (local < v_loc)
    tl = jnp.take_along_axis(logits, jnp.where(valid, local, 0)[..., None], axis=-1)[..., 0]
    tl = jax.lax.psum(jnp.where(valid, tl, jnp.zeros((), tl.dtype)), axis)
    return jnp.mean(lse - tl)


# -------------------------------------------------------------------- forward


@prof.scoped(prof.EMBED)
def gpt_embed(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    axis: Optional[str] = None,
    context_axis: Optional[str] = None,
    cp_layout: str = "contiguous",
):
    """[B, S] ids -> [B, S, D] hidden.  With ``context_axis`` the tokens are
    the context-LOCAL chunk [B, S/cp] and the position embedding follows the
    shard's global positions: contiguous (shard i owns
    [i*S_loc, (i+1)*S_loc)) or zigzag (chunks i and 2n-1-i — gather the
    owned rows)."""
    S = tokens.shape[-1]
    h = vocab_parallel_embed(params["tok_emb"], tokens, axis)
    if "pos_emb" not in params:  # rope: positions enter inside attention
        return h
    if context_axis is None:
        return h + params["pos_emb"][:S]
    if cp_layout == "zigzag":
        from ..ops.ring_attention import zigzag_positions

        n = axis_size(context_axis)
        pos, _ = zigzag_positions(jax.lax.axis_index(context_axis), S, n)
        return h + jnp.take(params["pos_emb"], pos, axis=0)
    off = jax.lax.axis_index(context_axis) * S
    return h + jax.lax.dynamic_slice_in_dim(params["pos_emb"], off, S, axis=0)


@prof.scoped(prof.HEAD)
def gpt_head(
    params: Dict[str, PyTree],
    h: jnp.ndarray,
    axis: Optional[str] = None,
    sp: bool = False,
    eps: float = 1e-5,
):
    """Final LN + column-parallel LM head.  Returns vocab-local logits
    [B, S, V_local] (full V when serial)."""
    h = layer_norm(h, params["ln_f"], eps)
    if axis is not None and sp:
        h = gather_from_sp(h, axis)
    return dense(h, params["head"])


def gpt_forward(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg: GPTConfig,
    axis: Optional[str] = None,
    sp: bool = False,
    remat: RematMode = False,
    dropout_key: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """tokens [B, S] -> logits [B, S, V_local].  Serial when ``axis`` is None,
    TP(/SP) inside shard_map otherwise.  ``remat`` checkpoints each block:
    False | True | 'flash' (save the flash kernel's residuals) |
    'flash_offload' (same, parked in pinned_host memory) — see
    :func:`..parallel.tensor_parallel.scan_blocks`.

    ``dropout_key`` enables residual dropout at ``cfg.dropout_rate``; under a
    mesh derive it with ``axis_unique_key(key, 'data')`` (utils/random.py) so
    data shards draw distinct masks while TP shards stay consistent.

    Context parallelism (``cfg.attn_impl`` 'ring'/'ulysses' +
    ``cfg.context_axis``): pass the context-LOCAL token chunk [B, S/cp]
    (in_spec ``P(None, context_axis)``); activations stay sequence-sharded
    end-to-end and only the attention op communicates over the context ring.
    The mean CE over local tokens then needs a ``pmean`` over the context
    axis, which the train step performs when the context axis is included in
    its data axes (the context axis IS a data axis for loss/grad purposes:
    equal shards make the global mean the mean of shard means)."""
    h = gpt_hidden(
        params, tokens, cfg, axis=axis, sp=sp, remat=remat,
        dropout_key=dropout_key,
    )
    return gpt_head(params, h, axis, sp, eps=cfg.norm_eps)


def gpt_hidden(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg: GPTConfig,
    axis: Optional[str] = None,
    sp: bool = False,
    remat: RematMode = False,
    dropout_key: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """tokens [B, S] -> post-blocks hidden [B, S(/tp if sp), D] — the shared
    embed + block-stack body of :func:`gpt_forward` and the streamed-CE path
    of :func:`gpt_loss` (one implementation, no drift)."""
    h = gpt_embed(params, tokens, axis, context_axis=cfg.context_axis, cp_layout=cfg.cp_layout)
    if axis is not None and sp:
        h = split_to_sp(h, axis)
    return scan_blocks(
        params["blocks"], h, cfg.block, axis, sp, remat=remat,
        dropout_key=dropout_key,
    )


@prof.scoped(prof.LOSS)
def streamed_head_loss(
    params: Dict[str, PyTree],
    h: jnp.ndarray,
    targets: jnp.ndarray,
    axis: Optional[str] = None,
    chunk: int = 256,
    eps: float = 1e-5,
) -> jnp.ndarray:
    """Head + CE scanned over SEQUENCE chunks: the [B, S, V] logits are never
    materialized — each scan step computes one [B, chunk, V] slab, reduces it
    to its lse/target-logit, and discards it.  The serial/DP-mode analogue of
    the vocab-parallel CE's memory win (for GPT-125M at S=2048, V=32k the
    full logits are ~2 GB of HBM traffic per step).  Equal chunks, so the
    mean of chunk means is the token mean.  ``h``: post-blocks hidden
    [B, S, D] (pre final-LN)."""
    h = layer_norm(h, params["ln_f"], eps)
    B, S, D = h.shape
    if S % chunk != 0:
        raise ValueError(
            f"sequence length {S} not divisible by xent_chunk {chunk} — "
            f"the fallback would materialize the full logits the caller "
            f"opted out of"
        )
    n = S // chunk
    hc = h.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)  # [n, B, chunk, D]
    tc = targets.reshape(B, n, chunk).transpose(1, 0, 2)

    # checkpoint the body: without it, AD stacks each slab's softmax
    # residuals to O(B*S*V) — exactly the memory this function avoids
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(acc, xt):
        hh, tt = xt
        return acc + vocab_parallel_xent(dense(hh, params["head"]), tt, axis), None

    # the carry must be closed over the body's varying axes (DESIGN.md §2):
    # under a DP mesh h/targets are data-varying, so the accumulator is too
    from ..parallel.data_parallel import _mark_varying, _vma

    acc0 = _mark_varying(
        jnp.zeros((), jnp.float32), tuple(_vma(h) | _vma(targets))
    )
    total, _ = jax.lax.scan(body, acc0, (hc, tc))
    return total / n


def gpt_loss(
    params: Dict[str, PyTree],
    batch: Dict[str, jnp.ndarray],
    cfg: GPTConfig,
    axis: Optional[str] = None,
    sp: bool = False,
    remat: RematMode = False,
    dropout_key: Optional[jax.Array] = None,
    xent_chunk: Optional[int] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy.  ``batch``: {'tokens': [B, S],
    'targets': [B, S]}.  ``xent_chunk`` streams the head+CE over sequence
    chunks of that size instead of materializing full logits
    (:func:`streamed_head_loss`)."""
    if xent_chunk is not None:
        h = gpt_hidden(
            params, batch["tokens"], cfg, axis=axis, sp=sp, remat=remat,
            dropout_key=dropout_key,
        )
        if axis is not None and sp:
            h = gather_from_sp(h, axis)
        return streamed_head_loss(
            params, h, batch["targets"], axis, chunk=xent_chunk,
            eps=cfg.norm_eps,
        )
    logits = gpt_forward(
        params, batch["tokens"], cfg, axis=axis, sp=sp, remat=remat,
        dropout_key=dropout_key,
    )
    return vocab_parallel_xent(logits, batch["targets"], axis)


# ------------------------------------------------------------------- pipeline


def gpt_pipeline_loss(
    params: Dict[str, PyTree],
    batch: Dict[str, jnp.ndarray],
    cfg: GPTConfig,
    num_microbatches: int,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    sp: bool = False,
    remat: RematMode = True,
) -> jnp.ndarray:
    """Pipelined GPT loss (traced; call inside shard_map over a mesh with the
    ``pipe`` axis, optionally + ``tensor``/``data``).

    ``batch``: {'tokens': [M, mbs, S], 'targets': [M, mbs, S]} microbatched on
    the leading dim.  The embedding runs PER TICK inside the pipeline scan on
    stage 0 (its grad arrives via the shard_map transpose psum over ``pipe``,
    the analogue of tied-embedding grad sync), so only the raw int tokens —
    never M pre-embedded activations — stay resident; the block stack is the
    pipelined region (each stage scans its slab of the layer-stacked params);
    LN + head + vocab-parallel CE run in the last stage's per-microbatch
    loss."""
    M = num_microbatches
    tokens, targets = batch["tokens"], batch["targets"]

    def first_fn(p, toks):
        h = gpt_embed(p, toks, tp_axis, context_axis=cfg.context_axis, cp_layout=cfg.cp_layout)
        if tp_axis is not None and sp:
            h = split_to_sp(h, tp_axis)
        return h

    def stage_fn(stacked, x):
        return scan_blocks(stacked, x, cfg.block, tp_axis, sp)

    def mb_loss(y, tgt):
        logits = gpt_head(params, y, tp_axis, sp, eps=cfg.norm_eps)
        return vocab_parallel_xent(logits, tgt, tp_axis)

    return pipeline_loss(
        params["blocks"],
        tokens,
        targets,
        stage_fn=stage_fn,
        loss_fn=mb_loss,
        num_microbatches=M,
        pipe_axis=pipe_axis,
        remat=remat,
        first_fn=first_fn,
        params=params,
    )


def interleave_stage_params(
    params: Dict[str, PyTree], num_chunks: int, pipe_size: int
) -> Dict[str, PyTree]:
    """Reshape the ``[L, ...]``-stacked block leaves into the interleaved
    pipeline layout ``[V, P, L/(P*V), ...]``: chunk v of stage s holds global
    layer slab ``v*P + s`` (round-robin — exactly the reshape's index
    decomposition, v major).  Shard dim 1 over the pipe axis
    (:func:`gpt_interleaved_param_specs`)."""

    def r(a):
        L = a.shape[0]
        if L % (num_chunks * pipe_size) != 0:
            raise ValueError(
                f"nlayers {L} not divisible by num_chunks*pipe "
                f"({num_chunks}*{pipe_size})"
            )
        return a.reshape(
            num_chunks, pipe_size, L // (num_chunks * pipe_size), *a.shape[1:]
        )

    return {**params, "blocks": jax.tree.map(r, params["blocks"])}


def deinterleave_stage_params(
    params: Dict[str, PyTree], num_chunks: int, pipe_size: int
) -> Dict[str, PyTree]:
    """Inverse of :func:`interleave_stage_params`: ``[V, P, Lc, ...]`` block
    leaves back to the ``[L, ...]`` stacked layout (serial layer order).
    Lets a checkpoint written from interleaved training resume classic
    pipelined (or serial) training and vice versa — the layouts are pure
    reshapes of each other."""

    def r(a):
        if a.shape[:2] != (num_chunks, pipe_size):
            raise ValueError(
                f"leaf leading dims {a.shape[:2]} != (V={num_chunks}, "
                f"P={pipe_size}) — not an interleaved layout"
            )
        return a.reshape(num_chunks * pipe_size * a.shape[2], *a.shape[3:])

    return {**params, "blocks": jax.tree.map(r, params["blocks"])}


def gpt_interleaved_param_specs(
    cfg: GPTConfig,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
) -> Dict[str, PyTree]:
    """Specs for the :func:`interleave_stage_params` layout: block leaves are
    ``[V, P, Lc, ...]`` with dim 1 (the stage dim) sharded over ``pipe``."""
    base = gpt_param_specs(cfg, tp_axis=tp_axis, pipe_axis=None)
    blocks = jax.tree.map(
        # [L, ...] spec (None, *dims) -> [V, P, Lc, ...] spec
        lambda s: P(None, pipe_axis, None, *tuple(s)[1:]),
        base["blocks"],
        is_leaf=lambda x: isinstance(x, P),
    )
    return {**base, "blocks": blocks}


def gpt_pipeline_1f1b(
    params: Dict[str, PyTree],
    batch: Dict[str, jnp.ndarray],
    cfg: GPTConfig,
    num_microbatches: int,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    sp: bool = False,
    remat: RematMode = True,
    dropout_key: Optional[jax.Array] = None,
    num_chunks: int = 1,
    shard_transfers: Optional[bool] = None,
):
    """1F1B-scheduled GPT training step core: returns ``(loss, grads)``
    directly (do NOT wrap in ``jax.grad`` — see
    :func:`...pipeline_parallel.pipeline_1f1b`).  Peak live activations are
    O(pipe_size), independent of the microbatch count, matching the
    reference's steady-state interleave
    (pipeline_parallel/pipeline_sched.py:163-211).

    Stage ownership: stage 0 embeds (per tick), the last stage runs LN + head
    + vocab-parallel CE inside its backward unit; embed/head grads are
    psum-ed over ``pipe`` once at the end.

    ``batch``: {'tokens': [M, mbs, S], 'targets': [M, mbs, S]}.

    ``dropout_key`` enables residual dropout through the pipeline: the key is
    folded with the stage index and the microbatch index (the schedule hands
    ``stage_fn`` the latter via ``stage_takes_mb``), and scan_blocks folds
    the local layer index — so every (stage, microbatch, layer) draws a
    distinct mask, and the 1F1B backward's recompute replays the exact same
    chain deterministically.  Derive the key per the usual recipe
    (``axis_unique_key(key, 'data')``) so data shards differ too.

    ``num_chunks`` (V > 1) runs the INTERLEAVED schedule (virtual pipeline
    stages — see ``pipeline_1f1b``): pass params in the
    :func:`interleave_stage_params` layout with
    :func:`gpt_interleaved_param_specs`; requires ``M % pipe == 0``.

    ``shard_transfers`` (default: auto — on exactly when ``tp_axis`` is set
    and ``sp`` is off): carry the inter-stage activation sliced 1/tp over
    the tensor axis (``pipeline_1f1b(transfer_shard_axis=...)``, the
    ``scatter_gather_tensors`` analogue, comm.py:108-155) — pipe-edge bytes
    and ring-buffer memory drop by tp.  Under SP the state is already
    sequence-sharded, so there is nothing to slice.
    """
    if shard_transfers is None:
        shard_transfers = tp_axis is not None and not sp
    transfer_shard_axis = tp_axis if shard_transfers else None

    def first_fn(p, toks):
        h = gpt_embed(p, toks, tp_axis, context_axis=cfg.context_axis, cp_layout=cfg.cp_layout)
        if tp_axis is not None and sp:
            h = split_to_sp(h, tp_axis)
        return h

    def fold_key(m, extra):
        k = None
        if dropout_key is not None and cfg.dropout_rate > 0.0:
            k = jax.random.fold_in(dropout_key, jax.lax.axis_index(pipe_axis))
            k = jax.random.fold_in(k, m)
            if extra is not None:
                k = jax.random.fold_in(k, extra)
        return k

    if num_chunks == 1:

        def stage_fn(p, x, m):
            return scan_blocks(
                p["blocks"], x, cfg.block, tp_axis, sp, remat=remat,
                dropout_key=fold_key(m, None),
            )

    else:

        def stage_fn(p, x, m, v):
            # local leaves are [V, 1, Lc, ...]; select chunk v's slab
            slab = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, v, axis=0, keepdims=False
                )[0],
                p["blocks"],
            )
            return scan_blocks(
                slab, x, cfg.block, tp_axis, sp, remat=remat,
                dropout_key=fold_key(m, v),
            )

    def last_fn(p, y, tgt):
        logits = gpt_head(p, y, tp_axis, sp, eps=cfg.norm_eps)
        return vocab_parallel_xent(logits, tgt, tp_axis)

    return pipeline_1f1b(
        params,
        batch["tokens"],
        batch["targets"],
        first_fn=first_fn,
        stage_fn=stage_fn,
        last_fn=last_fn,
        num_microbatches=num_microbatches,
        pipe_axis=pipe_axis,
        stage_takes_mb=True,
        num_chunks=num_chunks,
        transfer_shard_axis=transfer_shard_axis,
    )


def gpt_pipeline_zb(
    params: Dict[str, PyTree],
    batch: Dict[str, jnp.ndarray],
    cfg: GPTConfig,
    num_microbatches: int,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    sp: bool = False,
    remat: RematMode = True,
    dropout_key: Optional[jax.Array] = None,
    shard_transfers: Optional[bool] = None,
):
    """Zero-bubble GPT training step core: the :func:`gpt_pipeline_1f1b`
    contract (returns ``(loss, grads)`` directly) on the
    :func:`...pipeline_parallel.pipeline_zb_1f1b` schedule — backward
    split into a dgrad wavefront plus an M-tick wgrad drain; same stage
    ownership (stage 0 embeds, last stage runs LN + head + vocab-parallel
    CE), same dropout-key recipe (the key folds (stage, microbatch), so
    the dgrad AND wgrad recomputes replay identical masks).  No
    interleaved (``num_chunks``) variant; ``shard_transfers`` defaults on
    exactly when ``tp_axis`` is set and ``sp`` is off, as in the classic
    schedule."""
    from ..parallel.pipeline_parallel import pipeline_zb_1f1b

    if shard_transfers is None:
        shard_transfers = tp_axis is not None and not sp

    def first_fn(p, toks):
        h = gpt_embed(p, toks, tp_axis, context_axis=cfg.context_axis,
                      cp_layout=cfg.cp_layout)
        if tp_axis is not None and sp:
            h = split_to_sp(h, tp_axis)
        return h

    def stage_fn(p, x, m):
        k = None
        if dropout_key is not None and cfg.dropout_rate > 0.0:
            k = jax.random.fold_in(
                dropout_key, jax.lax.axis_index(pipe_axis))
            k = jax.random.fold_in(k, m)
        return scan_blocks(
            p["blocks"], x, cfg.block, tp_axis, sp, remat=remat,
            dropout_key=k,
        )

    def last_fn(p, y, tgt):
        logits = gpt_head(p, y, tp_axis, sp, eps=cfg.norm_eps)
        return vocab_parallel_xent(logits, tgt, tp_axis)

    return pipeline_zb_1f1b(
        params,
        batch["tokens"],
        batch["targets"],
        first_fn=first_fn,
        stage_fn=stage_fn,
        last_fn=last_fn,
        num_microbatches=num_microbatches,
        pipe_axis=pipe_axis,
        stage_takes_mb=True,
        transfer_shard_axis=tp_axis if shard_transfers else None,
    )


# ----------------------------------------------------------------- init/specs


def init_gpt_params(key, cfg: GPTConfig) -> Dict[str, PyTree]:
    ke, kp, kh, kb = jax.random.split(key, 4)
    D, V, S = cfg.dim, cfg.vocab_size, cfg.max_seq
    dt = cfg.dtype
    keys = jax.random.split(kb, cfg.nlayers)
    blocks = [init_block_params(k, cfg.block) for k in keys]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *blocks)
    out = {
        "tok_emb": (jax.random.normal(ke, (V, D)) * 0.02).astype(dt),
        "blocks": stacked,
        "ln_f": init_norm_params(D, dt, cfg.norm),
        "head": (jax.random.normal(kh, (D, V)) * (1.0 / math.sqrt(D))).astype(dt),
    }
    if cfg.pos == "learned":  # rope models carry no position table
        out["pos_emb"] = (jax.random.normal(kp, (S, D)) * 0.02).astype(dt)
    return out


def gpt_param_specs(
    cfg: GPTConfig,
    tp_axis: Optional[str] = None,
    pipe_axis: Optional[str] = None,
) -> Dict[str, PyTree]:
    """PartitionSpec tree: vocab-sharded embedding/head over ``tp_axis``,
    block stack sharded over ``pipe_axis`` on the layer dim composed with the
    per-block TP specs."""
    from ..parallel.tensor_parallel import stacked_block_specs

    blocks = stacked_block_specs(
        tp_axis, stack_axis=pipe_axis, gqa=cfg.block.is_gqa,
        norm=cfg.norm, act=cfg.act)
    out = {
        "tok_emb": P(tp_axis, None) if tp_axis else P(),
        "blocks": blocks,
        "ln_f": norm_param_specs(cfg.norm),
        "head": P(None, tp_axis) if tp_axis else P(),
    }
    if cfg.pos == "learned":
        out["pos_emb"] = P()
    return out
