"""Vision Transformer — the vision model family the reference exercises its
DP/ZeRO paths with (``examples/test_ddp.py:74-86`` uses timm resnet50;
``examples/test_zero_optim.py:88`` notes timm ViT).  Instead of wrapping an
external torch model, the ViT is built from the same TP/SP transformer blocks
as the GPT flagship, so every parallel strategy (DP, TP+SP, ZeRO, FSDP, EMA)
applies to a vision workload unchanged.

TPU notes: patchify is one reshape+matmul (a conv with stride=patch is
exactly a [P*P*C, D] matmul on unfolded patches — MXU-friendly, no conv
lowering needed); non-causal attention; mean-pool head (no CLS token keeps
shapes static and pooling free).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax

from jax.lax import axis_size
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.tensor_parallel import (
    RematMode,
    TransformerConfig,
    block_forward,
    init_block_params,
    init_norm_params,
    layer_norm,
    norm_param_specs,
    stacked_block_specs,
)

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    num_classes: int = 1000
    dim: int = 384
    nheads: int = 6
    nlayers: int = 12
    ffn_mult: int = 4
    dtype: Any = jnp.float32
    # 'naive' | 'flash' | 'ring' | 'ulysses' — ring/ulysses run non-causal
    # context parallelism over ``context_axis`` (patch tokens sharded)
    attn_impl: str = "naive"
    context_axis: Optional[str] = None
    dropout_rate: float = 0.0  # residual dropout (needs a dropout_key)
    # MoE knobs (models/vit_moe.py, V-MoE style): >0 experts turns every
    # moe_every-th block's FFN into the expert layer.  ViT is an ENCODER
    # (causal=False), so — unlike GPT-MoE — the 'expert_choice' router is
    # allowed here: the Zhou et al. setting, balanced by construction.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    moe_router: str = "topk"  # 'topk' | 'expert_choice' (encoder: both ok)
    moe_dispatch: str = "auto"  # 'dense' | 'sorted' | 'auto' (see MoEConfig)
    # 'layer' | 'rms' and 'gelu' | 'swiglu' — same structural dispatch as
    # the GPT family (tensor_parallel/layers.py)
    norm: str = "layer"
    act: str = "gelu"

    def __post_init__(self):
        if self.context_axis is not None and self.attn_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"context_axis={self.context_axis!r} requires attn_impl "
                f"'ring' or 'ulysses' (got {self.attn_impl!r})"
            )

    @property
    def num_patches(self) -> int:
        assert self.image_size % self.patch_size == 0
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels

    @property
    def block(self) -> TransformerConfig:
        return TransformerConfig(
            dim=self.dim, nheads=self.nheads, nlayers=self.nlayers,
            ffn_mult=self.ffn_mult, causal=False, dtype=self.dtype,
            attn_impl=self.attn_impl, context_axis=self.context_axis,
            dropout_rate=self.dropout_rate, norm=self.norm, act=self.act,
        )


def patchify(images: jnp.ndarray, patch: int) -> jnp.ndarray:
    """[B, H, W, C] -> [B, N, P*P*C] non-overlapping patches (pure reshape /
    transpose — XLA fuses it into the following matmul's operand load)."""
    B, H, W, C = images.shape
    gh, gw = H // patch, W // patch
    x = images.reshape(B, gh, patch, gw, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, gh * gw, patch * patch * C)


def init_vit_params(key, cfg: ViTConfig) -> Dict[str, PyTree]:
    kp, kpos, kh, kb = jax.random.split(key, 4)
    dt = cfg.dtype
    keys = jax.random.split(kb, cfg.nlayers)
    blocks = [init_block_params(k, cfg.block) for k in keys]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *blocks)
    return {
        "patch_proj": {
            "w": (jax.random.normal(kp, (cfg.patch_dim, cfg.dim))
                  / math.sqrt(cfg.patch_dim)).astype(dt),
            "b": jnp.zeros((cfg.dim,), dt),
        },
        "pos_emb": (jax.random.normal(kpos, (cfg.num_patches, cfg.dim)) * 0.02).astype(dt),
        "blocks": stacked,
        "ln_f": init_norm_params(cfg.dim, dt, cfg.norm),
        "head": {
            "w": (jax.random.normal(kh, (cfg.dim, cfg.num_classes))
                  / math.sqrt(cfg.dim)).astype(dt),
            "b": jnp.zeros((cfg.num_classes,), dt),
        },
    }


def vit_embed(
    params: Dict[str, PyTree],
    images: jnp.ndarray,
    cfg: ViTConfig,
) -> jnp.ndarray:
    """[B, H, W, C] images -> [B, N(/cp), D] patch embedding — shared by
    :func:`vit_forward` and the pipeline's stage-0 ``first_fn`` (one
    implementation, no drift)."""
    x = patchify(images.astype(cfg.dtype), cfg.patch_size)
    cp = cfg.context_axis if cfg.attn_impl in ("ring", "ulysses") else None
    if cp is not None:
        # context parallelism: slice the LOCAL patch chunk before the
        # projection so the [B, S, D] embed activation and its matmul are
        # O(S/cp) per device (patchify itself is a free reshape); the
        # (non-causal) ring/all_to_all inside the blocks sees the rest
        n_cp = axis_size(cp)
        if x.shape[1] % n_cp != 0:
            raise ValueError(
                f"num_patches {x.shape[1]} not divisible by context-parallel "
                f"size {n_cp} — trailing patches would be silently dropped"
            )
        s_loc = x.shape[1] // n_cp
        off = jax.lax.axis_index(cp) * s_loc
        x = jax.lax.dynamic_slice_in_dim(x, off, s_loc, axis=1)
        h = x @ params["patch_proj"]["w"] + params["patch_proj"]["b"]
        return h + jax.lax.dynamic_slice_in_dim(params["pos_emb"], off, s_loc, axis=0)
    h = x @ params["patch_proj"]["w"] + params["patch_proj"]["b"]
    return h + params["pos_emb"]


def vit_pool_logits(
    params: Dict[str, PyTree],
    h: jnp.ndarray,
    cfg: ViTConfig,
    axis: Optional[str] = None,
    sp: bool = False,
) -> jnp.ndarray:
    """Post-blocks hidden -> [B, num_classes(/tp)] logits (SP gather, final
    LN, patch mean-pool with the CP mean-of-means, class head) — shared by
    :func:`vit_forward` and the pipeline's last stage."""
    if axis is not None and sp:
        from ..parallel.tensor_parallel import gather_from_sp

        h = gather_from_sp(h, axis)
    h = layer_norm(h, params["ln_f"])
    pooled = jnp.mean(h, axis=1)  # mean-pool over (local) patches
    cp = cfg.context_axis if cfg.attn_impl in ("ring", "ulysses") else None
    if cp is not None:
        pooled = jax.lax.pmean(pooled, cp)  # equal chunks: mean of means
    return pooled @ params["head"]["w"] + params["head"]["b"]


def vit_forward(
    params: Dict[str, PyTree],
    images: jnp.ndarray,
    cfg: ViTConfig,
    axis: Optional[str] = None,
    sp: bool = False,
    remat: RematMode = False,
    dropout_key = None,
) -> jnp.ndarray:
    """[B, H, W, C] images -> [B, num_classes] logits.  TP(/SP) over ``axis``
    inside shard_map, serial when None — same contract as gpt_forward."""
    from ..parallel.tensor_parallel import scan_blocks

    h = vit_embed(params, images, cfg)
    if axis is not None and sp:
        from ..parallel.tensor_parallel import split_to_sp

        h = split_to_sp(h, axis)
    h = scan_blocks(params["blocks"], h, cfg.block, axis, sp, remat=remat,
                    dropout_key=dropout_key)
    return vit_pool_logits(params, h, cfg, axis=axis, sp=sp)


def vit_loss(
    params: Dict[str, PyTree],
    batch: Dict[str, jnp.ndarray],
    cfg: ViTConfig,
    axis: Optional[str] = None,
    sp: bool = False,
    remat: RematMode = False,
    dropout_key = None,
) -> jnp.ndarray:
    """Mean softmax cross-entropy.  ``batch``: {'images': [B,H,W,C],
    'labels': int [B]}.  Under TP the class dim of the head is sharded and
    the CE closes with the same collectives as the GPT vocab-parallel CE."""
    from .gpt import vocab_parallel_xent

    logits = vit_forward(params, batch["images"], cfg, axis=axis, sp=sp,
                         remat=remat, dropout_key=dropout_key)
    # static shape tells whether the head was class-sharded: a local shard is
    # narrower than num_classes (shapes are trace-time constants under XLA)
    tp = axis if logits.shape[-1] != cfg.num_classes else None
    return vocab_parallel_xent(logits, batch["labels"], tp)


def vit_param_specs(
    cfg: ViTConfig,
    tp_axis: Optional[str] = None,
    pipe_axis: Optional[str] = None,
) -> Dict[str, PyTree]:
    """PartitionSpec tree matching :func:`init_vit_params`: per-block TP specs
    with a leading stack-dim entry (``pipe_axis`` shards the stack for
    pipelining, None replicates it); class-sharded head when the class count
    divides the TP size (else keep the head replicated by passing specs with
    ``head`` overridden to P())."""
    blocks = stacked_block_specs(
        tp_axis, stack_axis=pipe_axis, norm=cfg.norm, act=cfg.act)
    head_w = P(None, tp_axis) if tp_axis else P()
    head_b = P(tp_axis) if tp_axis else P()
    return {
        "patch_proj": {"w": P(), "b": P()},
        "pos_emb": P(),
        "blocks": blocks,
        "ln_f": norm_param_specs(cfg.norm),
        "head": {"w": head_w, "b": head_b},
    }


def vit_pipeline_1f1b(
    params: Dict[str, PyTree],
    batch: Dict[str, jnp.ndarray],
    cfg: ViTConfig,
    num_microbatches: int,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    sp: bool = False,
    remat: RematMode = True,
    dropout_key: Optional[jax.Array] = None,
):
    """1F1B-scheduled ViT training core: returns ``(loss, grads)`` (see
    ``parallel.pipeline_parallel.pipeline_1f1b``).  The reference's PP
    example pipelines a VISION classifier
    (examples/model_parallel/test_pipeline.py:54-123, DummyClsDataset) — this
    is that capability on the native ViT: stage 0 embeds
    (:func:`vit_embed`), the block stack is the pipelined region, the last
    stage pools + classifies (:func:`vit_pool_logits`).

    ``batch``: {'images': [M, mbs, H, W, C], 'labels': int [M, mbs]}.
    Params use :func:`vit_param_specs` with ``pipe_axis`` set.
    ``dropout_key`` threads residual dropout through the pipeline with
    per-(stage, microbatch, layer) masks, same recipe as
    ``gpt_pipeline_1f1b``."""
    from ..parallel.pipeline_parallel import pipeline_1f1b
    from ..parallel.tensor_parallel import scan_blocks, split_to_sp
    from .gpt import vocab_parallel_xent

    # CP composition note: unlike the GPT CE (a mean over context-LOCAL
    # tokens, which makes the context axis a plain data axis), the ViT loss
    # pmean-pools patches over the context axis INSIDE the model, so
    # context must be treated as a MODEL axis by the train step:
    #   DataParallel(mesh, axis='data')      # context NOT in the data axes
    # Params then stay context-invariant-typed and shard_map AD resolves
    # each leaf on its own — pre-pool leaves get the automatic
    # transpose-psum of their per-rank SHARES, the post-pool class head
    # keeps its single full grad.  (An axis-wide sum would double-count the
    # head; an axis-wide mean would halve the shares.)  Golden-tested in
    # tests/test_vit.py::test_vit_1f1b_with_cp_matches_serial.

    def first_fn(p, images):
        h = vit_embed(p, images, cfg)
        if tp_axis is not None and sp:
            h = split_to_sp(h, tp_axis)
        return h

    def stage_fn(p, x, m):
        k = None
        if dropout_key is not None and cfg.dropout_rate > 0.0:
            k = jax.random.fold_in(dropout_key, jax.lax.axis_index(pipe_axis))
            k = jax.random.fold_in(k, m)
        return scan_blocks(
            p["blocks"], x, cfg.block, tp_axis, sp, remat=remat, dropout_key=k
        )

    def last_fn(p, y, labels):
        logits = vit_pool_logits(p, y, cfg, axis=tp_axis, sp=sp)
        tp = tp_axis if logits.shape[-1] != cfg.num_classes else None
        return vocab_parallel_xent(logits, labels, tp)

    return pipeline_1f1b(
        params,
        batch["images"],
        batch["labels"],
        first_fn=first_fn,
        stage_fn=stage_fn,
        last_fn=last_fn,
        num_microbatches=num_microbatches,
        pipe_axis=pipe_axis,
        stage_takes_mb=True,
    )
