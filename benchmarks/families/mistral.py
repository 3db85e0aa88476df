"""Mistral architecture keys (``hidden_size``, ``num_key_value_heads``,
``sliding_window``, ...) -> the benchmark's ``Arch`` and the program's
``GPTConfig``."""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.arch import Arch


def arch(cfg: Dict[str, Any], max_seq: int) -> Arch:
    if max_seq > cfg["max_position_embeddings"]:
        raise ValueError(f"{max_seq} positions asked of a model published "
                         f"for {cfg['max_position_embeddings']}")
    return Arch(
        dim=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layers=cfg["num_hidden_layers"], ffn=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], max_pos=max_seq, norm="rms", act="swiglu",
        pos="rope", eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        window=cfg["sliding_window"])


def program_config(cfg: Dict[str, Any], max_seq: int):
    import jax.numpy as jnp

    from torchdistpackage_tpu.models import GPTConfig

    a = arch(cfg, max_seq)
    if a.head_dim * a.heads != a.dim:
        raise ValueError("the program derives head_dim as dim // heads")
    return GPTConfig(
        vocab_size=a.vocab, dim=a.dim, nheads=a.heads, nlayers=a.layers,
        max_seq=max_seq, kv_heads=a.kv_heads, ffn_hidden=a.ffn,
        dtype=jnp.bfloat16, attn_impl="flash", norm="rms", act="swiglu",
        pos="rope", rope_theta=a.rope_theta, norm_eps=a.eps,
        sliding_window=a.window)
