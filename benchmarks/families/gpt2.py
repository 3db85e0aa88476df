"""GPT-2 architecture keys (``n_embd``, ``n_head``, ...) -> the benchmark's
``Arch`` and the program's ``GPTConfig``."""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.arch import Arch


def arch(cfg: Dict[str, Any], max_seq: int) -> Arch:
    if max_seq > cfg["n_positions"]:
        raise ValueError(f"{max_seq} positions asked of a model with "
                         f"{cfg['n_positions']} learned ones")
    return Arch(
        dim=cfg["n_embd"], heads=cfg["n_head"], kv_heads=cfg["n_head"],
        head_dim=cfg["n_embd"] // cfg["n_head"], layers=cfg["n_layer"],
        ffn=cfg["n_inner"], vocab=cfg["vocab_size"],
        max_pos=cfg["n_positions"], norm="layer", act="gelu_tanh",
        pos="learned", eps=cfg["layer_norm_epsilon"])


def program_config(cfg: Dict[str, Any], max_seq: int):
    import jax.numpy as jnp

    from torchdistpackage_tpu.models import GPTConfig

    a = arch(cfg, max_seq)
    # max_seq sizes the learned position table: always the published one
    return GPTConfig(
        vocab_size=a.vocab, dim=a.dim, nheads=a.heads, nlayers=a.layers,
        max_seq=a.max_pos, ffn_hidden=a.ffn, dtype=jnp.bfloat16,
        attn_impl="flash", norm="layer", act="gelu", pos="learned",
        norm_eps=a.eps)
