"""``nemotron_h`` architecture keys (``hybrid_override_pattern``,
``mamba_num_heads``, ``n_routed_experts``, ...) -> the benchmark's ``Shape``
of the stack (benchmarks/reference/nemotron_h.py) and the program's
``HybridConfig``; and everything else a runner asks a family that is not a
dense transformer for: seeded weights, reference logits, costs.

``n_routed_experts`` in the configuration file counts the experts HELD here
(the model-configs guide's reading); the router's width is
``published.n_routed_experts`` and the held range starts at
``deployment_share.first_expert``."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.reference.nemotron_h import Shape


def shape(cfg: Dict[str, Any], max_seq: int) -> Shape:
    if max_seq > cfg["max_position_embeddings"]:
        raise ValueError(f"{max_seq} positions asked of a model published "
                         f"for {cfg['max_position_embeddings']}")
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("group-limited routing is not written")
    routed = cfg.get("published", {}).get(
        "n_routed_experts", cfg["n_routed_experts"])
    return Shape(
        dim=cfg["hidden_size"], pattern=pattern, vocab=cfg["vocab_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], m_heads=cfg["mamba_num_heads"],
        m_head_dim=cfg["mamba_head_dim"], state=cfg["ssm_state_size"],
        groups=cfg["n_groups"], conv_kernel=cfg["conv_kernel"],
        experts=routed,
        held_first=cfg.get("deployment_share", {}).get("first_expert", 0),
        held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        latent=cfg["moe_latent_size"], moe_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        eps=cfg["layer_norm_epsilon"])


def program_config(cfg: Dict[str, Any], max_seq: int):
    import jax.numpy as jnp

    from torchdistpackage_tpu.models import HybridConfig

    s = shape(cfg, max_seq)
    if s.head_dim * s.heads != s.dim:
        raise ValueError("the program derives head_dim as dim // heads")
    return HybridConfig(
        vocab_size=s.vocab, dim=s.dim, pattern=s.pattern, max_seq=max_seq,
        nheads=s.heads, kv_heads=s.kv_heads, mamba_heads=s.m_heads,
        mamba_head_dim=s.m_head_dim, ssm_state=s.state, ssm_groups=s.groups,
        conv_kernel=s.conv_kernel, ssm_chunk=cfg["chunk_size"],
        moe_experts=s.experts, moe_held=(s.held_first, s.held),
        moe_top_k=s.top_k, moe_latent=s.latent, moe_ffn=s.moe_ffn,
        moe_shared_ffn=s.shared_ffn, moe_routed_scale=s.routed_scale,
        norm_eps=s.eps, dtype=jnp.bfloat16, state_dtype=jnp.float32)


def make_weights(s: Shape, seed: int):
    from benchmarks.weights_nemotron_h import make_weights as make

    return make(s, seed)


def reference_following(params, tokens, s: Shape,
                        quant: Optional[str] = None, follow=None):
    """``{logits, routing, deficit}`` of one sequence, the reference taking
    the experts ``follow`` names (its own where None)."""
    from benchmarks.reference.nemotron_h import forward_following

    return forward_following(params, tokens, s, quant, follow)


# -------------------------------------------------------------------- sizes


def layer_params(s: Shape) -> Dict[str, int]:
    """Parameters of one layer of each kind, norms included; an ``E``
    layer split into what every chip holds and one routed expert."""
    D = s.dim
    dkv = s.kv_heads * s.head_dim
    return {
        "M": (D + D * (s.d_inner + s.conv_channels + s.m_heads)
              + s.conv_channels * (s.conv_kernel + 1) + 3 * s.m_heads
              + s.d_inner + s.d_inner * D),
        "*": D + 2 * D * s.heads * s.head_dim + 2 * D * dkv,
        "E": (D + D * s.experts + s.experts + 2 * D * s.latent
              + 2 * D * s.shared_ffn),
        "expert": 2 * s.latent * s.moe_ffn,
    }


def num_params(s: Shape) -> int:
    """Parameters as run: the held experts only, both vocabulary tables."""
    n = layer_params(s)
    per = {"M": n["M"], "*": n["*"], "E": n["E"] + s.held * n["expert"]}
    return (sum(per[k] for k in s.pattern) + 2 * s.vocab * s.dim + s.dim)


def state_bytes_per_slot(s: Shape, state_itemsize: int = 4,
                         itemsize: int = 2) -> int:
    """One sequence's recurrent state over the Mamba layers."""
    one = (s.m_heads * s.m_head_dim * s.state * state_itemsize
           + (s.conv_kernel - 1) * s.conv_channels * itemsize)
    return s.pattern.count("M") * one


# -------------------------------------------------------------------- costs


def paged_decode(s: Shape, live_tokens: float, slots: float,
                 itemsize: int = 2) -> Dict[str, float]:
    """One attention layer's paged decode call: every live KV position is
    read once, one query row a slot (benchmarks/costs.py's convention)."""
    kv = 2.0 * live_tokens * s.kv_heads * s.head_dim * itemsize
    qo = 2.0 * slots * s.heads * s.head_dim * itemsize
    return {"flops": 4.0 * live_tokens * s.heads * s.head_dim,
            "bytes": kv + qo}


def decode_step(s: Shape, live_tokens: float, slots: float,
                experts_touched: float, itemsize: int = 2) -> Dict[str, float]:
    """The whole decode program, one execution: the bytes it must move at
    least once and the operations of its matmuls.

    bytes = the weights of the experts that the tick's rows touched
    (``experts_touched``: summed over the ``E`` layers) + every other
    weight once (of the embedding only the slots' rows) + the recurrent
    state read and written + the live keys and values read.  Activations
    are left out (a few MB).  flops = 2 x the matmul weights a token
    meets (its ``top_k`` x held share of routed experts) x slots, plus the
    attention's."""
    n = layer_params(s)
    kinds = {k: s.pattern.count(k) for k in "M*E"}
    fixed = (kinds["M"] * n["M"] + kinds["*"] * n["*"] + kinds["E"] * n["E"]
             + s.vocab * s.dim + s.dim + slots * s.dim)
    weights = (fixed + experts_touched * n["expert"]) * itemsize
    state = 2.0 * slots * state_bytes_per_slot(s, itemsize=itemsize)
    attn = paged_decode(s, live_tokens, slots, itemsize)
    routed_rows = kinds["E"] * slots * s.top_k * s.held / s.experts
    flops = (2.0 * slots * (fixed - slots * s.dim) + 2.0 * routed_rows
             * n["expert"] + kinds["*"] * attn["flops"])
    return {"flops": flops,
            "bytes": weights + state + kinds["*"] * attn["bytes"]}
