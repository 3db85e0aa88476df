"""``sarvam_mla`` architecture keys (``kv_lora_rank``, ``qk_nope_head_dim``,
``first_k_dense_replace``, ``num_experts``, ...) -> the benchmark's ``Shape``
of the stack (benchmarks/reference/sarvam_mla.py) and the program's
``HybridConfig``; and everything else ``runners/serve_family.py`` asks of a
family: seeded weights, reference logits, costs.

A published block (attention, then a feed-forward part) is two one-mixer
layers of the stack, so ``num_hidden_layers`` = 5 is the pattern
``*D*E*E*E*E``: ``first_k_dense_replace`` blocks with the dense MLP, the
rest with experts.  ``num_experts`` in the configuration file counts the
experts HELD here (the model-configs guide's reading); the router's width is
``published.num_experts`` and the held range starts at
``deployment_share.first_expert``."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.reference.sarvam_mla import Shape


def shape(cfg: Dict[str, Any], max_seq: int) -> Shape:
    if max_seq > cfg["max_position_embeddings"]:
        raise ValueError(f"{max_seq} positions asked of a model published "
                         f"for {cfg['max_position_embeddings']}")
    if cfg.get("q_lora_rank"):
        raise ValueError("a low-rank query path is not written")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not written")
    if (cfg["head_dim"] != cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
            or cfg["q_head_dim"] != cfg["qk_nope_head_dim"]
            + cfg["qk_rope_head_dim"]):
        raise ValueError("head_dim is what a position caches (latent + "
                         "rope), q_head_dim a query head (nope + rope)")
    rs = cfg["rope_scaling"]
    if rs["type"] != "deepseek_yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r} is not written")
    if cfg["num_shared_experts"] != 1 or not cfg["moe_router_enable_expert_bias"]:
        raise ValueError("one shared expert and a selection bias, as published")
    dense, blocks = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    return Shape(
        dim=cfg["hidden_size"],
        pattern="*D" * dense + "*E" * (blocks - dense),
        vocab=cfg["vocab_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], latent=cfg["kv_lora_rank"],
        rope_theta=float(cfg["rope_theta"]), yarn_factor=float(rs["factor"]),
        yarn_orig=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]),
        dense_ffn=cfg["intermediate_size"],
        experts=cfg.get("published", {}).get("num_experts", cfg["num_experts"]),
        held_first=cfg.get("deployment_share", {}).get("first_expert", 0),
        held=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        moe_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        eps=cfg["rms_norm_eps"])


def program_config(cfg: Dict[str, Any], max_seq: int):
    import jax.numpy as jnp

    from torchdistpackage_tpu.models import HybridConfig

    s = shape(cfg, max_seq)
    # a program without latent attention (a parent commit) refuses the
    # pattern's 'L' here, at once
    return HybridConfig(
        vocab_size=s.vocab, dim=s.dim, pattern=s.pattern.replace("*", "L"),
        max_seq=max_seq, nheads=s.heads, kv_heads=1,
        mla_latent=s.latent, mla_nope=s.nope, mla_rope=s.rope, mla_v=s.v_dim,
        rope_theta=s.rope_theta,
        rope_scaling={"rope_type": "yarn", "factor": s.yarn_factor,
                      "original_max_position_embeddings": s.yarn_orig,
                      "beta_fast": s.beta_fast, "beta_slow": s.beta_slow,
                      "mscale": s.mscale, "mscale_all_dim": s.mscale_all_dim},
        dense_ffn=s.dense_ffn, moe_experts=s.experts,
        moe_held=(s.held_first, s.held), moe_top_k=s.top_k, moe_ffn=s.moe_ffn,
        moe_shared_ffn=s.shared_ffn, moe_routed_scale=s.routed_scale,
        moe_act="swiglu", norm_eps=s.eps, dtype=jnp.bfloat16)


def make_weights(s: Shape, seed: int):
    from benchmarks.weights_sarvam_mla import make_weights as make

    return make(s, seed)


def reference_following(params, tokens, s: Shape,
                        quant: Optional[str] = None, follow=None):
    """``{logits, routing, deficit}`` of one sequence, the reference taking
    the experts ``follow`` names (its own where None)."""
    from benchmarks.reference.sarvam_mla import forward_following

    return forward_following(params, tokens, s, quant, follow)


# -------------------------------------------------------------------- sizes


def layer_params(s: Shape) -> Dict[str, int]:
    """Parameters of one layer of each kind, norms included; an ``E``
    layer split into what every chip holds and one routed expert."""
    D, H = s.dim, s.heads
    return {
        "*": (D + D * H * s.q_dim + s.q_dim + D * s.cached + s.latent
              + H * s.latent * (s.nope + s.v_dim) + H * s.v_dim * D),
        "D": D + 3 * D * s.dense_ffn,
        "E": D + D * s.experts + s.experts + 3 * D * s.shared_ffn,
        "expert": 3 * D * s.moe_ffn,
    }


def num_params(s: Shape) -> int:
    """Parameters as run: the held experts only, both vocabulary tables."""
    n = layer_params(s)
    per = {"*": n["*"], "D": n["D"], "E": n["E"] + s.held * n["expert"]}
    return sum(per[k] for k in s.pattern) + 2 * s.vocab * s.dim + s.dim


# -------------------------------------------------------------------- costs


def paged_decode(s: Shape, live_tokens: float, slots: float,
                 itemsize: int = 2) -> Dict[str, float]:
    """One attention layer's latent decode call, the least the mathematics
    needs: every live position's cached row read ONCE (it is key and value),
    a slot's ``heads`` absorbed queries read and their latent outputs
    written; each (head, live position) pair multiplies over the cached
    width for the score and over the latent for the value.  Counted in live
    TOKENS, not in the whole blocks a kernel fetches."""
    rows = live_tokens * s.cached * itemsize
    qo = slots * s.heads * (s.cached + s.latent) * itemsize
    return {"flops": 2.0 * live_tokens * s.heads * (s.cached + s.latent),
            "bytes": rows + qo}


def decode_step(s: Shape, live_tokens: float, slots: float,
                experts_touched: float, itemsize: int = 2) -> Dict[str, float]:
    """The whole decode program, one execution: the bytes it must move at
    least once and the operations of its matmuls.

    bytes = the weights of the experts that the tick's rows touched
    (``experts_touched``: summed over the ``E`` layers) + every other
    weight once (of the embedding only the slots' rows) + the live cached
    rows read.  No recurrent state.  Activations are left out.  flops = 2 x
    the matmul weights a token meets (its ``top_k`` x held share of routed
    experts; the absorbed up-projections are each head's own) x slots, plus
    the attention's."""
    n = layer_params(s)
    kinds = {k: s.pattern.count(k) for k in "*DE"}
    fixed = (sum(kinds[k] * n[k] for k in "*DE")
             + s.vocab * s.dim + s.dim + slots * s.dim)
    weights = (fixed + experts_touched * n["expert"]) * itemsize
    attn = paged_decode(s, live_tokens, slots, itemsize)
    routed_rows = kinds["E"] * slots * s.top_k * s.held / s.experts
    flops = (2.0 * slots * (fixed - slots * s.dim) + 2.0 * routed_rows
             * n["expert"] + kinds["*"] * attn["flops"])
    return {"flops": flops, "bytes": weights + kinds["*"] * attn["bytes"]}
