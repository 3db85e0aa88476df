"""``mimo_v2`` architecture keys (``hybrid_layer_pattern``, ``moe_layer_freq``,
``swa_num_key_value_heads``, ``v_head_dim``, ``partial_rotary_factor``,
``attention_value_scale``, ``add_swa_attention_sink_bias``, ...) -> the
benchmark's ``Shape`` of the stack (benchmarks/reference/mimo_v2.py) and the
program's ``HybridConfig``; and everything else ``runners/serve_family.py``
asks of a family: seeded weights, reference logits, costs.

A published block (attention, then a feed-forward part, two norms) is two
one-mixer layers of the stack: block ``i`` is ``*`` where
``hybrid_layer_pattern[i]`` is 0 (global) and ``W`` where it is 1 (window),
then ``D`` where ``moe_layer_freq[i]`` is 0 and ``E`` where it is 1, so 11
blocks are ``*D`` + ``WE`` x 4 + ``*E`` + ``WE`` x 5.  The shape writes the
GLOBAL layers as ``*``: the runner counts ``calls_per_execution`` of
``paged_decode`` by them.  The two kinds differ in their KV heads
(``num_key_value_heads`` / ``swa_num_key_value_heads``), their theta
(``rope_theta`` / ``swa_rope_theta``) and their sink
(``add_full_attention_sink_bias`` / ``add_swa_attention_sink_bias``); the
program has ONE head count and ONE pair of widths for both, so
``swa_num_attention_heads``, ``swa_head_dim`` and ``swa_v_head_dim`` must
repeat the global layers'.  ``n_routed_experts`` in the configuration file
counts the experts HELD here (the model-configs guide's reading); the
router's width is ``published.n_routed_experts`` and the held range starts at
``deployment_share.first_expert``.  What the published config does not spell
out (which dims rotate, the window's edges, the sink's form, where the value
scale multiplies) is in the file's ``assumed``, each with its source."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.reference.mimo_v2 import Shape


def shape(cfg: Dict[str, Any], max_seq: int) -> Shape:
    if max_seq > cfg["max_position_embeddings"]:
        raise ValueError(f"{max_seq} positions asked of a model published "
                         f"for {cfg['max_position_embeddings']}")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not written")
    if (cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]
            or cfg["topk_method"] != "noaux_tc" or cfg["n_shared_experts"]
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]
            or cfg["attention_bias"]
            or cfg["attention_projection_layout"] != "fused_qkv"
            or (cfg["rope_scaling"] or {}).get("rope_type", "default")
            != "default"):
        raise ValueError("a sigmoid noaux_tc router with renormalised "
                         "weights, no shared expert, SwiGLU, an untied head, "
                         "one fused projection without bias and plain rope, "
                         "as published")
    if ((cfg["swa_num_attention_heads"], cfg["swa_head_dim"],
         cfg["swa_v_head_dim"]) != (cfg["num_attention_heads"],
                                    cfg["head_dim"], cfg["v_head_dim"])
            or cfg["sliding_window"] != cfg["sliding_window_size"]):
        raise ValueError("window layers with the global layers' query heads "
                         "and widths, and one window, as published")
    blocks = cfg["num_hidden_layers"]
    kinds, ffn = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    if (len(kinds) != blocks or len(ffn) != blocks
            or (set(kinds) | set(ffn)) - {0, 1}):
        raise ValueError(f"hybrid_layer_pattern and moe_layer_freq must name "
                         f"{blocks} blocks, each 0 or 1")
    rope_dims = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    if rope_dims % 2:
        raise ValueError(f"{rope_dims} rotated dims do not pair")
    scale = cfg["routed_scaling_factor"]
    return Shape(
        dim=cfg["hidden_size"],
        pattern="".join("*W"[k] + "DE"[f] for k, f in zip(kinds, ffn)),
        vocab=cfg["vocab_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        window_kv_heads=cfg["swa_num_key_value_heads"],
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_dims=rope_dims, window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]),
        window_rope_theta=float(cfg["swa_rope_theta"]),
        value_scale=float(cfg["attention_value_scale"]),
        window_sink=bool(cfg["add_swa_attention_sink_bias"]),
        global_sink=bool(cfg["add_full_attention_sink_bias"]),
        dense_ffn=cfg["intermediate_size"],
        experts=cfg.get("published", {}).get("n_routed_experts",
                                             cfg["n_routed_experts"]),
        held_first=cfg.get("deployment_share", {}).get("first_expert", 0),
        held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        moe_ffn=cfg["moe_intermediate_size"],
        routed_scale=1.0 if scale is None else float(scale),
        eps=cfg["layernorm_epsilon"])


def program_config(cfg: Dict[str, Any], max_seq: int):
    import jax.numpy as jnp

    from torchdistpackage_tpu.models import HybridConfig

    s = shape(cfg, max_seq)
    # a program without value heads of their own width, KV heads a kind or a
    # theta a kind (a parent commit) refuses these fields here, at once
    return HybridConfig(
        vocab_size=s.vocab, dim=s.dim, pattern=s.pattern, max_seq=max_seq,
        nheads=s.heads, kv_heads=s.kv_heads, head_dim=s.head_dim,
        v_head_dim=s.v_head_dim, window_kv_heads=s.window_kv_heads,
        rope_dims=s.rope_dims, window=s.window,
        rope_theta=s.window_rope_theta, global_rope_theta=s.rope_theta,
        value_scale=s.value_scale, dense_ffn=s.dense_ffn,
        moe_experts=s.experts, moe_held=(s.held_first, s.held),
        moe_top_k=s.top_k, moe_ffn=s.moe_ffn,
        moe_routed_scale=s.routed_scale, moe_act="swiglu", norm_eps=s.eps,
        dtype=jnp.bfloat16)


#: the logits that the calls before handed out, newest last
_handed_out: list = []


def make_weights(s: Shape, seed: int):
    """New weights: the logits of a run before (2.0 GB, which a process that
    reads several seeds would hold beside 12 GB of weights and pools) go."""
    from benchmarks.weights_mimo_v2 import make_weights as make

    for old in _handed_out:
        old.delete()
    _handed_out.clear()
    return make(s, seed)


def reference_following(params, tokens, s: Shape,
                        quant: Optional[str] = None, follow=None):
    """``{logits, routing, deficit}`` of one sequence, the reference taking
    the experts ``follow`` names (its own where None).

    One sequence's logits are 2.0 GB in float32 at the cell's size, beside
    6.8 GB of weights and ~5 GB of one attention layer's float32 heads.  The
    runner holds each result until it has the next, so this collects (the
    engine it dropped is garbage in a cycle, its two pools with it) and
    deletes the logits of the call BEFORE the last, as families/zaya.py does
    and says why."""
    import gc

    from benchmarks.reference.mimo_v2 import forward_following

    gc.collect()
    for old in _handed_out[:-1]:
        old.delete()
    del _handed_out[:-1]
    out = forward_following(params, tokens, s, quant, follow)
    _handed_out.append(out["logits"])
    return out


# -------------------------------------------------------------------- sizes


def layer_params(s: Shape) -> Dict[str, int]:
    """Parameters of one layer of each kind, its norm included; an ``E``
    layer split into what every chip holds and one routed expert."""
    D, H = s.dim, s.heads

    def attention(hkv, sink):
        return (D + D * (H * s.head_dim + hkv * (s.head_dim + s.v_head_dim))
                + H * s.v_head_dim * D + H * sink)

    return {
        "W": attention(s.window_kv_heads, s.window_sink),
        "*": attention(s.kv_heads, s.global_sink),
        "D": D + 3 * D * s.dense_ffn,
        "E": D + D * s.experts + s.experts,
        "expert": 3 * D * s.moe_ffn,
    }


def num_params(s: Shape) -> int:
    """Parameters as run: the held experts only, both vocabulary tables."""
    n = layer_params(s)
    per = {**n, "E": n["E"] + s.held * n["expert"]}
    return sum(per[k] for k in s.pattern) + 2 * s.vocab * s.dim + s.dim


# -------------------------------------------------------------------- costs


def _unit(s: Shape, kv_heads: int, itemsize: int) -> Dict[str, float]:
    """One attention layer's unit costs, for the readers that count rows and
    positions from the program's own spans (layer_metrics/mimo_kernels.py):
    a (row, key) pair multiplies over ``head_dim`` for its score and over
    ``v_head_dim`` for its value, every query head; a position's keys and
    values are read once a KV head; a row's queries are read and its outputs
    written."""
    wide = s.head_dim + s.v_head_dim
    return {"flops_per_pair": 2.0 * s.heads * wide,
            "bytes_per_position": kv_heads * wide * itemsize,
            "bytes_per_row": s.heads * wide * itemsize}


def _attend(unit: Dict[str, float], tokens: float,
            queries: float) -> Dict[str, float]:
    """``queries`` rows against ``tokens`` (row, key) pairs, the keys and
    values read once."""
    return {"flops": unit["flops_per_pair"] * tokens,
            "bytes": (unit["bytes_per_position"] * tokens
                      + unit["bytes_per_row"] * queries)}


def paged_decode(s: Shape, live_tokens: float, slots: float,
                 itemsize: int = 2) -> Dict[str, Any]:
    """One GLOBAL attention layer's decode call, the least the mathematics
    needs: every live position's ``kv_heads`` keys (``head_dim``) and values
    (``v_head_dim``) read once, a slot's ``heads`` queries read and outputs
    written.  Under ``window``: one WINDOW layer's decode call, the
    positions inside the window alone (an upper bound from the call's
    totals: ``min(live, slots x window)``), at ITS KV heads;
    ``window_layers`` says how many such calls an execution holds.  Under
    ``global_unit`` / ``window_unit``: the unit costs of either kind's call
    (:func:`_unit`: 2,560 and 5,120 B a position at the published sizes,
    40,960 flop a pair in both).  Counted in TOKENS, not in the whole blocks
    a kernel fetches."""
    glob, win = (_unit(s, h, itemsize)
                 for h in (s.kv_heads, s.window_kv_heads))
    held = live_tokens if s.window is None else min(
        live_tokens, slots * s.window)
    return {**_attend(glob, live_tokens, slots),
            "window": _attend(win, held, slots),
            "window_layers": s.pattern.count("W"),
            "step_unit": step_unit(s, itemsize),
            "window_unit": win, "global_unit": glob}


def step_unit(s: Shape, itemsize: int = 2) -> Dict[str, float]:
    """What the decode program moves and multiplies beside its attention, in
    pieces a reader can put together for ONE call from the call's own
    counters: every weight but the routed experts' once (``fixed_bytes``; of
    the embedding only a slot's row, ``bytes_per_slot``), one routed expert
    (``expert_bytes``; how many a call touched is the program's to say: the
    ``experts_touched`` of its ``tdp:engine.fetch`` span) and the matmul
    operations a slot's token meets (``flops_per_slot``: its ``top_k`` x held
    share of routed experts included)."""
    n = layer_params(s)
    kinds = {k: s.pattern.count(k) for k in "W*DE"}
    fixed = sum(kinds[k] * n[k] for k in "W*DE") + s.vocab * s.dim + s.dim
    return {"fixed_bytes": fixed * itemsize, "bytes_per_slot": s.dim * itemsize,
            "expert_bytes": n["expert"] * itemsize,
            "flops_per_slot": 2.0 * (fixed + kinds["E"] * s.top_k * s.held
                                     / s.experts * n["expert"])}


def decode_step(s: Shape, live_tokens: float, slots: float,
                experts_touched: float, itemsize: int = 2) -> Dict[str, float]:
    """The whole decode program, one execution: the bytes it must move at
    least once and the operations of its matmuls.

    bytes = the weights of the experts that the tick's rows touched
    (``experts_touched``: summed over the ``E`` layers) + every other
    weight once (of the embedding only the slots' rows) + both kinds' keys
    and values: every live position in a global layer, the positions inside
    the window in a window layer.  Activations are left out.  flops = 2 x
    the matmul weights a token meets (its ``top_k`` x held share of routed
    experts) x slots, plus the attention's.  (:func:`step_unit`'s pieces.)"""
    u = step_unit(s, itemsize)
    attn = paged_decode(s, live_tokens, slots, itemsize)
    both = {k: s.pattern.count("*") * attn[k]
            + s.pattern.count("W") * attn["window"][k]
            for k in ("flops", "bytes")}
    return {"flops": slots * u["flops_per_slot"] + both["flops"],
            "bytes": (u["fixed_bytes"] + slots * u["bytes_per_slot"]
                      + experts_touched * u["expert_bytes"] + both["bytes"])}
