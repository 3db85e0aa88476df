"""``afmoe`` architecture keys (``layer_types``, ``sliding_window``,
``num_dense_layers``, ``route_scale``, ``mup_enabled``, ...) -> the
benchmark's ``Shape`` of the stack (benchmarks/reference/afmoe.py) and the
program's ``HybridConfig``; and everything else ``runners/serve_family.py``
asks of a family: seeded weights, reference logits, costs.

A published block (attention, then a feed-forward part, FOUR norms) is two
one-mixer layers of the stack, each normed before and after its mixer: block
``i`` is ``W`` (``sliding_attention``) or ``*`` (``full_attention``) and then
``D`` while ``i < num_dense_layers``, ``E`` after, so 16 blocks are
``WDWDWE*E`` + ``WEWEWE*E`` x 3.  The shape writes the GLOBAL layers as
``*``: the runner counts ``calls_per_execution`` of ``paged_decode`` by them,
and ``paged_decode`` carries the window kernels' costs under further keys.
``num_experts`` in the configuration file counts the experts HELD here (the
model-configs guide's reading); the router's width is
``published.num_experts`` and the held range starts at
``deployment_share.first_expert``.  What the published config does not carry
(the output gate, the head norms, which layers rotate, the four norms) is in
the file's ``assumed``, each with its source."""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

from benchmarks.reference.afmoe import Shape

_KINDS = {"sliding_attention": "W", "full_attention": "*"}


def shape(cfg: Dict[str, Any], max_seq: int) -> Shape:
    if max_seq > cfg["max_position_embeddings"]:
        raise ValueError(f"{max_seq} positions asked of a model published "
                         f"for {cfg['max_position_embeddings']}")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not written")
    if (cfg["score_func"] != "sigmoid" or not cfg["route_norm"]
            or cfg["num_shared_experts"] != 1 or cfg["hidden_act"] != "silu"
            or cfg["tie_word_embeddings"] or cfg["rope_scaling"]):
        raise ValueError("a sigmoid router with renormalised weights, one "
                         "shared expert, SwiGLU, an untied head and plain "
                         "rope, as published")
    blocks, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    kinds = cfg["layer_types"]
    if len(kinds) != blocks or set(kinds) - set(_KINDS):
        raise ValueError(f"layer_types must name {blocks} blocks, each one "
                         f"of {sorted(_KINDS)}")
    return Shape(
        dim=cfg["hidden_size"],
        pattern="".join(_KINDS[k] + ("D" if i < dense else "E")
                        for i, k in enumerate(kinds)),
        vocab=cfg["vocab_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], rope_theta=float(cfg["rope_theta"]),
        embed_scale=(math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"]
                     else 1.0),
        dense_ffn=cfg["intermediate_size"],
        experts=cfg.get("published", {}).get("num_experts", cfg["num_experts"]),
        held_first=cfg.get("deployment_share", {}).get("first_expert", 0),
        held=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        moe_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        routed_scale=float(cfg["route_scale"]), eps=cfg["rms_norm_eps"])


def program_config(cfg: Dict[str, Any], max_seq: int):
    import jax.numpy as jnp

    from torchdistpackage_tpu.models import HybridConfig

    s = shape(cfg, max_seq)
    # a program without window layers and a second pool (a parent commit)
    # refuses the pattern's 'W' (or the window field) here, at once
    return HybridConfig(
        vocab_size=s.vocab, dim=s.dim, pattern=s.pattern, max_seq=max_seq,
        nheads=s.heads, kv_heads=s.kv_heads, head_dim=s.head_dim,
        window=s.window, embed_scale=s.embed_scale, rope_theta=s.rope_theta,
        dense_ffn=s.dense_ffn, moe_experts=s.experts,
        moe_held=(s.held_first, s.held), moe_top_k=s.top_k, moe_ffn=s.moe_ffn,
        moe_shared_ffn=s.shared_ffn, moe_routed_scale=s.routed_scale,
        moe_act="swiglu", norm_eps=s.eps, dtype=jnp.bfloat16)


#: the logits that the calls before handed out, newest last
_handed_out: list = []


def make_weights(s: Shape, seed: int):
    """New weights: the logits of a run before (2.9 GB, which a process that
    reads several seeds would hold beside 13 GB of weights and pools) go."""
    from benchmarks.weights_afmoe import make_weights as make

    for old in _handed_out:
        old.delete()
    _handed_out.clear()
    return make(s, seed)


def reference_following(params, tokens, s: Shape,
                        quant: Optional[str] = None, follow=None):
    """``{logits, routing, deficit}`` of one sequence, the reference taking
    the experts ``follow`` names (its own where None).

    One sequence's logits are 2.9 GB in float32 at the cell's size, beside
    7.25 GB of weights.  The runner holds each result until it has the next,
    so this collects (the engine it dropped is garbage in a cycle, its two
    pools with it) and deletes the logits of the call BEFORE the last, as
    families/zaya.py does and says why."""
    import gc

    from benchmarks.reference.afmoe import forward_following

    gc.collect()
    for old in _handed_out[:-1]:
        old.delete()
    del _handed_out[:-1]
    out = forward_following(params, tokens, s, quant, follow)
    _handed_out.append(out["logits"])
    return out


# -------------------------------------------------------------------- sizes


def layer_params(s: Shape) -> Dict[str, int]:
    """Parameters of one layer of each kind, its two norms included; an
    ``E`` layer split into what every chip holds and one routed expert."""
    D, hd = s.dim, s.head_dim
    attention = (2 * D + 3 * D * s.heads * hd + 2 * D * s.kv_heads * hd
                 + 2 * hd)
    return {
        "W": attention, "*": attention,
        "D": 2 * D + 3 * D * s.dense_ffn,
        "E": 2 * D + D * s.experts + s.experts + 3 * D * s.shared_ffn,
        "expert": 3 * D * s.moe_ffn,
    }


def num_params(s: Shape) -> int:
    """Parameters as run: the held experts only, both vocabulary tables."""
    n = layer_params(s)
    per = {**n, "E": n["E"] + s.held * n["expert"]}
    return sum(per[k] for k in s.pattern) + 2 * s.vocab * s.dim + s.dim


# -------------------------------------------------------------------- costs


def window_tokens(s: Shape, live_tokens: float, slots: float) -> float:
    """The positions INSIDE the window that one window layer's decode call
    reads: a slot reads ``min(window, its context)``.  From the call's
    totals, which is all the runner hands over: all of the live positions
    while the mean context is within the window, else the window a slot: an
    UPPER bound wherever some contexts are short of it.  The readers that
    hold a kernel to this work take the exact count from the program's own
    spans instead (layer_metrics/swa_kernels.call_costs)."""
    return min(live_tokens, slots * s.window)


def _attend(s: Shape, tokens: float, queries: float,
            itemsize: int) -> Dict[str, float]:
    """``queries`` rows of ``heads`` against ``tokens`` (query, key) pairs a
    head: the keys and values read once a KV head, the queries read and the
    outputs written; each pair multiplies over ``head_dim`` twice."""
    return {"flops": 4.0 * tokens * s.heads * s.head_dim,
            "bytes": (2 * tokens * s.kv_heads * s.head_dim
                      + 2 * queries * s.heads * s.head_dim) * itemsize}


def paged_decode(s: Shape, live_tokens: float, slots: float,
                 itemsize: int = 2) -> Dict[str, Any]:
    """One GLOBAL attention layer's decode call, the least the mathematics
    needs: every live position's ``kv_heads`` keys and values read once, a
    slot's ``heads`` queries read and outputs written.  Under ``window``:
    one WINDOW layer's decode call, the positions inside the window alone
    (:func:`window_tokens`, an upper bound), whatever the kernel fetches, so
    a masked walk and a bounded one are held to the same work;
    ``window_layers`` says how many such calls an execution holds.  Under
    ``window_unit``: the unit costs of a window layer's call, for the
    readers that count its rows and positions from the program's own spans
    (layer_metrics/swa_kernels.py): a (row, position inside its window)
    pair's operations, a position's K and V, a row's query and output.
    Counted in TOKENS, not in the whole blocks a kernel fetches."""
    win = window_tokens(s, live_tokens, slots)
    return {**_attend(s, live_tokens, slots, itemsize),
            "window": _attend(s, win, slots, itemsize),
            "window_layers": s.pattern.count("W"),
            "step_unit": step_unit(s, itemsize),
            "window_unit": {
                "flops_per_pair": 4.0 * s.heads * s.head_dim,
                "bytes_per_position": 2 * s.kv_heads * s.head_dim * itemsize,
                "bytes_per_row": 2 * s.heads * s.head_dim * itemsize}}


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
