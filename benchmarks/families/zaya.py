"""``zaya`` architecture keys (``cca_time0``, ``router_hidden_size``,
``layer_types``, ``partial_rotary_factor``, ...) -> the benchmark's ``Shape``
of the stack (benchmarks/reference/zaya.py) and the program's
``HybridConfig``; and everything else ``runners/serve_family.py`` asks of a
family: seeded weights, reference logits, costs.

A published block (``layer_types`` ``hybrid``: attention, then experts) is
two one-mixer layers of the stack, so ``num_hidden_layers`` = 20 is the
pattern ``*E`` x 20.  Every expert is held here (the stated deployment cuts
depth alone, benchmarks/configs/zaya1-8b.json), so ``num_experts`` is the
router's width and the held range is all of it."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.reference.zaya import Shape


def shape(cfg: Dict[str, Any], max_seq: int) -> Shape:
    if max_seq > cfg["max_position_embeddings"]:
        raise ValueError(f"{max_seq} positions asked of a model published "
                         f"for {cfg['max_position_embeddings']}")
    # ``layer_types`` stays as published; a cut of depth runs its leading
    # ``num_hidden_layers``
    kinds = set(cfg["layer_types"][:cfg["num_hidden_layers"]])
    if len(cfg["layer_types"]) < cfg["num_hidden_layers"]:
        raise ValueError("one layer type a block")
    if kinds != {"hybrid"} or cfg.get("sliding_window"):
        raise ValueError(f"layer types {sorted(kinds)} with window "
                         f"{cfg.get('sliding_window')}: a windowed block "
                         f"('hybrid_sliding') is not written")
    if cfg["num_experts_per_tok"] != 1:
        raise ValueError("more than one expert a token behind the network "
                         "router is not written: the published weight of a "
                         "second expert is not known")
    if cfg["attention_bias"] or cfg["lm_head_bias"] or not cfg[
            "tie_word_embeddings"] or cfg["hidden_act"] != "silu":
        raise ValueError("projections without biases, a tied head and "
                         "SwiGLU experts, as published")
    rp = cfg["rope_parameters"]["hybrid"]
    if rp["rope_type"] != "default":
        raise ValueError(f"rope type {rp['rope_type']!r} is not written")
    return Shape(
        dim=cfg["hidden_size"], pattern="*E" * cfg["num_hidden_layers"],
        vocab=cfg["vocab_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        time0=cfg["cca_time0"], time1=cfg["cca_time1"],
        rope_dims=int(cfg["head_dim"] * rp["partial_rotary_factor"]),
        rope_theta=float(rp["rope_theta"]), experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], moe_ffn=cfg["moe_intermediate_size"],
        router_hidden=cfg["router_hidden_size"], eps=cfg["rms_norm_eps"])


def program_config(cfg: Dict[str, Any], max_seq: int):
    import jax.numpy as jnp

    from torchdistpackage_tpu.models import HybridConfig

    s = shape(cfg, max_seq)
    # a program without convolved attention (a parent commit) refuses the
    # pattern's 'C' (or the fields below) here, at once.  ``moe_held`` is
    # every expert and not None: the engine's counters of held rows and
    # experts touched exist where a range is given
    return HybridConfig(
        vocab_size=s.vocab, dim=s.dim, pattern=s.pattern.replace("*", "C"),
        max_seq=max_seq, nheads=s.heads, kv_heads=s.kv_heads,
        head_dim=s.head_dim, cca_time0=s.time0, cca_time1=s.time1,
        cca_rope=s.rope_dims, rope_theta=s.rope_theta,
        moe_experts=s.experts, moe_held=(0, s.experts), moe_top_k=s.top_k,
        moe_ffn=s.moe_ffn, moe_act="swiglu", moe_score="mlp",
        moe_router_hidden=s.router_hidden, norm_eps=s.eps, dtype=jnp.bfloat16)


def make_weights(s: Shape, seed: int):
    from benchmarks.weights_zaya import make_weights as make

    return make(s, seed)


#: the logits that the calls before handed out, newest last
_handed_out: list = []


def reference_following(params, tokens, s: Shape,
                        quant: Optional[str] = None, follow=None):
    """``{logits, routing, deficit}`` of one sequence, the reference taking
    the experts ``follow`` names (its own where None).

    One sequence's logits are 2.7 GB in float32 at the cell's size, beside
    9.4 GB of weights on a chip of 16.  The runner holds each result until
    it has the next (a name is rebound after the call returns), so with the
    control's two results a request THREE would be alive inside this call,
    and the engine it dropped with ``del`` is garbage in a cycle (its device
    step points back at it), whose 3.4 GB pool lingers until a collection.
    So this collects, and deletes the logits of the call BEFORE the last:
    the runner has their gaps on the host by then, and only the last call's
    may still be an operand of something it has dispatched."""
    import gc

    from benchmarks.reference.zaya import forward_following

    gc.collect()
    for old in _handed_out[:-1]:
        old.delete()
    del _handed_out[:-1]
    out = forward_following(params, tokens, s, quant, follow)
    _handed_out.append(out["logits"])
    return out


# -------------------------------------------------------------------- sizes


def layer_params(s: Shape) -> Dict[str, int]:
    """Parameters of one layer of each kind, its norm and its residual's
    four vectors included; an ``E`` layer split into its router (the first
    expert layer's has no ``gamma``: ``num_params`` takes it off) and one
    expert."""
    D, R, C, hd = s.dim, s.router_hidden, s.channels, s.head_dim
    return {
        "*": (5 * D + D * C + D * s.kv_heads * hd + s.time0 * C + C
              + s.time1 * (s.heads + s.kv_heads) * hd * hd + C + s.kv_heads
              + s.heads * hd * D),
        "E": (5 * D + D * R + R + R + R + 2 * (R * R + R) + R * s.experts
              + s.experts),
        "expert": 3 * D * s.moe_ffn,
    }


def num_params(s: Shape) -> int:
    """Parameters as run: every expert, the table ONCE (it is the head)."""
    n = layer_params(s)
    per = {"*": n["*"], "E": n["E"] + s.experts * n["expert"]}
    return (sum(per[k] for k in s.pattern) - s.router_hidden
            + s.vocab * s.dim + s.dim)


# -------------------------------------------------------------------- costs


def paged_decode(s: Shape, live_tokens: float, slots: float,
                 itemsize: int = 2) -> Dict[str, float]:
    """One attention layer's paged decode call, the least the mathematics
    needs: every live position's ``kv_heads`` keys and values read once, a
    slot's ``heads`` queries read and outputs written; each (query head,
    live position) pair multiplies over ``head_dim`` twice.  Counted in live
    TOKENS, not in the whole blocks a kernel fetches."""
    kv = 2 * live_tokens * s.kv_heads * s.head_dim * itemsize
    qo = 2 * slots * s.heads * s.head_dim * itemsize
    return {"flops": 4.0 * live_tokens * s.heads * s.head_dim,
            "bytes": kv + qo}


def decode_step(s: Shape, live_tokens: float, slots: float,
                experts_touched: float, itemsize: int = 2) -> Dict[str, float]:
    """The whole decode program, one execution: the bytes it must move at
    least once and the operations of its matmuls.

    bytes = the weights of the experts that the tick's rows touched
    (``experts_touched``: summed over the ``E`` layers) + every other
    weight once, the table ONCE though it is read twice (the slots' rows as
    embedding, all of it as head) + the live keys and values read + every
    slot's tail read and written.  Activations are left out.  flops = 2 x
    the matmul weights a token meets (ONE expert a layer) x slots, plus the
    attention's."""
    n = layer_params(s)
    kinds = {k: s.pattern.count(k) for k in "*E"}
    fixed = (sum(kinds[k] * n[k] for k in "*E") - s.router_hidden
             + s.vocab * s.dim + s.dim)
    tails = 2 * kinds["*"] * slots * s.tail
    weights = (fixed + experts_touched * n["expert"] + tails) * itemsize
    attn = paged_decode(s, live_tokens, slots, itemsize)
    flops = (2.0 * slots * (fixed + kinds["E"] * s.top_k * n["expert"])
             + kinds["*"] * attn["flops"])
    return {"flops": flops, "bytes": weights + kinds["*"] * attn["bytes"]}
