"""``granitemoehybrid`` architecture keys (``layer_types``, ``mamba_n_heads``,
``shared_intermediate_size``, the four multipliers, ...) -> the benchmark's
``Shape`` of the stack (benchmarks/reference/granite_hybrid.py) and the
program's ``HybridConfig``; and everything else ``runners/serve_family.py``
asks of a family: seeded weights, reference logits, costs.

The family as Granite-4.0-H-Micro publishes it: NO experts
(``num_local_experts`` 0), so a block is its mixer and the shared MLP, two
one-mixer layers of the program's stack: ``MD`` (``mamba``) or ``*D``
(``attention``), each under ``residual_multiplier``; 40 blocks are 80
layers.  Nothing is cut (``reduced`` is empty).  The model chooses nothing,
so there is no routing to follow and the runner's ``routing_deficit`` reads
0.0.  What the published config has no key for is in the file's ``assumed``,
each with its source."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.reference.granite_hybrid import Shape


def shape(cfg: Dict[str, Any], max_seq: int) -> Shape:
    if max_seq > cfg["max_position_embeddings"]:
        raise ValueError(f"{max_seq} positions asked of a model published "
                         f"for {cfg['max_position_embeddings']}")
    if (cfg["num_local_experts"] or cfg["num_experts_per_tok"]
            or cfg["hidden_act"] != "silu" or not cfg["tie_word_embeddings"]
            or cfg["position_embedding_type"] != "nope"
            or cfg["normalization_function"] != "rmsnorm"
            or cfg["attention_bias"] or cfg["mamba_proj_bias"]
            or not cfg["mamba_conv_bias"]):
        raise ValueError("no experts, SwiGLU, a tied head, no positions, "
                         "RMSNorm, a bias on the convolution alone, as "
                         "published")
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {
            "mamba", "attention"}:
        raise ValueError("layer_types must name num_hidden_layers blocks, "
                         "each 'mamba' or 'attention'")
    if cfg["mamba_expand"] * cfg["hidden_size"] != (
            cfg["mamba_n_heads"] * cfg["mamba_d_head"]):
        raise ValueError("mamba_expand x hidden_size is not mamba_n_heads x "
                         "mamba_d_head")
    heads = cfg["num_attention_heads"]
    return Shape(
        dim=cfg["hidden_size"], layer_types=kinds, vocab=cfg["vocab_size"],
        ffn=cfg["shared_intermediate_size"], heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // heads,
        attn_scale=float(cfg["attention_multiplier"]),
        m_heads=cfg["mamba_n_heads"], m_head_dim=cfg["mamba_d_head"],
        state=cfg["mamba_d_state"], groups=cfg["mamba_n_groups"],
        conv_kernel=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"],
        embed_scale=float(cfg["embedding_multiplier"]),
        residual_scale=float(cfg["residual_multiplier"]),
        logits_scale=1.0 / float(cfg["logits_scaling"]),
        eps=cfg["rms_norm_eps"])


def program_config(cfg: Dict[str, Any], max_seq: int):
    import jax.numpy as jnp

    from torchdistpackage_tpu.models import HybridConfig

    s = shape(cfg, max_seq)
    # a program without the three constants (a parent commit) refuses the
    # fields here, at once
    return HybridConfig(
        vocab_size=s.vocab, dim=s.dim, pattern=s.pattern, max_seq=max_seq,
        nheads=s.heads, kv_heads=s.kv_heads, head_dim=s.head_dim,
        mamba_heads=s.m_heads, mamba_head_dim=s.m_head_dim,
        ssm_state=s.state, ssm_groups=s.groups, conv_kernel=s.conv_kernel,
        ssm_chunk=s.chunk, dense_ffn=s.ffn, embed_scale=s.embed_scale,
        residual_scale=s.residual_scale, attn_scale=s.attn_scale,
        logits_scale=s.logits_scale, norm_eps=s.eps, dtype=jnp.bfloat16,
        state_dtype=jnp.float32)


#: the logits that the calls before handed out, newest last
_handed_out: list = []


def make_weights(s: Shape, seed: int):
    """New weights: the logits of a run before (1 GB a sequence, which a
    process that reads several seeds would hold beside the weights) go."""
    from benchmarks.weights_granite_hybrid import make_weights as make

    for old in _handed_out:
        old.delete()
    _handed_out.clear()
    return make(s, seed)


def reference_following(params, tokens, s: Shape,
                        quant: Optional[str] = None, follow=None):
    """``{logits, routing: None, deficit: None}`` of one sequence.  The
    runner holds each result until it has the next, and the engine it
    dropped is garbage in a cycle (its 4.9 GB of state and its pool with
    it): this collects, and deletes the logits of the call BEFORE the last,
    as families/zaya.py does and says why."""
    import gc

    from benchmarks.reference.granite_hybrid import forward_following

    gc.collect()
    for old in _handed_out[:-1]:
        old.delete()
    del _handed_out[:-1]
    out = forward_following(params, tokens, s, quant, follow)
    _handed_out.append(out["logits"])
    return out


# -------------------------------------------------------------------- sizes


def layer_params(s: Shape) -> Dict[str, int]:
    """Parameters of one layer of each kind of the program's stack, its
    norm included."""
    D = s.dim
    dkv = s.kv_heads * s.head_dim
    return {
        "M": (D + D * (s.d_inner + s.conv_channels + s.m_heads)
              + s.conv_channels * (s.conv_kernel + 1) + 3 * s.m_heads
              + s.d_inner + s.d_inner * D),
        "*": D + 2 * D * s.heads * s.head_dim + 2 * D * dkv,
        "D": D + 3 * D * s.ffn,
    }


def num_params(s: Shape) -> int:
    """Parameters as run: every block, the table ONCE (it is the head)."""
    n = layer_params(s)
    return sum(n[k] for k in s.pattern) + s.vocab * s.dim + s.dim


def state_bytes_per_slot(s: Shape, state_itemsize: int = 4,
                         itemsize: int = 2) -> int:
    """One sequence's recurrent state over the Mamba layers: ``S`` in
    float32 and the convolution's ``conv_kernel - 1`` carried rows."""
    one = (s.m_heads * s.m_head_dim * s.state * state_itemsize
           + (s.conv_kernel - 1) * s.conv_channels * itemsize)
    return s.pattern.count("M") * one


# -------------------------------------------------------------------- costs


def step_unit(s: Shape, itemsize: int = 2) -> Dict[str, float]:
    """What ONE decode call moves and multiplies, in pieces a reader puts
    together from the call's own counters (its dispatch span's ``slots``
    and ``live_tokens``): every weight once, the tied table ONCE though it
    is read twice, the slots' rows as embedding and all of it as head
    (``fixed_bytes``); a DECODING slot's recurrent state read and written
    (``state_bytes_per_slot``, both directions in it); a live position's K
    and V in one attention layer (``kv_bytes_per_position``) and a slot's
    queries read and outputs written there (``qo_bytes_per_slot``), of
    which an execution holds ``attention_layers``; the matmul operations a
    slot's token meets (``flops_per_slot``; the one-step recurrence's 6
    x state elements are in it) and a (slot, live position) pair's in one
    attention layer (``flops_per_position``).  Activations are left out."""
    n = layer_params(s)
    fixed = sum(n[k] for k in s.pattern) + s.vocab * s.dim + s.dim
    recur = s.pattern.count("M") * s.m_heads * s.m_head_dim * s.state
    return {
        "fixed_bytes": float(fixed * itemsize),
        "state_bytes_per_slot": 2.0 * state_bytes_per_slot(
            s, itemsize=itemsize),
        "kv_bytes_per_position": 2.0 * s.kv_heads * s.head_dim * itemsize,
        "qo_bytes_per_slot": 2.0 * s.heads * s.head_dim * itemsize,
        "attention_layers": s.pattern.count("*"),
        "flops_per_slot": 2.0 * fixed + 6.0 * recur,
        "flops_per_position": 4.0 * s.heads * s.head_dim,
    }


def decode_step(s: Shape, live_tokens: float, slots: float,
                experts_touched: float = 0.0,
                itemsize: int = 2) -> Dict[str, float]:
    """The whole decode program, one execution over ``slots`` decoding
    slots and ``live_tokens`` live positions: :func:`step_unit`'s pieces
    put together, with the state's part beside the sum (``state_bytes``).
    ``experts_touched`` is the runner's fourth argument (an engine of this
    family counts 0.0 of them a tick): the model has no experts to touch."""
    u = step_unit(s, itemsize)
    L = u["attention_layers"]
    state = slots * u["state_bytes_per_slot"]
    attn = L * (live_tokens * u["kv_bytes_per_position"]
                + slots * u["qo_bytes_per_slot"])
    return {"flops": (slots * u["flops_per_slot"]
                      + L * live_tokens * u["flops_per_position"]),
            "bytes": u["fixed_bytes"] + state + attn,
            "weight_bytes": u["fixed_bytes"], "state_bytes": state,
            "attention_bytes": attn}


def paged_decode(s: Shape, live_tokens: float, slots: float,
                 itemsize: int = 2) -> Dict[str, Any]:
    """One attention layer's paged decode call, the least the mathematics
    needs: every live position's ``kv_heads`` keys and values of
    ``head_dim`` read once (two heads to a 128-lane row of the pool: the
    bytes are the same), a slot's ``heads`` queries read and outputs
    written; each (query head, live position) pair multiplies over
    ``head_dim`` twice (the zeroed lanes' products are not counted: the
    mathematics does not need them).  Counted in live TOKENS, not in the
    whole blocks a kernel fetches.  Under ``step_unit``: the whole decode
    call's unit costs (:func:`step_unit`), for the readers that hold each
    traced execution to its own call's counters
    (layer_metrics/ssm_step.py)."""
    u = step_unit(s, itemsize)
    return {"flops": live_tokens * u["flops_per_position"],
            "bytes": (live_tokens * u["kv_bytes_per_position"]
                      + slots * u["qo_bytes_per_slot"]),
            "step_unit": u}
