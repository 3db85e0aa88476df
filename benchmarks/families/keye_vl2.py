"""``keye_vl2`` architecture keys (``sa_config``, ``rope_scaling.
mrope_section``, ``num_experts``, ``norm_topk_prob``, ...) -> the benchmark's
``Shape`` of the stack (benchmarks/reference/keye_vl2.py) and the program's
``HybridConfig``; and everything else ``runners/serve_family.py`` asks of a
family: seeded weights, reference logits, costs.

A published block (attention behind the indexer, then experts) is two
one-mixer layers of the stack, so ``num_hidden_layers`` = 8 is the pattern
``*E`` x 8 (``decoder_sparse_step`` 1, ``mlp_only_layers`` []: every block
has experts).  ``num_experts`` in the configuration file counts the experts
HELD here (the model-configs guide's reading); the router's width is
``published.num_experts`` and the held range starts at
``deployment_share.first_expert``.  What the published config does not
carry (the per-head norms, the indexer's query path, its key's LayerNorm,
its rope dims) is in the file's ``assumed``, each with its source.

What the runner hands from the program to the reference as ``routing`` is,
for this family, one flat int16 record a position
(``ServingEngine(record_routing=True)``'s where attention is indexed: a list
of the calls' pieces): the ``E-layers x k`` experts, then for each attention
layer the words of the positions the row kept, as bits.  The reference follows
both, and ``deficit`` holds both choices to the reference's own scores."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.reference.keye_vl2 import Shape


def shape(cfg: Dict[str, Any], max_seq: int) -> Shape:
    if max_seq > cfg["max_position_embeddings"]:
        raise ValueError(f"{max_seq} positions asked of a model published "
                         f"for {cfg['max_position_embeddings']}")
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("every block with experts, as published: a dense "
                         "MLP block is not written")
    if cfg.get("use_sliding_window") or cfg.get("sliding_window"):
        raise ValueError("a windowed block is not written")
    if (cfg["attention_bias"] or cfg["tie_word_embeddings"]
            or cfg["hidden_act"] != "silu" or not cfg["norm_topk_prob"]):
        raise ValueError("projections without biases, an untied head, "
                         "SwiGLU experts and renormalised top-k weights, as "
                         "published")
    rs, sa = cfg["rope_scaling"], cfg["sa_config"]
    if rs["rope_type"] != "default" or len(rs["mrope_section"]) != 3:
        raise ValueError(f"rope scaling {rs!r} is not written")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("ONE indexer key a position, as published")
    return Shape(
        dim=cfg["hidden_size"], pattern="*E" * cfg["num_hidden_layers"],
        vocab=cfg["vocab_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mrope_section=tuple(rs["mrope_section"]),
        rope_theta=float(cfg["rope_theta"]),
        idx_heads=sa["indexer_num_heads"], idx_dim=sa["indexer_head_dim"],
        idx_topk=sa["topk"], idx_rope=cfg["assumed"]["indexer_rope_dim"]["value"],
        experts=cfg.get("published", {}).get("num_experts", cfg["num_experts"]),
        held_first=cfg.get("deployment_share", {}).get("first_expert", 0),
        held=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        moe_ffn=cfg["moe_intermediate_size"], eps=cfg["rms_norm_eps"])


def program_config(cfg: Dict[str, Any], max_seq: int):
    from torchdistpackage_tpu.models import HybridConfig

    s = shape(cfg, max_seq)
    # a program without indexed attention (a parent commit) refuses the
    # pattern's 'S' (or the idx_* fields) here, at once
    return HybridConfig(
        vocab_size=s.vocab, dim=s.dim, pattern=s.pattern.replace("*", "S"),
        max_seq=max_seq, nheads=s.heads, kv_heads=s.kv_heads,
        head_dim=s.head_dim, idx_heads=s.idx_heads, idx_dim=s.idx_dim,
        idx_topk=s.idx_topk, idx_rope=s.idx_rope,
        mrope_section=s.mrope_section, rope_theta=s.rope_theta,
        moe_experts=s.experts, moe_held=(s.held_first, s.held),
        moe_top_k=s.top_k, moe_ffn=s.moe_ffn, moe_act="swiglu",
        moe_score="softmax", norm_eps=s.eps)


def make_weights(s: Shape, seed: int):
    from benchmarks.weights_keye_vl2 import make_weights as make

    return make(s, seed)


#: the logits that the calls before handed out, newest last
_handed_out: list = []


def reference_following(params, tokens, s: Shape,
                        quant: Optional[str] = None, follow=None):
    """``{logits, routing, deficit}`` of one sequence, the reference taking
    the experts and the kept positions that ``follow`` names (its own where
    None, and only then is ``routing`` put together: 207 MB at the cell's
    size, which a caller that handed the choices in already has; the
    module's docstring has the record's form).  ``deficit`` [S,
    E-layers + attention layers]: a followed expert's, on the probability
    scale, then a followed selection's, on the scale of the layer's index
    scores (``reference.keye_vl2.selection_deficit``).

    One sequence's logits are 2.2 GB in float32 at the cell's size, and a
    layer's ``[S, S]`` scores, mask and probabilities 2.5 GB more beside 3
    GB of weights.  The runner holds each result until it has the next, so
    this collects (the engine it dropped is garbage in a cycle, its 8 GB
    pool with it) and deletes the logits of the call BEFORE the last, as
    families/zaya.py does and says why."""
    import gc

    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference.keye_vl2 import forward_following

    gc.collect()
    for old in _handed_out[:-1]:
        old.delete()
    del _handed_out[:-1]
    layers = {k: s.pattern.count(k) for k in "E*"}
    experts = kept = None
    if follow is not None:
        flat = (np.concatenate(follow) if isinstance(follow, list)
                else np.asarray(follow))
        cut = layers["E"] * s.top_k
        experts = flat[:, :cut].reshape(-1, layers["E"], s.top_k)
        kept = flat[:, cut:].reshape(len(flat), layers["*"], -1)
    out = forward_following(params, tokens, s, quant, experts,
                            follow_selection=kept)
    _handed_out.append(out["logits"])
    return {"logits": out["logits"],
            "routing": None if follow is not None else np.concatenate(
                [np.asarray(out[k], np.int16).reshape(len(tokens), -1)
                 for k in ("routing", "selection")], axis=1),
            "deficit": jnp.concatenate(
                [out["deficit"], out["selection_deficit"]], axis=1)}


# -------------------------------------------------------------------- sizes


def layer_params(s: Shape) -> Dict[str, int]:
    """Parameters of one layer of each kind, norms included; the attention
    layer split into attention proper and its indexer, an ``E`` layer into
    its router and one expert."""
    D, hd = s.dim, s.head_dim
    return {
        "attention": (D + 2 * D * s.heads * hd + 2 * D * s.kv_heads * hd
                      + 2 * hd),
        "indexer": (D * s.idx_heads * s.idx_dim + D * s.idx_dim
                    + 2 * s.idx_dim + D * s.idx_heads),
        "E": D + D * s.experts,
        "expert": 3 * D * s.moe_ffn,
    }


def num_params(s: Shape) -> int:
    """Parameters as run: the held experts only, both vocabulary tables."""
    n = layer_params(s)
    per = {"*": n["attention"] + n["indexer"],
           "E": n["E"] + s.held * n["expert"]}
    return sum(per[k] for k in s.pattern) + 2 * s.vocab * s.dim + s.dim


# -------------------------------------------------------------------- costs


def selected_tokens(s: Shape, live_tokens: float, slots: float) -> float:
    """The (query, position) pairs attention reads in one decode call: a
    slot keeps ``min(idx_topk, its context)``.  From the call's totals: all
    of the live positions while the mean context is within ``idx_topk``,
    else ``idx_topk`` a slot (exact wherever every context is past it, as
    in a cell whose prompts are all longer)."""
    return min(live_tokens, slots * s.idx_topk)


def paged_decode(s: Shape, live_tokens: float, slots: float,
                 itemsize: int = 2) -> Dict[str, Any]:
    """One attention layer's decode call, the least the mathematics needs.
    Attention proper: the SELECTED positions' ``kv_heads`` keys and values
    read once, a slot's ``heads`` queries read and outputs written; each
    (query head, selected position) pair multiplies over ``head_dim``
    twice.  Under ``indexer``: every LIVE position's one key of ``idx_dim``
    read once and the slots' ``idx_heads`` queries and weights; each (index
    head, live position) pair one product over ``idx_dim``.  Counted in
    TOKENS, not in the whole blocks a kernel fetches."""
    sel = selected_tokens(s, live_tokens, slots)
    kv = 2 * sel * s.kv_heads * s.head_dim * itemsize
    qo = 2 * slots * s.heads * s.head_dim * itemsize
    return {"flops": 4.0 * sel * s.heads * s.head_dim, "bytes": kv + qo,
            "indexer": {
                "flops": 2.0 * live_tokens * s.idx_heads * s.idx_dim,
                "bytes": (live_tokens * s.idx_dim * itemsize
                          + slots * s.idx_heads * (s.idx_dim * itemsize + 4))}}


def decode_step(s: Shape, live_tokens: float, slots: float,
                experts_touched: float, itemsize: int = 2) -> Dict[str, float]:
    """The whole decode program, one execution: the bytes it must move at
    least once and the operations of its matmuls.

    bytes = the weights of the experts that the tick's rows touched
    (``experts_touched``: summed over the ``E`` layers) + every other
    weight once + the selected keys and values and the live indexer keys
    read.  Activations are left out.  flops = 2 x the matmul weights a
    token meets x slots (``top_k x held / experts`` experts a layer on
    average here), plus the attention's and the indexer's."""
    n = layer_params(s)
    kinds = {k: s.pattern.count(k) for k in "*E"}
    fixed = (kinds["*"] * (n["attention"] + n["indexer"]) + kinds["E"] * n["E"]
             + 2 * s.vocab * s.dim + s.dim)
    attn = paged_decode(s, live_tokens, slots, itemsize)
    per_layer = {k: attn[k] + attn["indexer"][k] for k in ("flops", "bytes")}
    here = s.top_k * s.held / s.experts
    flops = (2.0 * slots * (fixed - s.vocab * s.dim
                            + kinds["E"] * here * n["expert"])
             + kinds["*"] * per_layer["flops"])
    return {"flops": flops,
            "bytes": ((fixed + experts_touched * n["expert"]) * itemsize
                      + kinds["*"] * per_layer["bytes"])}
