"""MiMo-V2's block (models/hybrid.py: window layers with a learned sink and
their own number of KV heads beside global layers, key heads 1.5 x the value
heads' width, rotation of a head's leading dims by a theta a kind, a value
scale, sigmoid top-k experts with none shared) at toy widths that keep the
shape, on the CPU, float32: chunked prefill + decode through ``ServingEngine``
and its TWO pools of two block shapes against the plain reference's full
forward (benchmarks/reference/mimo_v2.py, which imports nothing of the
program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mimo_v2 as family
from benchmarks.reference import mimo_v2 as ref
from benchmarks.weights_mimo_v2 import make_weights
from torchdistpackage_tpu.models import HybridConfig, init_hybrid_params
from torchdistpackage_tpu.serving import Request, ServingEngine
from torchdistpackage_tpu.serving import paged_cache as PC

#: a ``mimo_v2`` configuration file in small: 8 query heads of 24 over
#: values of 16 (1.5 x), 2 KV heads in the global layers and 4 in the window
#: layers, int(24 x 0.334) = 8 rotated dims, a window of ONE block of 8, the
#: pattern's global / window and dense / expert blocks, 4 of 16 experts held
TOY = {
    "name": "toy-mimo", "family": "mimo_v2", "hidden_size": 64,
    "num_attention_heads": 8, "swa_num_attention_heads": 8,
    "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
    "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
    "swa_v_head_dim": 16, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "attention_value_scale": 0.707, "attention_bias": False,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True,
    "sliding_window": 8, "sliding_window_size": 8, "attention_chunk_size": 8,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1], "moe_layer_freq": [0, 1, 1, 1, 1],
    "num_hidden_layers": 5, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "published": {"n_routed_experts": 16},
    "deployment_share": {"first_expert": 4}, "num_experts_per_tok": 4,
    "n_shared_experts": None, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": None,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "layernorm_epsilon": 1e-5, "vocab_size": 211,
    "max_position_embeddings": 512,
}
PATTERN = "*DWEWE*EWE"
F32 = jnp.float32
MAX_CTX = 64
#: (prompt, new tokens): prompts inside the window, across two and three
#: chunk boundaries of 16 and the window's edge of 8, answers that hand
#: window blocks on several times, more requests than slots
REQUESTS = ((5, 20), (19, 24), (37, 12), (8, 30), (30, 20), (16, 9))


@pytest.fixture(scope="module")
def toy():
    """(Shape, the program's config in float32, float32 weights)."""
    s = family.shape(TOY, MAX_CTX)
    cfg = dataclasses.replace(family.program_config(TOY, MAX_CTX), dtype=F32)
    params = jax.tree.map(lambda a: a.astype(F32), make_weights(s, 7))
    return s, cfg, params


def _serve(cfg, params, requests=REQUESTS, **kw):
    rng = np.random.RandomState(0)
    kw.setdefault("attn_impl", "gather")
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(params, cfg, num_slots=3, block_size=8, chunk=16,
                            max_ctx=MAX_CTX, **kw)
        for p, n in requests:
            eng.submit(Request(tokens=rng.randint(0, 211, p).tolist(),
                               max_new_tokens=n))
        eng.run_until_idle()
    return eng


def _served_gap(s, params, finished):
    """The widest gap by which a served token's logit lies below the
    reference's best."""
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for f in finished:
            toks = np.asarray(f["tokens"])
            p = len(toks) - f["new_tokens"]
            logits = np.asarray(ref.forward_logits(params, toks[:-1], s))[p - 1:]
            served = logits[np.arange(len(toks) - p), toks[p:]]
            worst = max(worst, float((logits.max(-1) - served).max()))
    return worst


@pytest.fixture(scope="module")
def served(toy):
    _, cfg, params = toy
    return _serve(cfg, params, run_ahead=True, record_routing=True)


def test_two_pools_of_two_block_shapes_and_unequal_widths(toy):
    s, cfg, params = toy
    assert s.pattern == cfg.pattern == PATTERN
    assert (cfg.kv_layers, cfg.window_layers, cfg.kv_pack) == (2, 3, 1)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rope_dims) == (24, 16, 8)
    assert (cfg.kv_heads, cfg.window_kv_heads, cfg.window) == (2, 4, 8)
    assert (cfg.rope_theta, cfg.global_rope_theta, cfg.value_scale) == (
        1e4, 1e7, 0.707)
    # keys transposed, [.., width, block], beside values [.., block, width];
    # the window pool at ITS head count; every leaf at its logical bytes
    pool = PC.init_paged_kv(cfg, 7, 8, window_blocks=5)
    assert pool["k"].shape == (2, 7, 2, 24, 8)
    assert pool["v"].shape == (2, 7, 2, 8, 16)
    assert pool["win"]["k"].shape == (3, 5, 4, 24, 8)
    assert pool["win"]["v"].shape == (3, 5, 4, 8, 16)
    assert PC.block_size_of(pool) == 8
    assert PC.keys_transposed(pool["k"], pool["v"])
    assert PC.pool_bytes(pool) == PC.expected_pool_bytes(
        cfg, 7, 8, window_blocks=5)
    assert PC.window_bytes(pool) == 3 * 5 * 4 * 8 * (24 + 16) * 4
    # the family's count is the tree's, and the program's own seeded tree's
    # with a sink a window layer
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == family.num_params(s)
    own = init_hybrid_params(jax.random.PRNGKey(0), cfg)
    assert n == sum(int(np.prod(a.shape)) for a in jax.tree.leaves(own)) + 3 * 8
    assert [("sink" in lp) for lp in params["layers"]] == [
        k == "W" for k in PATTERN]
    # the engine's span says a head's widths and each pool's heads
    from torchdistpackage_tpu.utils.profiling import spans
    eng = ServingEngine(params, cfg, num_slots=2, block_size=8, chunk=16,
                        max_ctx=MAX_CTX, attn_impl="gather")
    attrs = [r[5] for r in spans.snapshot()
             if r[2] == "tdp:engine.init.pool"][-1]
    assert (attrs["key_width"], attrs["value_width"], attrs["kv_heads"],
            attrs["window_kv_heads"]) == (24, 16, 2, 4)
    assert attrs["window_blocks"] == eng.window_blocks == 1 + 2 * 3
    assert attrs["window_bytes"] == PC.window_bytes(eng.cache)
    with pytest.raises(ValueError, match="group-limited"):
        family.shape({**TOY, "n_group": 2}, MAX_CTX)


def test_prefill_and_decode_through_both_pools_read_the_reference(
        toy, served):
    """Six requests on three slots: prefill in chunks of 16 across the
    window's edge, then decoding past several hand-ons of window blocks;
    every served token is the reference's first within 1e-4 (it reads
    ~1e-6), the reference following nothing."""
    s, cfg, params = toy
    assert len(served.finished) == len(REQUESTS)
    assert _served_gap(s, params, served.finished.values()) <= 1e-4
    assert served.stats["blocks_handed_on"] >= 10
    assert served.serving_summary()["prefill_signatures"] == 1
    assert served.serving_summary()["decode_signatures"] == 1


def test_the_kernel_path_and_the_unpipelined_engine_serve_the_same(
        toy, served):
    _, cfg, params = toy
    kernel = _serve(cfg, params, requests=REQUESTS[:3], attn_impl="pallas")
    plain = _serve(cfg, params)
    for rid, f in served.finished.items():
        np.testing.assert_array_equal(plain.finished[rid]["tokens"],
                                      f["tokens"])
        if rid in kernel.finished:
            np.testing.assert_array_equal(kernel.finished[rid]["tokens"],
                                          f["tokens"])


# ------------------------------------------- the faults the model could hide


def _layers(params, kinds, change):
    """The tree with ``change(layer)`` in place of every layer of ``kinds``."""
    return {**params, "layers": [
        change(lp) if kind in kinds else lp
        for kind, lp in zip(PATTERN, params["layers"])]}


def _regrouped(lp, s):
    """A global layer's query heads grouped as a WINDOW layer groups its own
    (``heads / window_kv_heads`` to a KV head, as if the layer had the
    window layers' 4 KV heads' worth of keys): heads 2-3 and 4-5 change
    places in ``W_qkv``'s query columns and in ``W_o``'s rows, so each reads
    the other KV head."""
    hd, hv, H = s.head_dim, s.v_head_dim, s.heads
    order = np.asarray([0, 1, 4, 5, 2, 3, 6, 7])
    q = lp["wqkv"][:, :H * hd].reshape(-1, H, hd)[:, order].reshape(
        -1, H * hd)
    return {**lp, "wqkv": jnp.concatenate([q, lp["wqkv"][:, H * hd:]], -1),
            "wo": lp["wo"].reshape(H, hv, -1)[order].reshape(H * hv, -1)}


def _route_with(monkeypatch, weigh):
    """The serving router with ``weigh(scores, bias, idx, cfg)`` for its
    weights."""
    from torchdistpackage_tpu.parallel import moe

    def route(router, tokens, cfg):
        scores = jax.nn.sigmoid(jnp.dot(
            tokens, router["w"], preferred_element_type=jnp.float32))
        _, idx = jax.lax.top_k(scores + router["bias"], cfg.top_k)
        return scores, weigh(scores, router["bias"], idx, cfg), idx

    monkeypatch.setattr(moe, "_serve_route", route)


def _renormalised(of):
    w = lambda scores, bias, idx, cfg: (
        lambda c: c / (c.sum(-1, keepdims=True) + 1e-20))(
            jnp.take_along_axis(of(scores, bias), idx, axis=-1))
    return w


#: fault -> (cfg, params, monkeypatch) -> (cfg, params)
FAULTS = {
    "sink_dropped": lambda s, c, p, mp: (c, _layers(p, "W", lambda lp: {
        k: v for k, v in lp.items() if k != "sink"})),
    "sink_on_a_global_layer": lambda s, c, p, mp: (c, _layers(
        p, "*", lambda lp: {**lp, "sink": jnp.full((8,), 3.5, F32)})),
    "value_scale_dropped": lambda s, c, p, mp: (
        dataclasses.replace(c, value_scale=1.0), p),
    "whole_head_rotated": lambda s, c, p, mp: (
        dataclasses.replace(c, rope_dims=0), p),
    "nothing_rotated": lambda s, c, p, mp: (mp.setattr(
        "torchdistpackage_tpu.models.hybrid.apply_rope",
        lambda a, cache=None: a), (c, p))[1],
    "thetas_swapped": lambda s, c, p, mp: (dataclasses.replace(
        c, rope_theta=c.global_rope_theta, global_rope_theta=c.rope_theta), p),
    "window_off_by_one": lambda s, c, p, mp: (
        dataclasses.replace(c, window=9), p),
    "window_layers_left_global": lambda s, c, p, mp: (
        dataclasses.replace(c, window=1 << 20), p),
    "global_layers_grouped_as_window_layers": lambda s, c, p, mp: (
        c, _layers(p, "*", lambda lp: _regrouped(lp, s))),
    "weights_not_renormalised": lambda s, c, p, mp: (_route_with(
        mp, lambda scores, bias, idx, cfg: jnp.take_along_axis(
            scores, idx, axis=-1)), (c, p))[1],
    "selection_bias_in_the_weights": lambda s, c, p, mp: (_route_with(
        mp, _renormalised(lambda scores, bias: scores + bias)), (c, p))[1],
}


def _last_logits(cfg, params, tokens):
    """The program's logits behind ``tokens``: one prefill call of 48 rows
    (padding behind the prompt) through ``paged_forward_hybrid``, no engine;
    both pools hold every block, so a window that is wrong reads keys that
    are there."""
    n = len(tokens)
    padded = jnp.zeros((1, 48), jnp.int32).at[0, :n].set(jnp.asarray(tokens))
    table = jnp.arange(1, 7, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        _, _, logits, _ = PC.paged_forward_hybrid(
            params, padded, cfg,
            PC.init_paged_kv(cfg, 7, 8, window_blocks=7),
            {"ssm": (), "conv": (), "tail": ()}, (table, table),
            jnp.zeros((1,), jnp.int32), jnp.asarray([n], jnp.int32),
            last_idx=jnp.asarray([n - 1], jnp.int32))
    return np.asarray(logits[0])


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
def test_each_fault_the_model_adds_reads_far_over_the_tolerance(
        toy, fault, monkeypatch):
    """The program's logits behind a prompt of 43 against the reference's:
    within 1e-4 as it stands, and thirty times the tolerance and more with
    any one of the model's constants, kinds or orders wrong."""
    s, cfg, params = toy
    tokens = np.random.RandomState(4).randint(0, 211, 43)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward_logits(params, tokens, s))[-1]
    if fault is not None:
        cfg, params = FAULTS[fault](s, cfg, params, monkeypatch)
    off = float(np.abs(_last_logits(cfg, params, tokens) - want).max())
    if fault is None:
        assert off <= 1e-4, off
    else:
        assert off > 30 * 1e-4, (fault, off)


def test_the_reference_in_bands_is_the_reference_dense(toy, monkeypatch):
    """A window layer's block of rows scored against ``window + ROWS`` keys
    (what the cell's 26,624 positions take) gives the logits of the same
    rows scored against every key."""
    s, _, params = toy
    tokens = np.random.RandomState(5).randint(0, 211, 48)
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(ref.forward_logits(params, tokens, s))
        monkeypatch.setattr(ref, "ROWS", 8)
        ref._jitted.cache_clear()
        banded = np.asarray(ref.forward_logits(params, tokens, s))
    ref._jitted.cache_clear()
    np.testing.assert_allclose(banded, dense, atol=2e-6)


def test_the_sink_takes_a_real_share_of_a_window_row(toy):
    """A sink that rounds to nothing tests nothing: at seeded weights a full
    window row gives its sink 5-30% of the softmax's mass (toy: 8 keys, the
    draw moved so that the share is the cell's), head by head."""
    s, _, params = toy
    lp = jax.tree.map(lambda a: a.astype(F32), params["layers"][2])
    x = jax.random.normal(jax.random.PRNGKey(3), (32, s.dim), F32)
    H, hd = s.heads, s.head_dim
    qkv = x @ lp["wqkv"]
    q = qkv[:, :H * hd].reshape(32, H, hd)
    k = qkv[:, H * hd:H * hd + s.window_kv_heads * hd].reshape(32, -1, hd)
    sc = jnp.einsum("thd,jhd->htj", q, jnp.repeat(k, H // k.shape[1], 1))
    sc = sc[:, 8:] * hd ** -0.5                       # rows with a full window
    t, j = jnp.arange(8, 32)[:, None], jnp.arange(32)[None, :]
    e = jnp.where((j <= t) & (j > t - 8), jnp.exp(sc), 0.0).sum(-1)
    # the toy's rows have 8 keys where the cell's have 128: the same draw
    # less log(16) takes the share the cell's takes
    share = jnp.exp(lp["sink"] - np.log(16.0))[:, None] / (
        jnp.exp(lp["sink"] - np.log(16.0))[:, None] + e)
    assert 0.05 < float(share.mean()) < 0.30, float(share.mean())


# ----------------------------------------- the share ties back to the model


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(toy):
    """The guide's one test that ties the share to the model: a toy expert
    layer's routed part computed share by share (4 of 16 experts at a time,
    ``held_first`` 0, 4, 8, 12), by the PROGRAM, adds up to what the
    reference gives for the whole layer with all 16 held."""
    from torchdistpackage_tpu.parallel.moe import moe_serve_forward

    s, cfg, _ = toy
    whole = dataclasses.replace(s, held_first=0, held=16, pattern="E")
    p = jax.tree.map(lambda a: a.astype(F32),
                     make_weights(whole, 11))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, s.dim), F32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.moe(p, x[0], whole)
        total = 0.0
        for first in range(0, 16, 4):
            part = {**p, "experts": jax.tree.map(
                lambda w: w[first:first + 4], p["experts"])}
            mcfg = dataclasses.replace(cfg, moe_held=(first, 4)).moe
            total = total + moe_serve_forward(part, x, mcfg)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


# --------------------------------------- the other families keep their numbers


def test_the_new_fields_at_their_defaults_change_no_other_family():
    """A window / global stack without the model's fields: the forward with
    the new fields at their defaults is, bit for bit, the forward that
    spells the old shape out (values as wide as keys, the window layers'
    KV heads the global layers', the whole head rotated), and its pools are
    one block shape, a head a row."""
    old = HybridConfig(
        vocab_size=211, dim=64, pattern="WD*DWD", max_seq=MAX_CTX, nheads=4,
        kv_heads=2, window=8, dense_ffn=96, dtype=F32)
    assert (old.value_width, old.window_heads, old.rope_dims,
            old.global_rope_theta, old.value_scale) == (16, 2, 0, None, 1.0)
    params = init_hybrid_params(jax.random.PRNGKey(1), old)
    assert "wqkv" not in params["layers"][0]
    spelled = dataclasses.replace(
        old, v_head_dim=16, window_kv_heads=2, rope_dims=16)
    a = _serve(old, params, requests=REQUESTS[:3])
    b = _serve(spelled, params, requests=REQUESTS[:3])
    pool = PC.init_paged_kv(old, 5, 8, window_blocks=4)
    assert pool["k"].shape == pool["v"].shape == (1, 5, 2, 8, 16)
    assert pool["win"]["k"].shape == pool["win"]["v"].shape == (2, 4, 2, 8, 16)
    assert not PC.keys_transposed(pool["k"], pool["v"])
    for rid, f in a.finished.items():
        np.testing.assert_array_equal(b.finished[rid]["tokens"], f["tokens"])
    # the fields are the '*' / 'W' layers' alone
    with pytest.raises(ValueError, match="alone"):
        HybridConfig(vocab_size=8, dim=64, pattern="CE", max_seq=8, nheads=4,
                     kv_heads=2, v_head_dim=8, moe_experts=2, moe_ffn=8)
