"""The hybrid family's indexed-attention shape (models/hybrid.py kind ``S``,
ops/dsa_attention.py, the pool's ``idx`` leaf, the softmax router with a
held range: the ``keye_vl2`` architecture) at toy widths on the CPU, in
float32: chunked prefill + decode through ``ServingEngine`` against the plain
reference's full forward (benchmarks/reference/keye_vl2.py, which imports
nothing of the program) and the SAME selected sets; the three position rows;
``topk >= context`` as plain rotated GQA; a moved chunk boundary; the prefix
cache; the shares of the expert ranges; the kernels against their oracle;
the refusals."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import keye_vl2 as family
from benchmarks.reference import keye_vl2 as ref
from benchmarks.weights_keye_vl2 import make_weights
from torchdistpackage_tpu.models import init_hybrid_params
from torchdistpackage_tpu.models.hybrid import (
    hybrid_paged_forward, init_state, mrope_cache)
from torchdistpackage_tpu.ops import dsa_attention as D
from torchdistpackage_tpu.ops import paged_attention as P
from torchdistpackage_tpu.parallel.tensor_parallel.layers import rope_cache
from torchdistpackage_tpu.serving import (
    Request, ServingEngine, expected_pool_bytes, init_paged_kv, pool_bytes)
from torchdistpackage_tpu.serving.paged_cache import (
    _indexed_cache_ops, index_bytes)

#: a ``keye_vl2`` configuration file in small: 2 blocks, 4 query heads over 2
#: key heads of 16, an indexer of 2 heads of 8 that keeps 16 positions, 8
#: experts of which 4 are held, from the third on (``first_expert`` 2)
TOY = {
    "name": "toy-keye", "family": "keye_vl2", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "hidden_act": "silu", "norm_topk_prob": True,
    "tie_word_embeddings": False, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "num_hidden_layers": 2, "num_experts": 4,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 32,
                  "q_chunk_size": 32, "topk": 16},
    "sliding_window": None, "use_sliding_window": False,
    "vocab_size": 128, "max_position_embeddings": 512,
    "published": {"num_experts": 8}, "deployment_share": {"first_expert": 2},
    "assumed": {"indexer_rope_dim": {"value": 4}},
}
F32 = jnp.float32
TOL = 1e-4


@pytest.fixture(scope="module")
def toy():
    """(Shape, the program's config in float32, float32 weights)."""
    s = family.shape(TOY, 96)
    cfg = dataclasses.replace(family.program_config(TOY, 96), dtype=F32)
    return s, cfg, make_weights(s, 7, dtype=F32)


def count(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def forward_of(cfg, params, impl, tables):
    """The model function on one row of tokens, jitted: ``run(cache, tokens
    [1, S], offset [1], positions) -> (cache, logits [V], the positions
    every row kept in every 'S' layer as bits [S, layers, words])``."""
    ops = lambda layer: _indexed_cache_ops(tables, impl, cfg, layer)

    @jax.jit
    def run(cache, toks, off, positions=None):
        cache, _, logits, m = hybrid_paged_forward(
            params, toks, cfg, cache, init_state(cfg, 1),
            jnp.full((1,), toks.shape[1]), ops, off, positions=positions)
        return cache, logits[0], m["selection"][0]

    return run


def test_pattern_pool_leaves_and_parameter_counts(toy):
    s, cfg, params = toy
    assert s.pattern == "*E*E" and cfg.pattern == "SESE"
    assert (cfg.nlayers, cfg.kv_layers, cfg.state_layers) == (4, 2, 0)
    assert cfg.index_width == 8 and cfg.latent_width == 0
    assert cfg.moe.score == "softmax" and cfg.moe.held == (2, 4)
    pool = init_paged_kv(cfg, 7, 8)
    assert pool["k"].shape == pool["v"].shape == (2, 7, 2, 8, 16)
    # the indexer's key: one row of 8 a position, a block laid transposed
    assert pool["idx"].shape == (2, 7, 1, 8, 8)
    assert index_bytes(pool) == 2 * 7 * 8 * 8 * 4
    assert pool_bytes(pool) == expected_pool_bytes(cfg, 7, 8) \
        == 2 * 2 * 7 * 2 * 8 * 16 * 4 + index_bytes(pool)
    assert init_state(cfg, 3) == {"ssm": (), "conv": (), "tail": ()}
    assert count(params) == family.num_params(s)
    own = init_hybrid_params(jax.random.PRNGKey(0), cfg)
    for mine, theirs in zip(own["layers"], params["layers"]):
        mine.get("router", {}).pop("bias", None)   # the softmax route has none
        assert jax.tree.map(jnp.shape, mine) == jax.tree.map(jnp.shape, theirs)
    for bad, why in (("S*", "one kind of block pool"),
                     ("SL", "one kind of block pool"), ("SX", "pattern")):
        with pytest.raises(ValueError, match=why):
            dataclasses.replace(cfg, pattern=bad)
    with pytest.raises(ValueError, match="idx_heads"):
        dataclasses.replace(cfg, idx_topk=0)
    with pytest.raises(ValueError, match="mrope_section"):
        dataclasses.replace(cfg, mrope_section=(2, 3, 4))
    with pytest.raises(NotImplementedError, match="int8"):
        init_paged_kv(cfg, 7, 8, quantized=True)


def test_the_family_counts_what_the_issue_counted():
    """Parameters a layer and the cut's bytes from the published widths
    (ISSUE 39's arithmetic), and the cost functions by hand at one size."""
    from benchmarks import arch as A

    s = family.shape(A.load_config("keye-vl-2.0-30b-a3b"), 14336)
    n = family.layer_params(s)
    assert round(n["attention"] / 1e6, 2) == 18.88       # 18.87 + the norms
    assert round(n["indexer"] / 1e6, 2) == 2.26
    assert round(n["E"] / 1e6, 2) == 0.26
    assert round(n["expert"] / 1e6, 2) == 4.72
    assert s.pattern == "*E" * 8 and (s.experts, s.held, s.top_k) == (128, 32, 8)
    assert round(family.num_params(s) / 1e9, 3) == 1.535
    assert round(family.num_params(s) * 2 / 1e9, 2) == 3.07
    live, slots = 32 * 9000.0, 32.0
    paged = family.paged_decode(s, live, slots)
    sel = 32 * 2048
    assert family.selected_tokens(s, live, slots) == sel
    assert family.selected_tokens(s, 32 * 1000.0, slots) == 32 * 1000.0
    assert paged["bytes"] == 2 * sel * 4 * 128 * 2 + 2 * 32 * 32 * 128 * 2
    assert paged["flops"] == 4.0 * sel * 32 * 128
    # the indexer: 128 B and 2,048 flop a live position
    assert paged["indexer"]["flops"] == live * 2048
    assert paged["indexer"]["bytes"] == live * 128 + 32 * 16 * (128 + 4)
    step = family.decode_step(s, live, slots, experts_touched=8 * 32.0)
    # every held expert touched: every weight once, plus a layer's reads x 8
    assert step["bytes"] == family.num_params(s) * 2 + 8 * (
        paged["bytes"] + paged["indexer"]["bytes"])
    assert step["flops"] > 2.0 * slots * 8 * 2 * n["expert"]


# ------------------------------------------------------------------ positions


def test_three_position_rows(toy):
    """Equal rows are plain rope, bit for bit; unequal rows turn each
    section by its own row, in the program as in the reference."""
    s, cfg, params = toy
    pos = jnp.arange(5, 29)
    same = jnp.broadcast_to(pos, (3, 1, 24))
    cos, sin = mrope_cache(same, 16, 1e4, (2, 3, 3))
    c0, s0 = rope_cache(pos, 16, 1e4)
    np.testing.assert_array_equal(cos[0, 0], c0[0, 0])
    np.testing.assert_array_equal(sin[0, 0], s0[0, 0])
    apart = jnp.stack([pos, pos // 3, pos % 7])[:, None]
    cos3, _ = mrope_cache(apart, 16, 1e4, (2, 3, 3))
    np.testing.assert_array_equal(cos3[0, 0, :, :2], c0[0, 0, :, :2])
    assert not np.allclose(cos3[0, 0, :, 2:], c0[0, 0, :, 2:])

    # the model function against the reference, three unequal rows
    rng = np.random.RandomState(3)
    S = 40
    toks = rng.randint(0, 128, S)
    rows = np.stack([np.arange(S), np.arange(S) // 4, np.arange(S) % 5])
    pool = init_paged_kv(cfg, 6, 8)
    run = forward_of(cfg, params, "gather",
                     jnp.arange(1, 6, dtype=jnp.int32)[None])
    zero = jnp.zeros((1,), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.forward_following(params, toks, s, positions=rows)
        logits = run(pool, jnp.asarray(toks)[None], zero,
                     jnp.asarray(rows)[:, None])[1][None]
        text = run(pool, jnp.asarray(toks)[None], zero)[1][None]
    np.testing.assert_allclose(logits[0], want["logits"][-1], atol=TOL)
    assert np.abs(np.asarray(text[0] - logits[0])).max() > 100 * TOL


# ------------------------------------------------------------------ the engine


def _serve(toy, prompts=(70, 41, 19, 64), new=(12, 9, 14, 6), **kw):
    _, cfg, params = toy
    rng = np.random.RandomState(11)
    kw = {"num_slots": 2, "block_size": 8, "chunk": 32, "max_ctx": 96, **kw}
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(params, cfg, **kw)
        for n, m in zip(prompts, new):
            eng.submit(Request(tokens=rng.randint(0, 128, n).tolist(),
                               max_new_tokens=m))
        eng.run_until_idle()
    return eng


@pytest.fixture(scope="module")
def served(toy):
    return _serve(toy, attn_impl="gather", record_routing=True,
                  run_ahead=True)


def _record(f):
    """A finished request's record, put together: (experts [n, 2, 2], kept
    positions as words [n, 2, 6])."""
    flat = np.concatenate(f["routing"])
    return flat[:, :4].reshape(-1, 2, 2), flat[:, 4:].reshape(len(flat), 2, -1)


def _gap(s, params, finished, quant=None):
    worst = 0.0
    for f in finished:
        toks = np.asarray(f["tokens"])
        low = None if quant is None else ref.forward_following(
            params, toks[:-1], s, quant)
        took = _record(f) if low is None else (low["routing"],
                                               low["selection"])
        out = ref.forward_following(params, toks[:-1], s, follow=took[0],
                                    follow_selection=took[1])
        lg = np.asarray(out["logits"])
        got = toks[1:] if low is None else np.argmax(low["logits"], -1)
        gaps = lg.max(-1) - lg[np.arange(len(got)), got]
        worst = max(worst, float(gaps[f["prompt_len"] - 1:].max()))
    return worst


def test_engine_prefill_and_decode_equal_the_reference_forward(toy, served):
    """Logits, not tokens: every served token's logit in the reference's
    full forward (two slots of unequal length, prompts of 1-3 chunks, with
    ``run_ahead`` and ``record_routing``), the experts AND the kept
    positions the reference would choose itself (the record of every fed
    position, every layer), and the fp8 control failing the same
    tolerance."""
    s, _, params = toy
    assert len(served.finished) == 4 and served.audit(heal=False)["ok"]
    with jax.default_matmul_precision("highest"):
        for f in served.finished.values():
            f["prompt_len"] = len(f["tokens"]) - f["new_tokens"]
            toks, (experts, chosen) = np.asarray(f["tokens"]), _record(f)
            n = len(toks) - 1
            # the calls' pieces as fetched: a chunk's rows, a decode row
            assert isinstance(f["routing"], list)
            assert max(len(p) for p in f["routing"]) <= 32
            assert experts.shape == (n, 2, 2)
            assert chosen.shape == (n, 2, 96 // 16)
            own = ref.forward_following(params, toks[:-1], s)
            np.testing.assert_array_equal(
                np.sort(experts, -1), np.sort(own["routing"], -1))
            for layer in range(2):
                kept = [np.asarray(ref.unpack_mask(jnp.asarray(
                    words[:, layer]), n)) for words in (
                        chosen, own["selection"])]
                np.testing.assert_array_equal(kept[0], kept[1])
                assert (kept[0].sum(-1) == np.minimum(
                    16, np.arange(n) + 1)).all()
            followed = ref.forward_following(
                params, toks[:-1], s, follow=experts,
                follow_selection=chosen)
            assert float(followed["selection_deficit"].max()) == 0.0
        gap = _gap(s, params, served.finished.values())
        low = _gap(s, params, served.finished.values(), quant="fp8")
    assert gap <= TOL, gap
    assert low > 100 * TOL, low


def test_the_program_selects_the_references_positions(toy):
    """One sequence of 90 positions, prefilled in chunks of 32 and decoded:
    the positions the program's ops say they kept, from the pool they
    filled, are the reference's mask, layer 0's (the same input on both
    sides), row for row; every row keeps min(16, t + 1)."""
    s, cfg, params = toy
    rng = np.random.RandomState(5)
    S = 90
    toks = rng.randint(0, 128, S)
    pool = init_paged_kv(cfg, 13, 8)
    tables = jnp.arange(1, 13, dtype=jnp.int32)[None]
    lp = jax.tree.map(lambda a: a.astype(F32), params["layers"][0])
    with jax.default_matmul_precision("highest"):
        x = ref.rms(params["tok_emb"][toks], lp["norm"]["scale"], s.eps)
        _, words, deficit = ref.attention(lp, x, ref.text_positions(S), s)
        want = np.asarray(ref.unpack_mask(words, S))
        assert (want.sum(-1) == np.minimum(16, np.arange(S) + 1)).all()
        assert float(deficit.max()) == 0.0
        runs = {impl: forward_of(cfg, params, impl, tables)
                for impl in ("gather", "pallas")}

        def call(impl, cache, at, n):
            cache, _, kept = runs[impl](
                cache, jnp.asarray(toks[at:at + n])[None],
                jnp.full((1,), at, jnp.int32))
            got = np.asarray(ref.unpack_mask(kept[:, 0], S))
            np.testing.assert_array_equal(got, want[at:at + n])
            return cache

        cache, at = dict(pool), 0
        for n in (32, 32, 24, 1, 1):      # chunks, then decode rows
            if n != 32:   # the kernels, on the pool the oracle's path filled
                call("pallas", cache, at, n)
            cache = call("gather", cache, at, n)
            at += n


def test_a_followed_selection_is_taken_and_held_to_the_own_scores(toy):
    """The reference takes the positions it is handed in place of its own:
    a row's lowest-scored kept position swapped for its best dropped one
    moves the logits and reads a deficit of that swap's size; every
    position kept (a skipped selection) reads the scores' whole spread;
    rows past the handed ones select for themselves."""
    s, _, params = toy
    toks = np.random.RandomState(3).randint(0, 128, 80)
    with jax.default_matmul_precision("highest"):
        own = ref.forward_following(params, toks, s)
        same = ref.forward_following(
            params, toks, s, follow=np.asarray(own["routing"]),
            follow_selection=np.asarray(own["selection"])[:50])
        np.testing.assert_array_equal(same["logits"], own["logits"])
        kept = np.asarray(ref.unpack_mask(own["selection"][:, 0], 80))
        lp = jax.tree.map(lambda a: a.astype(F32), params["layers"][0])
        x = ref.rms(params["tok_emb"][toks], lp["norm"]["scale"], s.eps)
        sc = np.asarray(ref.index_scores(lp, x, ref.text_positions(80), s))
        t = 60
        worst = np.flatnonzero(kept[t])[np.argmin(sc[t][kept[t]])]
        best = np.flatnonzero(~kept[t][:t + 1])[
            np.argmax(sc[t][:t + 1][~kept[t][:t + 1]])]
        swapped = kept.copy()
        swapped[t, worst], swapped[t, best] = False, True
        words = np.asarray(own["selection"]).copy()
        words[:, 0] = np.asarray(ref.pack_mask(jnp.asarray(swapped)))
        got = ref.forward_following(params, toks, s, follow_selection=words)
        d = np.asarray(got["selection_deficit"])
        scale = np.sqrt(np.mean(sc[np.isfinite(sc)] ** 2))
        assert d[t, 0] == pytest.approx(
            (sc[t, worst] - sc[t, best]) / scale, rel=1e-4)
        assert d[:, 0].argmax() == t and (d[:t] == 0).all()
        assert float(jnp.abs(got["logits"][t] - own["logits"][t]).max()) > 1e-4
        np.testing.assert_array_equal(got["logits"][:t], own["logits"][:t])
        everything = np.asarray(ref.pack_mask(jnp.tril(jnp.ones((80, 80), bool))))
        words[:, 0] = everything
        skipped = ref.forward_following(params, toks, s,
                                        follow_selection=words)
        assert float(skipped["selection_deficit"][:, 0].max()) > 1.0


def test_a_moved_chunk_boundary_and_the_kernel_path_change_no_token(
        toy, served):
    """chunk 16 and 24 in place of 32 (no ``run_ahead``), and the Pallas
    kernels in interpret mode: the same tokens, request for request."""
    for kw in ({"chunk": 16}, {"chunk": 24, "attn_impl": "gather"},
               {"attn_impl": "pallas"}):
        got = _serve(toy, **{"attn_impl": "gather", **kw})
        for rid, f in served.finished.items():
            np.testing.assert_array_equal(got.finished[rid]["tokens"],
                                          f["tokens"])


def test_prefix_cache_gives_the_tokens_it_gives_without(toy):
    """A block's K, V and indexer key depend on nothing before it: blocks
    are shared by prefix, copy-on-write carries all three leaves."""
    _, cfg, params = toy
    rng = np.random.RandomState(2)
    system = rng.randint(0, 128, 48).tolist()
    prompts = [system + rng.randint(0, 128, n).tolist() for n in (9, 17, 0)]
    prompts.append(list(system))

    def run(**kw):
        with jax.default_matmul_precision("highest"):
            eng = ServingEngine(params, cfg, num_slots=2, block_size=8,
                                chunk=32, max_ctx=96, attn_impl="gather", **kw)
            for p in prompts:
                eng.submit(Request(tokens=p, max_new_tokens=8))
                eng.run_until_idle()
        return eng

    plain, shared = run(), run(prefix_cache=True)
    for rid, f in plain.finished.items():
        np.testing.assert_array_equal(shared.finished[rid]["tokens"],
                                      f["tokens"])
    summ = shared.serving_summary()
    assert summ["prefix_hit_rate"] > 0
    assert summ["prefix_cache"]["cow_copies"] >= 1
    assert shared.audit(heal=False)["ok"]


def test_engine_spans_counters_and_summary(toy, served):
    summ = served.serving_summary()
    assert summ["prefill_signatures"] == summ["decode_signatures"] == 1
    assert served.state_model and served.state_bytes == 0
    kv = summ["kv_pool"]
    assert kv["pool_bytes"] == kv["pool_bytes_expected"]
    assert kv["index_bytes"] == index_bytes(served.cache) > 0
    st = served.stats
    assert 0 < st["moe_rows_held"] < st["moe_rows_routed"]   # 4 of 8 held
    from torchdistpackage_tpu.utils.profiling import spans
    recs = spans.snapshot()
    pools = [r for r in recs if r[2] == "tdp:engine.init.pool"]
    assert pools[-1][5]["bytes"] == pool_bytes(served.cache)
    assert pools[-1][5]["index_bytes"] == index_bytes(served.cache)
    calls = [r[5] for r in recs if r[2] in (
        "tdp:engine.decode", "tdp:engine.prefill")
        and "indexed_positions" in r[5]]
    assert calls and all(
        0 < c["selected_positions"] <= c["indexed_positions"] for c in calls)
    assert any(c["selected_positions"] < c["indexed_positions"]
               for c in calls)
    decodes = [r[5] for r in recs if r[2] == "tdp:engine.decode"
               and "indexed_positions" in r[5]]
    # a decode row scores its whole context: the span's live_tokens
    assert all(c["indexed_positions"] == c["live_tokens"] for c in decodes)
    ticks = [t for t in served.tick_records if t["indexed_positions"]]
    assert ticks and all(
        0 < t["selected_positions"] <= t["indexed_positions"] for t in ticks)


def test_position_counts_by_hand():
    # one row of 5 real positions from offset 14, topk 16: contexts 15..19
    assert D.position_counts([14], [5], 16) == (15 + 16 + 17 + 18 + 19,
                                                15 + 16 * 4)
    assert D.position_counts([0, 40, 7], [3, 1, 0], 16) == (6 + 41, 6 + 16)


# ------------------------------------------------- the layer against plain GQA


def test_topk_past_the_context_is_rotated_gqa_without_an_indexer(toy):
    """``idx_topk`` >= every context: the layer attends to every cached
    position, whatever the indexer scores: the reference with its selection
    taken out, and the same logits with the indexer's weights redrawn."""
    s, cfg, params = toy
    wide = dataclasses.replace(cfg, idx_topk=128)
    rng = np.random.RandomState(9)
    S = 48
    toks = jnp.asarray(rng.randint(0, 128, S))[None]
    tables = jnp.arange(1, 7, dtype=jnp.int32)[None]
    zero = jnp.zeros((1,), jnp.int32)

    def last_logits(c, p):
        with jax.default_matmul_precision("highest"):
            return forward_of(c, p, "gather", tables)(
                init_paged_kv(c, 7, 8), toks, zero)[1]

    redrawn = jax.tree.map(lambda a: a, params)
    for lp in redrawn["layers"][::2]:
        lp["w_idx"] = -lp["w_idx"]
        lp["wq_idx"] = lp["wq_idx"][::-1]
    with jax.default_matmul_precision("highest"):
        all_of_it = ref.forward_logits(
            params, np.asarray(toks[0]),
            dataclasses.replace(s, idx_topk=128))[-1]
        selected = ref.forward_logits(params, np.asarray(toks[0]), s)[-1]
    np.testing.assert_allclose(last_logits(wide, params), all_of_it, atol=TOL)
    np.testing.assert_allclose(last_logits(wide, redrawn), all_of_it,
                               atol=TOL)
    # and the selection is not idle at topk 16
    np.testing.assert_allclose(last_logits(cfg, params), selected, atol=TOL)
    assert np.abs(np.asarray(selected - all_of_it)).max() > 100 * TOL


# ------------------------------------------------------------- expert shares


def test_the_shares_of_the_expert_ranges_add_up_to_the_uncut_layer(toy):
    """Two halves of the 8 experts, each what one chip of an EP pair
    computes, add up to the layer with every expert held."""
    from torchdistpackage_tpu.parallel.moe import moe_serve_forward

    s, cfg, _ = toy
    whole = dataclasses.replace(s, held_first=0, held=8)
    lp = make_weights(dataclasses.replace(whole, pattern="E"), 3,
                      dtype=F32)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 37, 64), F32)
    with jax.default_matmul_precision("highest"):
        want, idx, _ = ref.moe(lp, x[0], whole)
        full = moe_serve_forward(
            lp, x, dataclasses.replace(cfg.moe, held=(0, 8)))
        parts = []
        for first in (0, 4):
            share = {**lp, "experts": jax.tree.map(
                lambda a: a[first:first + 4], lp["experts"])}
            y, m = moe_serve_forward(
                share, x, dataclasses.replace(cfg.moe, held=(first, 4)),
                return_metrics=True)
            parts.append(y)
            np.testing.assert_array_equal(
                np.sort(m["gate_idx"][0], -1), np.sort(idx, -1))
            got, _, _ = ref.moe(share, x[0], dataclasses.replace(
                s, held_first=first, held=4))
            np.testing.assert_allclose(y[0], got, atol=TOL)
    np.testing.assert_allclose(full[0], want, atol=TOL)
    np.testing.assert_allclose((parts[0] + parts[1])[0], want, atol=TOL)


# ------------------------------------------------------ kernels against oracle


def _case(rng, B, S_in, mb, bs, offs, H=4, Hkv=2, hd=16, J=2, di=8):
    nb = 1 + B * mb
    f = lambda *sh: jnp.asarray(rng.standard_normal(sh), F32)
    tables = jnp.asarray(
        1 + rng.permutation(B * mb).reshape(B, mb), jnp.int32)
    return dict(q=f(B, H, S_in, hd), kp=f(2, nb, Hkv, bs, hd),
                vp=f(2, nb, Hkv, bs, hd), ip=f(2, nb, 1, di, bs),
                qi=f(B, J, S_in, di), w=f(B, S_in, J), tables=tables,
                offs=jnp.asarray(offs, jnp.int32))


#: a case's own walk: ``H`` query heads over the two KV heads, ``topk``, and
#: the constants of ``ops/paged_attention.py`` that size a walk, shrunk to
#: the toy's 13 columns of 8 keys; ``walk``: what ``shape_walk`` must then
#: give, ``(split, fw, hb, T)``, so that no case can stop testing its shape
_WALKS = {
    "decode": dict(S_in=1, offs=(37, 5, 90)),
    "chunk16": dict(S_in=16, offs=(24, 0, 70)),
    "chunk32": dict(S_in=32, offs=(8, 64, 0)),
    # 256 rows a head walk the grid in four tiles of 4 blocks: dead
    # sub-blocks in a live tile (slot 0 ends in column 4), three sub-blocks
    # past the table's 13th column, slot 1 full to its last position (104)
    "ragged_tiles": dict(S_in=32, offs=(8, 72, 40), H=16,
                         patch={"_CHUNK_TILE_KEYS": 32}, walk=(1, 4, 1, 0)),
    # 512 rows a head pass the cap: two programs a head, four query heads each
    "split_heads": dict(S_in=64, offs=(40, 0, 8), H=16,
                        patch={"_CHUNK_TILE_KEYS": 32, "_PROGRAM_ROWS": 256},
                        walk=(2, 4, 1, 0)),
    # deep slots that keep 4 positions: rows whose first tile holds none
    "empty_first_tile": dict(S_in=32, offs=(64, 72, 50), H=16, topk=4,
                             patch={"_CHUNK_TILE_KEYS": 32},
                             walk=(1, 4, 1, 0)),
    # few rows a head: both heads one program, the decode walk's tile on
    # the grid, 13 columns in four tiles of 4
    "few_rows": dict(S_in=16, offs=(24, 88, 70),
                     patch={"_KV_TILE_KEYS": 32}, walk=(1, 1, 2, 4)),
}


@pytest.fixture
def fresh_walk():
    """``dsa_chunk``'s walk is chosen as the call is traced: a case that
    shrinks a constant must neither meet nor leave a traced call."""
    D._chunk_attention_pallas.clear_cache()
    yield
    D._chunk_attention_pallas.clear_cache()


@pytest.mark.parametrize("name", list(_WALKS))
def test_the_kernels_equal_their_oracle(name, fresh_walk, monkeypatch):
    """``dsa_index``, ``dsa_select`` and ``dsa_decode`` / ``dsa_chunk`` in
    interpret mode against the gathered oracle, layer 1 of a two-layer
    pool, slots at unequal depths (one short of ``topk``)."""
    case = _WALKS[name]
    S_in, offs, topk = case["S_in"], case["offs"], case.get("topk", 16)
    H = case.get("H", 4)
    for const, value in case.get("patch", {}).items():
        monkeypatch.setattr(P, const, value)
    if "walk" in case:
        split, _cols, _rows, fw, hb, T = P.shape_walk(
            H // 2, S_in, 2, 13, 8, 8 * 16 * 4)
        assert (split, fw, hb, T) == case["walk"]
    c = _case(np.random.RandomState(4), 3, S_in, 13, 8, offs, H=H)
    args = (c["qi"], c["w"], c["ip"], c["tables"], c["offs"])
    sc = {impl: D.index_scores(*args, layer=1, impl=impl)
          for impl in ("gather", "pallas")}
    np.testing.assert_allclose(D.natural(sc["pallas"]), sc["gather"],
                               atol=1e-5)
    # the kernels' layout is by block; both from the oracle's scores
    given = {"gather": sc["gather"], "pallas": D.by_block(sc["gather"], 8)}
    bias = {impl: D.select_bias(given[impl], c["offs"], topk, impl=impl)
            for impl in ("gather", "pallas")}
    np.testing.assert_array_equal(D.natural(bias["pallas"]), bias["gather"])
    kept = np.asarray((bias["gather"] == 0).sum(-1))
    ctx = np.asarray(c["offs"])[:, None] + np.arange(S_in)[None] + 1
    np.testing.assert_array_equal(kept, np.minimum(topk, ctx))
    if name == "empty_first_tile":   # rows that keep nothing of tile 0
        assert (np.asarray(bias["gather"])[..., :32] != 0).all(-1).any()
    out = {impl: D.selected_attention(
        c["q"], c["kp"], c["vp"], bias[impl], c["tables"], c["offs"],
        layer=1, impl=impl) for impl in ("gather", "pallas")}
    np.testing.assert_allclose(out["pallas"], out["gather"], atol=1e-5)
    # the selection as bits, from either layout: bit i of word j is
    # position 16 j + i
    words = {impl: np.asarray(D.selection_words(bias[impl]))
             for impl in ("gather", "pallas")}
    np.testing.assert_array_equal(words["pallas"], words["gather"])
    bits = (words["gather"].view(np.uint16)[..., None] >> np.arange(16)) & 1
    np.testing.assert_array_equal(
        bits.reshape(3, S_in, -1)[..., :13 * 8], np.asarray(bias["gather"] == 0))
    np.testing.assert_array_equal(
        ref.pack_mask(jnp.asarray(bias["gather"][0] == 0)), words["gather"][0])


@pytest.mark.parametrize("chunk", [32, 96], ids=["few_rows", "grid_tile"])
def test_the_pool_span_says_the_walk_dsa_chunk_makes(
        toy, chunk, fresh_walk, monkeypatch):
    """``chunk_rows``, ``chunk_tile_keys`` and ``chunk_programs`` on
    ``tdp:engine.init.pool`` are what ``_chunk_attention_pallas`` asks
    ``pallas_call`` for at the engine's prefill call: the rows of a
    program's query block, the K blocks of a grid step, the programs a
    slot (64 rows a head: both heads one program and the decode walk's
    tile; 192: a head a program and ``chunk_tile``'s)."""
    from torchdistpackage_tpu.utils.profiling import spans
    _, cfg, params = toy
    eng = ServingEngine(params, cfg, num_slots=2, block_size=8, chunk=chunk,
                        max_ctx=192, attn_impl="pallas")
    said = [r[5] for r in spans.snapshot()
            if r[2] == "tdp:engine.init.pool"][-1]
    asked, real = [], D.pl.pallas_call

    def spy(kernel, **kw):
        if kw["name"] == "dsa_chunk":
            asked.append(kw["grid_spec"])
        return real(kernel, **kw)

    monkeypatch.setattr(D.pl, "pallas_call", spy)
    k, W = eng.cache["k"], eng.prefill_width
    sds = jax.ShapeDtypeStruct
    jax.eval_shape(
        lambda *a: D.selected_attention(*a, layer=0, impl="pallas"),
        sds((W, cfg.block.nheads, chunk, k.shape[-1]), k.dtype), k,
        eng.cache["v"], sds((W, eng.max_blocks, chunk, 8), F32),
        sds((W, eng.max_blocks), jnp.int32), sds((W,), jnp.int32))
    (spec,) = asked
    (_one, hb, rows, _hd), tile = spec.in_specs[0].block_shape, (
        len(spec.in_specs) - 1) // 3
    assert spec.grid == (W, k.shape[2] // hb, -(-eng.max_blocks // tile))
    assert (said["chunk_rows"], said["chunk_tile_keys"],
            said["chunk_programs"]) == (rows, tile * 8, spec.grid[1])
    assert (hb, tile) == {32: (2, 24), 96: (1, 24)}[chunk]


def test_equal_scores_keep_the_lower_position():
    """Planted ties at the threshold: a run of equal scores across it, and
    a row of zeros (every relu shut): ``lax.top_k``'s rule, in both."""
    rng = np.random.RandomState(8)
    P, k = 104, 16
    sc = rng.standard_normal((1, 4, P)).astype(np.float32)
    sc[0, 0, 10:60] = 0.25          # 50 equal scores straddle the 16th
    sc[0, 1, :] = 0.0               # all equal: the first 16 positions
    sc[0, 2, 5:9] = sc[0, 2].max() + 1.0
    sc[0, 3, 40:] = -0.0            # signed zeros are one score
    sc[0, 3, :40] = -1.0
    offs = jnp.asarray([P - 4], jnp.int32)
    sc = jnp.where(jnp.arange(P)[None, None] <= (P - 4 + jnp.arange(4))[
        None, :, None], jnp.asarray(sc), D.NEG_INF)
    sc = D._canonical(sc)
    want = D.select_bias(sc, offs, k, impl="gather")
    got = D.natural(D.select_bias(D.by_block(sc, 8), offs, k, impl="pallas"))
    np.testing.assert_array_equal(got, want)
    kept = np.asarray(want[0] == 0)
    assert kept.sum(-1).tolist() == [k] * 4
    assert kept[1, :k].all() and kept[3, 40:40 + k].all()
    top = np.flatnonzero(np.asarray(sc[0, 0]) > 0.25)
    ties = np.flatnonzero(kept[0] & (np.asarray(sc[0, 0]) == 0.25))
    assert (ties == 10 + np.arange(k - len(top))).all()


# ---------------------------------------------------------------- the refusals


def test_what_indexed_attention_refuses_and_why(toy):
    _, cfg, params = toy
    with pytest.raises(NotImplementedError, match="verify rows would each "
                                                  "select"):
        ServingEngine(None, cfg, spec_k=2)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=8, chunk=8,
                        max_ctx=32, attn_impl="gather")
    for leave in (lambda: eng.export_slot(0), lambda: eng.drain()):
        with pytest.raises(NotImplementedError, match="nothing outside its "
                                                      "blocks.*queue 2"):
            leave()
    with pytest.raises(ValueError, match="multiple of 8"):
        D._tile(12)
