"""The hybrid family's convolved-attention shape (models/hybrid.py kind ``C``,
the network router ``moe_score='mlp'``, the ``res`` leaves, a tied head: the
``zaya`` architecture) at toy widths on the CPU: the layer in chunks against
one call at every boundary offset, the router's stream through the depth,
the top-1 weight, and chunked prefill + decode through ``ServingEngine``
against the plain reference's full forward (benchmarks/reference/zaya.py,
which imports nothing of the program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import zaya as family
from benchmarks.reference import zaya as ref
from benchmarks.weights_zaya import make_weights
from torchdistpackage_tpu.models import HybridConfig, init_hybrid_params
from torchdistpackage_tpu.models.hybrid import cca_mixer, init_state
from torchdistpackage_tpu.parallel.moe import _mlp_route, moe_serve_forward
from torchdistpackage_tpu.serving import (
    Request, ServingEngine, expected_pool_bytes, init_paged_kv, pool_bytes)
from torchdistpackage_tpu.serving.paged_cache import _paged_cache_ops

#: a ``zaya`` configuration file in small: 3 blocks, 4 query heads over 2 key
#: heads of 16 in a model 48 wide (the heads do NOT tile the width), 8 experts
TOY = {
    "name": "toy-zaya", "family": "zaya", "hidden_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "cca_time0": 2, "cca_time1": 2, "attention_bias": False,
    "lm_head_bias": False, "hidden_act": "silu", "tie_word_embeddings": True,
    "layer_types": ["hybrid"] * 3, "num_hidden_layers": 3, "num_experts": 8,
    "num_experts_per_tok": 1, "moe_intermediate_size": 32,
    "router_hidden_size": 24, "rms_norm_eps": 1e-5,
    "partial_rotary_factor": 0.5, "sliding_window": None,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000,
                                   "rope_type": "default"}},
    "vocab_size": 211, "max_position_embeddings": 512,
}
F32 = jnp.float32


@pytest.fixture(scope="module")
def toy():
    """(Shape, the program's config in float32, float32 weights)."""
    s = family.shape(TOY, 64)
    cfg = dataclasses.replace(family.program_config(TOY, 64), dtype=F32)
    params = jax.tree.map(lambda a: a.astype(F32), make_weights(s, 7))
    return s, cfg, params


def count(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def test_pattern_pool_tail_and_parameter_counts(toy):
    s, cfg, params = toy
    assert s.pattern == "*E*E*E" and cfg.pattern == "CECECE"
    assert (cfg.nlayers, cfg.kv_layers, cfg.state_layers, cfg.ssm_layers) \
        == (6, 3, 3, 0)
    # the head size is the configuration's, not dim / nheads = 12
    assert cfg.head_dim == cfg.block.head_dim == 16
    assert cfg.block.kv_head_count == 2 and cfg.latent_width == 0
    pool = init_paged_kv(cfg, 7, 8)
    assert pool["k"].shape == pool["v"].shape == (3, 7, 2, 8, 16)
    assert pool_bytes(pool) == expected_pool_bytes(cfg, 7, 8) \
        == 2 * 3 * 7 * 2 * 8 * 16 * 4
    # the tail: two rows of z (6 heads x 16) and one shifted value head
    assert cfg.cca_tail == s.tail == 2 * 96 + 16
    state = init_state(cfg, 5)
    assert state["ssm"] == state["conv"] == ()
    assert [t.shape for t in state["tail"]] == [(5, 208)] * 3
    assert cfg.state_bytes(5) == 3 * 5 * 208 * 4
    # the family's count is the tree's, and so is the program's own init
    assert count(params) == family.num_params(s)
    own = init_hybrid_params(jax.random.PRNGKey(0), cfg,
                             scaled_residual=True, tied_head=True)
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    assert "head" not in params
    assert "gamma" not in params["layers"][1]["router"]
    assert "gamma" in params["layers"][3]["router"]
    for bad, why in (("CL", "one kind of block pool"), ("CX", "pattern")):
        with pytest.raises(ValueError, match=why):
            dataclasses.replace(cfg, pattern=bad)
    with pytest.raises(ValueError, match="even number of KV heads"):
        dataclasses.replace(cfg, kv_heads=1)
    with pytest.raises(ValueError, match="moe_router_hidden"):
        dataclasses.replace(cfg, moe_router_hidden=0)
    with pytest.raises(ValueError, match="say head_dim"):
        HybridConfig(vocab_size=8, dim=10, pattern="*", max_seq=8, nheads=4,
                     kv_heads=2)


def test_the_family_counts_what_the_issue_counted():
    """Parameters a layer and the cut's bytes, from the published widths
    (ISSUE 33's arithmetic), and the least a decode call moves."""
    from benchmarks import arch as A

    s = family.shape(A.load_config("zaya1-8b"), 2560)
    n = family.layer_params(s)
    assert round((n["*"] - 5 * 2048) / 1e6, 2) == 5.58   # 5.24 + 0.33
    assert round((n["E"] - 5 * 2048) / 1e6, 2) == 0.66
    assert round(n["expert"] / 1e6, 2) == 12.58
    assert s.pattern == "*E" * 20 and s.vocab == 262272 and s.tail == 2688
    assert round(family.num_params(s) / 1e9, 3) == 4.689
    assert round(family.num_params(s) * 2 / 1e9, 2) == 9.38
    live, slots = 64 * 1300.0, 64.0
    paged = family.paged_decode(s, live, slots)
    assert paged["bytes"] == live * 2 * 2 * 128 * 2 + slots * 2 * 8 * 128 * 2
    assert paged["flops"] == 4 * live * 8 * 128
    full = family.decode_step(s, live, slots, 20 * 16.0)
    # the table once: every parameter + the tails r/w + the live K/V
    want = (family.num_params(s) * 2 + 20 * 2 * 64 * 2688 * 2
            + 20 * paged["bytes"])
    assert full["bytes"] == pytest.approx(want)
    some = family.decode_step(s, live, slots, 20 * 14.0)
    assert full["bytes"] - some["bytes"] == 20 * 2 * n["expert"] * 2
    for bad, why in (({"num_experts_per_tok": 2}, "not written"),
                     ({"sliding_window": 4096}, "not written"),
                     ({"layer_types": ["hybrid_sliding"] * 40},
                      "not written"),
                     ({"tie_word_embeddings": False}, "as published")):
        with pytest.raises(ValueError, match=why):
            family.shape({**A.load_config("zaya1-8b"), **bad}, 2560)


# ------------------------------------------------------------ the CCA layer


def _layer_in_calls(p, x, cfg, cuts, pad=0, tail_dtype=None):
    """One ``C`` layer over x [1, S, D] in the calls ``cuts`` names, each
    padded at the end by ``pad`` rows that ``n_valid`` leaves out."""
    S, bs = x.shape[1], 8
    tables = jnp.asarray([[3, 1, 2, 4, 5]], jnp.int32)
    pool = init_paged_kv(cfg, 6, bs)
    ck, cv = pool["k"], pool["v"]
    tail = init_state(cfg, 1)["tail"][0]
    if tail_dtype is not None:
        tail = tail.astype(tail_dtype)
    outs = []
    for lo, hi in zip((0,) + cuts, cuts + (S,)):
        rows = jnp.concatenate(
            [x[:, lo:hi], jnp.full((1, pad, x.shape[2]), 9.0, x.dtype)], 1)
        y, ck, cv, tail = cca_mixer(
            p, rows, cfg, ck, cv, tail, jnp.asarray([lo]),
            jnp.asarray([hi - lo]), _paged_cache_ops(tables, "gather", 1))
        outs.append(y[:, :hi - lo])
    return jnp.concatenate(outs, 1), ck, tail


@pytest.mark.parametrize("cut", range(1, 12))
def test_cca_in_chunks_equals_one_call_at_every_boundary(toy, cut):
    """A boundary after ``cut`` positions, then decode-sized calls: the two
    rows of z and the one shifted value cross it, padding rows (a compact
    prefill call's) enter neither the tail nor the result."""
    s, cfg, params = toy
    p = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 14, 48), F32)
    with jax.default_matmul_precision("highest"):
        whole, ck0, tail0 = _layer_in_calls(p, x, cfg, ())
        parts, ck1, tail1 = _layer_in_calls(
            p, x, cfg, (cut, 12, 13), pad=3)
    np.testing.assert_allclose(parts, whole, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(tail1, tail0, rtol=1e-6, atol=1e-7)
    # only layer 1 of the pool was written; the live rows agree
    assert not np.asarray(ck1[0]).any() and not np.asarray(ck1[2]).any()
    np.testing.assert_allclose(ck1[1, 3], ck0[1, 3], rtol=2e-5, atol=2e-6)


def test_cca_equals_the_reference_and_a_row_without_positions_is_inert(toy):
    s, cfg, params = toy
    p = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 19, 48), F32)
    with jax.default_matmul_precision("highest"):
        got, _, tail = _layer_in_calls(p, x, cfg, (8, 16, 17, 18))
        want = ref.attention(p, x[0], s)
        # n_valid 0: the tail comes back bit for bit
        _, _, _, same = cca_mixer(
            p, x[:, :4], cfg, *init_paged_kv(cfg, 6, 8).values(), tail,
            jnp.asarray([0]), jnp.asarray([0]),
            _paged_cache_ops(jnp.zeros((1, 5), jnp.int32), "gather", 0))
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(same, tail)
    # the tail is the last two rows of z and the last shifted value
    z = x[0] @ p["wz"]
    np.testing.assert_allclose(tail[0, :192].reshape(2, 96), z[-2:],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(tail[0, 192:], (x[0, -1] @ p["wv"])[16:],
                               rtol=1e-5, atol=1e-6)


def test_a_tail_kept_in_bfloat16_fails_the_float32_tolerance(toy):
    """The tolerance of the chunked layer (2e-5: float32 summation order)
    is not one a lower-precision tail passes: bfloat16 rows of z are off by
    2^-9 of their size."""
    s, cfg, params = toy
    p = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 14, 48), F32)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = _layer_in_calls(p, x, cfg, ())
        low, _, _ = _layer_in_calls(p, x, cfg, (5, 12, 13),
                                    tail_dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(low - whole))) > 2e-4


# ------------------------------------------------- the router and the experts


def test_the_depth_stream_and_the_top1_weight(toy):
    """Layer 0 adds nothing to ``u``; layer 1 adds ``gamma * r``; the
    expert is argmax(p + bias) and weighs by p itself."""
    s, cfg, params = toy
    r0, r1 = (params["layers"][i]["router"] for i in (1, 3))
    x = jax.random.normal(jax.random.PRNGKey(9), (13, 48), F32)
    with jax.default_matmul_precision("highest"):
        p0, w0, i0, u0 = _mlp_route(r0, x, cfg.moe, None)
        np.testing.assert_allclose(
            u0, x @ r0["down"]["w"] + r0["down"]["b"], rtol=1e-5, atol=1e-6)
        p1, w1, i1, u1 = _mlp_route(r1, x, cfg.moe, u0)
        np.testing.assert_allclose(
            u1, x @ r1["down"]["w"] + r1["down"]["b"] + r1["gamma"] * u0,
            rtol=1e-5, atol=1e-6)
        alone = _mlp_route(r1, x, cfg.moe, jnp.zeros_like(u0))[3]
        assert float(jnp.max(jnp.abs(u1 - alone))) > 0.1
        gate, idx, deficit, u = ref.gates(r1, x, s, depth=u0)
    np.testing.assert_allclose(p1.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(i1[:, 0], jnp.argmax(p1 + r1["bias"], -1))
    np.testing.assert_allclose(w1, jnp.take_along_axis(p1, i1, -1))
    assert float(w1.max()) < 1.0 and float(jnp.abs(r1["bias"]).max()) > 0
    # the reference's own route: the same choice, weight and stream
    np.testing.assert_array_equal(idx, i1)
    np.testing.assert_allclose(gate.sum(-1), w1[:, 0], rtol=1e-5)
    np.testing.assert_allclose(u, u1, rtol=1e-5, atol=1e-6)
    assert float(deficit.max()) == 0.0
    # following another's choice: the deficit is on the probability scale
    other = (i1 + 1) % s.experts
    _, _, d, _ = ref.gates(r1, x, s, depth=u0,
                           follow=(other, jnp.ones(13, bool)))
    sel = p1 + r1["bias"]
    np.testing.assert_allclose(
        d, (sel.max(-1) - jnp.take_along_axis(sel, other, -1)[:, 0]),
        rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("tokens", [5, 200], ids=["batched", "grouped"])
def test_the_expert_layer_equals_the_reference_held_or_not(toy, tokens):
    """A decode-sized call runs the experts as one batched matmul, a larger
    one as ``ragged_dot`` groups: the reference's plain loop both; and
    ``held=(0, 8)`` (the counters' form) is ``held=None``."""
    s, cfg, params = toy
    p = params["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 1, 48), F32)
    depth = jax.random.normal(jax.random.PRNGKey(1), (tokens, 1, 24), F32)
    with jax.default_matmul_precision("highest"):
        y, m, u = moe_serve_forward(p, x, cfg.moe, return_metrics=True,
                                    depth=depth)
        free = dataclasses.replace(cfg, moe_held=None).moe
        y_free, u_free = moe_serve_forward(p, x, free, depth=depth)
        want, idx, _, u_ref = ref.moe(p, x[:, 0], s, depth=depth[:, 0])
    assert cfg.moe.held == (0, 8) and free.held is None
    np.testing.assert_array_equal(y, y_free)
    np.testing.assert_array_equal(u, u_free)
    np.testing.assert_allclose(y[:, 0], want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(u[:, 0], u_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(m["gate_idx"][:, 0], idx)
    assert m["gate_idx"].shape == (tokens, 1, 1)
    assert float(m["rows_routed"]) == float(m["rows_held"]) == tokens
    assert 1 <= float(m["experts_touched"]) <= 8


# ---------------------------------------------------------------- the engine


def _served_gap(s, params, finished, quant=None):
    worst = 0.0
    for f in finished:
        toks = np.asarray(f["tokens"])
        p = len(toks) - f["new_tokens"]
        out = ref.forward_following(params, toks[:-1], s, quant,
                                    None if quant else f["routing"])
        logits = np.asarray(out["logits"])[p - 1:]
        served = (logits.argmax(-1) if quant else toks[p:])
        if quant:   # the lower precision's first tokens, in the program's place
            logits = np.asarray(ref.forward_logits(
                params, toks[:-1], s))[p - 1:]
        gap = logits.max(-1) - logits[np.arange(len(toks) - p), served]
        worst = max(worst, float(gap.max()))
    return worst


def _serve(toy, **kw):
    """Seven requests on three slots, chunk 8: prompts that are and are not
    multiples of the chunk, one to three chunks long (so a compact prefill
    call carries padding rows), more requests than slots (so a slot is
    used again after a finished request)."""
    _, cfg, params = toy
    rng = np.random.RandomState(0)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(params, cfg, num_slots=3, block_size=8, chunk=8,
                            max_ctx=64, **kw)
        for i, n in enumerate((8, 13, 16, 5, 21, 24, 9)):
            eng.submit(Request(tokens=rng.randint(0, 211, n).tolist(),
                               max_new_tokens=4 + 3 * (i % 3)))
        eng.run_until_idle()
    return eng


@pytest.fixture(scope="module")
def served(toy):
    return _serve(toy, attn_impl="gather", record_routing=True,
                  run_ahead=True)


def test_engine_prefill_and_decode_equal_the_reference_forward(toy, served):
    """Logits, not tokens: every served token's logit in the reference's
    full forward, with ``run_ahead`` and ``record_routing`` on; and the
    fp8 control fails the same tolerance."""
    s, _, params = toy
    assert len(served.finished) == 7 and served.audit(heal=False)["ok"]
    with jax.default_matmul_precision("highest"):
        gap = _served_gap(s, params, served.finished.values())
        for f in served.finished.values():
            toks = np.asarray(f["tokens"])
            assert f["routing"].shape == (len(toks) - 1, 3, 1)
            own = ref.forward_following(params, toks[:-1], s)
            np.testing.assert_array_equal(f["routing"], own["routing"])
        low = _served_gap(s, params, served.finished.values(), quant="fp8")
    # float32 at 'highest' on both sides: summation order (the grouped
    # expert GEMM, the convolutions' taps, the paged softmax) is what is
    # left, and a top-1 choice that holds
    assert gap <= 1e-4, gap
    assert low > 100 * 1e-4, low


def test_engine_tails_spans_and_counters(toy, served):
    _, cfg, _ = toy
    summ = served.serving_summary()
    assert summ["prefill_signatures"] == summ["decode_signatures"] == 1
    assert served.state_model and served.state_bytes == 3 * 3 * 208 * 4
    assert served.state["ssm"] == () and len(served.state["tail"]) == 3
    assert served.cache["k"].shape == (3, served.num_blocks, 2, 8, 16)
    st = served.stats
    assert st["moe_rows_held"] == st["moe_rows_routed"] > 0   # all 8 held
    from torchdistpackage_tpu.utils.profiling import spans
    recs = spans.snapshot()
    pools = [r for r in recs if r[2] == "tdp:engine.init.pool"]
    assert pools[-1][5]["bytes"] == pool_bytes(served.cache)
    states = [r for r in recs if r[2] == "tdp:engine.init.state"]
    assert states[-1][5]["bytes"] == served.state_bytes
    decodes = [r for r in recs if r[2] == "tdp:engine.decode"
               and "live_tokens" in r[5]]
    assert decodes and all(r[5]["live_tokens"] >= r[5]["slots"]
                           for r in decodes)


def test_the_kernel_path_serves_the_gather_paths_tokens(toy, served):
    """``attn_impl='pallas'`` (the kernels in interpret mode) and no
    ``run_ahead``: the same tokens, request for request."""
    got = _serve(toy, attn_impl="pallas")
    for rid, f in served.finished.items():
        np.testing.assert_array_equal(got.finished[rid]["tokens"],
                                      f["tokens"])


def test_the_tied_head_is_the_table(toy, served):
    """A ``head`` leaf that holds the table's transpose serves the same
    tokens as no ``head`` leaf; another head does not."""
    _, cfg, params = toy
    untied = {**params, "head": params["tok_emb"].T}
    got = _serve((None, cfg, untied), attn_impl="gather", run_ahead=True)
    for rid, f in served.finished.items():
        np.testing.assert_array_equal(got.finished[rid]["tokens"],
                                      f["tokens"])


def test_what_an_attention_layer_with_a_tail_refuses(toy):
    """A block's keys depend on the two positions before it: blocks cannot
    be shared by prefix, a draft cannot be rolled back, a request cannot
    leave mid-flight, without snapshots of the tail."""
    _, cfg, params = toy
    for kw in ({"prefix_cache": True}, {"spec_k": 2}):
        with pytest.raises(NotImplementedError, match="tail"):
            ServingEngine(None, cfg, **kw)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=8, chunk=8,
                        max_ctx=32, attn_impl="gather")
    for leave in (lambda: eng.export_slot(0), lambda: eng.drain()):
        with pytest.raises(NotImplementedError, match="tails"):
            leave()
