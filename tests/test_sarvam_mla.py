"""The hybrid family's latent-attention shape (models/hybrid.py kinds ``L``,
``D`` and a gated ``E``: the ``sarvam_mla`` architecture) at toy widths on
the CPU: the absorbed form against the unabsorbed reference, the expert
layer's shares, yarn's table, the latent pool under the allocator's
copy-on-write, and chunked prefill + decode through ``ServingEngine``
against the plain reference's full forward (benchmarks/reference/
sarvam_mla.py, which imports nothing of the program)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import sarvam_mla as family
from benchmarks.reference import sarvam_mla as ref
from benchmarks.weights_sarvam_mla import make_weights
from torchdistpackage_tpu.models import HybridConfig, init_hybrid_params
from torchdistpackage_tpu.models.hybrid import latent_attention_mixer
from torchdistpackage_tpu.parallel.moe import moe_serve_forward
from torchdistpackage_tpu.parallel.tensor_parallel.layers import rope_cache
from torchdistpackage_tpu.serving import (
    Request, ServingEngine, block_size_of, expected_pool_bytes,
    init_paged_kv, pool_bytes)
from torchdistpackage_tpu.serving.paged_cache import _latent_cache_ops

#: a ``sarvam_mla`` configuration file in small: 3 blocks (1 dense, 2 with
#: experts), 16 experts routed, 4 held (the second of four shares)
TOY = {
    "name": "toy-sarvam", "family": "sarvam_mla", "hidden_size": 64,
    "num_attention_heads": 4, "head_dim": 40, "kv_lora_rank": 32,
    "q_head_dim": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "use_qk_norm": True, "intermediate_size": 128,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_experts": 4, "published": {"num_experts": 16},
    "deployment_share": {"first_expert": 4}, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "moe_intermediate_size": 32,
    "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "deepseek_yarn"},
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


def test_pattern_pool_and_parameter_counts(toy):
    s, cfg, params = toy
    assert s.pattern == "*D*E*E" and cfg.pattern == "LDLELE"
    assert (cfg.nlayers, cfg.kv_layers, cfg.state_layers) == (6, 3, 0)
    assert cfg.latent_width == s.cached == 40 and cfg.state_bytes(9) == 0
    assert cfg.mla_scale == pytest.approx(
        24 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    # ONE leaf, blocks transposed: [layers, blocks, 1, width, block size]
    pool = init_paged_kv(cfg, 7, 8)
    assert set(pool) == {"kv"} and pool["kv"].shape == (3, 7, 1, 40, 8)
    assert block_size_of(pool) == 8
    assert pool_bytes(pool) == expected_pool_bytes(cfg, 7, 8) \
        == 3 * 7 * 8 * 40 * 4
    with pytest.raises(NotImplementedError, match="int8"):
        init_paged_kv(cfg, 7, 8, quantized=True)
    # the family's count is the tree's, and so is the program's own init
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    assert count(params) == family.num_params(s)
    own = init_hybrid_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    for bad, why in (("L*", "one kind of block pool"), ("LX", "pattern")):
        with pytest.raises(ValueError, match=why):
            dataclasses.replace(cfg, pattern=bad)
    with pytest.raises(ValueError, match="mla_"):
        HybridConfig(vocab_size=8, dim=8, pattern="L", max_seq=8, nheads=2,
                     kv_heads=1)


def test_the_family_counts_what_the_issue_counted():
    """Parameters a layer and the cut's bytes, from the published widths
    (ISSUE 30's arithmetic), and the least a latent decode call moves."""
    from benchmarks import arch as A

    s = family.shape(A.load_config("sarvam-105b"), 4096)
    n = family.layer_params(s)
    assert round(n["*"] / 1e6, 2) == 94.64
    assert round(n["D"] / 1e6, 2) == 201.33
    assert round(n["E"] / 1e6, 2) == 25.69
    assert round(n["expert"] / 1e6, 2) == 25.17
    assert s.pattern.count("*") == 5 and s.vocab == 65536
    assert round(family.num_params(s) * 2 / 1e9, 2) == 9.07
    live, slots = 128 * 1600.0, 128.0
    paged = family.paged_decode(s, live, slots)
    assert paged["bytes"] == live * 576 * 2 + slots * 64 * (576 + 512) * 2
    assert paged["flops"] == 2 * live * 64 * (576 + 512)
    # memory-bound, with the MXU about half busy: 112 operations a byte
    assert 105 < paged["flops"] / paged["bytes"] < 125   # ridge: 240
    full = family.decode_step(s, live, slots, 4 * 32.0)
    want = ((family.num_params(s) - (s.vocab - 128) * s.dim) * 2
            + 5 * paged["bytes"])
    assert full["bytes"] == pytest.approx(want)
    some = family.decode_step(s, live, slots, 4 * 30.0)
    assert full["bytes"] - some["bytes"] == 4 * 2 * n["expert"] * 2


def test_yarn_table_against_the_closed_form():
    """The serving rope table of the 64 rope dims under the published
    ``deepseek_yarn`` keys: frequencies that turn more than ``beta_fast``
    times over the original 4096 positions are kept, those that turn fewer
    than ``beta_slow`` times are divided by 40, a linear ramp between; with
    ``mscale == mscale_all_dim`` the tables carry no factor."""
    rs = {"rope_type": "yarn", "factor": 40.0, "beta_fast": 32.0,
          "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
          "original_max_position_embeddings": 4096}
    pos = jnp.asarray([0, 1, 77, 4095, 100000])
    cos, sin = rope_cache(pos, 64, 10000.0, scaling=rs)
    i = np.arange(32)
    base = 10000.0 ** (-i / 32)
    dim_of = lambda turns: 64 * math.log(4096 / (turns * 2 * math.pi)) / (
        2 * math.log(10000.0))
    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv = base / 40 * ramp + base * (1 - ramp)
    assert inv[10] == base[10] and inv[23] == base[23] / 40
    ang = np.asarray(pos, np.float64)[:, None] * inv[None, :]
    np.testing.assert_allclose(cos[0, 0], np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(sin[0, 0], np.sin(ang), atol=2e-3)
    # float32 angles: exact to rounding up to position 4095
    np.testing.assert_allclose(cos[0, 0, :3], np.cos(ang[:3]), atol=1e-5)
    # the reference's own table is the same closed form
    s = family.shape({**TOY, "qk_rope_head_dim": 64, "head_dim": 96,
                      "q_head_dim": 80, "rope_scaling": {
                          **TOY["rope_scaling"],
                          "original_max_position_embeddings": 4096}}, 64)
    np.testing.assert_allclose(ref.yarn_inv_freq(s), inv, rtol=1e-6)


def test_absorbed_attention_equals_the_unabsorbed_reference(toy):
    """One ``L`` layer, a prompt written to the pool in two chunks and then
    three decode positions, against the reference's attention over the
    whole sequence with every head's keys and values materialised."""
    s, cfg, params = toy
    p = params["layers"][0]
    S, bs = 19, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, 64), F32)
    tables = jnp.asarray([[3, 1, 2]], jnp.int32)
    pool = init_paged_kv(cfg, 4, bs)["kv"]
    outs = []
    with jax.default_matmul_precision("highest"):
        for lo, hi in ((0, 8), (8, 16), (16, 17), (17, 18), (18, 19)):
            y, pool = latent_attention_mixer(
                p, x[:, lo:hi], cfg, pool, jnp.asarray([lo]),
                _latent_cache_ops(tables, "gather", cfg, 1))
            outs.append(y)
        want = ref.attention(p, x[0], s)
    np.testing.assert_allclose(jnp.concatenate(outs, 1)[0], want,
                               rtol=2e-4, atol=2e-5)
    # only layer 1 of the pool was written, and only the table's blocks
    assert not np.asarray(pool[0]).any() and not np.asarray(pool[2]).any()
    assert not np.asarray(pool[1, 0]).any()


def test_the_four_shares_add_up_to_the_uncut_layer(toy):
    """Experts 0-3 ... 12-15, each share's routed part with the shared
    expert counted once, add up to the uncut reference's expert layer."""
    s, cfg, params = toy
    p = params["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 64), F32)
    full_s = dataclasses.replace(s, held_first=0, held=16)
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    every = {"w1": jax.random.normal(k1, (16, 64, 64), F32) / 8,
             "w2": jax.random.normal(k2, (16, 32, 64), F32) / 6}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.moe({**p, "experts": every}, x[b], full_s)[0]
                          for b in range(2)])
        shared = jnp.stack([ref.dense_mlp(p["shared"], x[b])
                            for b in range(2)])
        total = 0.0
        for first in (0, 4, 8, 12):
            mcfg = dataclasses.replace(cfg, moe_held=(first, 4)).moe
            share = {**p, "experts": jax.tree.map(
                lambda w: w[first:first + 4], every)}
            y, m = moe_serve_forward(share, x, mcfg, return_metrics=True)
            total = total + (y - shared)
            assert m["gate_idx"].shape == (2, 9, 4)
    np.testing.assert_allclose(total + shared, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("tokens", [5, 200], ids=["batched", "grouped"])
def test_gated_experts_in_both_forms_equal_the_reference(toy, tokens):
    """A decode-sized call runs the gated experts as one batched matmul, a
    larger one as ``ragged_dot`` groups: the reference's plain loop both."""
    s, cfg, params = toy
    p = params["layers"][5]
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 1, 64), F32)
    valid = jnp.arange(tokens)[:, None] % 7 != 3
    with jax.default_matmul_precision("highest"):
        y, m = moe_serve_forward(p, x, cfg.moe, return_metrics=True,
                                 valid=valid)
        want, idx, _ = ref.moe(p, x[:, 0], s)
    # a padding row's routed part is left out (the shared expert's is not)
    real = np.asarray(valid[:, 0])
    np.testing.assert_allclose(y[real, 0], want[real], rtol=2e-4, atol=2e-5)
    assert np.abs(np.asarray(y[~real, 0] - want[~real])).max() > 0.1
    np.testing.assert_array_equal(np.sort(m["gate_idx"][:, 0], -1),
                                  np.sort(idx, -1))
    assert float(m["rows_routed"]) == float(valid.sum()) * 4


# ---------------------------------------------------------------- the engine


def _served_gap(s, params, finished):
    worst = 0.0
    for f in finished:
        toks = np.asarray(f["tokens"])
        p = len(toks) - f["new_tokens"]
        logits = np.asarray(ref.forward_logits(params, toks[:-1], s))[p - 1:]
        served = logits[np.arange(len(toks) - p), toks[p:]]
        worst = max(worst, float((logits.max(-1) - served).max()))
    return worst


def _serve(toy, **kw):
    """Seven requests on three slots, chunk 8: prompts that are and are not
    multiples of the chunk, one to three chunks long, more requests than
    slots."""
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
    full forward, with ``run_ahead`` and ``record_routing`` on."""
    s, _, params = toy
    assert len(served.finished) == 7 and served.audit(heal=False)["ok"]
    with jax.default_matmul_precision("highest"):
        gap = _served_gap(s, params, served.finished.values())
        for f in served.finished.values():
            toks = np.asarray(f["tokens"])
            assert f["routing"].shape == (len(toks) - 1, 2, 4)
            own = ref.forward_following(params, toks[:-1], s)
            np.testing.assert_array_equal(
                np.sort(f["routing"], -1), np.sort(own["routing"], -1))
    # float32 at 'highest' on both sides: summation order (absorbed against
    # unabsorbed, the grouped expert GEMM) is what is left
    assert gap <= 1e-4, gap


def test_engine_state_is_empty_and_the_spans_carry_the_new_attrs(toy, served):
    _, cfg, _ = toy
    summ = served.serving_summary()
    assert summ["prefill_signatures"] == summ["decode_signatures"] == 1
    assert served.state_model and served.state_bytes == 0
    assert served.state == {"ssm": (), "conv": (), "tail": ()}
    assert served.cache["kv"].shape == (3, served.num_blocks, 1, 40, 8)
    kv = summ["memory"]["kv_pool"] if "memory" in summ else None
    st = served.stats
    assert 0.1 < st["moe_rows_held"] / st["moe_rows_routed"] < 0.45
    from torchdistpackage_tpu.utils.profiling import spans
    recs = spans.snapshot()
    pools = [r for r in recs if r[2] == "tdp:engine.init.pool"]
    assert pools[-1][5]["bytes"] == pool_bytes(served.cache)
    decodes = [r for r in recs if r[2] == "tdp:engine.decode"
               and "live_tokens" in r[5]]
    assert decodes and all(r[5]["live_tokens"] >= r[5]["slots"]
                           for r in decodes)
    assert kv is None or kv["pool_bytes"] == kv["pool_bytes_expected"]


def test_the_kernel_path_serves_the_gather_paths_tokens(toy, served):
    """``attn_impl='pallas'`` (the kernels in interpret mode) and no
    ``run_ahead``: the same tokens, request for request."""
    got = _serve(toy, attn_impl="pallas")
    for rid, f in served.finished.items():
        np.testing.assert_array_equal(got.finished[rid]["tokens"],
                                      f["tokens"])


def test_prefix_match_and_copy_on_write_on_a_latent_pool(toy):
    """Two prompts share 20 tokens (two whole blocks and part of a third):
    the second maps the first's blocks into its table, copies the block it
    goes on writing, and is served the logits of an engine without the
    cache.  A hybrid WITH recurrent layers still refuses."""
    s, cfg, params = toy
    rng = np.random.RandomState(4)
    head = rng.randint(0, 211, 20).tolist()
    reqs = [head + rng.randint(0, 211, n).tolist() for n in (3, 6)]
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(params, cfg, num_slots=2, block_size=8, chunk=8,
                            max_ctx=64, attn_impl="gather", prefix_cache=True)
        for t in reqs:
            eng.submit(Request(tokens=t, max_new_tokens=5))
            eng.run_until_idle()
        assert eng.stats["prefix_hits"] == 1
        assert eng.stats["prefix_cached_tokens"] >= 16
        assert eng.audit(heal=False)["ok"]
        gap = _served_gap(s, params, eng.finished.values())
    assert gap <= 1e-4, gap
    with pytest.raises(NotImplementedError, match="state model"):
        ServingEngine(None, dataclasses.replace(
            cfg, pattern="LDM", mamba_heads=2, mamba_head_dim=4, ssm_state=4),
            prefix_cache=True)


def test_copy_blocks_and_migration_see_blocks_on_a_latent_pool(toy):
    from torchdistpackage_tpu.serving import copy_blocks, migrate_blocks

    _, cfg, _ = toy
    pool = init_paged_kv(cfg, 6, 8)
    pool = {"kv": jax.random.normal(jax.random.PRNGKey(1), pool["kv"].shape)}
    out = copy_blocks(pool, jnp.asarray([2, 0]), jnp.asarray([5, 0]))
    np.testing.assert_array_equal(out["kv"][:, 5], pool["kv"][:, 2])
    np.testing.assert_array_equal(out["kv"][:, 1:5], pool["kv"][:, 1:5])
    dst = migrate_blocks(pool, init_paged_kv(cfg, 4, 8), jnp.asarray([3]),
                         jnp.asarray([1]), compress=True)
    np.testing.assert_allclose(dst["kv"][:, 1], pool["kv"][:, 3], atol=0.05)
