"""Granite-4.0-H's block (models/hybrid.py: a Mamba-2 or position-free GQA
mixer and a dense SwiGLU behind it, each under a scaled residual; a scaled
embedding, a scaled softmax, scaled logits over a tied head; heads of 64 two
to a row of the pool) at toy widths on the CPU, float32: chunked prefill +
decode through ``ServingEngine`` against the plain reference's full forward
(benchmarks/reference/granite_hybrid.py, which imports nothing of the
program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import granite_hybrid as family
from benchmarks.reference import granite_hybrid as ref
from benchmarks.weights_granite_hybrid import make_weights
from torchdistpackage_tpu.models import HybridConfig, init_hybrid_params
from torchdistpackage_tpu.serving import Request, ServingEngine
from torchdistpackage_tpu.serving import paged_cache as PC

#: a ``granitemoehybrid`` configuration file in small: heads of 64 (4 / 2,
#: so two KV heads fill a lane row), 8 Mamba heads of 64, one group, chunk 8
TOY = {
    "name": "toy-granite", "family": "granite_hybrid", "hidden_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "num_hidden_layers": 4, "shared_intermediate_size": 96,
    "intermediate_size": 96, "num_local_experts": 0, "num_experts_per_tok": 0,
    "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5, "vocab_size": 211,
    "max_position_embeddings": 512,
}
F32 = jnp.float32
MAX_CTX = 64
#: (prompt, new tokens): prompts that end inside their first chunk of 8,
#: cross two and three chunk boundaries, and more requests than slots, so
#: that a slot is reused after a retirement
REQUESTS = ((5, 6), (19, 12), (27, 9), (8, 3), (30, 20), (11, 7), (24, 5))


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
        eng = ServingEngine(params, cfg, num_slots=3, block_size=8, chunk=8,
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
    return _serve(cfg, params, run_ahead=True)


def test_the_block_is_two_layers_and_the_pool_packs_two_heads(toy):
    s, cfg, params = toy
    assert s.pattern == cfg.pattern == "MD*DMDMD"
    assert (cfg.nlayers, cfg.kv_layers, cfg.ssm_layers) == (8, 1, 3)
    assert (cfg.head_dim, cfg.kv_pack) == (64, 2)
    assert (cfg.residual_scale, cfg.attn_scale, cfg.logits_scale,
            cfg.embed_scale) == (0.22, 1 / 64, 1 / 8, 12.0)
    # two KV heads of 64 side by side in a row of 128, the bytes unchanged
    pool = PC.init_paged_kv(cfg, 5, 8)
    assert pool["k"].shape == pool["v"].shape == (1, 5, 1, 8, 128)
    assert PC.pool_bytes(pool) == PC.expected_pool_bytes(cfg, 5, 8)
    with pytest.raises(NotImplementedError, match="narrower than a lane"):
        PC.init_paged_kv(cfg, 5, 8, quantized=True)
    # heads that do not fill rows in whole numbers, and kernels of their
    # own, keep a head a row
    for change in ({"kv_heads": 1}, {"head_dim": 48}, {"head_dim": 128}):
        assert dataclasses.replace(cfg, **change).kv_pack == 1
    # the family's count is the tree's, and the program's own seeded tree's
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == family.num_params(s)
    own = init_hybrid_params(jax.random.PRNGKey(0), cfg, tied_head=True)
    assert n == sum(int(np.prod(a.shape)) for a in jax.tree.leaves(own))
    assert family.state_bytes_per_slot(s, itemsize=4) == cfg.state_bytes(1)


def test_packed_rows_give_each_head_its_own_scores():
    """``paged_attention`` on a pool of two heads a row against plain GQA
    on the same keys and values, gathered oracle and kernel (interpret
    mode) alike, a scale that is not ``hd ** -0.5``."""
    B, H, Hkv, hd, bs, mb, S = 2, 8, 4, 64, 8, 3, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    k = jax.random.normal(ks[0], (B, Hkv, mb * bs, hd), F32)
    v = jax.random.normal(ks[1], (B, Hkv, mb * bs, hd), F32)
    q = jax.random.normal(ks[2], (B, H, S, hd), F32)
    tables = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    zero = jnp.zeros((B,), jnp.int32)
    offset = jnp.asarray([mb * bs - S, 5], jnp.int32)
    with jax.default_matmul_precision("highest"):
        plain = jnp.zeros((1, 1 + B * mb, Hkv, bs, hd), F32)
        packed = jnp.zeros((1, 1 + B * mb, Hkv // 2, bs, 2 * hd), F32)
        pools = []
        for pool in (plain, packed):
            pools.append([PC.paged_write(pool, a, zero, tables=tables, layer=0)
                          for a in (k, v)])
        want = PC.paged_attention(q, *pools[0], offset, tables=tables,
                                  layer=0, sm_scale=1 / 64)
        for impl in ("gather", "pallas"):
            got = PC.paged_attention(q, *pools[1], offset, tables=tables,
                                     layer=0, sm_scale=1 / 64, impl=impl)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        # and the default scale of a packed pool is the HEAD's, not the row's
        np.testing.assert_allclose(
            PC.paged_attention(q, *pools[1], offset, tables=tables, layer=0),
            PC.paged_attention(q, *pools[0], offset, tables=tables, layer=0),
            rtol=1e-5, atol=1e-6)


def test_engine_prefill_and_decode_equal_the_reference_forward(toy, served):
    """Logits, not tokens: every served token's logit in the reference's
    full forward, prompts that cross one, two and three chunk boundaries,
    then decoding through the state and the pool, ``run_ahead`` on."""
    s, _, params = toy
    assert len(served.finished) == len(REQUESTS)
    assert served.audit(heal=False)["ok"]
    summary = served.serving_summary()
    assert (summary["prefill_signatures"], summary["decode_signatures"]) \
        == (1, 1)
    gap = _served_gap(s, params, served.finished.values())
    assert gap <= 1e-4, gap


def test_a_reused_slot_starts_from_a_zero_state(toy, served):
    """Seven requests on three slots: the later ones ran in slots whose
    state a retired request left behind, and read the reference's logits
    all the same; so does a request alone in a fresh engine, token for
    token."""
    s, cfg, params = toy
    late = [f for rid, f in served.finished.items() if rid >= 3]
    assert len(late) == 4
    assert _served_gap(s, params, late) <= 1e-4
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 211, p).tolist() for p, _ in REQUESTS]
    alone = _serve(cfg, params, requests=())
    alone.submit(Request(tokens=prompts[5], max_new_tokens=REQUESTS[5][1]))
    with jax.default_matmul_precision("highest"):
        alone.run_until_idle()
    np.testing.assert_array_equal(alone.finished[0]["tokens"],
                                  served.finished[5]["tokens"])


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


def _scaled(params, kinds, leaf, by):
    """The tree with ``leaf`` of every layer of ``kinds`` multiplied."""
    layers = [{**lp, leaf: lp[leaf] * by} if leaf in lp and kind in kinds
              else lp for kind, lp in zip("MD*DMDMD", params["layers"])]
    return {**params, "layers": layers}


def _gate_behind_the_norm(monkeypatch):
    """``RMSNorm(y) * silu(z)`` where the model gates first.  The program's
    mixer is run with a CONSTANT gate (a bias column makes every z 30, and
    silu(30) = 30 is a factor the norm divides out) and the identity for its
    output projection; what comes back is gated and projected here."""
    from torchdistpackage_tpu.models import hybrid

    mixer = hybrid.mamba2_mixer

    def late_gate(p, x, c, ssm, conv, n_valid):
        di = c.d_inner
        z = hybrid.dense(x, p["in_proj"])[..., :di]
        w = jnp.concatenate([
            p["in_proj"].at[:, :di].set(0.0),
            jnp.zeros((1, p["in_proj"].shape[1]), x.dtype).at[0, :di].set(30.0)])
        ones = jnp.ones_like(x[..., :1])
        y, ssm, conv = mixer(
            {**p, "in_proj": w, "out_proj": jnp.eye(di, dtype=x.dtype)},
            jnp.concatenate([x, ones], -1), c, ssm, conv, n_valid)
        return hybrid.dense(y * jax.nn.silu(z), p["out_proj"]), ssm, conv

    monkeypatch.setattr(hybrid, "mamba2_mixer", late_gate)


FAULTS = {
    # the residual scale dropped on the mixers alone / on the MLPs alone:
    # the last projection of each absorbs 1 / 0.22
    "mixer_residual_unscaled": lambda s, cfg, p: (
        cfg, _scaled(_scaled(p, "M", "out_proj", 1 / 0.22), "*", "wo",
                     1 / 0.22)),
    "mlp_residual_unscaled": lambda s, cfg, p: (
        cfg, _scaled(p, "D", "w2", 1 / 0.22)),
    "logits_unscaled": lambda s, cfg, p: (
        dataclasses.replace(cfg, logits_scale=1.0), p),
    "attention_at_rsqrt_hd": lambda s, cfg, p: (
        dataclasses.replace(cfg, attn_scale=None), p),
    "embedding_unscaled": lambda s, cfg, p: (
        dataclasses.replace(cfg, embed_scale=1.0), p),
    "conv_bias_dropped": lambda s, cfg, p: (cfg, _scaled(p, "M", "conv_b", 0.0)),
}


def _last_logits(cfg, params, tokens):
    """The program's logits behind ``tokens``: one prefill call of 32 rows
    (padding behind the prompt) through ``paged_forward_hybrid``, no engine."""
    from torchdistpackage_tpu.models import init_state

    n = len(tokens)
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :n].set(jnp.asarray(tokens))
    with jax.default_matmul_precision("highest"):
        _, _, logits, _ = PC.paged_forward_hybrid(
            params, padded, cfg, PC.init_paged_kv(cfg, 5, 8),
            init_state(cfg, 1), jnp.asarray([[1, 2, 3, 4]], jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.asarray([n], jnp.int32),
            last_idx=jnp.asarray([n - 1], jnp.int32))
    return np.asarray(logits[0])


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS) + [
    "norm_before_gate"])
def test_each_fault_the_model_adds_reads_far_over_the_tolerance(
        toy, fault, monkeypatch):
    """The program's logits behind a prompt of 27 against the reference's:
    within 1e-4 as it stands (it reads 4e-8), and thirty times the
    tolerance and more with any one of the model's constants or orders
    wrong (the least, the convolution's bias dropped, reads 0.0087 on logits
    0.02 wide; the others 0.012-0.63)."""
    s, cfg, params = toy
    tokens = np.random.RandomState(4).randint(0, 211, 27)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward_logits(params, tokens, s))[-1]
    if fault == "norm_before_gate":
        _gate_behind_the_norm(monkeypatch)
    elif fault is not None:
        cfg, params = FAULTS[fault](s, cfg, params)
    off = float(np.abs(_last_logits(cfg, params, tokens) - want).max())
    if fault is None:
        assert off <= 1e-4, off
    else:
        assert off > 30 * 1e-4, (fault, off)


# --------------------------------------- the other families keep their numbers


def test_the_constants_at_their_defaults_change_no_other_family(toy):
    """A stack without the model's constants: the forward with the three
    new fields at their defaults is, bit for bit, the forward that spells
    the old arithmetic out (``h + y``, ``hd ** -0.5``, logits as they are),
    and a pool of heads that fill no row in whole numbers lies a head a
    row."""
    _, cfg, _ = toy
    old = HybridConfig(
        vocab_size=211, dim=64, pattern="MD*D", max_seq=MAX_CTX, nheads=4,
        kv_heads=2, mamba_heads=8, mamba_head_dim=8, ssm_state=16,
        ssm_chunk=8, dense_ffn=96, dtype=F32)
    assert (old.residual_scale, old.attn_scale, old.logits_scale,
            old.kv_pack) == (1.0, None, 1.0, 1)
    params = init_hybrid_params(jax.random.PRNGKey(1), old)
    spelled = dataclasses.replace(old, attn_scale=16 ** -0.5)
    a = _serve(old, params, requests=REQUESTS[:3])
    b = _serve(spelled, params, requests=REQUESTS[:3])
    assert PC.init_paged_kv(old, 5, 8)["k"].shape == (1, 5, 2, 8, 16)
    for rid, f in a.finished.items():
        np.testing.assert_array_equal(b.finished[rid]["tokens"], f["tokens"])
