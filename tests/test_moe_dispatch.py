"""Fused Pallas MoE dispatch (ops/moe_dispatch.py).

The load-bearing claims, each against the jnp dispatch paths as parity
oracles (PR-12's paged-attention discipline applied to the expert FFN):

- **Layer parity**: ``moe_forward(dispatch='pallas')`` — the routing
  decision fed straight into the fused gather->FFN->scatter kernel —
  matches the sorted AND dense materializations to ULP-level float
  tolerance (the tile-split matmuls vectorize differently than the
  full-view dot), forward and GRADS (the custom_vjp backward runs
  ``moe_ffn_oracle``, identical math), including a capacity that
  actually drops and the stacked SwiGLU expert.
- **EP parity**: under an EP-sharded mesh only the expert-FFN leg fuses
  (the all_to_all needs the [E, C, D] exchange layout); pallas vs sorted
  through the same shard_map must agree forward and grads.
- **int8**: ``quantize_moe_experts`` (q8, scale) pairs consumed with
  in-register dequant match the oracle's dequantize-then-matmul.
- **Engine token bit-parity**: a ``moe_dispatch='pallas'`` engine emits
  tokens BIT-equal to contiguous ``generate()`` and to the gather
  engine, at one decode signature, and ``serving_summary()['moe']``
  carries the live expert-load block the router's load index consumes.
- **Memory evidence**: the sorted arm's compiled forward materializes
  the [E, C, D] slot view (``modeled_slot_view_bytes`` prices it); the
  fused arm's program never allocates that shape — the HBM round-trip
  the kernel exists to eliminate.

Budget: ONE module-scope bundle (the test_serving MoE family) holds the
golden and the gather/pallas engine pair; layer tests share one routing
decision per shape.  On CPU the kernel runs in interpreter mode — parity
is the claim here; the HBM-traffic win is an on-chip claim (ROADMAP 5c).
"""

import dataclasses
import functools

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
    init_gpt_moe_params,
)
from torchdistpackage_tpu.obs.events import EventLog, set_default_event_log
from torchdistpackage_tpu.ops.moe_dispatch import (
    fused_moe_ffn,
    modeled_slot_view_bytes,
    moe_ffn_oracle,
    quantize_moe_experts,
    resolve_moe_dispatch,
    slot_maps,
)
from torchdistpackage_tpu.parallel.moe import (
    MoEConfig,
    _top_k_route,
    init_moe_params,
    moe_forward,
    moe_param_specs,
)
from torchdistpackage_tpu.serving import Request, ServingEngine

# The test_serving MoE family: cf = E/top_k -> no drops, so engine tokens
# must be BIT-equal to the contiguous generate() golden.
CFG = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=32,
                moe_experts=4, moe_top_k=2, moe_every=2,
                moe_capacity_factor=2.0)
PROMPT, NEW = 5, 6


def _run_staggered(eng, prompts):
    """The engine's real regime: request B admitted while A decodes."""
    r0 = eng.submit(Request(prompts[0].tolist(), NEW))
    eng.step()
    eng.step()
    r1 = eng.submit(Request(prompts[1].tolist(), NEW))
    eng.run_until_idle(max_ticks=500)
    return [np.asarray(eng.finished[r]["tokens"]) for r in (r0, r1)]


@pytest.fixture(scope="module")
def bundle():
    """Module-scope bundle: golden + the gather/pallas engine pair —
    every engine-level test reuses the same compiled programs."""
    params = init_gpt_moe_params(jax.random.PRNGKey(0), CFG)
    prompts = np.stack([
        np.asarray(jax.random.randint(
            jax.random.PRNGKey(10 + i), (PROMPT,), 0, CFG.vocab_size))
        for i in range(2)
    ]).astype(np.int32)
    want = np.asarray(jax.jit(
        lambda p, t: generate(p, t, CFG, max_new_tokens=NEW)
    )(params, jnp.asarray(prompts)))
    out = {"params": params, "prompts": prompts, "want": want,
           "eng": {}, "tokens": {}}
    ekw = dict(num_slots=2, block_size=8, chunk=4, max_ctx=16)
    for impl in ("pallas", "gather"):
        eng = ServingEngine(params, CFG, moe_dispatch=impl, **ekw)
        out["tokens"][impl] = _run_staggered(eng, prompts)
        out["eng"][impl] = eng
    return out


# ------------------------------------------------------------ layer parity


def _routed(cfg, seed=1, B=2, S=16):
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.dim))
    return params, x


def _loss(p, x, cfg):
    y, aux = moe_forward(p, x, cfg)
    return jnp.mean(y * y) + aux


def test_fused_matches_sorted_and_dense_fwd_and_grad():
    """moe_forward(dispatch='pallas') vs the sorted and dense
    materializations: same routing decision, bit-identical f32 outputs
    AND grads (the fused bwd runs moe_ffn_oracle — the same gather/FFN/
    scatter math the jnp paths compute), for the no-drop capacity, a
    capacity that actually DROPS, and the stacked SwiGLU expert.

    Fast-tier holder for the slow-tier matrix in test_moe.py
    (test_sorted_dispatch_matches_dense / .._under_ep_matches_serial)."""
    base = MoEConfig(dim=16, ffn_dim=32, num_experts=4, top_k=2,
                     capacity_factor=4.0)
    for variant in [base,
                    dataclasses.replace(base, capacity_factor=0.6),
                    dataclasses.replace(base, act="swiglu")]:
        params, x = _routed(variant)
        got = {}
        for dispatch in ("pallas", "sorted", "dense"):
            cfg = dataclasses.replace(variant, dispatch=dispatch)
            got[dispatch] = jax.jit(jax.value_and_grad(
                functools.partial(_loss, x=x, cfg=cfg)))(params)
        for other in ("sorted", "dense"):
            lp, ls = got["pallas"][0], got[other][0]
            np.testing.assert_allclose(
                float(lp), float(ls), rtol=1e-6,
                err_msg=f"cf={variant.capacity_factor} act={variant.act}")
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7,
                    err_msg=f"pallas vs {other} grads "
                            f"(cf={variant.capacity_factor}, "
                            f"act={variant.act})"),
                got["pallas"][1], got[other][1])


def test_fused_forward_matches_oracle():
    """fused_moe_ffn and moe_ffn_oracle consume the SAME slot maps and
    run the same f32 dot chain; the kernel tiles the capacity dim, so
    parity is ULP-level float tolerance (the PR-12 kernel bar — BIT
    equality is the engine-token claim below) — drops included."""
    T, D, E, k = 24, 16, 4, 2
    experts = init_moe_params(
        jax.random.PRNGKey(0),
        MoEConfig(dim=D, ffn_dim=32, num_experts=E, top_k=k))["experts"]
    tokens = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(2), (T, E)), axis=-1)
    for capacity in (T, 3):  # no-drop bound, and a capacity that drops
        gv, gi, slot, keep = _top_k_route(probs, k, capacity)
        got = fused_moe_ffn(experts, tokens, gv, gi, slot, keep, capacity)
        want = moe_ffn_oracle(experts, tokens, gv, gi, slot, keep, capacity)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6,
            err_msg=f"capacity={capacity}")


def test_int8_fused_matches_oracle():
    """quantize_moe_experts (q8, scale) pairs through the kernel's
    in-register dequant vs the oracle's dequantize-then-matmul: the same
    dequantized f32 values through the same FFN math, to ULP-level
    tolerance — gelu and SwiGLU expert stacks."""
    T, D, E, k = 16, 16, 4, 2
    for act in ("gelu", "swiglu"):
        experts = init_moe_params(
            jax.random.PRNGKey(0),
            MoEConfig(dim=D, ffn_dim=32, num_experts=E, top_k=k,
                      act=act))["experts"]
        q = quantize_moe_experts(experts)
        assert q["w1"][0].dtype == jnp.int8 and q["w2"][0].dtype == jnp.int8
        tokens = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
        probs = jax.nn.softmax(
            jax.random.normal(jax.random.PRNGKey(2), (T, E)), axis=-1)
        gv, gi, slot, keep = _top_k_route(probs, k, T)
        got = fused_moe_ffn(q, tokens, gv, gi, slot, keep, T)
        want = moe_ffn_oracle(q, tokens, gv, gi, slot, keep, T)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"act={act}")
        # and the dequantized values track the float expert to quant tol
        fp = moe_ffn_oracle(experts, tokens, gv, gi, slot, keep, T)
        np.testing.assert_allclose(np.asarray(got), np.asarray(fp),
                                   rtol=0.1, atol=0.05)


def test_slot_maps_compress_the_routing_decision():
    """slot_maps is the kernel's contract: each KEPT (token, choice)
    occupies exactly one (expert, slot) cell carrying its renormalized
    gate; dropped choices and empty slots carry comb == 0."""
    T, E, k, capacity = 12, 4, 2, 2  # capacity 2 < T*k/E: drops happen
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(3), (T, E)), axis=-1)
    gv, gi, slot, keep = _top_k_route(probs, k, capacity)
    idx, comb = slot_maps(gv, gi, slot, keep, capacity)
    assert idx.shape == (E, capacity) and comb.shape == (E, capacity)
    kept = np.asarray(jnp.sum(keep, axis=-1))  # [T, k]
    assert int(kept.sum()) == int((np.asarray(comb) != 0).sum())
    # every kept choice is found at its (expert, slot) cell with its gate
    gv_n, gi_n, sl_n = np.asarray(gv), np.asarray(gi), np.asarray(slot)
    for t in range(T):
        for j in range(k):
            if kept[t, j]:
                e, c = gi_n[t, j], sl_n[t, j]
                assert int(np.asarray(idx)[e, c]) == t
                np.testing.assert_allclose(
                    float(np.asarray(comb)[e, c]), float(gv_n[t, j]),
                    rtol=1e-6)


# --------------------------------------------------------------- EP parity


def test_fused_ep_matches_sorted(devices8):
    """Under EP only the expert-FFN leg fuses (the all_to_all exchange
    needs the [E, C, D] grouped layout — it IS the wire payload):
    dispatch='pallas' through a moe_dp=2 x moe_ep=2 shard_map must match
    'sorted' forward and grads.  Fast-tier EP holder for the slow-tier
    test_sorted_dispatch_under_ep_matches_serial."""
    tpc.setup_process_groups([("data", 4)], devices=devices8[:4])
    tpc.build_moe_mesh(moe_ep_size=2)
    mesh = tpc.get_view("moe")

    base = MoEConfig(dim=16, ffn_dim=32, num_experts=4, top_k=2,
                     capacity_factor=4.0)
    params = init_moe_params(jax.random.PRNGKey(0), base)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, base.dim))
    specs = moe_param_specs("moe_ep")
    xspec = P(("moe_dp", "moe_ep"))
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs)
    x_sh = jax.device_put(x, NamedSharding(mesh, xspec))

    got = {}
    for dispatch in ("pallas", "sorted"):
        cfg = dataclasses.replace(base, dispatch=dispatch)

        def loss(p, xx, cfg=cfg):
            y, aux = moe_forward(p, xx, cfg, ep_axis="moe_ep")
            return jax.lax.pmean(
                jnp.mean(y * y) + aux, ("moe_dp", "moe_ep"))

        got[dispatch] = jax.jit(shard_map(
            jax.value_and_grad(loss), mesh=mesh,
            in_specs=(specs, xspec), out_specs=(P(), specs),
        ))(sharded, x_sh)
    np.testing.assert_allclose(
        float(got["pallas"][0]), float(got["sorted"][0]), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7,
            err_msg="pallas vs sorted under EP"),
        got["pallas"][1], got["sorted"][1])


# ------------------------------------------------------ engine token parity


def test_engine_token_bit_parity(bundle):
    """The moe_dispatch='pallas' engine (interpreter-mode kernel at the
    serving no-drop capacity bound C=T) emits tokens BIT-equal to
    contiguous generate() and to the gather engine, one decode signature
    per arm."""
    for impl in ("pallas", "gather"):
        for row, got in enumerate(bundle["tokens"][impl]):
            np.testing.assert_array_equal(
                got, bundle["want"][row],
                err_msg=f"moe_dispatch={impl} diverged from generate()")
        s = bundle["eng"][impl].serving_summary()
        assert s["decode_signatures"] == 1
        assert s["requests"]["completed"] == 2


def test_engine_moe_summary_block(bundle):
    """serving_summary()['moe'] is the live expert-load block the
    router's load index consumes: real per-expert routed-token counts,
    normalized entropy, no drops at cf=E/top_k, and the dispatch arm
    recorded so an A/B artifact names its kernel."""
    for impl in ("pallas", "gather"):
        eng = bundle["eng"][impl]
        moe = eng.serving_summary()["moe"]
        assert moe["dispatch"] == impl
        assert moe["num_experts"] == CFG.moe_experts
        assert len(moe["expert_tokens"]) == CFG.moe_experts
        assert sum(moe["expert_tokens"]) > 0  # stats actually flowed
        assert moe["imbalance"] >= 0.0
        assert 0.0 <= moe["load_entropy"] <= 1.0
        assert moe["dropped_token_rate"] == 0.0  # cf = E/top_k: no drops
        assert eng.moe_imbalance() == pytest.approx(moe["imbalance"])
    # both arms routed through the SAME router weights on the same
    # prompts: the load pictures must agree
    ga = bundle["eng"]["gather"].serving_summary()["moe"]
    pa = bundle["eng"]["pallas"].serving_summary()["moe"]
    np.testing.assert_allclose(pa["expert_tokens"], ga["expert_tokens"])


# ----------------------------------------------------- memory-ledger evidence


def test_compiled_forward_drops_slot_view():
    """The static-ledger evidence (the paged-attention
    test_compiled_decode_drops_gathered_temp claim, for experts): the
    sorted arm's compiled FORWARD materializes the [E, C, D] slot view
    — the HBM buffer modeled_slot_view_bytes prices — while the fused
    arm's program never allocates that shape (its working set is the
    [c_tile, D] scratch).  Forward only: the custom_vjp backward
    deliberately differentiates moe_ffn_oracle, which gathers the view."""
    from torchdistpackage_tpu.obs.mem_ledger import static_ledger

    # ffn_dim deliberately != C: w2 is [E, F, D], which at F == C would
    # alias the slot-view shape string and false-positive the probe
    E, D = 4, 32
    base = MoEConfig(dim=D, ffn_dim=48, num_experts=E, top_k=2,
                     capacity_factor=2.0)
    params = init_moe_params(jax.random.PRNGKey(0), base)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, D))  # T = 64
    C = 64  # ceil(T * top_k * cf / E)
    view = f"f32[{E},{C},{D}]"
    assert modeled_slot_view_bytes(E, C, D) == 2 * E * C * D * 4

    texts = {}
    for dispatch in ("pallas", "sorted"):
        cfg = dataclasses.replace(base, dispatch=dispatch)
        comp = jax.jit(
            lambda p, xx, cfg=cfg: moe_forward(p, xx, cfg)[0]
        ).lower(params, x).compile()
        assert static_ledger(comp) is not None
        texts[dispatch] = comp.as_text()
    assert view in texts["sorted"], (
        "sorted arm lost its [E, C, D] slot view? shapes under test are "
        "stale")
    assert view not in texts["pallas"], (
        "fused forward still materializes the [E, C, D] slot view")


# ------------------------------------------------------------------ resolve


def test_resolve_moe_dispatch():
    """'auto' resolves per backend (the jnp size-based selection on CPU —
    the interpreter kernel is a correctness story, not a speed story),
    records the choice on the event timeline, and junk is rejected at
    both the op and engine layers."""
    log = EventLog()
    set_default_event_log(log)
    try:
        assert resolve_moe_dispatch("auto") == "auto"  # CPU container
        assert resolve_moe_dispatch(None) == "auto"
        sel = log.of_kind("moe_dispatch_selected")
        assert sel and sel[-1]["chosen"] == "auto"
    finally:
        set_default_event_log(None)
    for ok in ("dense", "sorted", "pallas"):
        assert resolve_moe_dispatch(ok) == ok
    with pytest.raises(ValueError, match="dispatch"):
        resolve_moe_dispatch("cuda")
    with pytest.raises(ValueError, match="moe_dispatch"):
        ServingEngine(None, CFG, moe_dispatch="dense")  # engine arm names
    dense_cfg = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2,
                          max_seq=32)
    with pytest.raises(ValueError, match="no MoE"):
        ServingEngine(None, dense_cfg, moe_dispatch="pallas")
