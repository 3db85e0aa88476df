"""The hybrid family (models/hybrid.py: Mamba-2 + latent MoE + position-free
GQA, one mixer a layer) at toy widths on the CPU: the mixer's two forms, the
expert layer's shares, and chunked prefill + decode through ``ServingEngine``
against the plain reference's full forward (benchmarks/reference/
nemotron_h.py, which imports nothing of the program)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import nemotron_h as family
from benchmarks.reference import nemotron_h as ref
from benchmarks.weights_nemotron_h import make_weights
from torchdistpackage_tpu.models import (
    GPTConfig, HybridConfig, init_gpt_moe_params, init_hybrid_params,
    init_state, mamba2_mixer)
from torchdistpackage_tpu.parallel.moe import (
    MoEConfig, init_moe_params, moe_forward, moe_serve_forward)
from torchdistpackage_tpu.serving import Request, ServingEngine

#: a ``nemotron_h`` configuration file in small: 16 experts routed, 4 held
#: (the second of four shares), chunk 8
TOY = {
    "name": "toy-nemotron", "family": "nemotron_h", "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "hybrid_override_pattern": "MEM*EME", "num_hidden_layers": 7,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 4, "published": {"n_routed_experts": 16},
    "deployment_share": {"first_expert": 4}, "num_experts_per_tok": 6,
    "moe_latent_size": 32, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "routed_scaling_factor": 5,
    "layer_norm_epsilon": 1e-5, "n_group": 1, "topk_group": 1,
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


def test_pattern_counts_and_state_bytes(toy):
    s, cfg, _ = toy
    assert (cfg.nlayers, cfg.kv_layers, cfg.state_layers) == (7, 1, 3)
    # 3 Mamba layers x (8 x 8 x 16 float32 + 3 rows x (64 + 2*2*16) float32)
    assert cfg.state_bytes(5) == 5 * 3 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert family.state_bytes_per_slot(s, itemsize=4) == cfg.state_bytes(1)
    with pytest.raises(ValueError, match="pattern"):
        dataclasses.replace(cfg, pattern="MXE")
    # the family's count is the tree's
    params = init_hybrid_params(jax.random.PRNGKey(0), cfg)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == family.num_params(s)


def test_chunked_scan_equals_the_one_step_recurrence_and_padding_is_inert(toy):
    _, cfg, _ = toy
    # the program's own seeded layout (the other tests take the benchmark's)
    p = init_hybrid_params(jax.random.PRNGKey(9), cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, cfg.dim), F32)
    st = init_state(cfg, 3)
    ssm0, conv0 = st["ssm"][0], st["conv"][0]
    n_valid = jnp.asarray([16, 11, 0])
    with jax.default_matmul_precision("highest"):
        y, ssm, conv = mamba2_mixer(p, x, cfg, ssm0, conv0, n_valid)
        # position by position through the S == 1 form
        s1, c1, ys = ssm0, conv0, []
        for t in range(16):
            yt, s1, c1 = mamba2_mixer(
                p, x[:, t:t + 1], cfg, s1, c1,
                (t < n_valid).astype(jnp.int32))
            ys.append(yt)
    # float32 sums in another order: the chunk's matrix form against 16 steps
    np.testing.assert_allclose(ssm, s1, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(conv), np.asarray(c1))
    got, want = np.asarray(y), np.asarray(jnp.concatenate(ys, axis=1))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[1, :11], want[1, :11], rtol=2e-4, atol=2e-5)
    # the row with no real position has its state back bit for bit, from
    # a state that is not zero too
    _, ssm2, conv2 = mamba2_mixer(p, x, cfg, ssm, conv, jnp.asarray([0, 0, 0]))
    np.testing.assert_array_equal(np.asarray(ssm2), np.asarray(ssm))
    np.testing.assert_array_equal(np.asarray(conv2), np.asarray(conv))
    assert float(jnp.abs(ssm[2]).max()) == 0.0


def test_the_four_shares_add_up_to_the_uncut_layer(toy):
    """Each share's routed part (through the up-projection) plus the shared
    expert counted ONCE is the uncut reference's layer."""
    s, cfg, _ = toy
    full = dataclasses.replace(s, held_first=0, held=16)
    whole = make_weights(full, 11)["layers"][1]          # an 'E' layer
    whole = jax.tree.map(lambda a: a.astype(F32), whole)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, s.dim), F32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(whole, x.reshape(18, s.dim), full)
        shared = ref.mm(ref.relu2(ref.mm(x.reshape(18, s.dim),
                                         whole["shared"]["w1"])),
                        whole["shared"]["w2"])
        total, held_rows = 0.0, 0.0
        for first in (0, 4, 8, 12):
            mcfg = dataclasses.replace(cfg.moe, held=(first, 4))
            part = dict(whole, experts=jax.tree.map(
                lambda w: w[first:first + 4], whole["experts"]))
            y, met = moe_serve_forward(part, x, mcfg, return_metrics=True)
            total = total + (y.reshape(18, s.dim) - shared)
            held_rows += float(met["rows_held"])
            assert float(met["rows_routed"]) == 18 * 6
    assert held_rows == 18 * 6          # every assignment is some share's
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("size", [9, 160], ids=["batched", "grouped"])
def test_a_padding_rows_choices_fall_on_no_held_expert(toy, size):
    """``valid``: a real row gets what it gets without the mask; a padding
    row gets the shared expert's part alone, and its choices are in no
    group of the grouped matmul (nor in the counts), whatever they were."""
    s, cfg, _ = toy
    p = make_weights(s, 11)["layers"][1]
    p = jax.tree.map(lambda a: a.astype(F32), p)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, size, s.dim), F32)
    valid = jnp.arange(size)[None, :] < jnp.asarray([size - 4, 0])[:, None]
    with jax.default_matmul_precision("highest"):
        y0, m0 = moe_serve_forward(p, x[:1], cfg.moe, return_metrics=True)
        y, m = moe_serve_forward(p, x, cfg.moe, return_metrics=True,
                                 valid=valid)
        shared = ref.mm(ref.relu2(ref.mm(x, p["shared"]["w1"])),
                        p["shared"]["w2"])
    np.testing.assert_allclose(y[0, :size - 4], y0[0, :size - 4],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y[1], shared[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y[0, size - 4:], shared[0, size - 4:],
                               rtol=1e-5, atol=1e-6)
    assert float(m["rows_routed"]) == (size - 4) * 6
    assert float(m["rows_held"]) == float(m["expert_tokens"].sum()) \
        <= float(m0["rows_held"])


def _old_serve_forward(params, x, cfg):
    """``moe_serve_forward``'s ragged path as it stood before the latent
    family was written into it, operation for operation."""
    B, S, D = x.shape
    T, E, k = B * S, cfg.num_experts, cfg.top_k
    tokens = x.reshape(T, D)
    probs = jax.nn.softmax(
        (tokens @ params["router"]["w"]).astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    flat_expert = gate_idx.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_tok = (order // k).astype(jnp.int32)
    sorted_expert = flat_expert[order]
    rows = tokens[sorted_tok]
    group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)
    ex = params["experts"]
    if ex["w1"].ndim == 4:
        F = ex["w1"].shape[-1]
        w1 = ex["w1"].transpose(0, 2, 1, 3).reshape(E, D, 2 * F)
        gu = jax.lax.ragged_dot(rows, w1, group_sizes)
        gu = gu + ex["b1"].reshape(E, 2 * F)[sorted_expert]
        h = jax.nn.silu(gu[:, :F]) * gu[:, F:]
    else:
        h = jax.lax.ragged_dot(rows, ex["w1"], group_sizes)
        h = jax.nn.gelu(h + ex["b1"][sorted_expert])
    out = jax.lax.ragged_dot(h, ex["w2"], group_sizes)
    out = out + ex["b2"][sorted_expert]
    g = gate_vals.reshape(-1)[order].astype(out.dtype)
    y = jnp.zeros((T, D), out.dtype).at[sorted_tok].add(g[:, None] * out)
    return y.reshape(B, S, D).astype(x.dtype)


@pytest.mark.parametrize("act,dtype", [("swiglu", jnp.bfloat16),
                                       ("gelu", jnp.float32)])
def test_the_mixtral_shaped_layer_keeps_its_results_bit_for_bit(act, dtype):
    cfg = MoEConfig(dim=32, ffn_dim=48, num_experts=4, top_k=2, act=act,
                    dtype=dtype)
    params = init_moe_params(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 7, 32)).astype(dtype)
    new, met = jax.jit(lambda p, x: moe_serve_forward(
        p, x, cfg, return_metrics=True))(params, x)
    old = jax.jit(lambda p, x: _old_serve_forward(p, x, cfg))(params, x)
    np.testing.assert_array_equal(np.asarray(new, np.float32),
                                  np.asarray(old, np.float32))
    assert float(met["expert_tokens"].sum()) == 2 * 7 * 2
    assert set(met) == {"expert_tokens", "dropped_token_rate"}


def test_the_training_path_refuses_the_latent_family(toy):
    _, cfg, params = toy
    with pytest.raises(NotImplementedError, match="serving path only"):
        moe_forward(params["layers"][1], jnp.zeros((1, 4, cfg.dim)), cfg.moe)
    with pytest.raises(ValueError, match="held experts"):
        dataclasses.replace(cfg.moe, held=(14, 4))


# ---------------------------------------------------------------- the engine


def _served_gap(s, params, finished):
    """The widest gap by which a served token's logit lies below the
    reference's best, the reference teacher-forced over each request."""
    worst = 0.0
    for f in finished:
        toks = np.asarray(f["tokens"])
        p = len(toks) - f["new_tokens"]
        logits = np.asarray(ref.forward_logits(params, toks[:-1], s))[p - 1:]
        served = logits[np.arange(len(toks) - p), toks[p:]]
        worst = max(worst, float((logits.max(-1) - served).max()))
    return worst


@pytest.fixture(scope="module")
def served(toy):
    """Seven requests on three slots, chunk 8: prompts that are (8, 16, 24)
    and are not (13, 5, 21, 9) multiples of the chunk, one to three chunks
    long; more requests than slots, so slots are re-admitted from a state
    that is not zero; their different lengths leave decode calls with
    masked slots and prefill calls with padding rows."""
    s, cfg, params = toy
    rng = np.random.RandomState(0)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(params, cfg, num_slots=3, block_size=8, chunk=8,
                            max_ctx=64, attn_impl="gather",
                            record_routing=True)
        for i, n in enumerate((8, 13, 16, 5, 21, 24, 9)):
            eng.submit(Request(tokens=rng.randint(0, 211, n).tolist(),
                               max_new_tokens=4 + 3 * (i % 3)))
        eng.run_until_idle()
    return eng


def test_engine_prefill_and_decode_equal_the_reference_forward(toy, served):
    s, _, params = toy
    assert len(served.finished) == 7
    masked = [t for t in served.tick_records
              if 0 < t["decode_slots"] < served.num_slots]
    assert masked, "no decode call had a masked slot"
    with jax.default_matmul_precision("highest"):
        gap = _served_gap(s, params, served.finished.values())
    # float32 at 'highest' on both sides; what is left is summation order
    # (the chunk's matrix form, the grouped expert GEMM): 1e-4 of a logit
    assert gap <= 1e-4, gap


def test_recorded_routing_is_the_references_own_choice(toy, served):
    """``record_routing``: a finished request carries the experts that each
    FED position (all but the last token) chose in each expert layer.  In
    float32 on both sides nothing flips: they are the reference's own, and
    following them changes nothing (deficit 0, the same logits)."""
    s, cfg, params = toy
    with jax.default_matmul_precision("highest"):
        for f in served.finished.values():
            toks = np.asarray(f["tokens"])
            assert f["routing"].shape == (len(toks) - 1, 3, 6)
            assert f["routing"].dtype == np.int16
            own = ref.forward_following(params, toks[:-1], s)
            np.testing.assert_array_equal(
                np.sort(f["routing"], -1), np.sort(own["routing"], -1))
            led = ref.forward_following(params, toks[:-1], s,
                                        follow=f["routing"])
            assert float(led["deficit"].max()) == 0.0
            np.testing.assert_allclose(led["logits"], own["logits"],
                                       rtol=1e-5, atol=1e-6)
    # another choice IS another function, and the deficit says how wrong
    wrong = np.array(f["routing"])
    wrong[3, 1, :] = np.argsort(np.asarray(own["routing"])[3, 1])[:6] + 10
    with jax.default_matmul_precision("highest"):
        led = ref.forward_following(params, toks[:-1], s, follow=wrong % 16)
    assert float(led["deficit"][3, 1]) > 0.01
    with pytest.raises(NotImplementedError, match="record_routing"):
        ServingEngine(None, GPTConfig(vocab_size=8, dim=8, nheads=2,
                                      nlayers=1, max_seq=8),
                      record_routing=True)


def test_engine_keeps_one_signature_each_and_counts_its_state(toy, served):
    _, cfg, _ = toy
    summ = served.serving_summary()
    assert summ["prefill_signatures"] == summ["decode_signatures"] == 1
    assert served.state_bytes == cfg.state_bytes(3) > 0
    assert len(served.state["ssm"]) == cfg.state_layers
    assert served.state["ssm"][0].shape == (3, 8, 8, 16)
    assert served.cache["k"].shape[0] == cfg.kv_layers
    st = served.stats
    # a quarter of the experts held: about a quarter of the rows, and the
    # tick records carry what the counters sum
    assert 0.1 < st["moe_rows_held"] / st["moe_rows_routed"] < 0.45
    assert st["experts_touched"] > 0
    assert sum(t["moe_rows_routed"] for t in served.tick_records) \
        == st["moe_rows_routed"]
    assert summ["moe"]["num_experts"] == 4 and served.moe_imbalance() >= 0.0
    from torchdistpackage_tpu.utils.profiling import spans
    names = {r[2] for r in spans.snapshot()}
    assert "tdp:engine.init.state" in names
    pre = [r for r in spans.snapshot() if r[2] == "tdp:engine.prefill"
           and "state_slots" in r[5]]
    assert pre and all(1 <= r[5]["state_slots"] <= 3 for r in pre)


@pytest.mark.parametrize("run_ahead", [False, True],
                         ids=["in_step", "run_ahead"])
def test_a_preempted_request_restarts_from_a_zero_state(toy, run_ahead):
    """Priority preemption requeues a half-decoded request: its slot goes to
    another sequence, and its replay starts at position 0 from a zero
    state, so it ends with the tokens of an undisturbed run.  With
    ``run_ahead`` the victim has a token in flight, which is dropped."""
    s, cfg, params = toy
    rng = np.random.RandomState(5)
    low = Request(tokens=rng.randint(0, 211, 11).tolist(), max_new_tokens=8)
    high = Request(tokens=rng.randint(0, 211, 14).tolist(), max_new_tokens=5,
                   priority=1)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(params, cfg, num_slots=1, block_size=8, chunk=8,
                            max_ctx=32, attn_impl="gather",
                            run_ahead=run_ahead)
        a = eng.submit(low)
        for _ in range(5):
            eng.step()
        b = eng.submit(high)
        eng.run_until_idle()
        assert eng.stats["preempted"] == 1
        gap = _served_gap(s, params, [eng.finished[a], eng.finished[b]])
    assert gap <= 1e-4, gap


def _serve(toy, run_ahead, temperature=0.0, eos=None, cancel_at=None):
    """The ``served`` fixture's seven requests on three slots, sampled at
    ``temperature`` (one for all, or {request index: its own}, the others
    greedy) from a seed each; ``eos``: {request index: eos_id}."""
    _, cfg, params = toy
    temps = temperature if isinstance(temperature, dict) else {
        i: temperature for i in range(7)}
    rng = np.random.RandomState(0)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(params, cfg, num_slots=3, block_size=8, chunk=8,
                            max_ctx=64, attn_impl="gather",
                            record_routing=True, run_ahead=run_ahead)
        for i, n in enumerate((8, 13, 16, 5, 21, 24, 9)):
            eng.submit(Request(tokens=rng.randint(0, 211, n).tolist(),
                               max_new_tokens=4 + 3 * (i % 3),
                               temperature=temps.get(i, 0.0), seed=100 + i,
                               eos_id=(eos or {}).get(i)))
        if cancel_at is not None:
            for _ in range(cancel_at):
                eng.step()
            assert eng.cancel(1)
        eng.run_until_idle()
    return eng


@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
def test_run_ahead_serves_the_unpipelined_engines_tokens(toy, temperature):
    """``run_ahead``: every request ends with the tokens, the chosen experts
    and the reason that the engine gives without it: the token and the key
    a slot is fed from the device are the ones the host would have fed.
    Also with an ``eos_id`` that strikes mid-way (the token in flight
    behind it is dropped) and with the same two programs."""
    plain = _serve(toy, False, temperature)
    # an eos that the plain run emits second (request 2) and fourth (5)
    eos = {i: int(plain.finished[i]["tokens"][plain.finished[i]["prompt_len"]
                                              + at]) for i, at in ((2, 1), (5, 3))}
    for ends in (None, eos):
        want = _serve(toy, False, temperature, ends) if ends else plain
        got = _serve(toy, True, temperature, ends)
        assert got.finished.keys() == want.finished.keys() == set(range(7))
        for rid, w in want.finished.items():
            g = got.finished[rid]
            np.testing.assert_array_equal(g["tokens"], w["tokens"])
            np.testing.assert_array_equal(g["routing"], w["routing"])
            assert (g["reason"], g["new_tokens"]) == (w["reason"],
                                                      w["new_tokens"])
        if ends:
            assert [want.finished[i]["reason"] for i in ends] == ["eos"] * 2
        assert got.stats["generated_tokens"] == want.stats["generated_tokens"]
        assert got._step_fn._cache_size() == 2      # one prefill, one decode
        summ = got.serving_summary()
        assert summ["prefill_signatures"] == summ["decode_signatures"] == 1
        assert got._flight is None and got.n_busy == 0
    # fewer host waits: the calls are the same, a retirement shows a tick late
    assert got.stats["decode_steps"] <= want.stats["decode_steps"] + 7


@pytest.mark.parametrize("run_ahead", [False, True],
                         ids=["in_step", "run_ahead"])
def test_one_sampling_request_among_greedy_ones(toy, run_ahead):
    """The state step's sampler chooses by its rows as the dense step's
    does: with request 1 alone sampling, every greedy request ends with the
    all-greedy run's tokens and request 1 with those it draws when every
    request samples (the same seed, neighbours that asked for something
    else), and ``sampled_rows`` on the spans reads 0 on the calls that took
    the greedy branch.  ``run_ahead`` carries the keys on the device."""
    from torchdistpackage_tpu.utils.profiling import spans

    def serve(temps):
        before = len(spans)  # not cleared: the ring is `served`'s too
        eng = _serve(toy, run_ahead, temps)
        counts = [r[5]["sampled_rows"] for r in spans.snapshot()[before:]
                  if r[2] in ("tdp:engine.prefill", "tdp:engine.decode")]
        return eng.finished, counts

    greedy, counts = serve(0.0)
    assert set(counts) == {0}
    every, counts = serve(0.9)
    assert 0 not in counts and max(counts) == 3
    mixed, counts = serve({1: 0.9})
    assert set(counts) == {0, 1}  # both branches ran in this engine
    for rid in range(7):
        want = every if rid == 1 else greedy
        np.testing.assert_array_equal(mixed[rid]["tokens"],
                                      want[rid]["tokens"])
        np.testing.assert_array_equal(mixed[rid]["routing"],
                                      want[rid]["routing"])
    assert not np.array_equal(every[1]["tokens"], greedy[1]["tokens"])


def test_call_ties_a_run_ahead_fetch_to_the_dispatch_of_the_tick_before(toy):
    """With ``run_ahead`` a tick's fetch waits for the decode call that the
    tick BEFORE dispatched: the ``call`` attr says which, so that a reader
    of the spans need not guess (benchmarks/layer_metrics/idle_by_phase.py)."""
    from torchdistpackage_tpu.utils import spans

    spans.clear()
    eng = _serve(toy, True)
    ring = spans.snapshot()
    ticks = {r[0]: r[5]["tick"] for r in ring if r[2] == "tdp:engine.tick"}
    made = {r[5]["call"]: (r[2], ticks[r[1]]) for r in ring
            if r[2] in ("tdp:engine.prefill", "tdp:engine.decode")}
    # a running count: every device call of the engine once (a prefill
    # span names its last)
    assert max(made) == (eng.stats["prefill_calls"]
                         + eng.stats["decode_steps"])
    behind = 0
    for r in ring:
        if r[2] != "tdp:engine.fetch":
            continue
        kind, tick = made[r[5]["call"]]
        assert tick <= ticks[r[1]]
        if kind == "tdp:engine.prefill":
            assert tick == ticks[r[1]]   # a prefill call is fetched in its tick
        behind += tick < ticks[r[1]]
    assert behind >= 5   # decode calls: fetched a tick (or an idle poll) on


def test_run_ahead_drops_the_token_in_flight_of_a_cancelled_request(toy):
    got = _serve(toy, True, cancel_at=5)
    want = _serve(toy, False, cancel_at=5)
    assert got.finished[1]["reason"] == "cancelled"
    for rid in set(range(7)) - {1}:
        np.testing.assert_array_equal(got.finished[rid]["tokens"],
                                      want.finished[rid]["tokens"])
    done = got.finished[1]["new_tokens"]
    np.testing.assert_array_equal(
        got.finished[1]["tokens"],
        want.finished[1]["tokens"][:len(got.finished[1]["tokens"])])
    assert done <= want.finished[1]["new_tokens"]
    assert got.audit(heal=False)["ok"]


def test_an_explicit_run_ahead_raises_where_the_engine_keeps_the_serial_order():
    """``run_ahead`` is every single-device engine's own choice now (the
    dense family's too: tests/test_tick_order.py); what is left of the
    refusal is an explicit ``True`` where the step has no ``prev`` form,
    here a speculative engine, whose next draft needs this tick's tokens."""
    cfg = GPTConfig(vocab_size=8, dim=8, nheads=2, nlayers=1, max_seq=8)
    with pytest.raises(NotImplementedError, match="run_ahead with spec_k"):
        ServingEngine(None, cfg, spec_k=2, run_ahead=True)
    with pytest.raises(NotImplementedError, match="record_routing"):
        ServingEngine(None, cfg, record_routing=True)


@pytest.mark.parametrize("kw", [{"prefix_cache": True}, {"spec_k": 2}],
                         ids=["prefix_cache", "spec_k"])
def test_a_state_model_refuses_what_needs_snapshots(toy, kw):
    _, cfg, params = toy
    with pytest.raises(NotImplementedError, match="state model"):
        ServingEngine(params, cfg, num_slots=2, block_size=8, chunk=8,
                      max_ctx=32, **kw)


def test_a_state_model_refuses_drain_and_migration(toy, served):
    with pytest.raises(NotImplementedError, match="state model"):
        served.drain()
    with pytest.raises(NotImplementedError, match="state model"):
        served.resume({})
    with pytest.raises(NotImplementedError, match="state model"):
        served.export_slot(0)
    with pytest.raises(NotImplementedError, match="state model"):
        served.import_slot({})
    _, cfg, params = toy
    with pytest.raises(ValueError, match="recurrence chunk"):
        ServingEngine(params, cfg, num_slots=2, block_size=8, chunk=12,
                      max_ctx=32)


def test_dense_and_moe_engines_still_equal_their_goldens():
    """The engine's dispatch was refactored around the state argument: a
    dense and a Mixtral-shaped model still produce ``generate()``'s tokens
    (tests/test_serving.py holds the full matrix; this is the two-line
    version that names what this PR must not move)."""
    from torchdistpackage_tpu.models import generate, init_gpt_params

    for moe in (0, 4):
        cfg = GPTConfig(vocab_size=97, dim=32, nheads=4, nlayers=2,
                        max_seq=48, moe_experts=moe, moe_top_k=2,
                        moe_every=2, moe_capacity_factor=2.0)
        init = init_gpt_moe_params if moe else init_gpt_params
        params = init(jax.random.PRNGKey(0), cfg)
        prompt = np.arange(1, 12, dtype=np.int32)
        want = np.asarray(generate(params, jnp.asarray(prompt[None]), cfg,
                                   max_new_tokens=6))[0]
        eng = ServingEngine(params, cfg, num_slots=2, block_size=8, chunk=8,
                            max_ctx=32, attn_impl="gather")
        rid = eng.submit(Request(tokens=prompt.tolist(), max_new_tokens=6))
        eng.run_until_idle()
        np.testing.assert_array_equal(eng.finished[rid]["tokens"], want)


def _force_grouped(monkeypatch):
    """Every unbiased expert layer from here on runs its ``ragged_dot``
    pair, whatever its rows (the counter still says what it WOULD take)."""
    from torchdistpackage_tpu.parallel import moe as M

    monkeypatch.setattr(
        M, "_batched_experts", lambda ex, rows, se, gs, C, act, fetch=False:
        jnp.where((se < ex["w1"].shape[0])[:, None],
                  M._grouped_experts(ex, rows, gs, act), 0))


def test_a_small_call_batches_the_experts_and_equals_the_grouped_gemm(
        toy, monkeypatch):
    """A decode-sized call runs the held experts as one batched matmul at
    capacity C = T; the ``ragged_dot`` groups give the same layer."""
    _, cfg, params = toy
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (5, 1, cfg.dim), F32)
    with jax.default_matmul_precision("highest"):
        batched, mb = moe_serve_forward(p, x, cfg.moe, return_metrics=True)
        _force_grouped(monkeypatch)
        grouped, mg = moe_serve_forward(p, x, cfg.moe, return_metrics=True)
    np.testing.assert_allclose(batched, grouped, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(mb["gate_idx"], mg["gate_idx"])
    assert float(jnp.abs(batched).max()) > 0


#: an unbiased held-expert layer by its family: the config, and what the
#: router and the layer carry beside the stacked experts
_FORM_LAYERS = {
    "relu2_latent_shared": MoEConfig(
        dim=32, ffn_dim=24, num_experts=16, top_k=6, act="relu2",
        score="sigmoid", routed_scale=2.5, latent_dim=16, shared_ffn=40,
        held=(4, 4)),
    "swiglu": MoEConfig(dim=32, ffn_dim=24, num_experts=8, top_k=2,
                        act="swiglu", score="sigmoid", held=(2, 4)),
    "mlp_top1": MoEConfig(dim=32, ffn_dim=24, num_experts=4, top_k=1,
                          act="swiglu", score="mlp", held=(0, 4)),
}


def _form_layer(family):
    """(config, float32 weights) with a bias that sends EVERY row to the
    first held expert too: that expert's group is the call's real rows."""
    cfg = _FORM_LAYERS[family]
    keys = iter(jax.random.split(jax.random.PRNGKey(40), 16))
    rnd = lambda *shape: jax.random.normal(next(keys), shape, F32) / np.sqrt(
        shape[-2] if len(shape) > 1 else 1.0)
    D, E, n = cfg.dim, cfg.num_experts, cfg.held[1]
    d = cfg.latent_dim or D
    gate = 2 if cfg.act == "swiglu" else 1
    bias = jnp.zeros((E,), F32).at[cfg.held[0]].set(10.0)
    if cfg.score == "mlp":
        R = 8
        router = {"down": {"w": rnd(D, R), "b": rnd(R)}, "gamma": rnd(R),
                  "norm": {"scale": jnp.ones((R,), F32)}, "w1": rnd(R, R),
                  "b1": rnd(R),
                  "w2": rnd(R, R), "b2": rnd(R), "w3": rnd(R, E),
                  "bias": bias}
    else:
        router = {"w": rnd(D, E), "bias": bias}
    p = {"router": router,
         "experts": {"w1": rnd(n, d, gate * cfg.ffn_dim),
                     "w2": rnd(n, cfg.ffn_dim, d)}}
    if cfg.latent_dim:
        p["latent"] = {"down": rnd(D, d), "up": rnd(d, D)}
    if cfg.shared_ffn:
        p["shared"] = {"w1": rnd(D, gate * cfg.shared_ffn),
                       "w2": rnd(cfg.shared_ffn, D)}
    return cfg, p


@pytest.mark.parametrize("call,batched", [
    ("wide_call_every_group_fits", 1.0), ("wide_call_one_group_crowded", 0.0),
    ("small_call", 1.0)])
@pytest.mark.parametrize("family", list(_FORM_LAYERS))
def test_the_experts_form_follows_the_largest_group_and_changes_nothing(
        family, call, batched, monkeypatch):
    """Past ``C`` rows a call asks its largest group: the batched form where
    every held expert got at most ``C`` REAL rows, the ``ragged_dot`` pair
    otherwise; a call of at most ``C`` rows never asks.  The output, the
    choices and every counter are the forced-``ragged_dot`` layer's, and
    ``layers_batched`` says which form ran."""
    from torchdistpackage_tpu.parallel import moe as M

    C = 8
    monkeypatch.setattr(M, "_BATCHED_EXPERTS_MAX_ROWS", C)
    cfg, p = _form_layer(family)
    first = cfg.held[0]
    # 3 rows of 8 positions; the padding is one token over and over, as a
    # compact prefill call's is
    B, S, real = {"wide_call_every_group_fits": (3, 8, 6),
                  "wide_call_one_group_crowded": (3, 8, 12),
                  "small_call": (1, 6, 6)}[call]
    x = jax.random.normal(jax.random.PRNGKey(41), (B, S, cfg.dim), F32)
    valid = (jnp.arange(B * S) < real).reshape(B, S)
    x = jnp.where(valid[..., None], x, x[0, 0])
    depth = (jax.random.normal(jax.random.PRNGKey(42), (B, S, 8), F32)
             if cfg.score == "mlp" else None)

    def layer():
        with jax.default_matmul_precision("highest"):
            return moe_serve_forward(p, x, cfg, return_metrics=True,
                                     valid=valid, depth=depth)

    y, m, *stream = layer()
    _force_grouped(monkeypatch)
    y_g, m_g, *stream_g = layer()
    np.testing.assert_allclose(y, y_g, rtol=1e-5, atol=1e-6)
    assert m.keys() == m_g.keys() >= {
        "expert_tokens", "rows_routed", "rows_held", "experts_touched",
        "gate_idx", "layers_batched"}
    for k in m:
        np.testing.assert_array_equal(m[k], m_g[k], err_msg=k)
    for a, b in zip(stream, stream_g):
        np.testing.assert_array_equal(a, b)
    assert float(m["layers_batched"]) == batched
    # the first held expert's group is the REAL rows: the padding rows
    # chose it as well, more often than it has slots, and fill none
    assert float(m["expert_tokens"][0]) == real
    assert np.asarray(m["gate_idx"] == first).any(-1).all()
    if call == "wide_call_every_group_fits":
        assert B * S - real > C
    assert float(m["rows_routed"]) == real * cfg.top_k
    assert float(jnp.abs(y).max()) > 0


@pytest.mark.parametrize("prompts,batched_share", [
    ((13,), "all"), ((8, 8, 8), "some")], ids=["one_live_row", "a_wave"])
def test_a_prefill_call_with_few_real_rows_batches_its_experts(
        toy, prompts, batched_share, monkeypatch):
    """The toy's compact prefill call is 3 x 8 rows, past ``C`` = 8.  With
    one live row no held expert gets more than 8 rows: every expert layer
    of every prefill call runs batched, and says so.  A wave's call crowds
    some expert of some layer and that layer falls back.  Either way the
    tokens are the forced-``ragged_dot`` engine's, from the same two
    programs."""
    from torchdistpackage_tpu.parallel import moe as M

    _, cfg, params = toy
    monkeypatch.setattr(M, "_BATCHED_EXPERTS_MAX_ROWS", 8)

    def serve():
        rng = np.random.RandomState(3)
        with jax.default_matmul_precision("highest"):
            eng = ServingEngine(params, cfg, num_slots=3, block_size=8,
                                chunk=8, max_ctx=32, attn_impl="gather")
            eng.prefill_width = 3   # the call this test is about
            for n in prompts:
                eng.submit(Request(tokens=rng.randint(0, 211, n).tolist(),
                                   max_new_tokens=5))
            eng.run_until_idle()
        return eng

    got = serve()
    st, layers = got.stats, cfg.pattern.count("E")
    calls = st["prefill_calls"]
    assert calls == (2 if batched_share == "all" else 1)
    assert st["prefill_moe_layers_run"] == calls * layers
    if batched_share == "all":
        assert st["prefill_moe_layers_batched"] == calls * layers
    else:
        assert 0 <= st["prefill_moe_layers_batched"] < calls * layers
    # a decode call (3 rows) never asks: all of its layers, every call
    decode = {k: st[f"moe_{k}"] - st[f"prefill_moe_{k}"]
              for k in ("layers_run", "layers_batched")}
    assert decode["layers_run"] == decode["layers_batched"] \
        == st["decode_steps"] * layers > 0
    for k in ("moe_layers_run", "prefill_moe_layers_batched"):
        assert sum(t[k] for t in got.tick_records) == st[k]
    assert got._step_fn._cache_size() == 2      # one prefill, one decode
    summ = got.serving_summary()
    assert summ["prefill_signatures"] == summ["decode_signatures"] == 1
    _force_grouped(monkeypatch)
    want = serve()
    assert want.finished.keys() == got.finished.keys()
    for rid, w in want.finished.items():
        np.testing.assert_array_equal(got.finished[rid]["tokens"],
                                      w["tokens"])
    assert want.stats["prefill_moe_layers_batched"] \
        == st["prefill_moe_layers_batched"]     # the counter is the rule's


def _conditionals(text):
    """The ``stablehlo.case`` operations of a lowered program, each as the
    list of its branches' lines (by the printer's indentation)."""
    lines, out = text.splitlines(), []
    for i, line in enumerate(lines):
        if '"stablehlo.case"' not in line:
            continue
        pad = line[:len(line) - len(line.lstrip())]
        branches = [[]]
        for inner in lines[i + 1:]:
            if inner.startswith(pad + "}) :"):
                break
            if inner == pad + "}, {":
                branches.append([])
            else:
                branches[-1].append(inner)
        out.append(["\n".join(b) for b in branches])
    return out


def test_the_prefill_program_holds_one_conditional_an_expert_layer_and_the_decode_program_none(
        toy, monkeypatch):
    """What "the decode call is untouched" means, without a chip: lowered
    for the TPU, the prefill program has ONE conditional an expert layer,
    the ``ragged_dot`` pair in one branch and the batched ``dot_general``
    pair in the other, and no ``ragged_dot`` outside them; the decode
    program has no ``ragged_dot`` and no conditional that holds a matmul
    (the sampler's, which both programs have, holds none)."""
    from torchdistpackage_tpu.parallel import moe as M

    _, cfg, params = toy
    monkeypatch.setattr(M, "_BATCHED_EXPERTS_MAX_ROWS", 8)
    eng = ServingEngine(params, cfg, num_slots=3, block_size=8, chunk=8,
                        max_ctx=32, attn_impl="gather")
    eng.prefill_width = 3   # a call of 3 x 8 rows, past the capacity of 8
    called = {}
    dispatch = eng._dispatch

    def keep(fn, args):
        called.setdefault(args[0].shape, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (eng.params, eng.cache, eng.state, *args)))
        return dispatch(fn, args)

    monkeypatch.setattr(eng, "_dispatch", keep)
    eng.submit(Request(tokens=list(range(1, 10)), max_new_tokens=3))
    eng.run_until_idle()
    assert set(called) == {(3, 8), (3, 1)}
    text = {shape: eng._step_fn.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
        for shape, args in called.items()}
    ragged = '"chlo.ragged_dot"'
    # the batched form's matmuls carry the expert as a batching dimension
    batched = re.compile(r"stablehlo\.dot_general .*batching_dims")

    prefill = [b for b in _conditionals(text[3, 8])
               if any("dot_general" in part or ragged in part for part in b)]
    assert len(prefill) == cfg.pattern.count("E") == 3
    for branches in prefill:
        assert len(branches) == 2
        assert sorted(part.count(ragged) for part in branches) == [0, 2]
        for part in branches:
            assert len(batched.findall(part)) == (0 if ragged in part else 2)
    assert text[3, 8].count(ragged) == 2 * 3
    assert ragged not in text[3, 1]
    assert not [b for b in _conditionals(text[3, 1])
                if any("dot_general" in part for part in b)]
    assert len(batched.findall(text[3, 1])) >= 2 * 3
