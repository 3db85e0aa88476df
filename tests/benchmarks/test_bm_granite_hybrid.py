"""The ``granite_hybrid`` family through the UNEDITED harness at toy width on
the CPU: a directory of new files (one configuration, one cell) plus new
manifest entries, as ``bm_toy.py`` adds its own.  The last line's keys, the
three new per-layer metrics beside the accepted ones, the fp8 control
failing, five broken timed paths reading ``correct`` false, the published
sizes, the configuration file against the catalog's row and the step's cost
by hand."""

import copy
import dataclasses
import itertools
import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks import arch as A
from benchmarks import harness
from benchmarks.families import granite_hybrid as family
from benchmarks.layer_metrics import ssm_step

from test_bm_afmoe import _trace_of_the_ring
from test_bm_runner_serve import check_line

TOY_CONFIG = {
    "name": "toy-granite", "family": "granite_hybrid", "source": "test",
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "num_hidden_layers": 4, "shared_intermediate_size": 96,
    "intermediate_size": 96, "num_local_experts": 0, "num_experts_per_tok": 0,
    "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5, "vocab_size": 1021,
    "max_position_embeddings": 512, "reduced": [],
}
TOY_CELL = {
    "name": "toy.granite", "config": "toy-granite",
    "traffic_name": "toyreason", "chips": 1, "runner": "serve_family",
    "engine": {"num_slots": 4, "block_size": 16, "chunk": 16, "max_ctx": 64,
               "run_ahead": True},
    "traffic": {"kind": "closed_loop", "clients": 8, "first_wave": 4,
                "population": 64, "population_seed": 5,
                "prompt_len": {"dist": "uniform", "lo": 4, "hi": 30},
                "output_len": {"dist": "log_uniform", "lo": 8, "hi": 28}},
    "check": {"sample": 32, "follow_routing": False},
    # bfloat16 against the float32 reference at width 256, a vocabulary of
    # 1021 (at 211 the best token stands too clear of the second for fp8 to
    # move it within 50 served tokens) and 460-550 served tokens a run of
    # 32 requests; the tied table at 2 / (12 x 16), logits ~0.016 wide
    # under logits_scaling 8.  My CPU runs, no device number: five sound
    # seeds read a gap of 0.0000-0.0006, the fp8 control 0.0015-0.0130
    # (0.0031 on the seed the test takes; over 12 requests it read
    # 0.0007-0.0082 and was not told apart on every seed); the broken paths
    # below, two seeds each, 12 requests: the convolution's bias dropped
    # 0.005-0.011, the gate behind the
    # norm 0.021-0.028, the state forgotten 0.028-0.093, the residual or
    # the embedding unscaled 0.10-0.12.  A softmax at 1 / sqrt(hd) reads
    # 0.0 here (one attention layer over <= 58 keys; tests/
    # test_granite_hybrid.py holds it, on the logits themselves)
    "limits": {"served_logit_gap": 0.0012, "routing_deficit": 1e-9},
}
NEW_METRICS = ("ssm_step_roofline.batch", "ssm_state_share.batch",
               "cache_padding_ratio.batch")
SHARED_METRICS = ("ssm_state_gb.batch", "paged_decode_roofline.batch")


@pytest.fixture
def toy(tmp_path, monkeypatch):
    for sub, spec in (("configs", TOY_CONFIG), ("workloads", TOY_CELL)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{spec['name']}.json").write_text(json.dumps(spec))
    manifest = copy.deepcopy(harness.load_manifest())
    manifest["workloads"].append(
        {"name": "toy.granite", "config": "toy-granite",
         "traffic": "toyreason", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tok_s" or m["name"] in (
                NEW_METRICS + SHARED_METRICS):
            m["workloads"].append("toy.granite")
    monkeypatch.setattr(A, "ROOTS", A.ROOTS + [str(tmp_path)])
    return manifest


def phases_of(line):
    return {r["phase"]: r for r in line["log"] if "phase" in r}


def test_run_last_line_sample_and_fp8_control(toy):
    line = harness.run_cell("toy.granite", 2**31 + 41, 2.0, False, toy,
                            look_for_chip=False, control="fp8")
    check_line(line, toy, "toy.granite", traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 32
    phases = phases_of(line)
    assert phases["window"]["prefill_signatures"] == 1
    assert phases["window"]["decode_signatures"] == 1
    # 3 Mamba layers x 4 slots x (8 x 64 x 16 float32 + 3 x 544 bfloat16)
    assert phases["window"]["state_bytes"] == 3 * 4 * (
        8 * 64 * 16 * 4 + 3 * 544 * 2)
    assert phases["window"]["moe_rows_routed"] == 0.0   # nothing is chosen
    assert phases["check"]["checked_requests"] == 32
    assert [(c["number"], c["value"]) for c in phases["check"]["compared"]
            ][1] == ("routing_deficit", 0.0)
    # the reference in fp8, in the program's place, is not correct, by the
    # gap: there is no choice to get wrong
    control = {c["number"]: c for c in phases["control"]["compared"]}
    assert phases["control"]["correct"] is False
    assert not control["served_logit_gap"]["within"]
    assert control["routing_deficit"]["within"]


def test_traced_run_reports_the_new_metrics_beside_the_accepted(
        toy, monkeypatch):
    """A toy trace laid under the run's own spans (``test_bm_afmoe``'s):
    every decode execution is held to its own call's slots and live
    positions."""
    import time

    def start(self):
        self.t_start = time.perf_counter()

    monkeypatch.setattr(harness.Tracer, "start", start)
    monkeypatch.setattr(harness.Tracer, "reduce",
                        lambda self: _trace_of_the_ring(self.t_start))
    # the CPU counts no device memory: a counter stands in for it, by which
    # each of the engine's two allocations "took" 3 MB
    from torchdistpackage_tpu.serving import engine as E
    reads = itertools.count()
    monkeypatch.setattr(E, "_device_bytes_in_use",
                        lambda: 3_000_000 * next(reads))
    line = harness.run_cell("toy.granite", 2**31 + 42, 1.0, True, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.granite", traced=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW_METRICS + SHARED_METRICS) <= set(got)
    assert got["ssm_state_gb.batch"] == pytest.approx(
        3 * 4 * (8 * 64 * 16 * 4 + 3 * 544 * 2) * 1e-9)
    assert 0.0 < got["ssm_step_roofline.batch"] < 100.0
    # 4 slots x 2 x 36 KB of state beside 1 MB of weights and a few KB of
    # K and V: the state is a fifth to a quarter of a call's bytes
    assert 5.0 < got["ssm_state_share.batch"] < 35.0
    assert 0.0 < got["paged_decode_roofline.batch"]
    # K and V: (4 slots x 4 blocks + NULL) x 16 x 128 bfloat16; the state
    assert got["cache_padding_ratio.batch"] == pytest.approx(
        6_000_000 / (2 * 17 * 16 * 128 * 2 + 432384))
    assert "moe_imbalance.batch" not in got and "tick_gap_ms.batch" in got


def test_the_padding_ratio_reads_the_two_init_spans(monkeypatch):
    """Where the backend counts its memory the two init spans carry what
    each allocation took of it: ``ssm_bytes`` and ``conv_bytes`` beside the
    state's ``bytes``, ``device_bytes`` on both, and the ratio is their
    sum over the logical sum."""
    from torchdistpackage_tpu.serving import ServingEngine
    from torchdistpackage_tpu.serving import engine as E
    from torchdistpackage_tpu.utils.profiling import span, spans

    pcfg = dataclasses.replace(family.program_config(TOY_CONFIG, 64),
                               dtype=jnp.float32)
    s = family.shape(TOY_CONFIG, 64)
    params = family.make_weights(s, 3)
    held = iter((1000, 1000 + 3 * 9 * 16 * 128 * 4,            # the pool
                 50000, 50000 + 400000))                       # the state
    monkeypatch.setattr(E, "_device_bytes_in_use", lambda: next(held))
    spans.clear()
    eng = ServingEngine(params, pcfg, num_slots=2, block_size=16, chunk=16,
                        max_ctx=64, attn_impl="gather")
    by_name = {r[2]: r[5] for r in spans.snapshot()}
    pool, state = by_name["tdp:engine.init.pool"], by_name[
        "tdp:engine.init.state"]
    # K and V: one layer x 9 blocks x ONE row of two heads x 16 x 128 f32
    assert pool["bytes"] == 2 * 9 * 16 * 128 * 4
    assert pool["device_bytes"] == 3 * pool["bytes"] // 2
    assert state["bytes"] == eng.state_bytes == (
        state["ssm_bytes"] + state["conv_bytes"])
    assert state["ssm_bytes"] == 3 * 2 * 8 * 64 * 16 * 4
    assert state["conv_bytes"] == 3 * 2 * 3 * 544 * 4
    assert state["device_bytes"] == 400000
    with span("tdp:engine.tick"):
        pass
    obs = {"spans": {"engine_step": [0.1]}, "values": {}, "costs": {},
           "peaks": {}, "trace": None}
    want = (pool["device_bytes"] + 400000) / (pool["bytes"] + state["bytes"])
    assert harness.read_layer_metric("cache_padding_ratio.batch", obs) \
        == pytest.approx(want)
    spans.clear()


def test_a_reader_with_nothing_to_read_leaves_the_new_metrics_out():
    """What a program without the attrs (a parent commit) gives: nothing,
    and no error."""
    obs = {"spans": {}, "values": {}, "costs": {}, "peaks": {}, "trace": None}
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None
    # ticks in the ring and the family's costs, but init spans without
    # ``device_bytes`` and a decode span without ``live_tokens``
    from torchdistpackage_tpu.utils.profiling import span, spans
    spans.clear()
    with span("tdp:engine.init.pool", bytes=10):
        pass
    with span("tdp:engine.init.state", bytes=10):
        pass
    with span("tdp:engine.tick"):
        with span("tdp:engine.decode", slots=2):
            pass
    s = family.shape(TOY_CONFIG, 64)
    obs.update(spans={"engine_step": [0.1]},
               costs={"paged_decode": family.paged_decode(s, 10.0, 2.0)})
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None
    spans.clear()


# ------------------------------------------------------- broken timed paths


def _with_config(monkeypatch, **changes):
    from torchdistpackage_tpu.models import hybrid

    forward = hybrid.hybrid_paged_forward
    monkeypatch.setattr(
        hybrid, "hybrid_paged_forward", lambda params, tokens, cfg, *a, **kw:
        forward(params, tokens, dataclasses.replace(cfg, **changes), *a, **kw))


def _residual_unscaled(monkeypatch):
    _with_config(monkeypatch, residual_scale=1.0)


def _embedding_unscaled(monkeypatch):
    _with_config(monkeypatch, embed_scale=1.0)


def _conv_bias_dropped(monkeypatch):
    from torchdistpackage_tpu.models import hybrid

    mixer = hybrid.mamba2_mixer
    monkeypatch.setattr(hybrid, "mamba2_mixer", lambda p, *a: mixer(
        {**p, "conv_b": jnp.zeros_like(p["conv_b"])}, *a))


def _gate_behind_the_norm(monkeypatch):
    """``RMSNorm(y) * silu(z)`` where the model gates first: the program's
    mixer with a CONSTANT gate (a bias column makes every z 30, and silu(30)
    = 30 is a factor the norm divides out) and the identity for its output
    projection; what comes back is gated and projected here."""
    from torchdistpackage_tpu.models import hybrid

    mixer = hybrid.mamba2_mixer

    def late_gate(p, x, c, ssm, conv, n_valid):
        di = c.d_inner
        z = hybrid.dense(x, p["in_proj"])[..., :di]
        w = jnp.concatenate([
            p["in_proj"].at[:, :di].set(0.0),
            jnp.zeros((1, p["in_proj"].shape[1]), x.dtype).at[0, :di].set(30.0)])
        y, ssm, conv = mixer(
            {**p, "in_proj": w, "out_proj": jnp.eye(di, dtype=x.dtype)},
            jnp.concatenate([x, jnp.ones_like(x[..., :1])], -1), c, ssm, conv,
            n_valid)
        return (hybrid.dense((y * jax.nn.silu(z)).astype(x.dtype),
                             p["out_proj"]), ssm, conv)

    monkeypatch.setattr(hybrid, "mamba2_mixer", late_gate)


def _state_forgotten(monkeypatch):
    """Every call starts its recurrence and its convolution from nothing:
    right for a sequence's first call, wrong after it."""
    from torchdistpackage_tpu.models import hybrid

    mixer = hybrid.mamba2_mixer

    def forgetful(p, x, c, ssm, conv, n_valid):
        y, _, _ = mixer(p, x, c, jnp.zeros_like(ssm), jnp.zeros_like(conv),
                        n_valid)
        return y, ssm, conv

    monkeypatch.setattr(hybrid, "mamba2_mixer", forgetful)


FAULTS = {"residual_unscaled": _residual_unscaled,
          "embedding_unscaled": _embedding_unscaled,
          "conv_bias_dropped": _conv_bias_dropped,
          "gate_behind_the_norm": _gate_behind_the_norm,
          "state_forgotten": _state_forgotten}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(toy, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    line = harness.run_cell("toy.granite", 2**31 + 43, 1.0, False, toy,
                            look_for_chip=False)
    assert line["correct"] is False and line["failed"] == 0
    compared = {c["number"]: c for c in phases_of(line)["check"]["compared"]}
    assert not compared["served_logit_gap"]["within"]


# --------------------------------------------- the configuration and the cell


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file with the
    row's value: nothing is cut."""
    cfg = A.load_config("granite-4.0-h-micro")
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    catalog = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 8192, "layer_types": period * 4,
        "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
        "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    assert cfg["reduced"] == [] and "published" not in cfg
    for key, want in catalog.items():
        assert cfg[key] == want, key
    assert {"head_dim", "state_dtype", "dt_limit", "dt_and_A_init",
            "gate_and_norm", "intermediate_size", "rope_theta"} <= set(
                cfg["assumed"])
    for text in ("departures", "deployment"):
        assert cfg[text]
    s = family.shape(cfg, 2560)
    assert s.pattern == ("MD" * 5 + "*D" + "MD" * 4) * 4
    assert (s.head_dim, s.attn_scale, s.logits_scale) == (64, 1 / 64, 1 / 8)
    # the published sizes, as the deployment text counts them
    assert family.num_params(s) == 3_191_396_096
    assert family.state_bytes_per_slot(s) == 76_437_504
    pc = family.program_config(cfg, 2560)
    assert (pc.pattern, pc.head_dim, pc.kv_pack) == (s.pattern, 64, 2)
    assert (pc.ssm_chunk, pc.ssm_groups, pc.d_inner, pc.conv_channels) == (
        256, 1, 4096, 4352)
    assert pc.state_bytes(1) == 76_437_504
    assert (pc.embed_scale, pc.residual_scale, pc.attn_scale,
            pc.logits_scale) == (12.0, 0.22, 1 / 64, 1 / 8)


def test_the_cell_fills_the_pool_it_names():
    """The cell's file against the configuration and against the cell whose
    traffic it shares to the letter."""
    from torchdistpackage_tpu.serving import expected_pool_bytes

    cell = A.load_json("workloads", "granite4hm.reason.json")
    twin = A.load_json("workloads", "zaya1.reason.json")
    geo, mix = cell["engine"], cell["traffic"]
    for key in ("kind", "clients", "first_wave", "population",
                "population_seed", "prompt_len", "output_len"):
        assert mix[key] == twin["traffic"][key], key
    for key in ("num_slots", "block_size", "chunk", "max_ctx", "num_blocks",
                "run_ahead"):
        assert geo[key] == twin["engine"][key], key
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] == geo["max_ctx"]
    assert geo["num_blocks"] == 1 + geo["num_slots"] * (
        geo["max_ctx"] // geo["block_size"]) == 1281
    config = A.load_config(cell["config"])
    assert geo["chunk"] == config["mamba_chunk_size"]
    pc = family.program_config(config, geo["max_ctx"])
    assert round(expected_pool_bytes(
        pc, geo["num_blocks"], geo["block_size"]) / 1e9, 3) == 1.343
    assert round(pc.state_bytes(geo["num_slots"]) / 1e9, 3) == 4.892
    assert cell["check"]["follow_routing"] is False
    assert set(cell["limits"]) == {"served_logit_gap", "routing_deficit"}


def test_the_steps_cost_adds_up_from_its_parts():
    """At the published sizes, 64 slots over 100k live positions: every
    weight once, twice the state, K and V of 2 KB a position and layer; the
    reader's per-call cost is the family's."""
    s = family.shape(A.load_config("granite-4.0-h-micro"), 2560)
    live, slots = 100_000.0, 64.0
    step = family.decode_step(s, live, slots)
    weights = 2 * 3_191_396_096
    state = 2 * 64 * 76_437_504
    attn = 4 * (live * 2 * 8 * 64 * 2 + slots * 2 * 32 * 64 * 2)
    assert step["weight_bytes"] == weights and step["state_bytes"] == state
    assert step["attention_bytes"] == attn
    assert step["bytes"] == weights + state + attn
    assert round(100 * state / step["bytes"]) == 58
    one = family.paged_decode(s, live, slots)
    assert one["bytes"] == attn / 4 and one["flops"] == 4 * live * 32 * 64
    own = ssm_step.call_cost(one["step_unit"],
                             {"slots": 64, "live_tokens": 100_000})
    assert (own["bytes"], own["flops"], own["state_bytes"]) == (
        step["bytes"], step["flops"], state)
    # a first-wave call of 5 slots moves 5 slots' state, not 64
    few = ssm_step.call_cost(one["step_unit"], {"slots": 5, "live_tokens": 3000})
    assert few["state_bytes"] == 2 * 5 * 76_437_504
    assert few["bytes"] == weights + few["state_bytes"] + 4 * (
        3000 * 2048 + 5 * 8192)
