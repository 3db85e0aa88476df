"""The ``zaya`` family through the UNEDITED harness at toy width on the CPU:
a directory of new files (one configuration, one cell) plus new manifest
entries, as ``bm_toy.py`` adds its own.  The last line's keys, the new
per-layer metrics beside the accepted ones, the fp8 control failing, four
broken timed paths reading ``correct`` false, and the configuration file
against the catalog's row."""

import copy
import json

import jax.numpy as jnp
import pytest

from benchmarks import arch as A
from benchmarks import harness
from benchmarks.families import zaya as family

from test_bm_runner_serve import FAKE_TRACE, check_line

TOY_CONFIG = {
    "name": "toy-zaya", "family": "zaya", "source": "test",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "cca_time0": 2, "cca_time1": 2, "attention_bias": False,
    "lm_head_bias": False, "hidden_act": "silu", "tie_word_embeddings": True,
    "layer_types": ["hybrid"] * 3, "num_hidden_layers": 3, "num_experts": 8,
    "num_experts_per_tok": 1, "moe_intermediate_size": 32,
    "router_hidden_size": 24, "rms_norm_eps": 1e-5,
    "partial_rotary_factor": 0.5, "sliding_window": None,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000,
                                   "rope_type": "default"}},
    "vocab_size": 211, "max_position_embeddings": 512, "reduced": [],
}
TOY_CELL = {
    "name": "toy.zaya", "config": "toy-zaya",
    "traffic_name": "toyreason", "chips": 1, "runner": "serve_family",
    "engine": {"num_slots": 4, "block_size": 16, "chunk": 16, "max_ctx": 64,
               "run_ahead": True},
    "traffic": {"kind": "closed_loop", "clients": 8, "first_wave": 4,
                "population": 64, "population_seed": 5,
                "prompt_len": {"dist": "uniform", "lo": 4, "hi": 30},
                "output_len": {"dist": "log_uniform", "lo": 4, "hi": 16}},
    "check": {"sample": 6, "follow_routing": True},
    # bfloat16 against the float32 reference at width 64, the reference
    # following the program's top-1 choice (logits of a tied table drawn at
    # 0.02 are small here: 0.16 wide): five seeds read a gap of 0.000-0.003
    # and a deficit of 0.003-0.015, the fp8 control 0.044-0.13 and 0.20-0.56;
    # of the broken paths below the dropped balance bias reads a deficit of
    # 0.080-0.087 and nothing in the gap, the dropped depth stream, the plain
    # residual and the forgotten tail a gap of 0.15-0.43 (and a deficit of
    # 0.46-1.06: the router reads a wrong stream)
    "limits": {"served_logit_gap": 0.012, "routing_deficit": 0.035},
}
NEW_METRICS = ("cca_cache_gb.batch", "cca_tail_mb.batch",
               "cca_live_tokens.batch")
SHARED_METRICS = ("paged_decode_roofline.batch", "moe_imbalance.batch",
                  "decode_bytes_roofline.batch")


@pytest.fixture
def toy(tmp_path, monkeypatch):
    for sub, spec in (("configs", TOY_CONFIG), ("workloads", TOY_CELL)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{spec['name']}.json").write_text(json.dumps(spec))
    manifest = copy.deepcopy(harness.load_manifest())
    manifest["workloads"].append(
        {"name": "toy.zaya", "config": "toy-zaya",
         "traffic": "toyreason", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tok_s" or m["name"] in (
                NEW_METRICS + SHARED_METRICS):
            m["workloads"].append("toy.zaya")
    monkeypatch.setattr(A, "ROOTS", A.ROOTS + [str(tmp_path)])
    return manifest


def phases_of(line):
    return {r["phase"]: r for r in line["log"] if "phase" in r}


def test_run_last_line_sample_and_fp8_control(toy):
    line = harness.run_cell("toy.zaya", 2**31 + 41, 2.0, False, toy,
                            look_for_chip=False, control="fp8")
    check_line(line, toy, "toy.zaya", traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 6
    phases = phases_of(line)
    assert phases["window"]["prefill_signatures"] == 1
    assert phases["window"]["decode_signatures"] == 1
    # 3 layers x 4 slots x (2 rows of 96 + 16) bfloat16
    assert phases["window"]["state_bytes"] == 3 * 4 * 208 * 2
    # every expert is held: no routed row falls elsewhere
    assert phases["window"]["moe_rows_held"] \
        == phases["window"]["moe_rows_routed"] > 0
    assert phases["check"]["checked_requests"] == 6
    assert [c["number"] for c in phases["check"]["compared"]] == [
        "served_logit_gap", "routing_deficit"]
    # the reference in fp8, in the program's place, is not correct
    assert phases["control"]["correct"] is False


def test_traced_run_reports_the_new_metrics_beside_the_accepted(
        toy, monkeypatch):
    kernel = FAKE_TRACE["events"]["/device:TPU:0"][0][0].replace(
        "%closed_call.2", "%paged_decode.3")
    trace = {**FAKE_TRACE, "events": {"/device:TPU:0": [
        (kernel, 0.1 * i, 0.05) for i in range(6)]}}
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: trace)
    monkeypatch.setattr(harness.Tracer, "start", lambda self: None)
    line = harness.run_cell("toy.zaya", 2**31 + 42, 1.0, True, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.zaya", traced=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW_METRICS + SHARED_METRICS) <= set(got)
    # k and v: 3 layers x (4 slots x 4 blocks + the NULL block) x 2 heads
    # x 16 positions x 16 wide, bfloat16
    assert got["cca_cache_gb.batch"] == pytest.approx(
        2 * 3 * 17 * 2 * 16 * 16 * 2 * 1e-9)
    assert got["cca_tail_mb.batch"] == pytest.approx(3 * 4 * 208 * 2 * 1e-6)
    assert 4 <= got["cca_live_tokens.batch"] <= 4 * 64
    assert 0.0 < got["paged_decode_roofline.batch"] < 100.0
    assert 0.0 < got["decode_bytes_roofline.batch"]
    assert got["moe_imbalance.batch"] >= 1.0
    assert "ssm_state_gb.batch" not in got and "tick_gap_ms.batch" in got
    assert "moe_held_rows_share.batch" not in got


def test_a_reader_with_nothing_to_read_leaves_the_new_metrics_out():
    """What a program without the attrs (a parent commit) gives: nothing,
    and no error."""
    obs = {"spans": {}, "values": {}, "costs": {}, "peaks": {}, "trace": None}
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None
    # ticks in the ring, but spans without the attrs
    from torchdistpackage_tpu.utils.profiling import span, spans
    spans.clear()
    with span("tdp:engine.init.pool"):
        pass
    with span("tdp:engine.tick"):
        with span("tdp:engine.decode", slots=2):
            pass
    obs["spans"] = {"engine_step": [0.1]}
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None
    spans.clear()


# ------------------------------------------------------- broken timed paths


def _no_balance_bias(monkeypatch):
    from torchdistpackage_tpu.parallel import moe

    route = moe._mlp_route
    monkeypatch.setattr(moe, "_mlp_route", lambda router, *a: route(
        {**router, "bias": jnp.zeros_like(router["bias"])}, *a))


def _no_depth_stream(monkeypatch):
    from torchdistpackage_tpu.parallel import moe

    route = moe._mlp_route
    monkeypatch.setattr(
        moe, "_mlp_route", lambda router, tokens, cfg, depth: route(
            router, tokens, cfg, None))


def _plain_residual(monkeypatch):
    from torchdistpackage_tpu.models import hybrid

    forward = hybrid.hybrid_paged_forward

    def plain(params, *a, **kw):
        layers = [{k: v for k, v in lp.items() if k != "res"}
                  for lp in params["layers"]]
        return forward({**params, "layers": layers}, *a, **kw)

    monkeypatch.setattr(hybrid, "hybrid_paged_forward", plain)


def _tail_left_at_zero(monkeypatch):
    """The convolutions and the shifted value start every call from an
    empty tail: right for a sequence's first call, wrong after it."""
    from torchdistpackage_tpu.models import hybrid

    mixer = hybrid.cca_mixer

    def forgetful(p, x, cfg, ck, cv, tail, *a):
        y, ck, cv, _ = mixer(p, x, cfg, ck, cv, jnp.zeros_like(tail), *a)
        return y, ck, cv, tail

    monkeypatch.setattr(hybrid, "cca_mixer", forgetful)


@pytest.mark.parametrize("fault,number", [
    (_no_balance_bias, "routing_deficit"),
    (_no_depth_stream, "routing_deficit"),
    (_plain_residual, "served_logit_gap"),
    (_tail_left_at_zero, "served_logit_gap"),
], ids=["balance_bias_dropped", "depth_stream_dropped", "residual_unscaled",
        "tail_forgotten"])
def test_a_broken_timed_path_is_not_correct(toy, monkeypatch, fault, number):
    fault(monkeypatch)
    line = harness.run_cell("toy.zaya", 2**31 + 43, 1.0, False, toy,
                            look_for_chip=False)
    assert line["correct"] is False and line["failed"] == 0
    compared = {c["number"]: c for c in phases_of(line)["check"]["compared"]}
    assert not compared[number]["within"]


# ---------------------------------------------------------- the configuration


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file with the
    row's value; the one that differs is the ``reduced`` one, with its
    published value beside it."""
    cfg = A.load_config("zaya1-8b")
    catalog = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
        "max_position_embeddings": 131072, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"},
        "router_hidden_size": 256, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 262272}
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 40}
    for key, want in catalog.items():
        if key in cfg["reduced"]:
            assert cfg[key] < want, key
        else:
            assert cfg[key] == want, key
    assert cfg["num_hidden_layers"] == 20
    assert cfg["deployment_share"]["pipeline_stages"] == 2
    assert {"latent_width", "convolutions", "qk_mean", "value_shift",
            "qk_norm", "rope", "router", "top1_weight", "no_skip_output",
            "residual", "windows"} <= set(cfg["assumed"])
    for text in ("departures", "deployment"):
        assert cfg[text]
    s = family.shape(cfg, 2560)
    assert (s.experts, s.top_k, s.vocab, s.rope_dims) == (16, 1, 262272, 64)
    assert s.pattern == "*E" * 20 and s.rope_theta == 5e6
    pc = family.program_config(cfg, 2560)
    assert pc.pattern == "CE" * 20 and pc.head_dim == 128
    assert pc.moe.held == (0, 16) and pc.moe.num_experts == 16
    assert (pc.moe.act, pc.moe.score, pc.moe.top_k) == ("swiglu", "mlp", 1)


def test_the_cell_fills_the_pool_it_names():
    """The cell's file against the configuration: every slot full at once
    is what its 1281 blocks hold, and that is 3.36 GB."""
    from torchdistpackage_tpu.serving import expected_pool_bytes

    cell = A.load_json("workloads", "zaya1.reason.json")
    geo, mix = cell["engine"], cell["traffic"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] == geo["max_ctx"]
    assert geo["num_blocks"] == 1 + geo["num_slots"] * (
        geo["max_ctx"] // geo["block_size"]) == 1281
    pc = family.program_config(A.load_config(cell["config"]), geo["max_ctx"])
    assert round(expected_pool_bytes(
        pc, geo["num_blocks"], geo["block_size"]) / 1e9, 2) == 3.36
    assert round(pc.state_bytes(geo["num_slots"]) / 1e6, 2) == 6.88
    assert mix["clients"] == 2 * geo["num_slots"] == 128
    assert (mix["population"], mix["population_seed"]) == (2048, 33)
    assert set(cell["limits"]) == {"served_logit_gap", "routing_deficit"}
