"""The ``sarvam_mla`` family through the UNEDITED harness at toy width on the
CPU: a directory of new files (one configuration, one cell) plus new
manifest entries, as ``bm_toy.py`` adds its own.  The last line's keys, the
new per-layer metrics beside the accepted ones, the fp8 control failing both
limits, three broken timed paths reading ``correct`` false, and the
configuration file against the catalog's row."""

import copy
import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arch as A
from benchmarks import harness
from benchmarks.families import sarvam_mla as family

from test_bm_runner_serve import FAKE_TRACE, check_line

TOY_CONFIG = {
    "name": "toy-sarvam", "family": "sarvam_mla", "source": "test",
    "hidden_size": 64, "num_attention_heads": 4, "head_dim": 40,
    "kv_lora_rank": 32, "q_head_dim": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "use_qk_norm": True,
    "intermediate_size": 128, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "num_experts": 4, "published": {"num_experts": 16},
    "deployment_share": {"first_expert": 8}, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "moe_intermediate_size": 32,
    "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "deepseek_yarn"},
    "vocab_size": 211, "max_position_embeddings": 512,
    "reduced": ["num_experts"],
}
TOY_CELL = {
    "name": "toy.sarvam", "config": "toy-sarvam",
    "traffic_name": "toyreason", "chips": 1, "runner": "serve_family",
    "engine": {"num_slots": 4, "block_size": 16, "chunk": 16, "max_ctx": 64,
               "run_ahead": True},
    "traffic": {"kind": "closed_loop", "clients": 8, "first_wave": 4,
                "population": 64, "population_seed": 5,
                "prompt_len": {"dist": "uniform", "lo": 4, "hi": 30},
                "output_len": {"dist": "log_uniform", "lo": 4, "hi": 16}},
    "check": {"sample": 6, "follow_routing": True},
    # bfloat16 against the float32 reference at width 64, the reference
    # following the program's choice of experts: five seeds read a gap of
    # 0.0-0.039 and a deficit of 0.004-0.009, the fp8 control 0.45-0.94 and
    # 0.13-0.41; of the broken paths below the dropped bias reads a deficit
    # of 0.13-0.20, the dropped norm a gap of 0.75-1.5, the shifted value 4-6
    "limits": {"served_logit_gap": 0.13, "routing_deficit": 0.035},
}
NEW_METRICS = ("mla_decode_roofline.batch", "mla_cache_gb.batch",
               "mla_live_tokens.batch")
SHARED_METRICS = ("moe_held_rows_share.batch", "moe_imbalance.batch",
                  "decode_bytes_roofline.batch")


@pytest.fixture
def toy(tmp_path, monkeypatch):
    for sub, spec in (("configs", TOY_CONFIG), ("workloads", TOY_CELL)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{spec['name']}.json").write_text(json.dumps(spec))
    manifest = copy.deepcopy(harness.load_manifest())
    manifest["workloads"].append(
        {"name": "toy.sarvam", "config": "toy-sarvam",
         "traffic": "toyreason", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tok_s" or m["name"] in (
                NEW_METRICS + SHARED_METRICS):
            m["workloads"].append("toy.sarvam")
    monkeypatch.setattr(A, "ROOTS", A.ROOTS + [str(tmp_path)])
    return manifest


def phases_of(line):
    return {r["phase"]: r for r in line["log"] if "phase" in r}


def test_run_last_line_sample_and_fp8_control(toy):
    line = harness.run_cell("toy.sarvam", 2**31 + 41, 2.0, False, toy,
                            look_for_chip=False, control="fp8")
    check_line(line, toy, "toy.sarvam", traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 6
    phases = phases_of(line)
    assert phases["window"]["prefill_signatures"] == 1
    assert phases["window"]["decode_signatures"] == 1
    assert phases["window"]["state_bytes"] == 0      # no recurrent layer
    assert phases["check"]["checked_requests"] == 6
    assert [c["number"] for c in phases["check"]["compared"]] == [
        "served_logit_gap", "routing_deficit"]
    # the reference in fp8, in the program's place, fails BOTH limits
    assert phases["control"]["correct"] is False
    assert not any(c["within"] for c in phases["control"]["compared"])


def test_traced_run_reports_the_new_metrics_beside_the_accepted(
        toy, monkeypatch):
    kernel = FAKE_TRACE["events"]["/device:TPU:0"][0][0].replace(
        "%closed_call.2", "%mla_decode.3")
    trace = {**FAKE_TRACE, "events": {"/device:TPU:0": [
        (kernel, 0.1 * i, 0.05) for i in range(6)]}}
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: trace)
    monkeypatch.setattr(harness.Tracer, "start", lambda self: None)
    line = harness.run_cell("toy.sarvam", 2**31 + 42, 1.0, True, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.sarvam", traced=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW_METRICS + SHARED_METRICS) <= set(got)
    # 3 attention layers x 4 slots x 4 blocks + the NULL block, 16 x 40 bf16
    assert got["mla_cache_gb.batch"] == pytest.approx(
        3 * 17 * 16 * 40 * 2 * 1e-9)
    assert 4 <= got["mla_live_tokens.batch"] <= 4 * 64
    assert 0.0 < got["mla_decode_roofline.batch"] < 100.0
    assert 10.0 < got["moe_held_rows_share.batch"] < 45.0   # 4 of 16 held
    assert "ssm_state_gb.batch" not in got and "tick_gap_ms.batch" in got


def test_a_reader_with_nothing_to_read_leaves_the_new_metrics_out():
    """What a program without the attrs or the kernel (a parent commit)
    gives: nothing, and no error."""
    obs = {"spans": {}, "values": {}, "costs": {}, "peaks": {}, "trace": None}
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None
    # ticks in the ring, but spans without the new attrs
    from torchdistpackage_tpu.utils.profiling import span, spans
    spans.clear()
    with span("tdp:engine.init.pool"):
        pass
    with span("tdp:engine.tick"):
        with span("tdp:engine.decode", slots=2):
            pass
    obs["spans"] = {"engine_step": [0.1]}
    for name in NEW_METRICS[1:]:
        assert harness.read_layer_metric(name, obs) is None
    spans.clear()


# ------------------------------------------------------- broken timed paths


def _no_router_bias(monkeypatch):
    from torchdistpackage_tpu.parallel import moe

    route = moe._serve_route
    monkeypatch.setattr(moe, "_serve_route", lambda router, tokens, cfg: route(
        {**router, "bias": jnp.zeros_like(router["bias"])}, tokens, cfg))


def _no_latent_norm(monkeypatch):
    from torchdistpackage_tpu.models import hybrid

    mixer = hybrid.latent_attention_mixer
    monkeypatch.setattr(
        hybrid, "latent_attention_mixer", lambda p, *a: mixer(
            {**p, "kv_norm": {"scale": jnp.ones_like(p["kv_norm"]["scale"])}},
            *a))


def _values_from_the_whole_row(monkeypatch):
    """The value read from the row's LAST ``latent`` columns, rope part
    included, not from its first."""
    from torchdistpackage_tpu.ops import mla_attention as M

    attend = M.mla_gather_attention

    def shifted(q, pool, tables, offsets, *, latent, sm_scale, layer=None):
        W = pool.shape[-2]
        full = attend(q, pool, tables, offsets, latent=W, sm_scale=sm_scale,
                      layer=layer)
        return full[..., W - latent:]

    monkeypatch.setattr(M, "mla_gather_attention", shifted)


@pytest.mark.parametrize("fault,number", [
    (_no_router_bias, "routing_deficit"),
    (_no_latent_norm, "served_logit_gap"),
    (_values_from_the_whole_row, "served_logit_gap"),
], ids=["router_bias_dropped", "latent_norm_dropped", "v_from_all_columns"])
def test_a_broken_timed_path_is_not_correct(toy, monkeypatch, fault, number):
    fault(monkeypatch)
    line = harness.run_cell("toy.sarvam", 2**31 + 43, 1.0, False, toy,
                            look_for_chip=False)
    assert line["correct"] is False and line["failed"] == 0
    compared = {c["number"]: c for c in phases_of(line)["check"]["compared"]}
    assert not compared[number]["within"]


# ---------------------------------------------------------- the configuration


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file with the
    row's value; the three that differ are the ``reduced`` ones, with their
    published values beside them; the family reads the share from them."""
    cfg = A.load_config("sarvam-105b")
    catalog = {
        "attn_implementation": None, "default_theta": 10000,
        "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "sarvam_mla",
        "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
        "num_attention_heads": 64, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_shared_experts": 1, "q_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "deepseek_yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
        "vocab_size": 262144}
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    for key, want in catalog.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == want and cfg[key] < want, key
        else:
            assert cfg[key] == want, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 32, 65536)
    assert cfg["deployment_share"] == {
        "chips_per_layer": 4, "pipeline_stages": 7, "first_expert": 0}
    assert {"q_lora_rank", "router_score", "use_qk_norm",
            "rope_layout"} <= set(cfg["assumed"])
    for text in ("departures", "deployment"):
        assert cfg[text]
    s = family.shape(cfg, 4096)
    assert (s.experts, s.held_first, s.held, s.vocab) == (128, 0, 32, 65536)
    assert s.pattern == "*D*E*E*E*E" and s.cached == 576
    pc = family.program_config(cfg, 4096)
    assert pc.pattern == "LDLELELELE" and pc.latent_width == 576
    assert pc.moe.held == (0, 32) and pc.moe.num_experts == 128
    assert pc.moe.act == "swiglu" and pc.moe.score == "sigmoid"
    assert pc.mla_scale == pytest.approx(192 ** -0.5 * 1.8738, rel=1e-4)
    for bad in ({"q_lora_rank": 1536}, {"n_group": 8}):
        with pytest.raises(ValueError, match="not written"):
            family.shape({**cfg, **bad}, 4096)


def test_the_cell_fills_the_pool_it_names():
    """The cell's file against the configuration: every slot full at once
    is what its 4097 blocks hold, and that is 3.02 GB."""
    from torchdistpackage_tpu.serving import expected_pool_bytes

    cell = A.load_json("workloads", "sarvam105b.reason.json")
    geo, mix = cell["engine"], cell["traffic"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] == geo["max_ctx"]
    assert geo["num_blocks"] == 1 + geo["num_slots"] * (
        geo["max_ctx"] // geo["block_size"])
    pc = family.program_config(A.load_config(cell["config"]), geo["max_ctx"])
    assert round(expected_pool_bytes(
        pc, geo["num_blocks"], geo["block_size"]) / 1e9, 2) == 3.02
    assert mix["clients"] == 2 * geo["num_slots"]
    assert set(cell["limits"]) == {"served_logit_gap", "routing_deficit"}


def test_every_line_of_the_manifest_is_short_and_printable():
    """A ``why``, a ``layer``, a configuration's ``source`` and each word of
    ``command`` have 1 to 200 characters on one line with no tab: the
    driver refuses the file for one that is longer (this PR's first
    configuration entry had 205)."""
    import os

    with open(os.path.join(os.path.dirname(A.ROOT), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    lines = list(manifest["command"])
    for config in manifest["configs"]:
        lines += [config["why"], config["source"]]
    lines += [cell["why"] for cell in manifest["workloads"]]
    lines += [metric["layer"] for metric in manifest["per_layer"]]
    for line in lines:
        assert 1 <= len(line) <= 200 and line.isprintable(), line
