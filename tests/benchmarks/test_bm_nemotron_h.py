"""The ``nemotron_h`` family through the UNEDITED harness at toy width on the
CPU: a directory of new files (one configuration, one cell) plus new
manifest entries, as ``bm_toy.py`` adds its own.  The last line's keys, the
four new per-layer metrics beside the accepted ones, the sample that the
check takes, the fp8 control failing the limit, and the family's costs."""

import copy
import json

import numpy as np
import pytest

from benchmarks import arch as A
from benchmarks import harness
from benchmarks.families import nemotron_h as family

from test_bm_runner_serve import FAKE_TRACE, check_line

TOY_CONFIG = {
    "name": "toy-nemotron", "family": "nemotron_h", "source": "test",
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "hybrid_override_pattern": "MEM*EME",
    "num_hidden_layers": 7, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
    "n_routed_experts": 4, "published": {"n_routed_experts": 16},
    "deployment_share": {"first_expert": 8}, "num_experts_per_tok": 6,
    "moe_latent_size": 32, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "routed_scaling_factor": 5,
    "layer_norm_epsilon": 1e-5, "n_group": 1, "topk_group": 1,
    "vocab_size": 211, "max_position_embeddings": 512,
    "reduced": ["n_routed_experts"],
}
TOY_CELL = {
    "name": "toy.nemotron", "config": "toy-nemotron",
    "traffic_name": "toydecode", "chips": 1, "runner": "serve_family",
    "engine": {"num_slots": 4, "block_size": 16, "chunk": 16, "max_ctx": 64,
               "run_ahead": True},
    "traffic": {"kind": "closed_loop", "clients": 8, "first_wave": 4,
                "population": 64, "population_seed": 5,
                "prompt_len": {"dist": "uniform", "lo": 4, "hi": 30},
                "output_len": {"dist": "log_uniform", "lo": 4, "hi": 16}},
    "check": {"sample": 6, "follow_routing": True},
    # bfloat16 against the float32 reference at width 64, the reference
    # following the program's choice of experts: four seeds read a gap of
    # 0.0-0.01 and a deficit of 0.003-0.012, the fp8 control 0.24-0.61 and
    # 0.12-0.24 (without following: gaps of 0.4-1.1 on sound seeds)
    "limits": {"served_logit_gap": 0.15, "routing_deficit": 0.05},
}
NEW_METRICS = ("ssm_state_gb.batch", "moe_held_rows_share.batch",
               "moe_imbalance.batch", "decode_bytes_roofline.batch")


@pytest.fixture
def toy(tmp_path, monkeypatch):
    for sub, spec in (("configs", TOY_CONFIG), ("workloads", TOY_CELL)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{spec['name']}.json").write_text(json.dumps(spec))
    manifest = copy.deepcopy(harness.load_manifest())
    manifest["workloads"].append(
        {"name": "toy.nemotron", "config": "toy-nemotron",
         "traffic": "toydecode", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tok_s" or m["name"] in NEW_METRICS:
            m["workloads"].append("toy.nemotron")
    monkeypatch.setattr(A, "ROOTS", A.ROOTS + [str(tmp_path)])
    return manifest


def test_run_last_line_sample_and_fp8_control(toy):
    line = harness.run_cell("toy.nemotron", 2**31 + 31, 2.0, False, toy,
                            look_for_chip=False, control="fp8")
    check_line(line, toy, "toy.nemotron", traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 6
    phases = {r["phase"]: r for r in line["log"] if "phase" in r}
    assert phases["window"]["prefill_signatures"] == 1
    assert phases["window"]["decode_signatures"] == 1
    assert phases["window"]["state_bytes"] > 0
    # the check takes the cell's sample of the window's finished requests
    assert phases["check"]["checked_requests"] == 6
    assert phases["check"]["finished_requests"] == line["attempted"]
    assert [c["number"] for c in phases["check"]["compared"]] == [
        "served_logit_gap", "routing_deficit"]
    # the reference in fp8, in the program's place, fails BOTH limits: its
    # tokens and its choice of experts
    assert phases["control"]["correct"] is False
    assert not any(c["within"] for c in phases["control"]["compared"])


def test_traced_run_reports_the_new_metrics_beside_the_accepted(
        toy, monkeypatch):
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: FAKE_TRACE)
    monkeypatch.setattr(harness.Tracer, "start", lambda self: None)
    line = harness.run_cell("toy.nemotron", 2**31 + 32, 1.0, True, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.nemotron", traced=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW_METRICS) <= set(got)
    # 3 Mamba layers x 4 slots x (8x8x16 float32 + 3 x 128 bfloat16)
    assert got["ssm_state_gb.batch"] == pytest.approx(
        3 * 4 * (8 * 8 * 16 * 4 + 3 * 128 * 2) * 1e-9)
    assert 10.0 < got["moe_held_rows_share.batch"] < 45.0   # 4 of 16 held
    assert got["moe_imbalance.batch"] >= 1.0
    assert 0.0 < got["decode_bytes_roofline.batch"] < 100.0
    assert "paged_roofline.batch" in got and "tick_gap_ms.batch" in got


def test_a_reader_with_nothing_to_read_leaves_the_new_metrics_out():
    """What a program without the counters (a parent commit) gives."""
    obs = {"spans": {}, "values": {"state_bytes": None}, "costs": {},
           "peaks": {}, "trace": None}
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under
    the same key; the four that differ are the ``reduced`` ones, with their
    published values beside them; the family reads the share from them."""
    cfg = A.load_config("nemotron-3-super-120b-a12b")
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    pub = cfg["published"]
    assert pub["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert len(pub["hybrid_override_pattern"]) == pub["num_hidden_layers"] == 88
    assert (pub["n_routed_experts"], pub["vocab_size"]) == (512, 131072)
    for key, want in {
            "hidden_size": 4096, "head_dim": 128, "num_attention_heads": 32,
            "num_key_value_heads": 2, "mamba_num_heads": 128,
            "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
            "conv_kernel": 4, "chunk_size": 128, "num_experts_per_tok": 22,
            "moe_latent_size": 1024, "moe_intermediate_size": 2688,
            "moe_shared_expert_intermediate_size": 5376,
            "routed_scaling_factor": 5, "expand": 2}.items():
        assert cfg[key] == want, key
    s = family.shape(cfg, 768)
    assert (s.experts, s.held_first, s.held, s.vocab) == (512, 0, 128, 32768)
    assert s.pattern.count("M") == s.pattern.count("E") == 5
    for text in ("assumed", "departures", "deployment"):
        assert cfg[text]
    pc = family.program_config(cfg, 768)
    assert pc.moe.held == (0, 128) and pc.moe.num_experts == 512
    assert pc.state_bytes(64) == 64 * family.state_bytes_per_slot(s)


def test_the_family_counts_what_the_issue_counted():
    """Parameters a layer, the bytes of the cut and of one decode tick, from
    the published widths (ISSUE 26's arithmetic)."""
    s = family.shape(A.load_config("nemotron-3-super-120b-a12b"), 768)
    n = family.layer_params(s)
    assert round(n["M"] / 1e6, 1) == 109.6
    assert round(n["*"] / 1e6, 1) == 35.7
    assert round(n["E"] / 1e6, 1) == 54.5
    assert n["expert"] == 2 * 1024 * 2688
    assert round(family.num_params(s) * 2 / 1e9, 1) == 9.3
    assert round(family.state_bytes_per_slot(s) / 1e6, 1) == 21.3
    # one layer's paged call: 2 KV heads x 128 x 2 bytes x (k and v) a token
    paged = family.paged_decode(s, 64 * 400.0, 64.0)
    assert paged["bytes"] == 2 * 64 * 400 * 2 * 128 * 2 + 2 * 64 * 32 * 128 * 2
    full = family.decode_step(s, 64 * 400.0, 64.0, 5 * 128.0)
    # every weight but the embedding's other rows, twice the state, the KV
    want = ((family.num_params(s) - (s.vocab - 64) * s.dim) * 2
            + 2 * 64 * family.state_bytes_per_slot(s) + paged["bytes"])
    assert full["bytes"] == pytest.approx(want)
    # fewer experts touched, fewer bytes; memory-bound at 64 slots
    some = family.decode_step(s, 64 * 400.0, 64.0, 5 * 100.0)
    assert full["bytes"] - some["bytes"] == 5 * 28 * n["expert"] * 2
    assert full["bytes"] / 819e9 > full["flops"] / 197e12
