"""The ``mimo_v2`` family through the UNEDITED harness at toy width on the
CPU: a directory of new files (one configuration, one cell) plus new manifest
entries, as ``bm_toy.py`` adds its own.  The last line's keys, the six new
per-layer metrics beside the accepted ones, the fp8 control failing, six
broken timed paths reading ``correct`` false, the configuration file against
the catalog's row, the sizes of the cell and the cost functions by hand."""

import copy
import dataclasses
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arch as A
from benchmarks import harness
from benchmarks.families import mimo_v2 as family

from test_bm_afmoe import (
    _attend_with_window, _forward_with, _trace_of_the_ring, phases_of)
from test_bm_runner_serve import check_line

TOY_CONFIG = {
    "name": "toy-mimo", "family": "mimo_v2", "source": "test",
    "hidden_size": 64, "num_attention_heads": 8, "swa_num_attention_heads": 8,
    "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
    "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
    "swa_v_head_dim": 16, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "attention_value_scale": 0.707, "attention_bias": False,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True,
    "sliding_window": 8, "sliding_window_size": 8, "attention_chunk_size": 8,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1], "num_hidden_layers": 6,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "published": {"n_routed_experts": 8},
    "deployment_share": {"first_expert": 4}, "num_experts_per_tok": 2,
    "n_shared_experts": None, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": None,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "layernorm_epsilon": 1e-5, "vocab_size": 211,
    "max_position_embeddings": 512, "reduced": ["n_routed_experts"],
}
PATTERN = "*DWEWE*EWEWE"
TOY_CELL = {
    "name": "toy.mimo", "config": "toy-mimo", "traffic_name": "toylong",
    "chips": 1, "runner": "serve_family",
    "engine": {"num_slots": 4, "block_size": 8, "chunk": 16, "max_ctx": 64,
               "run_ahead": True},
    # every prompt crosses the window of 8, most a chunk boundary of 16
    "traffic": {"kind": "closed_loop", "clients": 8, "first_wave": 4,
                "population": 64, "population_seed": 5,
                "prompt_len": {"dist": "log_uniform", "lo": 10, "hi": 44},
                "output_len": {"dist": "log_uniform", "lo": 6, "hi": 20}},
    "check": {"sample": 6, "follow_routing": True},
    # bfloat16 against the float32 reference at width 64, the reference
    # following the program's choice of experts: five seeds read a gap of
    # 0.000-0.047 and a deficit of 0.004-0.011, the fp8 control 0.46-1.24 and
    # 0.14-0.22; the broken paths below (two seeds each): the value scale
    # dropped 1.2-1.6 and 0.21-0.30, window layers left global 1.3-2.1, a
    # sink on the global layers 1.4-2.0, the sink dropped 2.7-3.1, the
    # thetas swapped 3.2-3.6, the whole head rotated 3.2-3.4 (my CPU runs,
    # no device number).  Each limit the geometric middle of the sound
    # runs' largest and the control's smallest
    "limits": {"served_logit_gap": 0.15, "routing_deficit": 0.04},
}
NEW_METRICS = ("sink_decode_roofline.batch", "sink_chunk_roofline.batch",
               "wide_decode_roofline.batch", "wide_chunk_roofline.batch",
               "mimo_step_roofline.batch", "pool_padding_ratio.batch")
SHARED_METRICS = ("moe_held_rows_share.batch", "moe_imbalance.batch",
                  "swa_kept_share.batch", "swa_cache_gb.batch")


@pytest.fixture
def toy(tmp_path, monkeypatch):
    for sub, spec in (("configs", TOY_CONFIG), ("workloads", TOY_CELL)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{spec['name']}.json").write_text(json.dumps(spec))
    manifest = copy.deepcopy(harness.load_manifest())
    manifest["workloads"].append(
        {"name": "toy.mimo", "config": "toy-mimo", "traffic": "toylong",
         "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tok_s" or m["name"] in (
                NEW_METRICS + SHARED_METRICS):
            m["workloads"].append("toy.mimo")
    monkeypatch.setattr(A, "ROOTS", A.ROOTS + [str(tmp_path)])
    return manifest


def test_run_last_line_sample_and_fp8_control(toy):
    line = harness.run_cell("toy.mimo", 2**31 + 41, 2.0, False, toy,
                            look_for_chip=False, control="fp8")
    check_line(line, toy, "toy.mimo", traced=False)
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


def _with_the_global_chunk_kernel(trace):
    """``test_bm_afmoe``'s toy trace runs ``swa_chunk`` in a prefill call;
    this model's runs ``paged_chunk`` (the global layers') behind it."""
    name = next(e[0] for e in trace["events"]["/device:TPU:0"]
                if "%swa_chunk" in e[0])
    more = [(name.replace("%swa_chunk.9", "%paged_chunk.4"),
             t + 0.8 * d, 0.15 * d)
            for n, t, d in trace["modules"] if n == "jit_step(2)"]
    events = sorted(trace["events"]["/device:TPU:0"] + more,
                    key=lambda e: e[1])
    return {**trace, "events": {"/device:TPU:0": events}}


def test_traced_run_reports_the_new_metrics_beside_the_accepted(
        toy, monkeypatch):
    """A toy trace laid under the run's own spans: two programs, the decode
    call (``swa_decode`` and ``paged_decode`` inside it) and the prefill
    call (``swa_chunk`` and ``paged_chunk``).  Every execution is held to
    its own call's work, each kind of layer at its own unit cost."""
    import time

    def start(self):
        self.t_start = time.perf_counter()

    monkeypatch.setattr(harness.Tracer, "start", start)
    monkeypatch.setattr(
        harness.Tracer, "reduce", lambda self: _with_the_global_chunk_kernel(
            _trace_of_the_ring(self.t_start)))
    # the CPU counts no device memory: a counter stands in for it, by which
    # the pools' allocation "took" 3 MB
    from torchdistpackage_tpu.serving import engine as E
    reads = itertools.count()
    monkeypatch.setattr(E, "_device_bytes_in_use",
                        lambda: 3_000_000 * next(reads))
    line = harness.run_cell("toy.mimo", 2**31 + 42, 1.0, True, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.mimo", traced=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW_METRICS + SHARED_METRICS) <= set(got)
    # 4 window layers x (4 slots x 3 blocks + NULL) x 4 KV heads x 8 x (24 +
    # 16) bfloat16; beside them 2 global layers x (4 x 8 + NULL) x 2 heads
    window = 4 * 13 * 4 * 8 * 40 * 2
    assert got["swa_cache_gb.batch"] == pytest.approx(window * 1e-9)
    assert got["pool_padding_ratio.batch"] == pytest.approx(
        3_000_000 / (window + 2 * 33 * 2 * 8 * 40 * 2))
    assert 10.0 < got["swa_kept_share.batch"] < 80.0
    for name in NEW_METRICS[:5]:
        assert 0.0 < got[name] < 100.0, name
    assert 25.0 < got["moe_held_rows_share.batch"] < 75.0   # 4 of 8 held
    assert "swa_decode_roofline.batch" not in got
    assert "cache_padding_ratio.batch" not in got
    assert "tick_gap_ms.batch" in got


def test_each_kind_of_layer_is_held_to_its_own_unit_cost():
    """``mimo_kernels.call_costs`` by hand: two decode calls and two prefill
    spans; the window layers at ``window_unit``, the global layers at
    ``global_unit``, the step from both."""
    from benchmarks.layer_metrics import mimo_kernels as K

    win = {"flops_per_pair": 2.0, "bytes_per_position": 8.0,
           "bytes_per_row": 5.0}
    glob = {"flops_per_pair": 2.0, "bytes_per_position": 4.0,
            "bytes_per_row": 5.0}
    step = {"fixed_bytes": 1000.0, "bytes_per_slot": 7.0,
            "expert_bytes": 11.0, "flops_per_slot": 13.0}
    costs = {"paged_decode": {"window_unit": win, "global_unit": glob,
                              "step_unit": step, "window_layers": 3,
                              "calls_per_execution": 2}}
    decodes = [({"slots": 3, "window_positions": 20, "live_tokens": 50},
                {"experts_touched": 7.0}, []),
               ({"slots": 4, "window_positions": 30, "live_tokens": 51},
                {"experts_touched": 8.0}, [])]
    got = K.call_costs(costs, decodes, True)
    assert got["window"] == {"flops": 2.0 * 50, "bytes": 8.0 * 50 + 5.0 * 7}
    assert got["global"] == {"flops": 2.0 * 101, "bytes": 4.0 * 101 + 35.0}
    assert got["step"] == {
        "flops": 13.0 * 7 + 3 * 100.0 + 2 * 202.0,
        "bytes": (2 * 1000.0 + 7.0 * 7 + 11.0 * 15 + 3 * 435.0
                  + 2 * 439.0)}
    chunks = [({"tokens": 10, "window_positions": 8, "live_tokens": 30,
                "window_pairs": 70, "live_pairs": 200}, {}, [])]
    got = K.call_costs(costs, chunks, False)
    assert got["window"] == {"flops": 140.0, "bytes": 8.0 * 8 + 50.0}
    assert got["global"] == {"flops": 400.0, "bytes": 4.0 * 30 + 50.0}
    # a family with one unit cost for both kinds (trinity-mini's): nothing
    one = {"paged_decode": {k: v for k, v in costs["paged_decode"].items()
                            if k != "global_unit"}}
    assert K.call_costs(one, decodes, True) is None
    # a decode span from before the attr: no step
    bare = [(c[0], {}, c[2]) for c in decodes]
    assert "step" not in K.call_costs(costs, bare, True)


def test_a_reader_with_nothing_to_read_leaves_the_new_metrics_out():
    """What a program without the attrs or the kernels (a parent commit)
    gives: nothing, and no error."""
    from test_bm_runner_serve import FAKE_TRACE

    obs = {"spans": {}, "values": {}, "costs": {}, "peaks": {}, "trace": None}
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None
    # ticks in the ring, but an init span without the widths and dispatch
    # spans without the window attrs; a trace, but no program that runs the
    # kernels; a family that gives one unit cost
    from torchdistpackage_tpu.utils.profiling import span, spans
    spans.clear()
    with span("tdp:engine.init.pool", bytes=8, device_bytes=8):
        pass
    with span("tdp:engine.tick"):
        with span("tdp:engine.prefill", tokens=8, calls=1, rows=32):
            pass
        with span("tdp:engine.decode", slots=2, live_tokens=9):
            pass
    obs.update(spans={"engine_step": [0.1]}, trace=FAKE_TRACE,
               peaks=harness.peaks_for("TPU v5 lite"),
               costs={"paged_decode": {"flops": 1.0, "bytes": 1.0,
                                       "calls_per_execution": 2},
                      "decode_step": {"flops": 1.0, "bytes": 1.0,
                                      "calls_per_execution": 1}})
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None, name
    spans.clear()


# ------------------------------------------------------- broken timed paths


def _sinks(kinds, value):
    """The tree with ``value`` (None: nothing) for the sink of every layer
    of ``kinds``."""
    def change(p):
        layers = []
        for kind, lp in zip(PATTERN, p["layers"]):
            if kind in kinds:
                lp = {k: v for k, v in lp.items() if k != "sink"}
                if value is not None:
                    lp["sink"] = jnp.full((8,), value, jnp.float32)
            layers.append(lp)
        return {**p, "layers": layers}
    return change


#: a fault of the timed path, each in what the model adds
FAULTS = {
    "sink_dropped": lambda mp: _forward_with(mp, params=_sinks("W", None)),
    "sink_on_the_global_layers": lambda mp: _forward_with(
        mp, params=_sinks("*", 3.5)),
    "value_scale_dropped": lambda mp: _forward_with(
        mp, cfg=lambda c: dataclasses.replace(c, value_scale=1.0)),
    "whole_head_rotated": lambda mp: _forward_with(
        mp, cfg=lambda c: dataclasses.replace(c, rope_dims=0)),
    "thetas_swapped": lambda mp: _forward_with(
        mp, cfg=lambda c: dataclasses.replace(
            c, rope_theta=c.global_rope_theta,
            global_rope_theta=c.rope_theta)),
    "window_layers_left_global": lambda mp: _attend_with_window(
        mp, lambda w: None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(toy, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    line = harness.run_cell("toy.mimo", 2**31 + 43, 1.0, False, toy,
                            look_for_chip=False)
    assert line["correct"] is False and line["failed"] == 0
    compared = {c["number"]: c for c in phases_of(line)["check"]["compared"]}
    assert not compared["served_logit_gap"]["within"]


# ---------------------------------------------------------- the configuration


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file with the
    row's value; the five that differ are the ``reduced`` ones, with their
    published values beside them; the family reads the share from them."""
    cfg = A.load_config("mimo-v2.5")
    kinds = [0] + ([1] * 4 + [0]) + ([1] * 5 + [0]) * 7
    catalog = {
        "attention_bias": False, "attention_chunk_size": 128,
        "attention_value_scale": 0.707,
        "attention_projection_layout": "fused_qkv",
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
        "swa_num_attention_heads": 64, "swa_head_dim": 192,
        "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
        "hidden_size": 4096, "hybrid_block_size": None,
        "hybrid_layer_pattern": kinds, "intermediate_size": 16384,
        "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576,
        "model_type": "mimo_v2", "moe_intermediate_size": 2048,
        "moe_layer_freq": [0] + [1] * 47, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": None,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
        "rope_scaling": {"rope_type": "default", "type": "default"},
        "rope_theta": 10000000, "routed_scaling_factor": None,
        "scoring_func": "sigmoid", "sliding_window": 128,
        "sliding_window_size": 128, "swa_rope_theta": 10000,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152576}
    assert len(kinds) == 48 and kinds.count(0) == 9
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                              "moe_layer_freq", "n_routed_experts",
                              "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    for key, want in catalog.items():
        if key in ("hybrid_layer_pattern", "moe_layer_freq"):
            assert cfg[key] == want[:11], key
        elif key in cfg["reduced"]:
            assert cfg["published"][key] == want and cfg[key] < want, key
        else:
            assert cfg[key] == want, key
    assert (cfg["name"], cfg["family"]) == ("mimo-v2.5", "mimo_v2")
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (11, 8, 19072)
    share = cfg["deployment_share"]
    assert (share["chips_per_layer"], share["stages"],
            share["first_expert"]) == (32, 4, 0) and share["why"]
    assert {"rotated_dims", "window_edges", "sink", "sink_draw",
            "value_scale", "no_qk_norm_no_bias", "bias_draw",
            "attention_chunk_size"} <= set(cfg["assumed"])
    for what in cfg["assumed"].values():
        assert what["why"] and "value" in what
    assert {"mtp", "towers"} <= set(cfg["departures"]) and cfg["deployment"]
    # the manifest's entries for it
    manifest = harness.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "mimo-v2.5")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmarks/configs/mimo-v2.5.json"
    cell = harness.find_cell(manifest, "mimov25.long24k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2.5", "long2k-24k", 1)
    for name in NEW_METRICS:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["mimov25.long24k"]
        assert m["moves"] == "serve_tok_s"


def test_the_cell_at_its_sizes_and_the_step_cost_by_hand():
    """``num_params`` is 3,409,017,920 and the two pools 4,362,731,520 and
    949,616,640 B at the cell's sizes; a position costs 2,560 B in a global
    layer and 5,120 in a window layer, a pair 40,960 flop in both; the step
    cost's parts add up."""
    from torchdistpackage_tpu.serving import paged_cache as PC

    cfg = A.load_config("mimo-v2.5")
    geo = A.load_json("workloads", "mimov25.long24k.json")["engine"]
    s = family.shape(cfg, geo["max_ctx"])
    assert s.pattern == "*D" + "WE" * 4 + "*E" + "WE" * 5
    assert (s.experts, s.held_first, s.held, s.vocab) == (256, 0, 8, 19072)
    assert (s.rope_dims, s.window, s.value_scale) == (64, 128, 0.707)
    assert (s.window_sink, s.global_sink) == (True, False)
    n = family.layer_params(s)
    assert (n["*"] + n["D"], n["*"] + n["E"] + 8 * n["expert"],
            n["W"] + n["E"] + 8 * n["expert"]) == (
        290_463_744, 291_512_576, 296_755_520)
    assert family.num_params(s) == 3_409_017_920
    pc = family.program_config(cfg, geo["max_ctx"])
    assert (pc.kv_layers, pc.window_layers, pc.kv_heads, pc.window_heads,
            pc.head_dim, pc.value_width, pc.rope_dims) == (
        2, 9, 4, 8, 192, 128, 64)
    assert (pc.rope_theta, pc.global_rope_theta) == (1e4, 1e7)
    assert pc.moe.held == (0, 8) and pc.moe.num_experts == 256
    assert pc.moe.shared_ffn == 0 and pc.moe.routed_scale == 1.0
    reach = PC.window_reach(pc.window, geo["chunk"], geo["block_size"])
    blocks = 1 + geo["num_slots"] * reach
    assert (reach, blocks, geo["num_blocks"]) == (5, 161, 6657)
    pool = jax.eval_shape(lambda: PC.init_paged_kv(
        pc, geo["num_blocks"], geo["block_size"], window_blocks=blocks))
    assert pool["k"].shape == (2, 6657, 4, 192, 128)
    assert pool["win"]["v"].shape == (9, 161, 8, 128, 128)
    assert PC.window_bytes(pool) == 949_616_640
    assert PC.pool_bytes(pool) - PC.window_bytes(pool) == 4_362_731_520
    # the unit costs, and the step put together from them
    live, slots, touched = 350_000.0, 32.0, 75.0
    cost = family.paged_decode(s, live, slots)
    assert cost["global_unit"] == {"flops_per_pair": 40960.0,
                                   "bytes_per_position": 2560,
                                   "bytes_per_row": 40960}
    assert cost["window_unit"]["bytes_per_position"] == 5120
    assert cost["window_unit"]["flops_per_pair"] == 40960.0
    assert cost["bytes"] == 2560 * live + 40960 * slots
    assert cost["window"]["bytes"] == 5120 * slots * 128 + 40960 * slots
    assert cost["window_layers"] == 9
    unit = family.step_unit(s)
    fixed = family.num_params(s) - 10 * 8 * n["expert"] - s.vocab * s.dim
    assert unit["fixed_bytes"] == 2 * fixed
    assert unit["expert_bytes"] == 2 * 25_165_824
    step = family.decode_step(s, live, slots, touched)
    assert step["bytes"] == (
        unit["fixed_bytes"] + slots * 8192 + touched * unit["expert_bytes"]
        + 2 * cost["bytes"] + 9 * cost["window"]["bytes"])
    assert step["flops"] == (slots * unit["flops_per_slot"]
                             + 2 * cost["flops"] + 9 * cost["window"]["flops"])
