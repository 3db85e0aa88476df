"""The ``afmoe`` family through the UNEDITED harness at toy width on the CPU:
a directory of new files (one configuration, one cell) plus new manifest
entries, as ``bm_toy.py`` adds its own.  The last line's keys, the six new
per-layer metrics beside the accepted ones, the fp8 control failing, nine
broken timed paths reading ``correct`` false, the configuration file against
the catalog's row and the cost functions by hand."""

import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arch as A
from benchmarks import harness
from benchmarks.families import afmoe as family

from test_bm_runner_serve import FAKE_TRACE, check_line

TOY_CONFIG = {
    "name": "toy-afmoe", "family": "afmoe", "source": "test",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 96, "num_hidden_layers": 8,
    "num_dense_layers": 1,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "sliding_window": 16, "num_experts": 4, "published": {"num_experts": 8},
    "deployment_share": {"first_expert": 4}, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "moe_intermediate_size": 32,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "n_group": 1, "topk_group": 1, "mup_enabled": True, "hidden_act": "silu",
    "tie_word_embeddings": False, "rope_scaling": None, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "vocab_size": 211, "max_position_embeddings": 512,
    "reduced": ["num_experts"],
}
TOY_CELL = {
    "name": "toy.afmoe", "config": "toy-afmoe", "traffic_name": "toymixed",
    "chips": 1, "runner": "serve_family",
    "engine": {"num_slots": 4, "block_size": 8, "chunk": 8, "max_ctx": 64,
               "run_ahead": True},
    # a third of the prompts under the window of 16, every answer crosses it
    "traffic": {"kind": "closed_loop", "clients": 8, "first_wave": 4,
                "population": 64, "population_seed": 5,
                "prompt_len": {"dist": "log_uniform", "lo": 6, "hi": 44},
                "output_len": {"dist": "log_uniform", "lo": 6, "hi": 20}},
    "check": {"sample": 6, "follow_routing": True},
    # bfloat16 against the float32 reference at width 64, the reference
    # following the program's choice of experts: five seeds read a gap of
    # 0.009-0.075 and a deficit of 0.005-0.020, the fp8 control 0.64-1.23 and
    # 0.17-0.30; the broken paths below (two seeds each): global layers
    # rotated 0.38-0.39 and 0.15-0.20, weights not renormalised 0.50-0.55
    # and 0.13, the embedding unscaled 0.99-1.17, a post-mixer norm dropped
    # 1.4-1.8, weights unscaled by 2.826 1.5-1.6, the window off by one
    # 1.7-2.2 (its extra key lies in a block that was handed on), the shared
    # expert dropped 2.2-2.3, the gate dropped 3.3-3.7, window layers left
    # global 3.4-4.7.  Each limit near the geometric middle of the sound
    # runs' largest and the smallest of the rest
    "limits": {"served_logit_gap": 0.17, "routing_deficit": 0.05},
}
NEW_METRICS = ("swa_decode_roofline.batch", "swa_full_decode_roofline.batch",
               "swa_chunk_roofline.batch", "swa_step_roofline.batch",
               "swa_cache_gb.batch", "swa_kept_share.batch")
SHARED_METRICS = ("moe_held_rows_share.batch", "moe_imbalance.batch")


@pytest.fixture
def toy(tmp_path, monkeypatch):
    for sub, spec in (("configs", TOY_CONFIG), ("workloads", TOY_CELL)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{spec['name']}.json").write_text(json.dumps(spec))
    manifest = copy.deepcopy(harness.load_manifest())
    manifest["workloads"].append(
        {"name": "toy.afmoe", "config": "toy-afmoe", "traffic": "toymixed",
         "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tok_s" or m["name"] in (
                NEW_METRICS + SHARED_METRICS):
            m["workloads"].append("toy.afmoe")
    monkeypatch.setattr(A, "ROOTS", A.ROOTS + [str(tmp_path)])
    return manifest


def phases_of(line):
    return {r["phase"]: r for r in line["log"] if "phase" in r}


def test_run_last_line_sample_and_fp8_control(toy):
    line = harness.run_cell("toy.afmoe", 2**31 + 41, 2.0, False, toy,
                            look_for_chip=False, control="fp8")
    check_line(line, toy, "toy.afmoe", traced=False)
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


def _trace_of_the_ring(t_start):
    """A trace that the spans since ``t_start`` could have left: every
    dispatched call ran once, in order, as late as its fetch (and the call
    behind it) allows and for at most 50 us, never before its dispatch span
    opened; a decode call runs ``swa_decode`` and ``paged_decode``, a
    prefill call ``swa_chunk``.  On a clock of its own (seconds since the
    capture began), as a profiler's is."""
    from torchdistpackage_tpu.utils.profiling import spans

    recs = [r for r in spans.snapshot() if r[3] >= t_start]
    fetched = {r[5]["call"]: r[4] for r in recs
               if r[2] == "tdp:engine.fetch" and "call" in r[5]}
    name = FAKE_TRACE["events"]["/device:TPU:0"][0][0]
    op = lambda kernel: name.replace("%closed_call.2", kernel)
    events, modules = [], []
    calls = [r for r in recs if r[2] in ("tdp:engine.prefill",
                                         "tdp:engine.decode")]
    window_s = free = max(r[4] for r in recs) - t_start
    for r in sorted(calls, key=lambda r: -r[3]):
        k = r[5].get("calls", 1)
        hi = min(free, fetched.get(r[5]["call"], r[4] + 1e-3) - t_start - 1e-6)
        lo = max(hi - 50e-6, r[3] - t_start + 1e-6)
        assert hi > lo
        d = (hi - lo) / k
        for j in range(k):
            t = lo + j * d
            if r[2].endswith("decode"):
                modules.append(("jit_step(1)", t, d))
                events += [(op("%swa_decode.3"), t, 0.4 * d),
                           (op("%paged_decode.5"), t + 0.4 * d, 0.2 * d),
                           (op("%fusion.7"), t + 0.6 * d, 0.4 * d)]
            else:
                modules.append(("jit_step(2)", t, d))
                events.append((op("%swa_chunk.9"), t, 0.8 * d))
        free = lo
    return {**FAKE_TRACE, "events": {"/device:TPU:0": sorted(
                events, key=lambda e: e[1])},
            "modules": sorted(modules, key=lambda m: m[1]),
            "window_s": window_s}


def test_traced_run_reports_the_new_metrics_beside_the_accepted(
        toy, monkeypatch):
    """A toy trace laid under the run's own spans: two programs, the decode
    call (``swa_decode`` and ``paged_decode`` inside it) and the prefill
    call (``swa_chunk``).  Every execution is held to its own call's work."""
    import time

    def start(self):
        self.t_start = time.perf_counter()

    monkeypatch.setattr(harness.Tracer, "start", start)
    monkeypatch.setattr(harness.Tracer, "reduce",
                        lambda self: _trace_of_the_ring(self.t_start))
    line = harness.run_cell("toy.afmoe", 2**31 + 42, 1.0, True, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.afmoe", traced=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW_METRICS + SHARED_METRICS) <= set(got)
    # 6 window layers x (4 slots x 3 blocks + NULL) x K and V of 2 heads
    assert got["swa_cache_gb.batch"] == pytest.approx(
        2 * 6 * 13 * 2 * 8 * 16 * 2 * 1e-9)
    assert 30.0 < got["swa_kept_share.batch"] < 95.0
    for name in NEW_METRICS[:4]:
        assert 0.0 < got[name] < 100.0, name
    assert 25.0 < got["moe_held_rows_share.batch"] < 75.0   # 4 of 8 held
    assert "dsa_cache_gb.batch" not in got and "tick_gap_ms.batch" in got


def test_an_execution_is_held_to_the_work_of_its_own_call():
    """Spans and executions by hand: three ticks, a prefill span of two
    calls then a decode call each (``run_ahead``: fetched a tick later), the
    capture 5.0 s into the spans' clock.  The costs are the calls' own
    counters at the unit costs; a decode call whose execution the capture
    cut off is left out, and a trace that breaks the order gives nothing."""
    from benchmarks.layer_metrics import swa_kernels as K

    wall, modules, call = [], [], 0
    for i in range(3):
        t = 5.0 + 0.1 * i
        wall.append(("tdp:engine.tick", t, t + 0.09, {}))
        call += 2
        wall.append(("tdp:engine.prefill", t + 0.001, t + 0.002, {
            "call": call, "calls": 2, "tokens": 10 + i, "rows": 16,
            "window_positions": 20, "live_tokens": 30 + i,
            "window_pairs": 100 + i, "live_pairs": 200 + i}))
        modules += [("jit_step(2)", t - 5.0 + 0.002 + 0.02 * j, 0.02)
                    for j in range(2)]
        wall.append(("tdp:engine.fetch", t + 0.003, t + 0.0425,
                     {"call": call}))
        call += 1
        wall.append(("tdp:engine.decode", t + 0.05, t + 0.051, {
            "call": call, "slots": 3 + i, "window_positions": 40 + i,
            "live_tokens": 50 + i}))
        if i < 2:   # the last decode call's execution: past the capture
            modules.append(("jit_step(1)", t - 5.0 + 0.052, 0.03))
            wall.append(("tdp:engine.fetch", t + 0.101, t + 0.102,
                         {"call": call, "experts_touched": 7.0 + i}))
    decodes = K.matched_calls(modules, wall, "jit_step(1)", True)
    assert [(c[0]["slots"], c[1]["experts_touched"], len(c[2]))
            for c in decodes] == [(3, 7.0, 1), (4, 8.0, 1)]
    chunks = K.matched_calls(modules, wall, "jit_step(2)", False)
    assert [(c[0]["tokens"], len(c[2])) for c in chunks] == [
        (10, 2), (11, 2), (12, 2)]
    assert decodes[1][2] == [pytest.approx((0.152, 0.182))]
    unit = {"flops_per_pair": 2.0, "bytes_per_position": 3.0,
            "bytes_per_row": 5.0}
    step = {"fixed_bytes": 1000.0, "bytes_per_slot": 7.0,
            "expert_bytes": 11.0, "flops_per_slot": 13.0}
    costs = {"paged_decode": {"window_unit": unit, "step_unit": step,
                              "window_layers": 3, "calls_per_execution": 1}}
    got = K.call_costs(costs, decodes, True)
    assert got["window"] == {"flops": 2.0 * 81, "bytes": 3.0 * 81 + 5.0 * 7}
    assert got["global"] == {"flops": 2.0 * 101, "bytes": 3.0 * 101 + 5.0 * 7}
    assert got["step"] == {
        "flops": 13.0 * 7 + 3 * 2.0 * 81 + 2.0 * 101,
        "bytes": (2 * 1000.0 + 7.0 * 7 + 11.0 * 15 + 3 * (3.0 * 81 + 35)
                  + 3.0 * 101 + 35)}
    got = K.call_costs(costs, chunks, False)
    assert got["window"] == {"flops": 2.0 * 303, "bytes": 3.0 * 60 + 5.0 * 33}
    assert got["global"] == {"flops": 2.0 * 603, "bytes": 3.0 * 93 + 5.0 * 33}
    # a decode span from before the attr: no step; a prefill span: nothing
    bare = [(c[0], {}, c[2]) for c in decodes]
    assert "step" not in K.call_costs(costs, bare, True)
    assert K.call_costs(costs, [({"tokens": 1}, {}, [])], False) is None
    # an execution that ends after its fetch returned: no number
    late = [m if i != 2 else (m[0], m[1], 0.2) for i, m in enumerate(modules)]
    assert K.matched_calls(late, wall, "jit_step(1)", True) is None


def test_a_reader_with_nothing_to_read_leaves_the_new_metrics_out():
    """What a program without the attrs or the kernels (a parent commit)
    gives: nothing, and no error."""
    obs = {"spans": {}, "values": {}, "costs": {}, "peaks": {}, "trace": None}
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None
    # ticks in the ring, but spans without the new attrs; a trace, but no
    # program that runs the kernels; a family that gives no window costs
    from torchdistpackage_tpu.utils.profiling import span, spans
    spans.clear()
    with span("tdp:engine.init.pool", bytes=8):
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


def _forward_with(monkeypatch, params=None, cfg=None):
    """The program's forward with its parameters or its config rewritten."""
    from torchdistpackage_tpu.models import hybrid

    forward = hybrid.hybrid_paged_forward

    def planted(p, tokens, c, *a, **kw):
        return forward(params(p) if params else p, tokens,
                       cfg(c) if cfg else c, *a, **kw)

    monkeypatch.setattr(hybrid, "hybrid_paged_forward", planted)


def _layers_without(leaf, kinds, pattern="WDWEWE*EWEWEWE*E"):
    def strip(p):
        return {**p, "layers": [
            {k: v for k, v in lp.items() if not (k == leaf and kind in kinds)}
            for kind, lp in zip(pattern, p["layers"])]}
    return strip


def _attend_with_window(monkeypatch, rewrite):
    from torchdistpackage_tpu.serving import paged_cache as PC

    attend = PC.paged_attention
    monkeypatch.setattr(
        PC, "paged_attention", lambda *a, window=None, **kw: attend(
            *a, window=rewrite(window), **kw))


def _window_layers_left_global(monkeypatch):
    _attend_with_window(monkeypatch, lambda w: None)


def _window_off_by_one(monkeypatch):
    _attend_with_window(monkeypatch, lambda w: w and w + 1)


def _global_layers_rotated(monkeypatch):
    from torchdistpackage_tpu.models import hybrid

    mixer = hybrid.attention_mixer

    def rotated(p, x, cfg, ck, cv, offset, ops, window=None):
        if window is not None:
            return mixer(p, x, cfg, ck, cv, offset, ops, window=window)
        write, attend = ops
        return mixer(p, x, cfg, ck, cv, offset, (
            write, lambda *a, window=None: attend(*a, window=None)),
            window=1 << 20)

    monkeypatch.setattr(hybrid, "attention_mixer", rotated)


def _shared_expert_dropped(monkeypatch):
    _forward_with(monkeypatch, params=_layers_without("shared", "E"),
                  cfg=lambda c: dataclasses.replace(c, moe_shared_ffn=0))


def _weights_not_renormalised(monkeypatch):
    from torchdistpackage_tpu.parallel import moe

    def route(router, tokens, cfg):
        scores = jax.nn.sigmoid(jnp.dot(
            tokens, router["w"], preferred_element_type=jnp.float32))
        _, idx = jax.lax.top_k(scores + router["bias"], cfg.top_k)
        return (scores, jnp.take_along_axis(scores, idx, axis=-1)
                * cfg.routed_scale, idx)

    monkeypatch.setattr(moe, "_serve_route", route)


#: a fault, and the number that reads it
FAULTS = {
    "window_layers_left_global": _window_layers_left_global,
    "window_off_by_one": _window_off_by_one,
    "global_layers_rotated": _global_layers_rotated,
    "gate_dropped": lambda mp: _forward_with(
        mp, params=_layers_without("wg", "W*")),
    "a_post_mixer_norm_dropped": lambda mp: _forward_with(
        mp, params=_layers_without("post_norm", "*")),
    "embedding_unscaled": lambda mp: _forward_with(
        mp, cfg=lambda c: dataclasses.replace(c, embed_scale=1.0)),
    "weights_unscaled_by_2.826": lambda mp: _forward_with(
        mp, cfg=lambda c: dataclasses.replace(c, moe_routed_scale=1.0)),
    "weights_not_renormalised": _weights_not_renormalised,
    "shared_expert_dropped": _shared_expert_dropped,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(toy, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    line = harness.run_cell("toy.afmoe", 2**31 + 43, 1.0, False, toy,
                            look_for_chip=False)
    assert line["correct"] is False and line["failed"] == 0
    compared = {c["number"]: c for c in phases_of(line)["check"]["compared"]}
    assert not compared["served_logit_gap"]["within"]


# ---------------------------------------------------------- the configuration


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file with the
    row's value; the four that differ are the ``reduced`` ones, with their
    published values beside them; the family reads the share from them."""
    cfg = A.load_config("trinity-mini")
    kinds = (["sliding_attention"] * 3 + ["full_attention"]) * 8
    catalog = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "layer_types": kinds, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    for key, want in catalog.items():
        if key == "layer_types":
            assert cfg[key] == want[:16]
        elif key in cfg["reduced"]:
            assert cfg["published"][key] == want and cfg[key] < want, key
        else:
            assert cfg[key] == want, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (16, 32, 50048)
    share = cfg["deployment_share"]
    assert (share["chips_per_layer"], share["stages"],
            share["first_expert"]) == (4, 2, 0) and share["why"]
    assert {"output_gate", "qk_norm", "rotation", "four_norms", "embed_scale",
            "router"} <= set(cfg["assumed"])
    for what in cfg["assumed"].values():
        assert what["why"] and "value" in what
    for text in ("departures", "deployment"):
        assert cfg[text]
    s = family.shape(cfg, 14336)
    assert (s.experts, s.held_first, s.held, s.vocab) == (128, 0, 32, 50048)
    assert s.pattern == "WDWDWE*E" + "WEWEWE*E" * 3 and s.window == 2048
    assert s.embed_scale == pytest.approx(2048 ** 0.5)
    pc = family.program_config(cfg, 14336)
    assert pc.pattern == s.pattern and pc.window == 2048
    assert (pc.kv_layers, pc.window_layers) == (4, 12)
    assert pc.moe.held == (0, 32) and pc.moe.num_experts == 128
    assert pc.moe.act == "swiglu" and pc.moe.score == "sigmoid"
    assert pc.moe.routed_scale == 2.826 and pc.moe.shared_ffn == 1024
    for bad in ({"n_group": 8}, {"score_func": "softmax"},
                {"layer_types": kinds[:15]}):
        with pytest.raises(ValueError, match="not written|as published|must"):
            family.shape({**cfg, **bad}, 14336)


def test_the_family_counts_what_the_issue_counted():
    """Parameters a layer and the cut's bytes, from the published widths
    (ISSUE 41's arithmetic), and the least the decode calls move, by hand."""
    s = family.shape(A.load_config("trinity-mini"), 14336)
    n = family.layer_params(s)
    assert round(n["W"] / 1e6, 2) == round(n["*"] / 1e6, 2) == 27.27
    assert round(n["D"] / 1e6, 2) == 37.75
    assert round(n["expert"] / 1e6, 2) == 6.29
    # a block of experts as run: attention + router + shared + 32 held
    assert round((n["W"] + n["E"] + 32 * n["expert"]) / 1e6, 1) == 235.2
    assert round((n["W"] + n["D"]) / 1e6, 1) == 65.0
    assert round(family.num_params(s) / 1e9, 2) == 3.63
    assert round(family.num_params(s) * 2 / 1e9, 2) == 7.25
    # every expert whole: the published model, 26.1B
    full = dataclasses.replace(
        s, held=128, vocab=200192, pattern="WDWDWE*E" + "WEWEWE*E" * 7)
    assert round(family.num_params(full) / 1e9, 1) == 26.1
    live, slots = 32 * 5000.0, 32.0
    paged = family.paged_decode(s, live, slots)
    assert paged["bytes"] == 2 * live * 4 * 128 * 2 + 2 * slots * 32 * 128 * 2
    assert paged["flops"] == 4 * live * 32 * 128
    win = paged["window"]
    assert win["bytes"] == (2 * slots * 2048 * 4 * 128 * 2
                            + 2 * slots * 32 * 128 * 2)
    assert win["flops"] == 4 * slots * 2048 * 32 * 128
    assert paged["window_layers"] == 12 and s.pattern.count("*") == 4
    assert paged["window_unit"] == {
        "flops_per_pair": 4 * 32 * 128, "bytes_per_position": 2 * 4 * 128 * 2,
        "bytes_per_row": 2 * 32 * 128 * 2}
    # short contexts: all of them inside the window
    short = family.paged_decode(s, 32 * 900.0, slots)
    assert short["window"]["flops"] == short["flops"]
    step = family.decode_step(s, live, slots, 14 * 30.0)
    want = ((family.num_params(s) - 32 * 14 * n["expert"]
             - (s.vocab - 32) * s.dim + 14 * 30 * n["expert"]) * 2
            + 4 * paged["bytes"] + 12 * win["bytes"])
    assert step["bytes"] == pytest.approx(want)
    # ISSUE 41's reckoning of a tick: ~7 GB of weights, ~1.3 GB of global
    # keys and values, ~1.6 GB of window ones
    assert 6.5e9 < step["bytes"] - 4 * paged["bytes"] - 12 * win["bytes"] < 7.3e9
    assert round(4 * paged["bytes"] / 1e9, 1) == 1.3
    assert round(12 * win["bytes"] / 1e9, 1) == 1.6


def test_the_cell_fills_the_pools_it_names():
    """The cell's file against the configuration: every slot full at once is
    what its 3585 blocks hold of the global layers, the engine sizes the
    window layers' pool itself at 20 blocks a slot, and together with the
    weights they are 13.0 GB."""
    from torchdistpackage_tpu.serving import expected_pool_bytes
    from torchdistpackage_tpu.serving.paged_cache import window_reach

    cell = A.load_json("workloads", "trinitymini.mixedlen.json")
    geo, mix = cell["engine"], cell["traffic"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] == geo["max_ctx"]
    assert geo["num_blocks"] == 1 + geo["num_slots"] * (
        geo["max_ctx"] // geo["block_size"])
    cfg = A.load_config(cell["config"])
    pc = family.program_config(cfg, geo["max_ctx"])
    reach = window_reach(pc.window, geo["chunk"], geo["block_size"])
    assert reach == 20
    wblocks = 1 + geo["num_slots"] * reach
    both = expected_pool_bytes(pc, geo["num_blocks"], geo["block_size"],
                               window_blocks=wblocks)
    one = expected_pool_bytes(pc, geo["num_blocks"], geo["block_size"],
                              window_blocks=2)
    assert round(one / 1e9, 2) == 3.77 and round((both - one) / 1e9, 2) == 2.01
    weights = family.num_params(family.shape(cfg, geo["max_ctx"])) * 2
    assert round((weights + both) / 1e9, 1) == 13.0
    assert mix["clients"] == 2 * geo["num_slots"]
    assert set(cell["limits"]) == {"served_logit_gap", "routing_deficit"}
    # the mix: a third of the prompts under the window, mean ~4.5k
    from benchmarks.traffic import generator
    reqs = generator.requests(mix, 50048, 1)
    plen = np.asarray([len(r["tokens"]) for r in reqs])
    assert 0.2 < (plen < 2048).mean() < 0.4 and 4000 < plen.mean() < 5000
    assert plen.max() <= 12288 and plen.min() >= 1024
    assert max(r["max_new_tokens"] for r in reqs) <= 2048
