"""The ``keye_vl2`` family through the UNEDITED harness at toy width on the
CPU: a directory of new files (one configuration, one cell) plus new manifest
entries, as ``bm_toy.py`` adds its own.  The last line's keys, the new
per-layer metrics beside the accepted ones, the two readers on a toy trace,
the fp8 control failing, six broken timed paths reading a gap over the toy
limits, and the configuration file against the catalog's row."""

import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arch as A
from benchmarks import harness
from benchmarks.families import keye_vl2 as family

from test_bm_runner_serve import FAKE_TRACE, check_line

TOY_CONFIG = {
    "name": "toy-keye", "family": "keye_vl2", "source": "test",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "attention_bias": False, "hidden_act": "silu",
    "norm_topk_prob": True, "tie_word_embeddings": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "num_hidden_layers": 2,
    "num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 32,
                  "q_chunk_size": 32, "topk": 48},
    "sliding_window": None, "use_sliding_window": False,
    "vocab_size": 211, "max_position_embeddings": 512, "reduced": [],
    "published": {"num_experts": 8}, "deployment_share": {"first_expert": 2},
    "assumed": {"indexer_rope_dim": {"value": 4}},
}
TOY_CELL = {
    "name": "toy.keye", "config": "toy-keye",
    "traffic_name": "toylong", "chips": 1, "runner": "serve_family",
    "engine": {"num_slots": 4, "block_size": 16, "chunk": 32, "max_ctx": 224,
               "run_ahead": True},
    "traffic": {"kind": "closed_loop", "clients": 8, "first_wave": 4,
                "population": 64, "population_seed": 5,
                "prompt_len": {"dist": "uniform", "lo": 96, "hi": 208},
                "output_len": {"dist": "log_uniform", "lo": 4, "hi": 16}},
    "check": {"sample": 6, "follow_routing": True},
    # bfloat16 against the float32 reference, which FOLLOWS the program's
    # experts and kept positions.  Six seeds (my CPU runs, PR 39): sound, a
    # gap of 0.000-0.043 and a deficit of 0.099-0.19 (the experts' 0.00-0.02,
    # the selection's 0.03-0.19: a score's rounding on the layer's scale);
    # the fp8 control 0.15-1.98 and 1.58-3.06.  Without following the
    # selection the same toy read 0.46-1.9 sound: a row keeps 48 of 100-220
    # positions and two flipped choices are 4% of its keys.  The broken
    # paths below: a chunk's indexer keys not cached 0.000 and 14.6, scores
    # without the relu 0.03 and 2.3, without ``w`` 0.001 and 9.6, a skipped
    # selection 0.000 and 10.2 (the reference follows what was kept: the
    # deficit reads it), experts not renormalised 0.55 and 1.5
    "limits": {"served_logit_gap": 0.1, "routing_deficit": 0.4},
}
NEW_METRICS = ("dsa_decode_roofline.batch", "dsa_index_roofline.batch",
               "dsa_step_roofline.batch", "dsa_selected_share.batch",
               "dsa_cache_gb.batch")
SHARED_METRICS = ("moe_held_rows_share.batch", "moe_imbalance.batch")


@pytest.fixture
def toy(tmp_path, monkeypatch):
    for sub, spec in (("configs", TOY_CONFIG), ("workloads", TOY_CELL)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{spec['name']}.json").write_text(json.dumps(spec))
    manifest = copy.deepcopy(harness.load_manifest())
    manifest["workloads"].append(
        {"name": "toy.keye", "config": "toy-keye",
         "traffic": "toylong", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tok_s" or m["name"] in (
                NEW_METRICS + SHARED_METRICS):
            m["workloads"].append("toy.keye")
    monkeypatch.setattr(A, "ROOTS", A.ROOTS + [str(tmp_path)])
    return manifest


def phases_of(line):
    return {r["phase"]: r for r in line["log"] if "phase" in r}


def test_run_last_line_sample_and_fp8_control(toy):
    line = harness.run_cell("toy.keye", 2**31 + 41, 2.0, False, toy,
                            look_for_chip=False, control="fp8")
    check_line(line, toy, "toy.keye", traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 6
    phases = phases_of(line)
    assert phases["window"]["prefill_signatures"] == 1
    assert phases["window"]["decode_signatures"] == 1
    assert phases["window"]["state_bytes"] == 0
    # 4 of 8 experts held: some routed rows fall elsewhere
    assert 0 < phases["window"]["moe_rows_held"] \
        < phases["window"]["moe_rows_routed"]
    assert phases["check"]["checked_requests"] == 6
    assert [c["number"] for c in phases["check"]["compared"]] == [
        "served_logit_gap", "routing_deficit"]
    # the reference in fp8, in the program's place, is not correct
    assert phases["control"]["correct"] is False


def test_traced_run_reports_the_new_metrics_beside_the_accepted(
        toy, monkeypatch):
    base = FAKE_TRACE["events"]["/device:TPU:0"][0][0]
    events = []
    for i in range(6):
        for j, name in enumerate(("dsa_index", "dsa_select", "dsa_decode")):
            events.append((base.replace("%closed_call.2", f"%{name}.{3 + j}"),
                           0.1 * i + 0.02 * j, 0.01 * (j + 1)))
    trace = {**FAKE_TRACE, "events": {"/device:TPU:0": events}}
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: trace)
    monkeypatch.setattr(harness.Tracer, "start", lambda self: None)
    line = harness.run_cell("toy.keye", 2**31 + 42, 1.0, True, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.keye", traced=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW_METRICS + SHARED_METRICS) <= set(got)
    # k and v: 2 layers x (4 slots x 14 blocks + the NULL block) x 2 heads x
    # 16 positions x 16 wide, and the indexer's 8 a position, bfloat16
    assert got["dsa_cache_gb.batch"] == pytest.approx(
        (2 * 2 * 57 * 2 * 16 * 16 + 2 * 57 * 16 * 8) * 2 * 1e-9)
    # contexts of 96-224 keep 48 (a prompt's first 48 rows keep all they see)
    assert 20.0 < got["dsa_selected_share.batch"] < 60.0
    # the two rooflines read their own kernel: the decode kernel's events
    # are three times the index kernel's, its cost not
    assert 0.0 < got["dsa_decode_roofline.batch"] < 100.0
    assert 0.0 < got["dsa_index_roofline.batch"] < 100.0
    assert 0.0 < got["dsa_step_roofline.batch"] < 100.0
    # the built-in reader would take the cell's most frequent program, the
    # prefill call: the cell is not on that metric's list
    assert "decode_bytes_roofline.batch" not in got
    assert got["moe_imbalance.batch"] >= 1.0
    assert 20.0 < got["moe_held_rows_share.batch"] < 80.0
    assert "paged_decode_roofline.batch" not in got


def test_the_three_readers_on_a_toy_trace():
    """The readers count their kernel inside the executions of the program
    that runs ``dsa_decode``, though another program (the prefill call, with
    a ``dsa_index`` of its own) runs more often; the index reader takes its
    cost one key deeper."""
    base = FAKE_TRACE["events"]["/device:TPU:0"][0][0]
    op = lambda name: base.replace("%closed_call.2", name)
    peaks = harness.peaks_for("TPU v5 lite")
    events = [(op("%dsa_index.7"), 0.10, 0.002),     # in the decode program
              (op("%dsa_decode.9"), 0.20, 0.004),
              (op("%dsa_select.8"), 0.30, 0.5),
              (op("%dsa_index.3"), 1.10, 0.07),      # in the prefill program
              (op("%dsa_chunk.4"), 1.20, 0.09)]
    modules = [("jit_step(1)", 0.0, 1.0), ("jit_step(2)", 1.0, 0.5),
               ("jit_step(2)", 1.5, 0.5), ("jit_step(2)", 2.0, 0.5)]
    cost = {"flops": 1e6, "bytes": peaks["hbm_bytes_per_s"] * 1e-3,
            "calls_per_execution": 2,
            "indexer": {"flops": 1e6, "bytes": peaks["hbm_bytes_per_s"] * 1e-4}}
    step = {"flops": 1e6, "bytes": peaks["hbm_bytes_per_s"] * 0.253,
            "calls_per_execution": 1}
    obs = {"spans": {}, "values": {},
           "costs": {"paged_decode": cost, "decode_step": step},
           "peaks": peaks,
           "trace": {**FAKE_TRACE, "modules": modules,
                     "events": {"/device:TPU:0": events}}}
    # one execution, two calls: 2 x 1 ms of bytes over 4 ms; 2 x 0.1 over 2
    assert harness.read_layer_metric("dsa_decode_roofline.batch", obs) \
        == pytest.approx(50.0)
    assert harness.read_layer_metric("dsa_index_roofline.batch", obs) \
        == pytest.approx(10.0)
    # the whole decode call: its three kernels' 0.506 s hold every operation
    assert harness.read_layer_metric("dsa_step_roofline.batch", obs) \
        == pytest.approx(50.0)
    del cost["indexer"], obs["costs"]["decode_step"]
    assert harness.read_layer_metric("dsa_index_roofline.batch", obs) is None
    assert harness.read_layer_metric("dsa_step_roofline.batch", obs) is None
    obs["trace"]["events"]["/device:TPU:0"].pop(1)   # no dsa_decode anywhere
    assert harness.read_layer_metric("dsa_decode_roofline.batch", obs) is None


def test_a_reader_with_nothing_to_read_leaves_the_new_metrics_out():
    """What a program without the attrs (a parent commit) gives: nothing,
    and no error."""
    obs = {"spans": {}, "values": {}, "costs": {}, "peaks": {}, "trace": None}
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None
    from torchdistpackage_tpu.utils.profiling import span, spans
    spans.clear()
    with span("tdp:engine.init.pool", bytes=5):
        pass
    with span("tdp:engine.tick"):
        with span("tdp:engine.decode", slots=2, live_tokens=9):
            pass
    obs["spans"] = {"engine_step": [0.1]}
    obs["trace"] = FAKE_TRACE   # a trace without the kernels' names
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, obs) is None
    spans.clear()


# ------------------------------------------------------- broken timed paths


def _selection_skipped(monkeypatch):
    """Every cached position attended: inside a tolerance at real widths,
    and not this model."""
    from torchdistpackage_tpu.ops import dsa_attention as D

    monkeypatch.setattr(
        D, "select_bias", lambda scores, offsets, topk, impl="gather":
        jnp.where(scores > 0.5 * D.NEG_INF, 0.0, D.NEG_INF))


def _scores_without_relu(monkeypatch):
    """The oracle's index scores with the relu left out (the CPU's path)."""
    from torchdistpackage_tpu.ops import dsa_attention as D

    def plain(qi, w, idx_pool, tables, offs, layer):
        g = idx_pool[layer, tables]
        B, mb, _one, W, bs = g.shape
        ki = g[:, :, 0].transpose(0, 1, 3, 2).reshape(B, mb * bs, W)
        s = jnp.einsum("bjsd,bpd->bsjp", qi.astype(jnp.float32),
                       ki.astype(jnp.float32))
        s = jnp.sum(s * w[..., None], axis=2)
        qpos = offs[:, None] + jnp.arange(qi.shape[2])[None, :]
        return jnp.where(jnp.arange(mb * bs)[None, None] <= qpos[..., None],
                         s, D.NEG_INF)

    monkeypatch.setattr(D, "_index_scores_gather", plain)


def _scores_without_w(monkeypatch):
    from torchdistpackage_tpu.ops import dsa_attention as D

    scores = D.index_scores
    monkeypatch.setattr(
        D, "index_scores", lambda qi, w, *a, **kw: scores(
            qi, jnp.full_like(w, 0.1), *a, **kw))


def _chunk_index_keys_not_cached(monkeypatch):
    """A prefill chunk attends with its own indexer keys and never writes
    them: a later row scores zeros there."""
    from torchdistpackage_tpu.serving import paged_cache as PC

    ops = PC._indexed_cache_ops

    def forgetful(tables, attn_impl, cfg, layer):
        write, write_idx, attend = ops(tables, attn_impl, cfg, layer)

        def attend_then_forget(q, ck, cv, cidx, qi, w, offset):
            return attend(q, ck, cv, cidx, qi, w, offset)

        def skip(pool, rows, offset):
            return pool if rows.shape[1] > 1 else write_idx(pool, rows, offset)

        return write, skip, attend_then_forget

    monkeypatch.setattr(PC, "_indexed_cache_ops", forgetful)


def _experts_not_renormalised(monkeypatch):
    from torchdistpackage_tpu.parallel import moe

    def route(router, tokens, cfg):
        probs = jax.nn.softmax(
            (tokens @ router["w"]).astype(jnp.float32), axis=-1)
        vals, idx = jax.lax.top_k(probs, cfg.top_k)
        return probs, vals, idx

    monkeypatch.setattr(moe, "_serve_route", route)


#: a fault, and the number that reads it: a selection made from something
#: else is FOLLOWED by the reference, so the logits agree and the deficit
#: holds the choice to the reference's own scores
FAULTS = {
    "selection_skipped": (_selection_skipped, "routing_deficit"),
    "scores_without_relu": (_scores_without_relu, "routing_deficit"),
    "scores_without_w": (_scores_without_w, "routing_deficit"),
    "chunk_index_keys_not_cached": (_chunk_index_keys_not_cached,
                                    "routing_deficit"),
    "experts_not_renormalised": (_experts_not_renormalised,
                                 "served_logit_gap"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(toy, monkeypatch, fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    line = harness.run_cell("toy.keye", 2**31 + 43, 1.0, False, toy,
                            look_for_chip=False)
    assert line["correct"] is False and line["failed"] == 0
    compared = {c["number"]: c for c in phases_of(line)["check"]["compared"]}
    assert not compared[number]["within"]


def test_a_permuted_mrope_section_reads_over_the_limit():
    """The engine serves text positions, where the three rows are equal and
    a permuted section is no fault at all; with three UNEQUAL rows (the
    model function's ``positions``) the program with ``mrope_section``
    permuted lies far from the reference, the sound one within the cell's
    limit."""
    from benchmarks.reference import keye_vl2 as ref
    from benchmarks.weights_keye_vl2 import make_weights
    from torchdistpackage_tpu.models.hybrid import (
        hybrid_paged_forward, init_state)
    from torchdistpackage_tpu.serving import init_paged_kv
    from torchdistpackage_tpu.serving.paged_cache import _indexed_cache_ops

    s = family.shape(TOY_CONFIG, 64)
    cfg = family.program_config(TOY_CONFIG, 64)
    params = make_weights(s, 2**31 + 44)
    rng = np.random.RandomState(1)
    S = 48
    toks = rng.randint(0, s.vocab, S)
    rows = np.stack([np.arange(S), np.arange(S) // 3, np.arange(S) % 7])
    tables = jnp.arange(1, 4, dtype=jnp.int32)[None]

    def gap(c):
        ops = lambda layer: _indexed_cache_ops(tables, "gather", c, layer)
        run = jax.jit(lambda cache, t, pos: hybrid_paged_forward(
            params, t, c, cache, init_state(c, 1), jnp.full((1,), S), ops,
            jnp.zeros((1,), jnp.int32), positions=pos)[2][0])
        logits = run(init_paged_kv(c, 4, 16), jnp.asarray(toks)[None],
                     jnp.asarray(rows)[:, None])
        want = np.asarray(ref.forward_logits(params, toks, s,
                                             positions=rows)[-1])
        return float(want.max() - want[int(np.argmax(logits))])

    limit = TOY_CELL["limits"]["served_logit_gap"]
    assert gap(cfg) <= limit
    assert gap(dataclasses.replace(cfg, mrope_section=(3, 3, 2))) > limit


# ---------------------------------------------------------- the configuration


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file with the
    row's value; those that differ are the ``reduced`` ones, with their
    published values beside them."""
    cfg = A.load_config("keye-vl-2.0-30b-a3b")
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    for key, want in catalog.items():
        if key in cfg["reduced"]:
            assert cfg[key] < want, key
        else:
            assert cfg[key] == want, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 32, 37984)
    assert cfg["vocab_size"] * 4 == 151936
    share = cfg["deployment_share"]
    assert (share["chips_per_layer"], share["stages"],
            share["first_expert"]) == (4, 6, 0)
    assert {"qk_norm", "indexer_query", "indexer_key_norm",
            "indexer_head_weights", "indexer_rope_dim",
            "chunk_sizes"} <= set(cfg["assumed"])
    assert all("why" in v for v in cfg["assumed"].values())
    assert {"hadamard", "indexer_precision", "vision"} <= set(
        cfg["departures"])
    s = family.shape(cfg, 14336)
    assert (s.experts, s.held_first, s.held, s.top_k) == (128, 0, 32, 8)
    assert (s.idx_heads, s.idx_dim, s.idx_topk, s.idx_rope) == (16, 64, 2048,
                                                                32)
    assert s.mrope_section == (16, 24, 24) and s.rope_theta == 1e7
    pc = family.program_config(cfg, 14336)
    assert pc.pattern == "SE" * 8 and pc.head_dim == 128
    assert pc.moe.held == (0, 32) and pc.moe.num_experts == 128
    assert (pc.moe.act, pc.moe.score, pc.moe.top_k) == ("swiglu", "softmax", 8)
    manifest = harness.load_manifest()
    entry = [c for c in manifest["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_cell_fills_the_pool_it_names():
    """The cell's file against the configuration: every slot full at once
    is what its 3585 blocks hold, and that is 7.99 GB; every context is
    past ``topk`` from its first decode tick."""
    from torchdistpackage_tpu.serving import expected_pool_bytes

    cell = A.load_json("workloads", "keyevl2.longctx.json")
    geo, mix = cell["engine"], cell["traffic"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] == geo["max_ctx"]
    assert geo["num_blocks"] == 1 + geo["num_slots"] * (
        geo["max_ctx"] // geo["block_size"]) == 3585
    cfg = A.load_config(cell["config"])
    pc = family.program_config(cfg, geo["max_ctx"])
    assert round(expected_pool_bytes(
        pc, geo["num_blocks"], geo["block_size"]) / 1e9, 2) == 7.99
    assert pc.state_bytes(geo["num_slots"]) == 0
    assert mix["prompt_len"]["lo"] > cfg["sa_config"]["topk"]
    assert mix["clients"] == 2 * geo["num_slots"] == 64
    assert (geo["chunk"], geo["block_size"], geo["run_ahead"]) == (512, 128,
                                                                   True)
    assert (mix["population"], mix["population_seed"]) == (2048, 39)
    assert set(cell["limits"]) == {"served_logit_gap", "routing_deficit"}
    manifest = harness.load_manifest()
    entry = harness.find_cell(manifest, "keyevl2.longctx")
    assert (entry["traffic"], entry["chips"]) == (cell["traffic_name"], 1)
    got = {m["name"] for m in harness.metrics_of(
        manifest, "per_layer", "keyevl2.longctx")}
    assert set(NEW_METRICS + SHARED_METRICS) <= got
    assert "paged_decode_roofline.batch" not in got
