"""The serving runner in-process at toy width on the CPU: the last line's
keys and metrics, the control that must fail, and a token altered where it
is produced.  Also the harness's own refusals."""

import numpy as np
import pytest

import bm_toy
from benchmarks import arch as A
from benchmarks import harness

KERNEL = ("%closed_call.2 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %x), "
          "custom_call_target=\"tpu_custom_call\"")
#: what Tracer.reduce gives on a chip, in small: the CPU has no device plane
FAKE_TRACE = {
    "busy_s": 0.9, "window_s": 1.0,
    "device_ops": [["%closed_call.2 custom-call", 0.3]],
    "idle_gaps": [["bm:feed", 0.1]],
    "events": {"/device:TPU:0": [(KERNEL, 0.1 * i, 0.05) for i in range(6)]},
    "modules": [("jit_step(1)", 0.0, 1.0)],
}


@pytest.fixture
def toy(tmp_path, monkeypatch):
    manifest = bm_toy.toy_benchmark(tmp_path)
    monkeypatch.setattr(A, "ROOTS", A.ROOTS + [str(tmp_path)])
    return manifest


def check_line(line, manifest, cell, traced):
    """The contract's keys, and every declared metric with its unit."""
    need = {"correct", "attempted", "failed", "metrics", "device"}
    assert need <= set(line)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    section = "per_layer" if traced else "end_to_end"
    declared = harness.metrics_of(manifest, section, cell)
    assert declared
    for m in declared:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and np.isfinite(got["value"])
    assert set(line["metrics"]) == {m["name"] for m in declared}
    if traced:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_serve_run_last_line_and_control(toy):
    line = harness.run_cell("toy.decode", 2**31 + 21, 2.0, False, toy,
                            look_for_chip=False, control="fp8")
    check_line(line, toy, "toy.decode", traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 10
    phases = {r["phase"]: r for r in line["log"] if "phase" in r}
    assert phases["check"]["compared"][0]["number"] == "served_logit_gap"
    # every request that the window finished went through the reference
    assert phases["check"]["checked_requests"] == line["attempted"]
    assert phases["control"]["correct"] is False


def test_serve_traced_run_reports_every_declared_layer_metric(toy, monkeypatch):
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: FAKE_TRACE)
    monkeypatch.setattr(harness.Tracer, "start", lambda self: None)
    line = harness.run_cell("toy.decode", 2**31 + 22, 1.0, True, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.decode", traced=True)


def test_open_loop_run_times_requests_from_when_they_were_due(toy):
    line = harness.run_cell("toy.chat", 2**31 + 24, 2.0, False, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.chat", traced=False)
    assert line["correct"] and line["attempted"] > 20
    window = [r for r in line["log"] if r.get("phase") == "window"][0]
    assert window["generator_lateness"]["n"] == window["finished"]
    assert window["ttft_ms"]["n"] == window["finished"]
    # arrivals stop at 85% of the window and the rest drains
    assert window["unfinished"] == line["failed"] == 0


class Chaos:
    """The engine's own fault-injection hook.  ``every_third``: every slot's
    token in every third tick.  Else one token of one decoding slot in one
    tick, and nothing else (the hook runs after the tick's prefill call and
    after its decode call; only the second reads a decoding slot's token)."""

    def __init__(self, every_third: bool):
        self.every_third, self.hit = every_third, None

    def before_engine_tick(self, tick, engine):
        if not self.every_third and self.hit is None and tick >= 40:
            busy = engine.decode_slots()
            if busy:
                self.hit = (tick, busy[0][1])

    def perturb_engine_tokens(self, tick, tok):
        if self.every_third:
            return (tok + 1) % 211 if tick % 3 == 0 else tok
        if self.hit is not None and self.hit[0] == tick:
            tok = tok.copy()
            tok[self.hit[1]] = (tok[self.hit[1]] + 1) % 211
        return tok


@pytest.mark.parametrize("every_third,seed", [(True, 2**31 + 23),
                                              (False, 2**31 + 25)],
                         ids=["every_slot_every_third_tick", "one_slot_once"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        toy, every_third, seed):
    chaos = Chaos(every_third)
    line = harness.run_cell("toy.decode", seed, 1.0, False, toy,
                            look_for_chip=False, chaos=chaos)
    assert every_third or chaos.hit is not None
    assert line["correct"] is False
    check = [r for r in line["log"] if r.get("phase") == "check"][0]
    assert check["tokens_not_top"] > 0 and not check["compared"][0]["within"]


def test_the_harness_refuses_what_it_cannot_measure(toy):
    with pytest.raises(SystemExit):   # no TPU here
        harness.run_cell("toy.decode", 1, 1.0, False, toy)
    with pytest.raises(SystemExit):   # no such cell
        harness.run_cell("nope", 1, 1.0, False, toy, look_for_chip=False)
    with pytest.raises(SystemExit):   # a traced run with nothing on the device
        harness.result_line(
            harness.Context(manifest=toy, workload=toy["workloads"][-1],
                            cell={}, config={}, seed=1, seconds=1.0, trace=True,
                            peaks={}, compiles=None, trace_dir=""),
            {"correct": True, "attempted": 1, "failed": 0,
             "memory_peak_bytes": 0, "obs": {"trace": None}})


def test_metrics_of_follows_the_manifest_only(toy):
    names = {m["name"] for m in harness.metrics_of(toy, "per_layer", "toy.train")}
    assert "mfu.train" in names and "decode_tick_ms.batch" not in names
    names = {m["name"] for m in harness.metrics_of(toy, "end_to_end", "toy.decode")}
    assert names == {"setup_s", "serve_tok_s"}


def test_a_reader_with_nothing_to_read_leaves_the_metric_out():
    obs = {"spans": {}, "values": {}, "costs": {}, "peaks": {}, "trace": None}
    for name in ("decode_tick_ms.batch", "mfu.train", "paged_roofline.batch",
                 "hbm_peak_gb.train"):
        assert harness.read_layer_metric(name, obs) is None
