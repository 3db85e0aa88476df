"""The training runner in-process at toy width on the CPU: the last line's
keys and metrics, the control that must fail, and a broken step."""

import jax
import optax
import pytest

import bm_toy
from benchmarks import arch as A
from benchmarks import harness
from benchmarks.runners import train as train_runner
from test_bm_runner_serve import FAKE_TRACE, check_line, toy  # noqa: F401


def test_train_run_last_line_and_control(toy):
    line = harness.run_cell("toy.train", 2**31 + 11, 2.0, False, toy,
                            look_for_chip=False, control="fp8")
    check_line(line, toy, "toy.train", traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    phases = {r["phase"]: r for r in line["log"] if "phase" in r}
    # each number compared is printed beside its limit
    assert {c["number"] for c in phases["check"]["compared"]} == {
        "loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert all("limit" in c for c in phases["check"]["compared"])
    # the reference in fp8, put in the program's place, is not correct
    assert phases["control"]["correct"] is False


def test_train_traced_run_reports_every_declared_layer_metric(toy, monkeypatch, tmp_path):
    # a dry addition: one new metric file and one new entry
    (tmp_path / "layer_metrics").mkdir(exist_ok=True)
    (tmp_path / "layer_metrics" / "feed_ms.train.json").write_text(
        '{"name": "feed_ms.train", "unit": "ms", "args": {"scale": 1000.0}}')
    # ... with a reader of its own beside it, found by the metric's name
    (tmp_path / "layer_metrics" / "feed_ms.train.py").write_text(
        "def read(obs, scale):\n"
        "    return max(obs['spans']['feed']) * scale\n")
    toy["per_layer"].append({
        "name": "feed_ms.train", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "train step builder",
        "moves": "train_tok_s_chip"})
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: FAKE_TRACE)
    monkeypatch.setattr(harness.Tracer, "start", lambda self: None)
    line = harness.run_cell("toy.train", 2**31 + 12, 1.0, True, toy,
                            look_for_chip=False)
    check_line(line, toy, "toy.train", traced=True)
    assert line["metrics"]["feed_ms.train"]["value"] > 0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(toy, monkeypatch):
    real = train_runner.build_step

    def broken(*args, **kw):
        step = real(*args, **kw)

        class Frozen:
            def lower(self, params, state, batch):
                inner = step.lower(params, state, batch).compile()

                class C:
                    def as_text(self):
                        return inner.as_text()

                    def __call__(self, p, s, b):
                        keep = jax.tree.map(lambda x: x + 0, (p, s))
                        _, _, loss = inner(p, s, b)
                        return keep[0], keep[1], loss

                class L:
                    def compile(self):
                        return C()
                return L()
        return Frozen()

    monkeypatch.setattr(train_runner, "build_step", broken)
    line = harness.run_cell("toy.train", 2**31 + 13, 1.0, False, toy,
                            look_for_chip=False)
    assert line["correct"] is False
    check = [r for r in line["log"] if r.get("phase") == "check"][0]
    bad = {c["number"] for c in check["compared"] if not c["within"]}
    assert "update_norm_gap" in bad


def test_a_learning_rate_of_seven_tenths_is_not_correct(toy, monkeypatch):
    """A real leaf's update wrong by 30% in norm: what ``update_norm_gap`` is
    there to catch beside the unchanged state."""
    real = train_runner.build_step
    args = bm_toy.TOY_TRAIN["optimizer"]

    def slower(dp, pcfg, opt, specs, tp_axis, mix):
        opt = optax.adamw(**{**args, "learning_rate": 0.7 * args["learning_rate"]})
        return real(dp, pcfg, opt, specs, tp_axis, mix)

    monkeypatch.setattr(train_runner, "build_step", slower)
    line = harness.run_cell("toy.train", 2**31 + 14, 1.0, False, toy,
                            look_for_chip=False)
    assert line["correct"] is False
    check = [r for r in line["log"] if r.get("phase") == "check"][0]
    bad = {c["number"]: c["value"] for c in check["compared"] if not c["within"]}
    assert set(bad) == {"update_norm_gap"} and 0.2 < bad["update_norm_gap"] < 0.4
    assert check["worst_leaves"]["without_gradient"] == ["blocks.attn.bqkv.k"]
