"""BENCHMARK.json against the contract's static rules, and every file that a
cell, a configuration or a metric names."""

import json
import os
import re

import pytest

from benchmarks import arch as A
from benchmarks import harness

REPO = harness.REPO
MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ALL_METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_size():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= MANIFEST["run_seconds"] <= 51
    cells = 24  # later PRs may fill the table; the length must fit then
    runs = 2 + 14 * cells
    assert (runs * (MANIFEST["run_seconds"] + 60) + cells * 2 * 90 + 1200
            <= 43200)
    assert MANIFEST["command"][1].startswith(tuple(MANIFEST["paths"]))


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    e2e = metric in MANIFEST["end_to_end"]
    allowed |= {"bound"} if e2e else {"layer", "moves"}
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        moved = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"]]
        assert len(moved) == 1
        # each of its cells reports the end-to-end metric it moves
        for cell in metric.get("workloads", []):
            assert cell in moved[0].get("workloads", CELLS)
        spec = A.load_json("layer_metrics", f"{metric['name']}.json")
        assert spec["name"] == metric["name"] and spec["unit"] == metric["unit"]
        if "roofline" in metric["name"] or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


def test_names_are_unique():
    for section in ("configs", "workloads"):
        names = [x["name"] for x in MANIFEST[section]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in ALL_METRICS]
    assert len(names) == len(set(names))
    assert "setup_s" in names


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_file(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in [c["name"] for c in MANIFEST["configs"]]
    spec = A.load_json("workloads", f"{cell['name']}.json")
    assert (spec["name"], spec["config"], spec["traffic_name"], spec["chips"]) \
        == (cell["name"], cell["config"], cell["traffic"], cell["chips"])
    assert os.path.exists(os.path.join(
        A.ROOT, "runners", f"{spec['runner']}.py"))
    # setup_s, one more end-to-end metric and one per-layer metric at least
    e2e = {m["name"] for m in harness.metrics_of(MANIFEST, "end_to_end", cell["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(MANIFEST, "per_layer", cell["name"])
    # every number the check compares has a limit of its own, above zero
    assert spec["limits"] and all(v > 0 for v in spec["limits"].values())


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and len(config["reduced"]) <= 16
    assert config["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
    with open(os.path.join(REPO, config["file"])) as f:
        spec = json.load(f)
    assert spec["name"] == config["name"] and spec["source"] == config["source"]
    assert spec["reduced"] == config["reduced"]
    widths = re.compile(
        r"(_dim$|_rank$|hidden_size|intermediate|latent|state_size|n_embd|"
        r"n_inner|head_dim|expand|per_tok)")
    assert not any(widths.search(k) for k in config["reduced"])
    # every configuration has a cell
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])
    A.family_of(spec)  # the family it names is a file that imports


def test_four_chip_cells_within_a_quarter():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


def test_paths_hold_only_benchmark_files_with_plain_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        assert ok.match(path) and len(path) <= 200
        for root, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(root, f), REPO))
