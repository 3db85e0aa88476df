"""Tests for bench.py's baseline-policy machinery — the perf-honesty rules
(VERDICT r2 item 2 / BASELINE.md "first measurement wins"): per-
(backend, config) records, never overwritten, vs_baseline against the BEST
recorded config.  Pure-python, no accelerator."""

import importlib.util
import json
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_load_baselines_migrations(tmp_path):
    b = _bench()
    p = tmp_path / "b.json"

    # oldest layout: one flat record
    p.write_text(json.dumps(
        {"backend": "tpu", "value": 100.0, "unit": "tokens/sec/chip", "config": "cfgA"}
    ))
    out = b._load_baselines(str(p))
    assert out["tpu"]["cfgA"]["value"] == 100.0

    # legacy layout: one record per backend
    p.write_text(json.dumps(
        {"tpu": {"backend": "tpu", "value": 100.0, "config": "cfgA"}}
    ))
    out = b._load_baselines(str(p))
    assert out["tpu"]["cfgA"]["value"] == 100.0

    # current layout: {backend: {config: record}}
    p.write_text(json.dumps(
        {"tpu": {"cfgA": {"backend": "tpu", "value": 100.0, "config": "cfgA"}}}
    ))
    out = b._load_baselines(str(p))
    assert out["tpu"]["cfgA"]["value"] == 100.0

    # unreadable / missing -> empty
    assert b._load_baselines(str(tmp_path / "missing.json")) == {}
    p.write_text("not json")
    assert b._load_baselines(str(p)) == {}


def test_record_baseline_first_wins(tmp_path):
    b = _bench()
    p = str(tmp_path / "b.json")
    baselines = {}
    b._record_baseline(baselines, p, "tpu", "cfgA", 100.0)
    # a slower re-measurement of the same config must NOT overwrite
    b._record_baseline(baselines, p, "tpu", "cfgA", 50.0)
    assert baselines["tpu"]["cfgA"]["value"] == 100.0
    # a new config gets its own record without touching cfgA
    b._record_baseline(baselines, p, "tpu", "cfgB", 80.0)
    assert baselines["tpu"]["cfgA"]["value"] == 100.0
    assert baselines["tpu"]["cfgB"]["value"] == 80.0
    on_disk = json.loads(Path(p).read_text())
    assert on_disk["tpu"]["cfgA"]["value"] == 100.0

    # vs_baseline semantics: bench.py's own denominator is the BEST recorded
    # config, so a config switch can never re-base the history (the round-2
    # failure mode)
    assert b._best_recorded(baselines, "tpu", fallback=80.0) == 100.0
    assert 80.0 / b._best_recorded(baselines, "tpu", 80.0) < 1.0
    # no records for a backend -> the current measurement is its own baseline
    assert b._best_recorded(baselines, "cpu", fallback=42.0) == 42.0

    # metric scoping: different model sizes are different series — the 1b
    # config's denominator ignores 125m records and vice versa
    baselines["tpu"]["cfgA"]["metric"] = "gpt-125m-train-throughput"
    baselines["tpu"]["cfgB"]["metric"] = "gpt-125m-train-throughput"
    b._record_baseline(baselines, p, "tpu", "big1", 12.0,
                       metric="gpt-1b-train-throughput")
    assert b._best_recorded(
        baselines, "tpu", 12.0, metric="gpt-1b-train-throughput") == 12.0
    assert b._best_recorded(
        baselines, "tpu", 80.0, metric="gpt-125m-train-throughput") == 100.0


def test_only_index_parsing():
    b = _bench()
    assert b._only_index(["bench.py", "--ab", "--only", "2"]) == 2
    assert b._only_index(["bench.py", "--ab"]) is None
    assert b._only_index(["bench.py", "--only"]) is None  # missing operand


def test_peak_flops_lookup():
    b = _bench()
    assert b._peak_flops("TPU v5 lite") == 197e12
    assert b._peak_flops("TPU v4") == 275e12
    assert b._peak_flops("cpu") is None
    with pytest.raises(ValueError, match="some future chip"):
        b._peak_flops("some future chip")


def test_record_baseline_stamps_date_and_chip(tmp_path):
    b = _bench()
    p = str(tmp_path / "b.json")
    baselines = {}
    b._record_baseline(baselines, p, "tpu", "cfgA", 100.0, chip="TPU v5 lite")
    rec = baselines["tpu"]["cfgA"]
    assert rec["chip"] == "TPU v5 lite"
    assert len(rec["recorded"]) == 10  # YYYY-MM-DD
