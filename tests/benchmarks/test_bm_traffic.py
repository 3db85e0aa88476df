"""The traffic generator repeats from a seed, differs across seeds in the
tokens alone, and gives every seed the same sizes in the same order;
percentile arithmetic."""

import numpy as np
import pytest

from benchmarks import arch as A
from benchmarks import stats
from benchmarks.traffic import generator

BIG = 2**31 + 12345  # the driver's seeds run past 32 signed bits
DECODE = A.load_json("workloads", "mistral7b.decode.json")["traffic"]
OPEN = {"kind": "open_loop", "population": 200, "population_seed": 3,
        "rate_per_s": 4.0, "arrival_gap": {"dist": "exponential"},
        "prompt_len": {"dist": "log_normal", "median": 192, "sigma": 0.9,
                       "lo": 32, "hi": 1536},
        "output_len": {"dist": "log_normal", "median": 64, "sigma": 0.7,
                       "lo": 16, "hi": 384},
        "max_total": 2048}


@pytest.mark.parametrize("mix", [DECODE, OPEN], ids=["closed", "open"])
def test_requests_repeat_from_a_seed_and_differ_across_seeds(mix):
    a = generator.requests(mix, 32000, BIG)
    b = generator.requests(mix, 32000, BIG)
    c = generator.requests(mix, 32000, BIG + 1)
    assert a == b
    assert [r["tokens"] for r in a] != [r["tokens"] for r in c]
    assert len(a) == mix["population"]


@pytest.mark.parametrize("mix", [DECODE, OPEN], ids=["closed", "open"])
def test_every_seed_gets_the_same_sizes_in_the_same_order(mix):
    a = generator.requests(mix, 32000, 1)
    c = generator.requests(mix, 32000, BIG)
    sizes = lambda reqs: [(len(r["tokens"]), r["max_new_tokens"], r["due_s"])
                          for r in reqs]
    assert sizes(a) == sizes(c)
    assert len(set(sizes(a))) > len(a) // 2   # a mix, not one size


@pytest.mark.parametrize("mix", [DECODE, OPEN], ids=["closed", "open"])
def test_another_population_seed_is_another_mix(mix):
    a = generator.requests(mix, 32000, 1)
    b = generator.requests({**mix, "population_seed": 99}, 32000, 1)
    assert [len(r["tokens"]) for r in a] != [len(r["tokens"]) for r in b]
    assert sorted(r["due_s"] is None for r in a) == \
        sorted(r["due_s"] is None for r in b)


def test_closed_loop_lengths_and_first_wave():
    reqs = generator.requests(DECODE, 32000, BIG)
    lo, hi = DECODE["prompt_len"]["lo"], DECODE["prompt_len"]["hi"]
    assert all(lo <= len(r["tokens"]) <= hi for r in reqs)
    assert all(1 <= r["max_new_tokens"] <= DECODE["output_len"]["hi"] for r in reqs)
    assert all(r["due_s"] is None for r in reqs)
    assert all(0 <= t < 32000 for r in reqs[:5] for t in r["tokens"])
    # the first wave is scaled down, so it retires from the first second on
    wave = [r["max_new_tokens"] for r in reqs[:DECODE["first_wave"]]]
    assert min(wave) < DECODE["output_len"]["lo"]


def test_open_loop_arrivals():
    reqs = generator.requests(OPEN, 50257, BIG)
    due = [r["due_s"] for r in reqs]
    assert due[0] == 0.0 and due == sorted(due)
    assert abs(due[-1] / (len(due) - 1) - 1 / OPEN["rate_per_s"]) < 0.1
    assert all(len(r["tokens"]) + r["max_new_tokens"] <= 2048 for r in reqs)


@pytest.mark.parametrize("dist", [
    {"dist": "uniform", "lo": 2, "hi": 9},
    {"dist": "log_uniform", "lo": 2, "hi": 900},
    {"dist": "log_normal", "median": 64, "sigma": 0.7, "lo": 16, "hi": 384},
    {"dist": "exponential", "mean": 3.0}], ids=lambda d: d["dist"])
def test_distributions(dist):
    v = generator.draw(dist, 4000, np.random.RandomState(0))
    assert v.shape == (4000,) and (v >= 0).all()
    if "mean" in dist:
        assert abs(v.mean() / dist["mean"] - 1) < 0.2
    if "lo" in dist:
        assert dist["lo"] <= v.min() and v.max() <= dist["hi"]
    if "median" in dist:
        assert abs(np.median(v) / dist["median"] - 1) < 0.1
    with pytest.raises(ValueError):
        generator.draw({"dist": "nope"}, 1, np.random.RandomState(0))


def test_train_batches_differ_by_row_step_and_seed():
    mix = {"seq": 16}
    a = generator.train_batch(mix, 100, BIG, 0, 4)
    assert a["tokens"].shape == (4, 16) and a["targets"].shape == (4, 16)
    assert (a["tokens"][:, 1:] == a["targets"][:, :-1]).all()
    assert len({tuple(r) for r in a["tokens"]}) == 4
    b = generator.train_batch(mix, 100, BIG, 1, 4)
    c = generator.train_batch(mix, 100, BIG + 1, 0, 4)
    again = generator.train_batch(mix, 100, BIG, 0, 4)
    assert (a["tokens"] == again["tokens"]).all()
    assert (a["tokens"] != b["tokens"]).any() and (a["tokens"] != c["tokens"]).any()


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (90, 4.6), (100, 5.0)])
def test_percentile_matches_numpy(q, want):
    v = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(v, q) == pytest.approx(want)
    assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_spread_and_lateness():
    v = [10.0, 10.2, 9.9, 10.1, 10.0, 10.3]
    import statistics
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert stats.iqr_spread(v) == pytest.approx((q3 - q1) / statistics.median(v))
    late = stats.lateness([0.0, 1.0, 2.0], [0.0, 1.5, 1.9])
    assert late == {"n": 3, "median_s": 0.0, "max_s": 0.5}
    with pytest.raises(ValueError):
        stats.percentile([], 50)
