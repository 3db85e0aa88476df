"""costs.py against hand-worked numbers for both configurations."""

import pytest

from benchmarks import arch as A
from benchmarks import costs


def arch_of(name, seq):
    cfg = A.load_config(name)
    return A.family_of(cfg).arch(cfg, seq)


def test_cerebras_parameters_and_train_flops():
    a = arch_of("cerebras-gpt-1.3b", 2048)
    # per layer: qkv 3*2048^2, wo 2048^2, mlp 2*2048*8192; head 2048*50257
    mm = 24 * (4 * 2048**2 + 2 * 2048 * 8192) + 2048 * 50257
    assert a.matmul_params() == mm == 1_310_885_888
    assert a.num_params() == 1_418_649_600
    # causal attention at half: mean context (2048 + 1) / 2
    assert costs.mean_context(2048, None) == pytest.approx(1024.5)
    want = 6 * mm + 12 * 24 * 1024.5 * 2048
    assert costs.train_flops_per_token(a, 2048) == pytest.approx(want)
    assert want == pytest.approx(8.4696e9, rel=1e-4)


def test_mistral_parameters_and_window():
    a = arch_of("mistral-7b-v0.1", 1024)
    per_layer = 2 * 4096**2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    mm = 16 * per_layer + 4096 * 32000
    assert a.matmul_params() == mm == 3_620_732_928
    assert a.gqa and a.window == 4096
    # a window wider than the sequence never binds
    assert costs.mean_context(1024, 4096) == pytest.approx(512.5)
    # window 4 over 8 positions: 1,2,3,4,4,4,4,4 keys
    assert costs.mean_context(8, 4) == pytest.approx(26 / 8)


def test_flash_and_paged_kernel_costs_and_rooflines():
    peak = A.load_json("peaks.json")["TPU v5 lite"]
    a = arch_of("cerebras-gpt-1.3b", 2048)
    f = costs.flash_fwd_bwd(a, 4, 2048)
    one = 2 * 4 * 16 * 2048 * 1024.5 * 128
    assert f["flops"] == pytest.approx(7 * one)
    q = 4 * 16 * 2048 * 128 * 2
    assert f["bytes"] == pytest.approx(12 * q)  # MHA: k, v as large as q
    r = costs.roofline_seconds(f, peak)
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(7 * one / 197e12)
    m = arch_of("mistral-7b-v0.1", 1024)
    p = costs.paged_decode(m, live_tokens=64 * 400, slots=64)
    assert p["bytes"] == pytest.approx(2 * 25600 * 8 * 128 * 2 + 2 * 64 * 32 * 128 * 2)
    assert p["flops"] == pytest.approx(4 * 25600 * 32 * 128)
    r = costs.roofline_seconds(p, peak)
    assert r["bound"] == "memory"
    assert r["seconds"] == pytest.approx(p["bytes"] / 819e9)


def test_a_device_kind_without_a_row_is_an_error():
    from benchmarks import harness

    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        harness.peaks_for("source")


def test_roofline_reader_counts_calls_from_the_program_not_from_events():
    from benchmarks.layer_metrics import readers

    kernel = "%closed_call.2 = bf16[8]{0} custom-call(bf16[8]{0} %x)"
    obs = {
        "peaks": {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0},
        # one call: 100 operations = 1 s (compute bound); two calls a run
        "costs": {"k": {"flops": 100.0, "bytes": 1.0, "calls_per_execution": 2}},
        "trace": {"window_s": 20.0, "events": {"d0": [
            (kernel, 1.0, 2.0), (kernel, 4.0, 2.0), (kernel, 11.0, 4.0),
            (kernel, 30.0, 5.0)]},
            "modules": [("jit_step(1)", 0.0, 8.0), ("jit_step(1)", 10.0, 8.0),
                        ("jit_other(2)", 19.0, 1.0)]}}
    # two executions x two calls x 1 s = 4 s at least; they took 2 + 2 + 4
    assert readers.roofline(obs, r" custom-call\(", "k") == pytest.approx(50.0)
    assert readers.roofline({**obs, "trace": None}, "x", "k") is None
    assert readers.mfu({"values": {"tokens_per_s_per_chip": 10.0,
                                   "flops_per_token": 2.0,
                                   "peak_flops": 100.0}}) == pytest.approx(20.0)


def test_the_small_readers():
    from benchmarks.layer_metrics import readers

    fusion = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop"
    reduce_ = "%all-reduce.1 = bf16[8]{0} all-reduce(bf16[8]{0} %g)"
    obs = {"spans": {"a": [0.001, 0.003, 0.002]}, "values": {"n": 6.0, "d": 3.0},
           "trace": {"window_s": 10.0, "events": {"d0": [
               (fusion, 0.0, 2.0), (reduce_, 1.0, 3.0)]}, "modules": []}}
    assert readers.span_median(obs, "a", 1000.0) == pytest.approx(2.0)
    assert readers.span_percentile(obs, "a", 100, 1000.0) == pytest.approx(3.0)
    assert readers.span_median(obs, "missing") is None
    assert readers.value(obs, "n", 0.5) == 3.0 and readers.value(obs, "x") is None
    assert readers.ratio(obs, "n", "d", 100.0) == pytest.approx(200.0)
    assert readers.ratio(obs, "n", "zero") is None
    # the all-reduce runs [1, 4); compute covers [0, 2): two seconds exposed
    assert readers.exposed_collectives(obs) == pytest.approx(20.0)
