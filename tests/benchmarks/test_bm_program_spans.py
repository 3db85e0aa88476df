"""The readers of the program's own spans and kernel names
(benchmarks/layer_metrics/program_spans.py and the metric files that call
it), on a synthetic ring and a synthetic event list."""

import itertools

import pytest

from benchmarks import harness
from benchmarks import trace_reduce as R
from benchmarks.layer_metrics import program_spans as P
from torchdistpackage_tpu.utils.profiling import spans

SPAN_METRICS = ("prefill_useful_share.batch", "dispatch_ms.batch",
                "tick_gap_ms.batch", "engine_init_s.batch",
                "first_calls_s.batch")
FLASH_METRICS = ("flash_fwd_ms.train", "flash_bwd_dq_ms.train",
                 "flash_bwd_dkv_ms.train")


def _ring(ticks):
    """Records as the engine leaves them: an init with its pool fill, then
    for each ``(t0, [(phase, start, end, attrs), ...], t1)`` the children
    and, after them, the tick."""
    ids = itertools.count(1)
    init, pool = next(ids), next(ids)
    out = [(pool, init, "tdp:engine.init.pool", 0.5, 2.5, {}),
           (init, None, "tdp:engine.init", 0.0, 3.0, {})]
    for n, (t0, kids, t1) in enumerate(ticks, 1):
        tick = next(ids)
        out += [(next(ids), tick, f"tdp:engine.{p}", a, b, attrs)
                for p, a, b, attrs in kids]
        out.append((tick, None, "tdp:engine.tick", t0, t1, {"tick": n}))
    return out


def _decode_tick(t0, dispatch=0.001, wait=0.060, **attrs):
    return (t0, [("audit", t0, t0 + 0.0002, {}),
                 ("sched", t0 + 0.0002, t0 + 0.0003, {}),
                 ("decode", t0 + 0.0010, t0 + 0.0010 + dispatch,
                  {"slots": 64, "rids": [], **attrs}),
                 ("fetch", t0 + 0.0010 + dispatch,
                  t0 + 0.0010 + dispatch + wait, attrs)],
            t0 + 0.0010 + dispatch + wait + 0.0005)


def _prefill_tick(t0, tokens, **attrs):
    """A tick that prefills (0.9 s) and then decodes."""
    _, kids, t1 = _decode_tick(t0 + 0.9, **attrs)
    kids = [k for k in kids if k[0] in ("decode", "fetch")]
    return (t0, [("audit", t0, t0 + 0.0002, {}),
                 ("sched", t0 + 0.0002, t0 + 0.0004, {}),
                 ("prefill", t0 + 0.0020, t0 + 0.0030,
                  {"tokens": tokens, "rows": 64 * 256, "rids": [], **attrs}),
                 ("fetch", t0 + 0.0030, t0 + 0.9, attrs)] + kids, t1)


# set-up: a tick whose prefill and decode calls are their signatures' first,
# and one plain decode tick; then the window: a prefill tick and three
# decode-only ticks
SETUP = [_prefill_tick(10.0, 200, first=True), _decode_tick(30.0)]
WINDOW = [_prefill_tick(100.0, 100), _decode_tick(101.0, dispatch=0.0008),
          _decode_tick(101.064, dispatch=0.0012),
          _decode_tick(101.130, dispatch=0.0020)]


def _obs(steps):
    return {"spans": {"engine_step": [0.0] * steps}, "values": {},
            "costs": {}, "peaks": {}, "trace": None}


@pytest.fixture
def ring():
    spans.clear()
    spans.extend(_ring(SETUP + WINDOW))
    yield spans
    spans.clear()


def test_the_window_is_the_last_n_ticks_with_their_children(ring):
    ticks, kids, before = P.window(_obs(len(WINDOW)))
    assert len(before) == len(_ring(SETUP)) and before[1][2] == "tdp:engine.init"
    assert [t[5]["tick"] for t in ticks] == [3, 4, 5, 6]
    assert [len(k) for k in kids] == [6, 4, 4, 4]
    assert all(k[1] == t[0] for t, ks in zip(ticks, kids) for k in ks)
    # a shorter window takes fewer ticks from the same end
    assert [t[5]["tick"] for t in P.window(_obs(2))[0]] == [5, 6]


def test_prefill_useful_share_counts_tokens_over_rows_in_the_window(ring):
    # the window's one prefill call: 100 real tokens in 16,384 rows; the
    # set-up's 200 tokens are left out
    got = harness.read_layer_metric("prefill_useful_share.batch", _obs(4))
    assert got == pytest.approx(100.0 * 100 / 16384)
    # with the set-up tick inside the window: (100 + 200) over two calls
    assert harness.read_layer_metric(
        "prefill_useful_share.batch", _obs(6)) == pytest.approx(
            100.0 * 300 / (2 * 16384))
    # a window without a prefill call has no share
    assert harness.read_layer_metric(
        "prefill_useful_share.batch", _obs(3)) is None


def test_dispatch_ms_is_the_median_decode_dispatch_of_decode_only_ticks(ring):
    # decode-only ticks of the window: 0.8, 1.2, 2.0 ms; the prefill tick's
    # decode dispatch (1.0 ms) is left out
    assert harness.read_layer_metric(
        "dispatch_ms.batch", _obs(4)) == pytest.approx(1.2)


def test_tick_gap_is_fetch_end_to_next_dispatch_start(ring):
    t = WINDOW
    want = []
    for before, after in zip(t, t[1:]):
        end = max(k[2] for k in before[1] if k[0] == "fetch")
        start = min(k[1] for k in after[1] if k[0] in ("prefill", "decode"))
        want.append((start - end) * 1e3)
    want.sort()
    assert harness.read_layer_metric(
        "tick_gap_ms.batch", _obs(4)) == pytest.approx(want[1])
    # by hand: 39.0 after the prefill tick, then 3.2 and 4.8 ms
    assert want == pytest.approx([3.2, 4.8, 39.0], abs=1e-6)
    # one tick has no tick before it
    assert harness.read_layer_metric("tick_gap_ms.batch", _obs(1)) is None


def test_setup_metrics_read_what_lies_before_the_window(ring):
    assert harness.read_layer_metric(
        "engine_init_s.batch", _obs(4)) == pytest.approx(3.0)
    # the four spans marked first: prefill dispatch 1 ms + its fetch, and
    # the decode dispatch 1 ms + its fetch 60 ms, all in the first tick
    first = [k for k in SETUP[0][1] if k[3].get("first")]
    assert len(first) == 4
    assert harness.read_layer_metric(
        "first_calls_s.batch", _obs(4)) == pytest.approx(
            sum(k[2] - k[1] for k in first))
    # a first call INSIDE the window is a recompile, not set-up
    assert harness.read_layer_metric("first_calls_s.batch", _obs(6)) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_nothing_to_read_leaves_the_metric_out(name):
    spans.clear()
    # an empty ring (the train cell runs no engine)
    assert harness.read_layer_metric(name, _obs(4)) is None
    # spans, but no engine's
    spans.append((1, None, "tdp:other", 0.0, 1.0, {}))
    assert harness.read_layer_metric(name, _obs(4)) is None
    # fewer ticks in the ring than steps in the window
    spans.clear()
    spans.extend(_ring(WINDOW))
    assert harness.read_layer_metric(name, _obs(5)) is None
    # no engine step in the window (a runner without one)
    assert harness.read_layer_metric(
        name, {**_obs(0), "spans": {}}) is None
    spans.clear()


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_wrapped_ring_leaves_the_metric_out(name, monkeypatch):
    small = type(spans)(maxlen=len(_ring(SETUP + WINDOW)))
    small.extend(_ring(SETUP + WINDOW))   # full: its oldest may be gone
    import torchdistpackage_tpu.utils.profiling as prof

    monkeypatch.setattr(prof, "spans", small)
    assert harness.read_layer_metric(name, _obs(4)) is None
    roomy = type(spans)(maxlen=len(small) + 1)
    roomy.extend(small)
    monkeypatch.setattr(prof, "spans", roomy)
    assert harness.read_layer_metric(name, _obs(4)) is not None


def test_a_program_without_the_ring_leaves_the_metric_out(monkeypatch):
    """The parent commit has no ``spans``: the readers return nothing."""
    import torchdistpackage_tpu.utils.profiling as prof

    monkeypatch.delattr(prof, "spans")
    assert P.ring() is None
    for name in SPAN_METRICS:
        assert harness.read_layer_metric(name, _obs(4)) is None


# ------------------------------------------------------------ kernel names


def _hlo(name, op="custom-call"):
    return (f"%{name} = bf16[8,128]{{1,0:T(8,128)(2,1)}} {op}(bf16[8,128]{{1,0}} "
            f"%x), custom_call_target=\"tpu_custom_call\"")


def _train_trace():
    """Two executions of the step ([0,1) and [1,2)), each with two layers'
    flash kernels, a fusion, and the scan's while around them; one more
    program that ran once and holds a kernel of its own."""
    ev = []
    for step in (0.0, 1.0):
        ev.append((_hlo("while.17", "while"), step, 0.9))
        for layer in (0, 1):
            t = step + 0.4 * layer
            ev += [(_hlo("flash_fwd.7"), t, 0.023),
                   (_hlo("fusion.3", "fusion"), t + 0.03, 0.1),
                   (_hlo("flash_bwd_dq.9"), t + 0.15, 0.025),
                   (_hlo("flash_bwd_dkv.9"), t + 0.2, 0.032)]
    ev.append((_hlo("flash_fwd.2"), 2.5, 0.5))   # in another program
    return {"events": {"/device:TPU:0": ev},
            "modules": [("jit_step(1)", 0.0, 1.0), ("jit_step(1)", 1.0, 1.0),
                        ("jit_other(2)", 2.4, 0.7)]}


def test_flash_metrics_split_the_custom_call_time_by_kernel():
    tr = _train_trace()
    obs = {**_obs(0), "trace": tr}
    got = {n: harness.read_layer_metric(n, obs) for n in FLASH_METRICS}
    assert got == {"flash_fwd_ms.train": pytest.approx(46.0),
                   "flash_bwd_dq_ms.train": pytest.approx(50.0),
                   "flash_bwd_dkv_ms.train": pytest.approx(64.0)}
    # together: every custom-call of the step's executions, a step, which is
    # the time behind flash_roofline.train
    ev = tr["events"]["/device:TPU:0"]
    inside = R.within(ev, R.union([(0.0, 1.0), (1.0, 2.0)]))
    assert sum(got.values()) == pytest.approx(
        1e3 * R.op_seconds(inside, r" custom-call\(") / 2)


def test_kernel_patterns_match_the_name_and_nothing_longer():
    ev = [(_hlo("flash_bwd_dq.9"), 0.0, 0.1), (_hlo("flash_bwd_dq"), 0.2, 0.1),
          (_hlo("flash_bwd_dqx.1"), 0.4, 0.1), (_hlo("flash_fwd_2.1"), 0.6, 0.1)]
    obs = {**_obs(0), "trace": {"events": {"d": ev},
                                "modules": [("jit_step(1)", 0.0, 1.0)]}}
    assert harness.read_layer_metric(
        "flash_bwd_dq_ms.train", obs) == pytest.approx(200.0)
    assert harness.read_layer_metric("flash_fwd_ms.train", obs) is None


@pytest.mark.parametrize("name", FLASH_METRICS + ("paged_decode_roofline.batch",))
def test_a_trace_without_the_kernels_name_leaves_the_metric_out(name):
    """The parent commit's trace calls its kernels ``closed_call`` and
    ``checkpoint``; an untraced run has no trace at all."""
    ev = [(_hlo("closed_call.14"), 0.1, 0.2), (_hlo("checkpoint.19"), 0.4, 0.2)]
    one = {"flops": 1e9, "bytes": 1e9, "calls_per_execution": 16}
    obs = {**_obs(0), "costs": {"paged_decode": one},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"events": {"d": ev},
                     "modules": [("jit_step(1)", 0.0, 1.0)]}}
    assert harness.read_layer_metric(name, obs) is None
    assert harness.read_layer_metric(name, {**obs, "trace": None}) is None


def test_paged_decode_roofline_reads_the_decode_kernel_alone():
    """With the chunk kernel in the same program, the named pattern keeps
    the decode kernel's time; the unnamed one adds both."""
    peaks = harness.peaks_for("TPU v5 lite")
    # a call that the memory bound puts at 1 ms, two calls an execution
    one = {"flops": 1e6, "bytes": 1e-3 * peaks["hbm_bytes_per_s"],
           "calls_per_execution": 2}
    ev = [(_hlo("paged_decode.3"), 0.1, 0.004), (_hlo("paged_decode.3"), 0.2, 0.004),
          (_hlo("paged_chunk.5"), 0.3, 0.1)]
    obs = {**_obs(0), "costs": {"paged_decode": one}, "peaks": peaks,
           "trace": {"events": {"d": ev},
                     "modules": [("jit_step(1)", 0.0, 1.0)]}}
    named = harness.read_layer_metric("paged_decode_roofline.batch", obs)
    unnamed = harness.read_layer_metric("paged_roofline.batch", obs)
    assert named == pytest.approx(100.0 * 0.002 / 0.008)
    assert unnamed == pytest.approx(100.0 * 0.002 / 0.108)
