"""The arithmetic behind the seven metrics of PR 36
(benchmarks/layer_metrics/idle_by_phase.py and the metric files that call
it): the device's idle time credited to the innermost open span, the
capture's start found from the fetches, the order that physics demands, and
the stall rule, all on synthetic span and event lists."""

import itertools

import pytest

from benchmarks import harness
from benchmarks.layer_metrics import idle_by_phase as I
from torchdistpackage_tpu.serving.tracing import stalls
from torchdistpackage_tpu.utils.profiling import spans

MS = 1e-3
IDLE_METRICS = tuple(f"idle_{c}_ms.batch" for c in I.CLASSES)
STALL_METRICS = ("stall_fetch_s.batch", "stall_host_s.batch")
T = "tdp:engine."


def _tick(b, call, *, prefill=False):
    """One tick of an engine without ``run_ahead`` that opens at ``b``
    (seconds): its spans as (name, start, end, attrs), the execution its
    decode call made and that execution's operations (a 0.5 ms hole in the
    middle).  The device idles from 7.5 ms into a tick to 1.5 ms into the
    next: delivery 0.5, absorb 0.6 around a collection of 0.4, record 0.8,
    the tick's own tail 0.2, the caller 2.0, audit 0.2, sched 0.1, build
    0.7, launch 0.5."""
    def at(name, s, e, **attrs):
        return (name, b + s * MS, b + e * MS, attrs)

    host = [at(T + "tick", 0, 10), at(T + "audit", 0, .2),
            at(T + "sched", .2, .3), at(T + "build", .3, 1),
            at(T + "decode", 1, 2, call=call), at(T + "fetch", 2, 8, call=call),
            at(T + "absorb", 8, 9), at("tdp:host.gc", 8.2, 8.6, generation=0),
            at(T + "record", 9, 9.8)]
    if prefill:   # the dispatch span alone: the kind of the tick
        host.append(at(T + "prefill", .3, .3, call=call - 1, calls=0))
    run = ("jit_step", b + 1.5 * MS, 6 * MS)
    ops = [("%fusion.1 = f32[] fusion()", b + 1.5 * MS, 2.5 * MS),
           ("%fusion.2 = f32[] fusion()", b + 4.5 * MS, 3 * MS)]
    return host, run, ops


def _three_ticks(shift=0.0, **kw):
    """Three such ticks 12 ms apart; the host's spans moved by ``shift``."""
    host, runs, ops = [], [], []
    for k in range(3):
        h, r, o = _tick(1.0 + 12 * MS * k, 7 + k, **kw)
        host += [(n, s + shift, e + shift, a) for n, s, e, a in h]
        runs.append(r)
        ops += o
    return host, runs, ops


WANT_MS = {"in_program": .5, "launch": .5, "deliver": .5, "engine": 3.0,
           "caller": 2.0}


# ---------------------------------------------------------- the arithmetic


def test_innermost_span_wins_and_a_gap_splits_at_every_boundary():
    host, runs, ops = _three_ticks()
    got = I.idle_by_phase(runs, ops, host, 1.0, 1.0 + 36 * MS)
    for cls, ms in WANT_MS.items():
        assert got[cls] == pytest.approx(3 * ms * MS), cls
    assert got["idle_s"] == pytest.approx(3 * 6.5 * MS)
    assert got["ticks"] == {I.DECODE_ONLY: 3, I.WITH_PREFILL: 0}
    names = got["by_name"][I.DECODE_ONLY]
    # the gap from one execution's end to the next one's start crosses the
    # tick's end: fetch, absorb, the collection INSIDE absorb, record, the
    # tick's own tail, the caller, and the next tick's audit, sched, build
    # and dispatch, each credited what it covers
    want = {T + "fetch": .5, T + "absorb": .6, "tdp:host.gc": .4,
            T + "record": .8, T + "tick": .2, I.CALLER: 2.0, T + "audit": .2,
            T + "sched": .1, T + "build": .7, T + "decode": .5,
            I.IN_PROGRAM: .5}
    assert set(names) == set(want)
    for name, ms in want.items():
        assert names[name] == pytest.approx(3 * ms * MS), name
    assert got["by_name"][I.WITH_PREFILL] == {}


def test_the_five_classes_sum_to_the_window_less_the_busy_time():
    from benchmarks import trace_reduce as R

    host, runs, ops = _three_ticks()
    t0, t1 = 1.0 + 3 * MS, 1.0 + 31 * MS   # cuts two executions' tails off
    got = I.idle_by_phase(runs, ops, host, t0, t1)
    busy = R.busy_seconds(R.clip(ops, t0, t1))
    assert got["idle_s"] == pytest.approx((t1 - t0) - busy)
    assert sum(got["ticks"].values()) == 1   # one tick lies wholly inside


def test_ticks_with_a_prefill_call_are_kept_apart():
    host, runs, ops = _three_ticks(prefill=True)
    got = I.idle_by_phase(runs, ops, host, 1.0, 1.0 + 36 * MS)
    assert got["ticks"] == {I.DECODE_ONLY: 0, I.WITH_PREFILL: 3}
    assert got["by_name"][I.DECODE_ONLY] == {}
    assert got["by_name"][I.WITH_PREFILL][I.CALLER] == pytest.approx(6 * MS)


def test_an_execution_with_holes_reads_idle_in_program():
    host, runs, ops = _three_ticks()
    ops = [o for o in ops if "fusion.2" not in o[0]]   # 3.5 ms of hole each
    got = I.idle_by_phase(runs, ops, host, 1.0, 1.0 + 36 * MS)
    assert got["in_program"] == pytest.approx(3 * 3.5 * MS)
    assert got["launch"] == pytest.approx(3 * .5 * MS)   # the rest unmoved


@pytest.mark.parametrize("shift_ms, ok", [
    (0.0, True), (0.55, True), (0.65, False), (-0.55, True), (-0.65, False)])
def test_broken_order_gives_no_number(shift_ms, ok):
    """The host's spans on a clock that is off: past the launch's 0.5 ms +
    slack a dispatch opens after its execution began, past the delivery's
    0.5 ms + slack (the other way) a fetch ends before its execution."""
    host, runs, ops = _three_ticks(shift=shift_ms * MS)
    got = I.idle_by_phase(runs, ops, host, 1.0, 1.0 + 36 * MS)
    assert (got is not None) == ok


def test_a_dispatch_without_its_call_or_an_unknown_program_gives_none():
    host, runs, ops = _three_ticks()
    bare = [(n, s, e, {k: v for k, v in a.items() if k != "call"})
            for n, s, e, a in host]
    assert I.idle_by_phase(runs, ops, bare, 1.0, 1.0 + 36 * MS) is None
    # a fourth execution that no span dispatched
    more = runs + [("jit_cow", 1.0 + 35 * MS, .5 * MS)]
    assert I.idle_by_phase(more, ops, host, 1.0, 1.0 + 36 * MS) is None
    assert I.idle_by_phase([], ops, host, 1.0, 1.0 + 36 * MS) is None


def _run_ahead(n=4, period=10.0, zero=0.0):
    """``run_ahead``: tick k dispatches call k and THEN fetches call k-1,
    whose execution ended 0.1 ms before that fetch returns; executions
    follow each other without a gap.  Host spans on a clock ``zero`` ahead
    of the device's."""
    host, runs = [], []
    for k in range(n):
        b = 2.0 + period * MS * k
        host += [(T + "tick", b, b + 5 * MS, {}),
                 (T + "build", b, b + 1 * MS, {}),
                 (T + "decode", b + 1 * MS, b + 2 * MS, {"call": 100 + k}),
                 (T + "fetch", b + 2 * MS, b + 3 * MS, {"call": 99 + k}),
                 (T + "absorb", b + 3 * MS, b + 4 * MS, {})]
        runs.append(("jit_step", b + 2.9 * MS, period * MS))
    return [(n_, s + zero, e + zero, a) for n_, s, e, a in host], runs


def test_run_ahead_fetches_are_matched_by_call_not_by_tick():
    host, runs = _run_ahead(zero=1234.5)
    calls, fetched = I.calls_of(I.by_tick(host)[1])
    assert [c[0] for c in calls] == [100, 101, 102, 103]
    assert sorted(fetched) == [99, 100, 101, 102]
    matched = I.match(runs, calls)
    # the fetch in tick k+1 waited for the call of tick k: its execution
    # ended 0.1 ms before; the least such delivery is the capture's start
    assert I.trace_zero(matched, fetched) == pytest.approx(1234.5 + .1 * MS)
    on_trace = {c: e - 1234.5 for c, e in fetched.items()}
    moved = [(c, s - 1234.5, e - 1234.5, i) for c, s, e, i in calls]
    assert I.in_order(matched, moved, on_trace)
    # a reader that took the fetch of the SAME tick for the dispatch would
    # have it return 7.1 ms before its execution ends
    guessed = {c + 1: e for c, e in on_trace.items()}
    assert not I.in_order(matched, moved, guessed)


def test_a_prefill_span_of_k_calls_covers_k_executions():
    kids = [[(T + "prefill", 1.0, 1.2, {"calls": 3, "call": 12}),
             (T + "fetch", 1.2, 1.5, {"call": 12}),
             (T + "decode", 1.6, 1.7, {"call": 13}),
             (T + "fetch", 1.7, 1.9, {"call": 13})]]
    calls, fetched = I.calls_of(kids)
    assert [c[0] for c in calls] == [10, 11, 12, 13]
    assert fetched == {12: 1.5, 13: 1.9}
    runs = [("p", 1.01 + .1 * j, .09) for j in range(3)] + [("d", 1.65, .2)]
    assert I.match(runs, calls)[12] == pytest.approx((1.21, 1.30))


# ------------------------------------------------ through the metric files


def _ring_of(host, t_ring0=500.0):
    """Ring records (perf_counter seconds from ``t_ring0``) of host spans
    given on the wall clock from 0: ids in closing order, parents by time."""
    ids = itertools.count(1)
    ticks = [h for h in host if h[0] == T + "tick"]
    out = []
    for t in ticks:
        tid = next(ids)
        out += [(next(ids), tid, n, s + t_ring0, e + t_ring0, a)
                for n, s, e, a in host
                if n != T + "tick" and t[1] <= s and e <= t[2]]
        out.append((tid, None, t[0], t[1] + t_ring0, t[2] + t_ring0, t[3]))
    return out


@pytest.fixture
def traced_run():
    """Three ticks in the ring on ``perf_counter``, anchored to a wall clock
    1.79e18 ns ahead, and a trace whose clock started 0.75 s before the
    first tick: what a traced run of a cell hands the readers."""
    host, runs, ops = _three_ticks()
    wall0 = 1_790_000_000 * 10**9
    spans.clear()
    kept = list(spans.anchors)
    spans.anchors.clear()
    spans.anchors.extend([(400.0, wall0 - 100 * 10**9),
                          (600.0, wall0 + 100 * 10**9)])
    spans.extend(_ring_of(host))
    started = 0.25   # of the wall clock's seconds since ring time 500
    obs = {"spans": {"engine_step": [0.0] * 3}, "values": {}, "costs": {},
           "peaks": {},
           "trace": {"window_s": 36 * MS, "busy_s": 3 * 5.5 * MS,
                     "events": {"/device:TPU:0": [
                         (n, s - started, d) for n, s, d in ops]},
                     "modules": [(n, s - started, d) for n, s, d in runs]}}
    yield obs
    spans.clear()
    spans.anchors.clear()
    spans.anchors.extend(kept)


def test_a_traced_run_reads_the_five_per_tick_and_they_sum_to_the_idle(traced_run):
    got = {m: harness.read_layer_metric(m, traced_run) for m in IDLE_METRICS}
    # the capture's start is taken for the fastest delivery (0.5 ms here):
    # deliver reads low by it and launch high, the rest as they are
    want = dict(WANT_MS, deliver=0.0, launch=1.0)
    for cls, ms in want.items():
        assert got[f"idle_{cls}_ms.batch"] == pytest.approx(ms, abs=2e-3), cls
    tr = traced_run["trace"]
    assert sum(got.values()) == pytest.approx(
        1e3 * (tr["window_s"] - tr["busy_s"]) / 3, rel=1e-3)
    split = I.traced_split(traced_run)
    assert split["traced_ticks"] == 3
    # least delivery + least launch: how far the two laws leave the zero open
    assert split["zero_bracket_us"] == pytest.approx(1000.0, abs=2.0)


@pytest.mark.parametrize("name", IDLE_METRICS)
def test_nothing_to_read_leaves_an_idle_metric_out(traced_run, name):
    assert harness.read_layer_metric(name, traced_run) is not None
    # an untraced run
    assert harness.read_layer_metric(name, {**traced_run, "trace": None}) is None
    # a parent without anchors
    kept = list(spans.anchors)
    spans.anchors.clear()
    assert harness.read_layer_metric(name, traced_run) is None
    spans.anchors.extend(kept)
    # a ring that has wrapped: its oldest records are gone
    recs = spans.snapshot()
    spans.extend([(0, None, "t:filler", 0.0, 0.0, {})] * spans.maxlen)
    spans.extend(recs)
    assert harness.read_layer_metric(name, traced_run) is None


# ------------------------------------------------------------------ stalls


def _plain(n, tick_ms=20.0, gap_ms=1.0, t0=10.0):
    """n decode-only ticks: (start, end) and children (name, start, end)."""
    ticks, kids = [], []
    for k in range(n):
        b = t0 + k * (tick_ms + gap_ms) * MS
        ticks.append([b, b + tick_ms * MS])
        kids.append([(T + "build", b, b + 1 * MS),
                     (T + "decode", b + 1 * MS, b + 2 * MS),
                     (T + "fetch", b + 2 * MS, b + (tick_ms - 2) * MS),
                     (T + "absorb", b + (tick_ms - 2) * MS, b + tick_ms * MS)])
    return ticks, kids


def _late(ticks, kids, k, seconds):
    """Tick k and all behind it ``seconds`` later: the gap before k grows."""
    for j in range(k, len(ticks)):
        ticks[j] = [ticks[j][0] + seconds, ticks[j][1] + seconds]
        kids[j] = [(n, s + seconds, e + seconds) for n, s, e in kids[j]]


def _slow(ticks, kids, k, child, seconds):
    """``child`` of tick k takes ``seconds`` longer."""
    at = [n for n, _, _ in kids[k]].index(child)
    name, s, e = kids[k][at]
    kids[k][at] = (name, s, e + seconds)
    kids[k][at + 1:] = [(n, a + seconds, b + seconds)
                        for n, a, b in kids[k][at + 1:]]
    ticks[k][1] += seconds
    _late(ticks, kids, k + 1, seconds)


def test_stalls_credit_a_planted_fetch_and_a_planted_gap():
    ticks, kids = _plain(60)
    _slow(ticks, kids, 20, T + "fetch", 0.15)
    _late(ticks, kids, 40, 2.0)
    got = stalls(ticks, kids)
    assert got["slow"] == 2 and got["ticks"] == 60
    assert got["by_part"][T + "fetch"] == pytest.approx(0.15)
    assert got["by_part"]["(between ticks)"] == pytest.approx(2.0)
    assert got["lost_s"] == pytest.approx(2.15)
    # 15 ms more on a 21 ms tick is not a stall: under the 20 ms floor
    ticks, kids = _plain(60)
    _slow(ticks, kids, 30, T + "fetch", 0.015)
    assert stalls(ticks, kids)["slow"] == 0


def test_stalls_judge_a_tick_by_its_own_kind():
    ticks, kids = _plain(40)
    for k in range(0, 40, 4):   # every fourth tick prefills for 95 ms first
        _slow(ticks, kids, k, T + "build", 0.095)
        kids[k].insert(0, (T + "prefill", ticks[k][0], ticks[k][0]))
    assert stalls(ticks, kids)["slow"] == 0
    _slow(ticks, kids, 8, T + "absorb", 0.15)   # a prefill tick
    got = stalls(ticks, kids)
    assert got["slow"] == 1
    assert got["by_part"] == {T + "absorb": pytest.approx(0.15)}


@pytest.mark.parametrize("name, want", [
    ("stall_fetch_s.batch", 0.15), ("stall_host_s.batch", 2.0)])
def test_the_stall_metrics_read_the_windows_ticks_from_the_ring(name, want):
    ticks, kids = _plain(60)
    _slow(ticks, kids, 20, T + "fetch", 0.15)
    _late(ticks, kids, 40, 2.0)
    host = [(T + "tick", s, e, {}) for s, e in ticks] + [
        (n, s, e, {}) for ks in kids for n, s, e in ks]
    spans.clear()
    spans.extend(_ring_of(host, t_ring0=0.0))
    obs = {"spans": {"engine_step": [0.0] * 60}, "trace": None}
    try:
        assert harness.read_layer_metric(name, obs) == pytest.approx(want)
        # a traced run stops its profiler behind the capture's end, here in
        # the planted 2 s between ticks 39 and 40: the benchmark's own pause
        # is no stall of the program's
        traced = {**obs, "trace": {"window_s": ticks[39][1] - ticks[0][0]}}
        assert harness.read_layer_metric(name, traced) == pytest.approx(
            want if "fetch" in name else 0.0, abs=1e-9)
        # the set-up's ticks are not the window's: a window of the last 15
        obs["spans"]["engine_step"] = [0.0] * 15
        assert harness.read_layer_metric(name, obs) == 0.0
        spans.clear()
        assert harness.read_layer_metric(name, obs) is None
    finally:
        spans.clear()
