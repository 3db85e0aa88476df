"""The one span primitive (utils/profiling.py) and what the serving engine
records with it: ``tdp:engine.*`` spans on the profiler's clock (the ring's
anchors put a record there), the tick phases summed from them, the tick's
host work between them covered, a collection as a span, the prefill waste
counted where it happens, the ``first`` mark of a compiling call, the
``call`` that ties a fetch to its dispatch, and a stable name on every
Pallas kernel."""

import gc
import glob
import importlib
import pathlib
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from torchdistpackage_tpu.models import GPTConfig, init_gpt_params
from torchdistpackage_tpu.serving import Request, ServingEngine
from torchdistpackage_tpu.serving.engine import PREFILL_WIDTH
from torchdistpackage_tpu.serving.tracing import TICK_PHASES
from torchdistpackage_tpu.utils import span, spans

CFG = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=64,
                ffn_mult=2, dtype=jnp.float32)
SLOTS, CHUNK = 4, 8
PHASE_SPANS = {f"tdp:engine.{p}" for p in TICK_PHASES if p != "host"}
#: the tick's host work outside the phases: children of the tick that divide
#: ``host`` in the ring and are no phase of their own
COVER_SPANS = {"tdp:engine.build", "tdp:engine.absorb", "tdp:engine.record"}


@pytest.fixture(scope="module")
def params():
    return init_gpt_params(jax.random.PRNGKey(0), CFG)


def _engine(params, **kw):
    return ServingEngine(params, CFG, num_slots=SLOTS, block_size=8,
                         chunk=CHUNK, max_ctx=64, **kw)


def _by_name(records, name):
    return [r for r in records if r[2] == name]


def _kids(ring, tick):
    """The engine's child spans of ``tick`` in time order (a collection
    that ran right under the tick is the interpreter's, not the engine's)."""
    return sorted((r for r in ring if r[1] == tick[0]
                   and r[2].startswith("tdp:engine.")), key=lambda r: r[3])


# ------------------------------------------------------------ the primitive


def test_span_records_parent_attrs_and_survives_its_opener():
    spans.clear()
    with span("t:outer", tick=7) as outer:
        with span("t:inner") as inner:
            inner.attrs["rows"] = 32   # attrs may be filled until the close
    del outer, inner
    (i_rec, o_rec) = spans.snapshot()   # closed in order: inner first
    assert i_rec[2:3] + o_rec[2:3] == ("t:inner", "t:outer")
    assert i_rec[1] == o_rec[0] and o_rec[1] is None
    assert o_rec[5] == {"tick": 7} and i_rec[5] == {"rows": 32}
    assert o_rec[3] <= i_rec[3] <= i_rec[4] <= o_rec[4]
    spans.clear()
    assert spans.snapshot() == []


def test_span_ring_is_bounded_and_parents_are_per_thread():
    assert spans.maxlen == 1 << 17
    spans.clear()
    seen = {}

    def other():
        with span("t:thread") as sp:
            seen["parent"] = sp.parent

    with span("t:main"):
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=30)
    assert not th.is_alive()
    assert seen["parent"] is None   # the main thread's open span is not its parent
    assert {r[2]: r[1] for r in spans.snapshot()} == {
        "t:thread": None, "t:main": None}


def test_trace_annotation_has_one_owner_in_the_package():
    """``span`` is the one way the package opens a host span."""
    root = pathlib.Path(__file__).resolve().parent.parent / "torchdistpackage_tpu"
    users = [str(p.relative_to(root)) for p in root.rglob("*.py")
             if "TraceAnnotation(" in p.read_text()]
    assert users == ["utils/profiling.py"]


# ------------------------------------------------------------- the engine


def test_engine_spans_lie_on_the_profilers_clock(params, tmp_path):
    """A real ``jax.profiler`` capture around three ticks: the host plane
    holds each ``tdp:engine.tick`` with its phases inside it in time, and
    every event's duration agrees with the ring's span to 1 ms: one clock.
    And the ring's anchors put every record's START on that clock: the
    profiler writes the wall clock less the capture's own start (the
    ``profile_start_time`` of the trace's ``Task Environment`` plane)."""
    from jax.profiler import ProfileData

    eng = _engine(params)
    eng.submit(Request(tokens=[1, 2, 3, 4, 5], max_new_tokens=8))
    for _ in range(2):   # both compile outside the capture, and from the
        eng.step()       # third tick on a decode call is in flight
    assert eng.run_ahead and eng._flight is not None
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    ring = [r for r in spans.snapshot() if r[2].startswith("tdp:engine.")]
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    planes = list(ProfileData.from_file(path).planes)
    (started,) = [dict(p.stats)["profile_start_time"] for p in planes
                  if p.name == "Task Environment"]
    traced = [(e.name, e.start_ns, e.duration_ns)
              for plane in planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for e in line.events
              if e.name.startswith("tdp:engine.")]
    traced.sort(key=lambda e: (e[1], -e[2]))   # by start, a parent first
    ring.sort(key=lambda r: (r[3], -r[4]))
    assert [e[0] for e in traced] == [r[2] for r in ring]
    assert [e[0] for e in traced].count("tdp:engine.tick") == 3
    for (name, start_ns, dur_ns), rec in zip(traced, ring):
        assert abs(dur_ns * 1e-9 - (rec[4] - rec[3])) < 1e-3, name
        assert abs(spans.to_trace_clock(rec[3]) - started - start_ns) < 1e6, name
    ticks = [e for e in traced if e[0] == "tdp:engine.tick"]
    for _, t0, dur in ticks:
        inside = {e[0] for e in traced
                  if e[0] != "tdp:engine.tick"
                  and t0 <= e[1] and e[1] + e[2] <= t0 + dur}
        assert inside == COVER_SPANS | {
            "tdp:engine.audit", "tdp:engine.sched", "tdp:engine.decode",
            "tdp:engine.fetch"}
    # every phase event lies inside some tick of the trace
    for name, s, d in traced:
        if name != "tdp:engine.tick":
            assert any(t0 <= s and s + d <= t0 + dur for _, t0, dur in ticks)


def test_tick_children_do_not_overlap_and_phases_are_their_sums(params):
    spans.clear()
    eng = _engine(params, spec_k=2)   # the speculative tick drafts too
    eng.submit(Request(tokens=list(range(1, 13)), max_new_tokens=6))
    eng.run_until_idle(max_ticks=50)
    ring = spans.snapshot()
    ticks = _by_name(ring, "tdp:engine.tick")
    assert [t[5]["tick"] for t in ticks] == [
        r["tick"] for r in eng.tick_records]
    seen = set()
    for tick, rec in zip(ticks, eng.tick_records):
        kids = _kids(ring, tick)
        assert {k[2] for k in kids} <= PHASE_SPANS | COVER_SPANS
        seen |= {k[2] for k in kids}
        for k in kids:
            assert tick[3] <= k[3] <= k[4] <= tick[4]
        for a, b in zip(kids, kids[1:]):
            assert a[4] <= b[3]
        sums = dict.fromkeys(TICK_PHASES, 0.0)
        for k in kids:
            if k[2] in PHASE_SPANS:
                sums[k[2].rpartition(".")[2]] += k[4] - k[3]
        assert set(rec["phases"]) == set(TICK_PHASES)
        for p in TICK_PHASES:
            if p != "host":
                assert rec["phases"][p] == pytest.approx(sums[p], abs=1e-8)
        named = sum(v for p, v in rec["phases"].items() if p != "host")
        assert rec["phases"]["host"] == pytest.approx(
            rec["tick_s"] - named, abs=1e-8)
        assert rec["t_start"] == tick[3] and rec["t_end"] <= tick[4]
    assert seen == PHASE_SPANS | COVER_SPANS
    # the engine_tick event carries the children that had closed when it
    # was emitted (inside ``record``), measured
    # (the default event log is the process's: this engine's ticks only)
    ev = [e for e in eng._ev.as_list() if e.get("kind") == "engine_tick"
          and e["t_start"] >= ticks[0][3]]
    by_tick = {t[5]["tick"]: t for t in ticks}
    assert ev
    for e in ev:
        kids = [k for k in _kids(ring, by_tick[e["tick"]])
                if k[2] != "tdp:engine.record"]
        assert [sp for sp in e["spans"] if sp[0].startswith("tdp:engine.")
                ] == [[k[2], k[3], k[4]] for k in kids]


def test_tick_phases_are_what_they_were_and_the_children_cover_the_tick(params):
    """``build``, ``absorb`` and ``record`` divide the remainder ``host`` in
    the ring only: the phases, their names and ``host`` = the tick outside
    the six measured phases stay.  With them the tick's children leave a
    small remainder: the median tick is covered to 90% even at this toy
    size, where a tick is a millisecond (97% and more in a cell's)."""
    assert TICK_PHASES == ("audit", "sched", "prefill", "draft", "decode",
                           "fetch", "host")
    spans.clear()
    eng = _engine(params)
    for n in (5, 11, 3):
        eng.submit(Request(tokens=list(range(1, n + 1)), max_new_tokens=12))
    eng.run_until_idle(max_ticks=60)
    ring = spans.snapshot()
    covered = []
    for tick, rec in zip(_by_name(ring, "tdp:engine.tick"), eng.tick_records):
        kids = _kids(ring, tick)
        assert list(rec["phases"]) == list(TICK_PHASES)
        named = sum(k[4] - k[3] for k in kids if k[2] in PHASE_SPANS)
        assert rec["phases"]["host"] == pytest.approx(
            rec["tick_s"] - named, abs=1e-8)
        # the three lie in the remainder, between the phases, in this order
        cover = [k[2].rpartition(".")[2] for k in kids if k[2] in COVER_SPANS]
        assert cover[-1] == "record" and cover.count("record") == 1
        # a decode call built finds nobody decoding in the first wave's
        # ticks (a prompt's last slice is fetched behind it): no absorb
        assert 1 <= cover.count("absorb") <= cover.count("build")
        assert sum(k[4] - k[3] for k in kids if k[2] in COVER_SPANS) <= (
            rec["phases"]["host"] + (tick[4] - rec["t_end"]) + 1e-8)
        covered.append(sum(k[4] - k[3] for k in kids) / (tick[4] - tick[3]))
    covered.sort()
    assert covered[len(covered) // 2] >= 0.90


@pytest.mark.parametrize("run_ahead", [False, None],
                         ids=["serial", "run_ahead"])
def test_a_dispatch_and_the_fetch_that_waits_for_it_share_a_call(params,
                                                                 run_ahead):
    spans.clear()
    eng = _engine(params, run_ahead=run_ahead)
    eng.prefill_width = 2
    for i in range(3):   # three prefilling slots: two calls under one span
        eng.submit(Request(tokens=[1] * (3 + i), max_new_tokens=5))
    eng.run_until_idle(max_ticks=20)
    ring = sorted((r for r in spans.snapshot()
                   if r[2] in ("tdp:engine.prefill", "tdp:engine.decode",
                               "tdp:engine.fetch")), key=lambda r: r[3])
    # a running count of the engine's device calls; a prefill span's is its
    # LAST call's, and every fetch follows the dispatch it names
    assert ring[0][2:3] == ("tdp:engine.prefill",)
    assert (ring[0][5]["calls"], ring[0][5]["call"]) == (2, 2)
    made = eng.stats["prefill_calls"] + eng.stats["decode_steps"]
    disps = [r for r in ring if r[2] != "tdp:engine.fetch"]
    fetches = [r for r in ring if r[2] == "tdp:engine.fetch"]
    assert [d[5]["call"] for d in disps] == sorted(d[5]["call"]
                                                   for d in disps)
    assert disps[-1][5]["call"] == made
    # every call is waited for once, in the device's order
    assert [f[5]["call"] for f in fetches] == [d[5]["call"] for d in disps]
    tick_of = {r[0]: r[5]["tick"] for r in spans.snapshot()
               if r[2] == "tdp:engine.tick"}
    by_call = {d[5]["call"]: d for d in disps}
    lag = set()
    for f in fetches:
        d = by_call[f[5]["call"]]
        assert d[4] <= f[3]
        if d[2] == "tdp:engine.decode":
            lag.add(tick_of[f[1]] - tick_of[d[1]])
        else:   # the prefill calls are fetched in their own tick
            assert tick_of[f[1]] == tick_of[d[1]]
    # run_ahead: a decode call is fetched by the tick AFTER its dispatch,
    # behind that tick's own decode dispatch span
    assert lag == ({1} if eng.run_ahead else {0})
    if not eng.run_ahead:   # serial: dispatch, fetch, dispatch, fetch
        for disp, fetch in zip(ring[::2], ring[1::2]):
            assert fetch[2] == "tdp:engine.fetch" and disp[2] != fetch[2]
            assert disp[5]["call"] == fetch[5]["call"]


# ---------------------------------------------- the ring's clock, collections


def test_anchors_put_a_ring_time_on_the_wall_clock_once_a_second():
    with span("t:first"):
        pass
    n = len(spans.anchors)
    assert n >= 1
    for _ in range(20000):   # ~0.1 s of spans: no anchor each
        with span("t:many"):
            pass
    assert len(spans.anchors) - n <= 2
    t = time.perf_counter()
    wall = time.time_ns()
    assert isinstance(spans.to_trace_clock(t), int)
    assert abs(spans.to_trace_clock(t) - wall) < 1e6
    # between two anchors the line through them, beyond them perf_counter's rate
    kept = list(spans.anchors)
    spans.anchors.clear()
    assert spans.to_trace_clock(t) is None
    spans.anchors.extend([(10.0, 5_000_000_000), (12.0, 7_000_200_000)])
    assert spans.to_trace_clock(11.0) == 6_000_100_000
    assert spans.to_trace_clock(9.0) == 4_000_000_000
    assert spans.to_trace_clock(13.5) == 8_500_200_000
    spans.anchors.clear()
    spans.anchors.extend(kept)
    spans.clear()
    assert len(spans.anchors) == len(kept)   # clear() empties the spans only


def test_a_collection_is_a_span_under_whatever_was_open():
    spans.clear()
    gc.collect()
    (rec,) = _by_name(spans.snapshot(), "tdp:host.gc")
    assert rec[1] is None and rec[5] == {"generation": 2} and rec[3] <= rec[4]
    with span("t:outer") as outer:
        gc.collect(1)
    rec = _by_name(spans.snapshot(), "tdp:host.gc")[-1]
    assert rec[1] == outer.id and rec[5] == {"generation": 1}
    assert outer.t0 <= rec[3] <= rec[4] <= outer.t1
    # nothing when none runs
    spans.clear()
    gc.disable()
    try:
        for _ in range(1000):
            with span("t:quiet"):
                [[] for _ in range(10)]
        quiet = spans.snapshot()
    finally:
        gc.enable()
    assert len(quiet) == 1000 and not _by_name(quiet, "tdp:host.gc")


def test_prefill_span_counts_real_tokens_against_dispatched_rows(params):
    spans.clear()
    eng = _engine(params)
    eng.prefill_width = W = 2   # of the 4 slots: calls of [2, CHUNK] rows
    rid = eng.submit(Request(tokens=[1, 2, 3, 4, 5], max_new_tokens=2))
    eng.step()
    (pre,) = _by_name(spans.snapshot(), "tdp:engine.prefill")
    assert pre[5]["tokens"] == 5
    # one slot prefills: one call of the compact width, not SLOTS * CHUNK rows
    assert pre[5]["calls"] == 1
    assert pre[5]["rows"] == pre[5]["calls"] * W * CHUNK == 16
    assert pre[5]["rids"] == [rid]
    eng.step()   # its first decode step is the tick after its last slice
    (dec,) = _by_name(spans.snapshot(), "tdp:engine.decode")
    assert dec[5]["slots"] == 1 and dec[5]["rids"] == [rid]
    # a prompt longer than a chunk: its slices' real tokens, chunk by chunk
    spans.clear()
    eng.submit(Request(tokens=list(range(1, 12)), max_new_tokens=1))
    eng.run_until_idle(max_ticks=20)
    assert [p[5]["tokens"] for p in
            _by_name(spans.snapshot(), "tdp:engine.prefill")] == [8, 3]
    # more slots prefilling than one call carries: ceil(n / W) calls under
    # ONE span and one fetch, the real tokens summed over them
    spans.clear()
    rids = [eng.submit(Request(tokens=[1] * (3 + i), max_new_tokens=1))
            for i in range(W + 1)]
    eng.step()
    (pre,) = _by_name(spans.snapshot(), "tdp:engine.prefill")
    assert pre[5]["calls"] == 2
    assert len(_by_name(spans.snapshot(), "tdp:engine.fetch")) == 1
    assert pre[5]["rows"] == 2 * W * CHUNK
    assert pre[5]["tokens"] == sum(3 + i for i in range(W + 1))
    assert pre[5]["rids"] == rids
    s = eng.serving_summary()
    assert s["prefill_signatures"] == 1
    # ticks that prefilled (1 + 2 + 1) against compiled calls (1 + 2 + 2)
    assert (s["prefill_chunks"], s["prefill_calls"]) == (4, 5)


@pytest.mark.parametrize("run_ahead", [False, None],
                         ids=["serial", "run_ahead"])
def test_a_wave_at_the_default_width_is_one_span_of_calls_and_one_fetch(
        params, run_ahead):
    """An engine as it is constructed: a first wave of more prompts than
    ``PREFILL_WIDTH`` is ``ceil(n / W)`` calls of the one signature under
    ONE dispatch span, and one fetch behind them that names the last."""
    W = PREFILL_WIDTH
    n = 2 * W + 1
    eng = ServingEngine(params, CFG, num_slots=n, block_size=8, chunk=CHUNK,
                        max_ctx=64, run_ahead=run_ahead)
    assert eng.prefill_width == W and eng.run_ahead == (run_ahead is None)
    spans.clear()
    rids = [eng.submit(Request(tokens=[1] * (2 + i % 5), max_new_tokens=4))
            for i in range(n)]
    eng.step()
    ring = spans.snapshot()
    (pre,) = _by_name(ring, "tdp:engine.prefill")
    fetches = _by_name(ring, "tdp:engine.fetch")
    assert (pre[5]["calls"], pre[5]["call"]) == (3, 3)
    assert pre[5]["rows"] == 3 * W * CHUNK and pre[5]["rids"] == rids
    assert pre[5]["tokens"] == sum(2 + i % 5 for i in range(n))
    # the wave's one fetch opens after the last dispatch returned
    assert [f[5]["call"] for f in fetches] == [3] and fetches[0][3] >= pre[4]
    eng.step()   # the wave's first decode call, and (serial) its fetch
    if eng.run_ahead:   # which the NEXT tick makes, behind its own dispatch
        assert [f[5]["call"] for f in _by_name(
            spans.snapshot(), "tdp:engine.fetch")] == [3]
        eng.step()
        assert _by_name(spans.snapshot(), "tdp:engine.decode")[-1][5][
            "call"] == 5
    assert [f[5]["call"] for f in _by_name(spans.snapshot(),
                                           "tdp:engine.fetch")] == [3, 4]
    assert [s.state for s in eng._slots] == ["decode"] * n
    assert eng.audit(heal=False)["ok"]
    s = eng.serving_summary()
    assert s["prefill_signatures"] == s["decode_signatures"] == 1
    assert (s["prefill_chunks"], s["prefill_calls"]) == (1, 3)


def test_first_marks_the_one_compiling_call_of_a_signature(params):
    spans.clear()
    eng = _engine(params)
    for _ in range(2):
        eng.submit(Request(tokens=[1, 2, 3], max_new_tokens=3))
        eng.run_until_idle(max_ticks=20)
        eng.reset_metrics()   # forgets the counted signatures, not the calls
    ring = spans.snapshot()
    for name in ("tdp:engine.prefill", "tdp:engine.decode"):
        calls = _by_name(ring, name)
        assert len(calls) >= 2
        assert [bool(c[5].get("first")) for c in calls] == (
            [True] + [False] * (len(calls) - 1))
    # the fetch after a first call carries the mark too: two of them
    fetches = _by_name(ring, "tdp:engine.fetch")
    assert sum(bool(f[5].get("first")) for f in fetches) == 2
    firsts = [r for r in ring if r[5].get("first")]
    assert [r[2].rpartition(".")[2] for r in firsts] == [
        "prefill", "fetch", "decode", "fetch"]
    assert eng.serving_summary()["decode_signatures"] == 0   # just reset


def test_engine_init_span_holds_the_pool_fill(params):
    spans.clear()
    _engine(params)
    (pool, init) = spans.snapshot()
    assert (init[2], pool[2]) == ("tdp:engine.init", "tdp:engine.init.pool")
    assert pool[1] == init[0] and init[1] is None
    assert init[3] <= pool[3] <= pool[4] <= init[4]


@pytest.mark.parametrize("kw, rows", [
    ({}, 8), ({"spec_k": 2}, 8), ({"kv_quant": True}, 8)])
def test_pool_span_says_how_the_kernel_walks(params, kw, rows):
    """``kv_heads_per_step`` and ``kv_tile_blocks`` on ``tdp:engine.init.pool``
    are the shape function's result for the engine's decode call (the K+1
    verify rows with ``spec_k``; 0 blocks a tile for an int8 pool, which
    keeps the grid's walk); the gather path runs no kernel and says
    nothing."""
    from torchdistpackage_tpu.ops.paged_attention import decode_walk

    spans.clear()
    eng = _engine(params, attn_impl="pallas", **kw)
    attrs = _by_name(spans.snapshot(), "tdp:engine.init.pool")[0][5]
    blk, int8 = CFG.block, bool(kw.get("kv_quant"))
    hb, T = decode_walk(
        blk.kv_head_count, rows, eng.max_blocks, 1, 8,
        8 * blk.head_dim * (1 if int8 else 4), int8)
    assert (attrs["kv_heads_per_step"], attrs["kv_tile_blocks"]) == (hb, T)
    assert hb == blk.kv_head_count and (T == 0) == int8
    # the chunk's call: 8 rows a head, one tile over the table's columns
    assert (attrs["chunk_rows"], attrs["chunk_tile_keys"],
            attrs["chunk_programs"]) == (8, eng.max_blocks * 8, 1)
    spans.clear()
    _engine(params)
    attrs = _by_name(spans.snapshot(), "tdp:engine.init.pool")[0][5]
    assert "bytes" in attrs and "kv_tile_blocks" not in attrs


@pytest.mark.parametrize("kw, want", [
    ({}, (136, 256, 4)), ({"kv_quant": True}, (136, 256, 4)),
    ({"chunk": 64}, (64, 256, 2))],
    ids=["grid-walk", "int8", "in-kernel-walk"])
def test_pool_span_says_the_chunks_tile(params, kw, want):
    """``chunk_rows``, ``chunk_tile_keys`` and ``chunk_programs`` are
    ``call_walk``'s result for the engine's prefill call: past 128 rows a
    head the grid walks, one KV head a program, all of the table's 32
    columns ONE key tile a step; up to 128 rows the in-kernel walk's tile
    and as many heads a program as keep its rows within 128."""
    from torchdistpackage_tpu.ops.paged_attention import call_walk

    spans.clear()
    eng = ServingEngine(params, CFG, num_slots=2, block_size=8, max_ctx=256,
                        attn_impl="pallas", **{"chunk": 136, **kw})
    attrs = _by_name(spans.snapshot(), "tdp:engine.init.pool")[0][5]
    assert (attrs["chunk_rows"], attrs["chunk_tile_keys"],
            attrs["chunk_programs"]) == want
    blk, int8 = CFG.block, bool(kw.get("kv_quant"))
    rows, fw, hb, T = call_walk(
        eng.chunk, blk.kv_head_count, eng.max_blocks, 8,
        8 * blk.head_dim * (1 if int8 else 4), int8)
    assert want == (rows, (T or fw) * 8, blk.kv_head_count // hb)
    assert "window_chunk_tile_keys" not in attrs


@pytest.mark.parametrize("run_ahead", [False, None],
                         ids=["serial", "run_ahead"])
def test_fetch_ends_at_the_tokens_and_telemetry_falls_into_host(params,
                                                                run_ahead):
    """``Telemetry.end_step`` is called after the fetch span has closed, and
    its step record still holds the wait for the device.  With ``run_ahead``
    it closes the step of the call BEFORE the newest and waits for nothing
    (``wait=False``): the newest call's outputs are never blocked on."""
    from torchdistpackage_tpu.obs import Telemetry

    last_closed = []

    waited = []

    class Tel(Telemetry):
        def end_step(self, *a, **kw):
            last_closed.append(spans.snapshot()[-1][2])
            waited.append(kw.get("wait", True))
            return super().end_step(*a, **kw)

    tel = Tel(run="t", sinks=[], poll_memory=False)
    spans.clear()
    eng = _engine(params, telemetry=tel, run_ahead=run_ahead)
    eng.submit(Request(tokens=[1, 2, 3], max_new_tokens=5))
    eng.run_until_idle(max_ticks=20)
    assert last_closed and set(last_closed) == {"tdp:engine.fetch"}
    assert set(waited) == {not eng.run_ahead}
    steps = [r for r in tel.history if r.get("type") == "step"]
    assert len(steps) == len(last_closed)
    # the device span runs from the dispatch's return, so it covers the
    # engine's own wait: at least the fetch span of that tick
    fetches = _by_name(spans.snapshot(), "tdp:engine.fetch")[-len(steps):]
    for rec, f in zip(steps, fetches):
        assert rec["span_device_s"] >= f[4] - f[3]


# ------------------------------------------------------------ kernel names


def _kernel_cases():
    """name -> () -> (function, arguments)"""
    S = jax.ShapeDtypeStruct
    bf = jnp.bfloat16

    def flash(q, k, v):
        from torchdistpackage_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, block_q=128, block_k=128).astype(
            jnp.float32).sum()

    q = S((1, 2, 256, 128), bf)
    flash_args = (jax.grad(flash, argnums=(0, 1, 2)), (q, q, q))

    # GQA 8 / 4: the decode rows take all four KV heads in one program that
    # walks the slot's live blocks itself, the chunk's 2 x 128 rows a head
    # take one head a grid step (ops/paged_attention.py)
    B, H, Hkv, hd, bs, mb = 2, 8, 4, 128, 128, 2
    pool = S((1 + B * mb, Hkv, bs, hd), bf)

    def paged(s_in):
        from torchdistpackage_tpu.ops.paged_attention import (
            paged_decode_attention)

        return (lambda q, k, v, t, o: paged_decode_attention(
            q, k, v, t, o, fetch_width=2, q_pad_to=8),
                (S((B, H, s_in, hd), bf), pool, pool,
                 S((B, mb), jnp.int32), S((B,), jnp.int32)))

    def narrow(s_in):
        """GQA 32 / 8 at heads of 64 (Granite-4.0-H): two KV heads to a
        128-lane row of the pool, the query spread into its own head's
        lanes, the scale 1/64 (serving/paged_cache.py "Narrow heads")."""
        from torchdistpackage_tpu.serving.paged_cache import paged_attention

        return (lambda q, k, v, t, o: paged_attention(
            q, k, v, o, tables=t, impl="pallas", layer=1, sm_scale=1 / 64),
                (S((B, 32, s_in, 64), bf), S((2, 1 + B * mb, 4, bs, 128), bf),
                 S((2, 1 + B * mb, 4, bs, 128), bf),
                 S((B, mb), jnp.int32), S((B,), jnp.int32)))

    def wide(s_in, hkv, window):
        """MiMo-V2's two kinds of layer: 64 query heads of 192 over values
        of 128, the K pool transposed (``[.., 192, bs]``); a window layer
        (8 KV heads, a window of one block of a table of eight, a sink a
        query head) or a global one (4 KV heads, no sink)."""
        from torchdistpackage_tpu.ops.paged_attention import (
            paged_decode_attention)

        args = (S((B, 64, s_in, 192), bf), S((2, 1 + B * 8, hkv, 192, bs), bf),
                S((2, 1 + B * 8, hkv, bs, 128), bf), S((B, 8), jnp.int32),
                S((B,), jnp.int32))
        if window is None:
            return (lambda q, k, v, t, o: paged_decode_attention(
                q, k, v, t, o, layer=1), args)
        return (lambda q, k, v, t, o, sink: paged_decode_attention(
            q, k, v, t, o, layer=1, window=window, sink=sink),
                args + (S((64,), jnp.float32),))

    def carry():
        from torchdistpackage_tpu.ops.paged_attention import (
            paged_carry_attention)

        return (lambda q, k, v, t, o: paged_carry_attention(
            q, k, v, t, o, fetch_width=2, q_pad_to=8),
                (S((B, H, 16, hd), bf), pool, pool,
                 S((B, mb), jnp.int32), S((B,), jnp.int32)))

    def latent(s_in):
        from torchdistpackage_tpu.ops.mla_attention import mla_paged_attention

        # 8 heads over one cached row of 128 + 64 a position
        return (lambda q, p, t, o: mla_paged_attention(
            q, p, t, o, latent=128, sm_scale=0.1, fetch_width=2),
                (S((B, H, s_in, 192), bf), S((1 + B * mb, 1, 192, bs), bf),
                 S((B, mb), jnp.int32), S((B,), jnp.int32)))

    return {
        "flash_fwd": lambda: flash_args,
        "flash_bwd_dq": lambda: flash_args,
        "flash_bwd_dkv": lambda: flash_args,
        "paged_decode": lambda: paged(1),
        "paged_chunk": lambda: paged(128),
        "paged_decode-hd64": lambda: narrow(1),
        "paged_chunk-hd64": lambda: narrow(128),
        "paged_carry": carry,
        "mla_decode": lambda: latent(1),
        "mla_chunk": lambda: latent(64),
        "swa_decode-hd192": lambda: wide(1, 8, 128),
        "swa_chunk-hd192": lambda: wide(512, 8, 128),
        "paged_decode-hd192": lambda: wide(1, 4, None),
        "paged_chunk-hd192": lambda: wide(512, 4, None),
    }


@pytest.mark.parametrize("kernel", [
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_decode",
    "paged_chunk", "paged_decode-hd64", "paged_chunk-hd64", "paged_carry",
    "mla_decode", "mla_chunk", "swa_decode-hd192", "swa_chunk-hd192",
    "paged_decode-hd192", "paged_chunk-hd192"])
def test_kernel_lowers_under_its_name(monkeypatch, kernel):
    """XLA names a Mosaic custom call after the name-stack component before
    ``pallas_call``: that is the kernel's ``name=``, which the device
    trace then shows (``%flash_fwd.1 = ... custom-call``).  Lowered for TPU
    from the CPU: every kernel the package holds lowers for the chip.  The
    cases at unequal widths (``-hd192``) are also COMPILED, for a described
    v5e: Mosaic takes the transposed key tile, the contraction over 192 and
    the sinks as a fourth scalar operand, and no copy carries a pool."""
    fn, args = _kernel_cases()[kernel]()
    # every module imported BEFORE any is patched: two of them take their
    # `_interpret` from flash_attention as they are imported, and one first
    # imported under the patch would keep it for the rest of the process
    mods = [importlib.import_module(f"torchdistpackage_tpu.ops.{mod}")
            for mod in ("flash_attention", "paged_attention", "mla_attention")]
    for mod in mods:
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text
    # outside a scan a transformation wraps the name: jvp(flash_fwd)
    named = set(re.findall(r"(\w+)\)*/pallas_call", text))
    assert kernel.split("-")[0] in named
    # no kernel under another name
    assert named <= {k.split("-")[0] for k in _kernel_cases()}
    if kernel.endswith("-hd192"):
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        from torchdistpackage_tpu.ops import paged_attention as P

        monkeypatch.setattr(P, "default_paged_params",
                            lambda: P.paged_params_for("TPU v5 lite"))
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        hlo = jax.jit(fn).lower(*(jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one) for a in args)).compile().as_text()
        assert re.search(rf"%{kernel.split('-')[0]}[.0-9]* = ", hlo)
        assert not re.search(r"= bf16\[2,17,\d,\d+,128\]\S* copy\(", hlo)
