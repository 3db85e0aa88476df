"""Readers of what the PROGRAM names itself: the host spans it records
(``torchdistpackage_tpu.utils.profiling.spans``: ``tdp:engine.*`` with their
attrs) and the names it gives its Pallas kernels in the device trace.

The window's ticks are the LAST N ``tdp:engine.tick`` spans of the ring,
N = the number of ``eng.step()`` calls the runner made inside the window
(``obs["spans"]["engine_step"]``): the runner steps no engine after it.
Every reader returns None where there is nothing to read: a program without
the ring (a parent commit), a cell that runs no engine, a ring that has
wrapped past what is asked for, a trace without the kernel's name."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks import stats
from benchmarks import trace_reduce as R
from benchmarks.layer_metrics.readers import _first_device

TICK = "tdp:engine.tick"
DISPATCH = ("tdp:engine.prefill", "tdp:engine.decode")
#: one closed span, as the program records it
Span = Tuple[int, Optional[int], str, float, float, Dict[str, Any]]


def ring() -> Optional[List[Span]]:
    """The program's closed spans, oldest first; None when the ring has
    wrapped (its oldest records are gone) or the program has none."""
    try:
        from torchdistpackage_tpu.utils.profiling import spans
    except ImportError:
        return None
    recs = spans.snapshot()
    return None if len(recs) >= spans.maxlen else recs


def window(obs: Dict[str, Any]) -> Optional[
        Tuple[List[Span], List[List[Span]], List[Span]]]:
    """(the window's tick spans, each one's child spans in time order, the
    spans that closed before the window's first tick opened)."""
    n = len(obs["spans"].get("engine_step") or ())
    recs = ring()
    if not recs or not n:
        return None
    ticks = [r for r in recs if r[2] == TICK]
    if len(ticks) < n:
        return None
    ticks = ticks[-n:]
    index = {t[0]: i for i, t in enumerate(ticks)}
    kids: List[List[Span]] = [[] for _ in ticks]
    for r in recs:
        if r[1] in index:
            kids[index[r[1]]].append(r)
    return ticks, kids, [r for r in recs if r[4] <= ticks[0][3]]


def _named(kids: Sequence[Span], *names: str) -> List[Span]:
    return [k for k in kids if k[2] in names]


def prefill_useful_share(obs: Dict[str, Any]) -> Optional[float]:
    """Real prompt tokens over the rows the compiled prefill calls computed,
    percent, over the window."""
    win = window(obs)
    if win is None:
        return None
    calls = [k for kids in win[1] for k in _named(kids, DISPATCH[0])]
    rows = sum(k[5]["rows"] for k in calls)
    return 100.0 * sum(k[5]["tokens"] for k in calls) / rows if rows else None


def dispatch_ms(obs: Dict[str, Any]) -> Optional[float]:
    """The host's dispatch of the compiled decode call on decode-only
    ticks, median, ms."""
    win = window(obs)
    if win is None:
        return None
    took = [k[4] - k[3] for kids in win[1] if not _named(kids, DISPATCH[0])
            for k in _named(kids, DISPATCH[1])]
    return stats.median(took) * 1e3 if took else None


def tick_gap_ms(obs: Dict[str, Any]) -> Optional[float]:
    """From the end of a tick's last fetch to the start of the next tick's
    first dispatch, median, ms: what the device waits for while the host
    walks the fetched tokens, the caller turns its loop, and the next tick
    audits, schedules and builds its call's arrays."""
    win = window(obs)
    if win is None:
        return None
    gaps = []
    for before, after in zip(win[1], win[1][1:]):
        fetched = _named(before, "tdp:engine.fetch")
        called = _named(after, *DISPATCH)
        if fetched and called:
            gaps.append(min(k[3] for k in called) - max(k[4] for k in fetched))
    return stats.median(gaps) * 1e3 if gaps else None


def engine_init_s(obs: Dict[str, Any]) -> Optional[float]:
    """``ServingEngine.__init__`` of the engine that the window drove."""
    win = window(obs)
    inits = [r for r in win[2] if r[2] == "tdp:engine.init"] if win else []
    return inits[-1][4] - inits[-1][3] if inits else None


def first_calls_s(obs: Dict[str, Any]) -> Optional[float]:
    """The calls that compiled or loaded a program before the window (one a
    signature) with the fetch that waited for each, summed."""
    win = window(obs)
    firsts = [r for r in win[2] if r[5].get("first")] if win else []
    return sum(r[4] - r[3] for r in firsts) if firsts else None


def kernel_ms(obs: Dict[str, Any], pattern: str) -> Optional[float]:
    """Device time of the operations matching ``pattern`` (a kernel's own
    name) inside the executions of the window's most frequent program, over
    the number of those executions: ms a step."""
    ev = _first_device(obs)
    mods = (obs.get("trace") or {}).get("modules") or []
    if ev is None or not mods:
        return None
    name = R.most_frequent_module(mods)
    runs = [(s, s + d) for n, s, d in mods if n == name]
    took = R.op_seconds(R.within(ev, R.union(runs)), pattern)
    return 1e3 * took / len(runs) if took > 0 else None
