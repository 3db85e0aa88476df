"""The device's idle time in a serving tick, credited to the program's own
spans, and the time a window lost to stalls, by the phase that held it.

Pure functions over tuples, as ``program_spans.py``; the tests drive them
with synthetic lists.  Seven metrics read them, each a JSON + a few lines
of ``.py`` beside this file: ``idle_in_program_ms.batch``,
``idle_launch_ms.batch``, ``idle_deliver_ms.batch``,
``idle_engine_ms.batch``, ``idle_caller_ms.batch`` (the traced part of the
window; ms a traced tick) and ``stall_fetch_s.batch``,
``stall_host_s.batch`` (the whole window, the ring alone).

**The clocks.**  The program's spans are ``perf_counter`` seconds in its
ring.  The profiler stamps its events with the wall clock and writes them
less the capture's own start, which lies in the trace's file and not in
what the harness keeps of it.  So the program gives what it can know, the
ring's anchors to the wall clock (``spans.to_trace_clock``), and the ONE
constant left, the capture's start, is found here from what physics
demands: no fetch returns before the execution it waits for has ended, so
the capture started no later than the least ``fetch end - execution end``
over the traced calls (:func:`trace_zero`).  That bound is off by the
fastest delivery of the trace (some tens of microseconds; PERF.md gives the
chip's number): ``idle_deliver`` reads low by it and ``idle_launch`` high.
The order check (:func:`in_order`) then holds every call to both laws, and
a reading that breaks one gives no number, not a wrong one.

:func:`idle_by_phase` is written so that it can take the place of
``trace_reduce.idle_gaps`` (a later ``benchmark`` issue's): it needs the
executions, the operations and the host spans of one device on one clock,
and credits each idle microsecond to the INNERMOST span open over it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks import trace_reduce as R
from benchmarks.layer_metrics import program_spans as P

FETCH = "tdp:engine.fetch"
CALLER, IN_PROGRAM = "(caller)", "(in program)"
#: the five classes an idle microsecond falls into; they sum to the idle time
CLASSES = ("in_program", "launch", "deliver", "engine", "caller")
#: what the order check allows a clock to be off by, seconds
SLACK_S = 100e-6
#: a tick's kind, as PERF.md's tables split them
DECODE_ONLY, WITH_PREFILL = "decode_only", "with_prefill"

Interval = Tuple[float, float]
#: a host span on the trace's clock: (name, start, end, attrs)
HostSpan = Tuple[str, float, float, Dict[str, Any]]
#: one device call: (call id, dispatch span's start, its end, tick index)
Call = Tuple[int, float, float, int]


def innermost(spans: Sequence[HostSpan], t0: float, t1: float,
              top: str = P.TICK) -> List[Tuple[float, float, str, int, bool]]:
    """[t0, t1] cut into segments ``(start, end, name, tick, under)`` over
    each of which ONE span is the innermost open: its ``name``,
    :data:`CALLER` where none is.  ``tick`` counts the ``top`` spans that
    have opened by then, less one (-1 before the first); ``under`` says
    whether the segment lies under one (else it is the caller's, behind
    tick ``tick``).  Spans nest or follow each other, as one thread's do."""
    segs: List[Tuple[float, float, str, int, bool]] = []
    stack: List[HostSpan] = []
    cur, tick = t0, -1

    def emit(upto: float) -> None:
        nonlocal cur
        upto = min(upto, t1)
        if upto > cur:
            segs.append((cur, upto, stack[-1][0] if stack else CALLER, tick,
                         bool(stack) and stack[0][0] == top))
            cur = upto

    for sp in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= sp[1]:
            emit(stack[-1][2])
            stack.pop()
        emit(sp[1])
        stack.append(sp)
        if sp[0] == top and len(stack) == 1:
            tick += 1
    while stack:
        emit(stack[-1][2])
        stack.pop()
    emit(t1)
    return segs


def by_tick(spans: Sequence[HostSpan], top: str = P.TICK) -> Tuple[
        List[HostSpan], List[List[HostSpan]]]:
    """(the ``top`` spans in time order, for each the spans that start
    inside it)."""
    ticks = sorted((s for s in spans if s[0] == top), key=lambda s: s[1])
    kids: List[List[HostSpan]] = [[] for _ in ticks]
    i = 0
    for sp in sorted((s for s in spans if s[0] != top), key=lambda s: s[1]):
        while i < len(ticks) and ticks[i][2] <= sp[1]:
            i += 1
        if i < len(ticks) and ticks[i][1] <= sp[1]:
            kids[i].append(sp)
    return ticks, kids


def calls_of(ticks: Sequence[Sequence[HostSpan]]) -> Optional[
        Tuple[List[Call], Dict[int, float]]]:
    """(every device call the ticks' dispatch spans made, in order; call id
    -> end of the fetch span that waited for it).  A dispatch span with
    ``calls=k, call=c`` made calls ``c-k+1 .. c``.  None where a dispatch
    span carries no ``call`` (a program from before the attr)."""
    calls: List[Call] = []
    fetched: Dict[int, float] = {}
    for i, kids in enumerate(ticks):
        for name, s, e, attrs in sorted(kids, key=lambda k: k[1]):
            if name in P.DISPATCH:
                if "call" not in attrs:
                    return None
                k = int(attrs.get("calls", 1))
                calls += [(attrs["call"] - k + 1 + j, s, e, i)
                          for j in range(k)]
            elif name == FETCH and "call" in attrs:
                fetched[attrs["call"]] = e
    return calls, fetched


def match(executions: Sequence[R.Event], calls: Sequence[Call],
          fetched: Optional[Dict[int, float]] = None) -> Optional[
        Dict[int, Interval]]:
    """call id -> its execution's (start, end): the device runs the calls
    in the order they were dispatched, so the j-th whole execution is the
    j-th call (the last calls' executions may lie past the capture's end).
    The first call is the first that the spans dispatch; with ``fetched``
    (fetch ends ON THE EXECUTIONS' CLOCK) the first whose fetch had not
    returned when the first whole execution began, for a capture that
    opened after some of the spans' calls.  None where the device ran more
    programs than the spans dispatched."""
    runs = sorted((s, s + d) for _, s, d in executions)
    if fetched is not None and runs:
        calls = [c for c in calls
                 if not fetched.get(c[0], runs[0][0] + 1) <= runs[0][0]]
    if len(runs) > len(calls):
        return None
    return {c[0]: run for c, run in zip(calls, runs)}


def trace_zero(matched: Dict[int, Interval],
               fetched_wall: Dict[int, float]) -> Optional[float]:
    """The capture's start on the wall clock, seconds: the least ``fetch
    end - execution end`` over the calls that have both (module
    docstring)."""
    gaps = [fetched_wall[c] - run[1] for c, run in matched.items()
            if c in fetched_wall]
    return min(gaps) if gaps else None


def in_order(matched: Dict[int, Interval], calls: Sequence[Call],
             fetched: Dict[int, float], slack: float = SLACK_S) -> bool:
    """What physics demands of spans and executions on ONE clock: no
    execution starts before its dispatch span opens, no fetch ends before
    its execution does."""
    for cid, opened, _, _ in calls:
        run = matched.get(cid)
        if run is None:
            continue
        if run[0] < opened - slack:
            return False
        if cid in fetched and fetched[cid] < run[1] - slack:
            return False
    return True


def idle_by_phase(executions: Sequence[R.Event], ops: Sequence[R.Event],
                  spans: Sequence[HostSpan], t0: float, t1: float,
                  ) -> Optional[Dict[str, Any]]:
    """One device's idle time inside [t0, t1], every part of it credited
    once.  ``executions`` are its whole program executions, ``ops`` its
    operations, ``spans`` the host's spans, all on one clock.

    Inside an execution, time that the union of its operations does not
    cover is the device's own (``in_program``).  Between executions the
    innermost open span decides: a dispatch span ``launch``, the fetch
    ``deliver``, any other span under a ``tdp:engine.tick`` or the tick
    itself ``engine``, and what lies under no tick ``caller``.  Operations
    outside every whole execution (a capture that opened or closed inside
    one) count as an execution from the first to the last of them on that
    side.  Returns the five sums in seconds, ``idle_s`` (their sum),
    ``by_name``: kind of tick -> span name -> seconds (a caller's share
    goes to the tick before it, an execution's to the tick that dispatched
    it), and ``ticks``: kind -> the ticks wholly inside.  None where the
    device ran nothing, where a dispatch span lacks its ``call``, or where
    the order that physics demands fails."""
    ops = R.clip(ops, t0, t1)
    executions = [x for x in executions if t0 <= x[1] and x[1] + x[2] <= t1]
    whole = sorted((s, s + d) for _, s, d in executions)
    tick_spans, kids = by_tick(spans)
    found = calls_of(kids)
    if not whole or not tick_spans or found is None:
        return None
    calls, fetched = found
    matched = match(executions, calls, fetched)
    if matched is None or not in_order(matched, calls, fetched):
        return None
    # a capture that opened or closed inside an execution: its operations
    lo, hi = whole[0][0], whole[-1][1]
    cut = [(min(o[1] for o in side), max(o[1] + o[2] for o in side))
           for side in ([o for o in ops if o[1] + o[2] <= lo],
                        [o for o in ops if o[1] >= hi]) if side]
    busy = R.union((s, s + d) for _, s, d in ops)

    def kind(tick: int) -> str:
        tick = min(max(tick, 0), len(tick_spans) - 1)
        return (WITH_PREFILL if any(k[0] == P.DISPATCH[0] for k in kids[tick])
                else DECODE_ONLY)

    out: Dict[str, Any] = dict.fromkeys(CLASSES, 0.0)
    by_name: Dict[str, Dict[str, float]] = {DECODE_ONLY: {}, WITH_PREFILL: {}}

    def credit(cls: str, tick: int, name: str, seconds: float) -> None:
        out[cls] += seconds
        row = by_name[kind(tick)]
        row[name] = row.get(name, 0.0) + seconds

    dispatched_in = {matched[c[0]]: c[3] for c in calls if c[0] in matched}
    for run in whole + cut:
        at = dispatched_in.get(run, 0 if run[0] < lo else len(tick_spans) - 1)
        credit("in_program", at, IN_PROGRAM,
               R.total(R.subtract([run], busy)))
    gaps = R.subtract([(t0, t1)], R.union(whole + cut))
    g = 0
    for s, e, name, tick, under in innermost(spans, t0, t1):
        while g < len(gaps) and gaps[g][1] <= s:
            g += 1
        for a, b in gaps[g:]:
            if a >= e:
                break
            cls = ("caller" if not under else
                   "launch" if name in P.DISPATCH else
                   "deliver" if name == FETCH else "engine")
            credit(cls, tick, name, min(e, b) - max(s, a))
    out["idle_s"] = sum(out[c] for c in CLASSES)
    out["by_name"] = by_name
    inside = [i for i, t in enumerate(tick_spans)
              if t[1] >= t0 - SLACK_S and t[2] <= t1 + SLACK_S]
    out["ticks"] = {k: sum(1 for i in inside if kind(i) == k)
                    for k in (DECODE_ONLY, WITH_PREFILL)}
    return out


# ------------------------------------------------------ what a run hands over


def _on_wall_clock(recs: Sequence[P.Span]) -> Optional[
        Tuple[int, List[HostSpan]]]:
    """(the wall clock's ns when the first of the ring's records ``recs``
    opened, the records in seconds since then): whole ns until the
    difference is taken, since a float holds the wall clock's seconds to
    0.2 us only.  None where the program has no anchors (a parent commit)."""
    from torchdistpackage_tpu.utils.profiling import spans as ring

    conv = getattr(ring, "to_trace_clock", None)
    if conv is None or not getattr(ring, "anchors", None) or not recs:
        return None
    base = conv(min(r[3] for r in recs))
    return base, [(r[2], (conv(r[3]) - base) * 1e-9,
                   (conv(r[4]) - base) * 1e-9, r[5]) for r in recs]


def traced_split(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """:func:`idle_by_phase` of the run's traced part, with ``traced_ticks``
    (the ticks wholly inside it), ``zero_wall_ns`` (the capture's start as
    :func:`trace_zero` found it) and ``zero_bracket_us`` (how far apart the
    two laws of :func:`in_order` leave that start: the least delivery plus
    the least launch of the trace)."""
    tr = obs.get("trace") or {}
    win = P.window(obs)
    ev = P._first_device(obs)
    if win is None or ev is None or not tr.get("modules"):
        return None
    # from the window's first tick (the capture runs) to a little past the
    # capture's end, on the ring's own clock
    opened = win[0][0][3]
    on_wall = _on_wall_clock([
        r for r in P.ring() or () if r[2].startswith("tdp:")
        and opened <= r[3] < opened + tr["window_s"] + 2.0])
    if on_wall is None:
        return None
    base, wall = on_wall
    found = calls_of(by_tick(wall)[1])
    if found is None:
        return None
    calls, fetched = found
    matched = match(tr["modules"], calls)
    zero = trace_zero(matched, fetched) if matched else None
    if zero is None:
        return None
    spans = [(n, s - zero, e - zero, a) for n, s, e, a in wall]
    t0 = min(min(s[1] for s in spans), tr["modules"][0][1],
             min(o[1] for o in ev))
    t1 = t0 + tr["window_s"]
    out = idle_by_phase(tr["modules"], ev, spans, t0, t1)
    if out is None or not sum(out["ticks"].values()):
        return None
    out["traced_ticks"] = sum(out["ticks"].values())
    out["zero_wall_ns"] = base + round(zero * 1e9)
    out["zero_bracket_us"] = 1e6 * min(
        matched[c[0]][0] + zero - c[1] for c in calls if c[0] in matched)
    return out


def idle_ms(obs: Dict[str, Any], cls: str) -> Optional[float]:
    """One class of :data:`CLASSES`, ms a traced tick."""
    got = traced_split(obs)
    return None if got is None else 1e3 * got[cls] / got["traced_ticks"]


def window_stalls(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``serving.tracing.stalls`` over the window's ticks, from the ring
    (the rule is the program's: an operator asks the same question).  A
    traced run stops its profiler between two ticks of the window, for
    seconds: that pause is the benchmark's own and is taken out (the ticks
    behind the capture's end follow the tick before it at once)."""
    try:
        from torchdistpackage_tpu.serving.tracing import stalls
    except ImportError:
        return None
    win = P.window(obs)
    if win is None:
        return None
    ticks = [(t[3], t[4]) for t in win[0]]
    kids = [[(k[2], k[3], k[4]) for k in ks] for ks in win[1]]
    tr = obs.get("trace")
    if tr:
        closed = ticks[0][0] + tr["window_s"]   # the capture's end, ring clock
        after = next((i for i, t in enumerate(ticks) if t[0] > closed), None)
        if after:
            pause = ticks[after][0] - ticks[after - 1][1]
            ticks[after:] = [(a - pause, b - pause) for a, b in ticks[after:]]
            kids[after:] = [[(n, a - pause, b - pause) for n, a, b in ks]
                            for ks in kids[after:]]
    return stalls(ticks, kids)


def stall_s(obs: Dict[str, Any], where: str) -> Optional[float]:
    """Seconds of the window lost in slow ticks whose excess lies in the
    wait for the device (``where='fetch'``) or anywhere else (``'host'``)."""
    got = window_stalls(obs)
    if got is None:
        return None
    in_fetch = got["by_part"].get(FETCH, 0.0)
    return in_fetch if where == "fetch" else got["lost_s"] - in_fetch
