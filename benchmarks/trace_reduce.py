"""From a profiler trace to numbers.  ``read_xplane`` turns the profiler's
``.xplane.pb`` into plain tuples; everything else works on tuples, so the
tests drive it with a synthetic event list.

An event is ``(name, start_s, duration_s)``.  Device events are the XLA
operations of one chip; host spans are the benchmark's own
``TraceAnnotation``s (names starting ``bm:``) on the same clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def subtract(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the (merged) intervals ``a`` that no interval of ``b`` covers."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def busy_seconds(events: Sequence[Event]) -> float:
    return total(union((s, s + d) for _, s, d in events))


def op_seconds(events: Sequence[Event], pattern: str) -> float:
    """Summed device time of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(d for name, _, d in events
               if rx.search(name) and not is_wrapper(name))


def op_count(events: Sequence[Event], pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(1 for name, _, _ in events
               if rx.search(name) and not is_wrapper(name))


_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
#: operations that only wrap others: their time is their children's
WRAPPERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """The profiler names a device operation by its whole HLO line
    (``%fusion.3 = bf16[...]{...} fusion(...)``): keep ``%fusion.3 fusion``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    m = _OPCODE.search(rhs)
    return f"{lhs} {m.group(1)}" if m else lhs[:120]


def is_wrapper(name: str) -> bool:
    return short_name(name).rsplit(" ", 1)[-1] in WRAPPERS


def top_ops(events: Sequence[Event], n: int = 10) -> List[List]:
    """The operations that took most time, wrappers (a scan's ``while``)
    left out so that no time is counted twice."""
    by: Dict[str, float] = {}
    for name, _, d in events:
        if is_wrapper(name):
            continue
        key = short_name(name)
        by[key] = by.get(key, 0.0) + d
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Sequence[Event], spans: Sequence[Event],
              t0: float, t1: float, n: int = 10) -> List[List]:
    """Idle time of one device inside [t0, t1], summed by the host span
    that overlapped each gap most ('(no span)' where none did)."""
    gaps = subtract([(t0, t1)], union((s, s + d) for _, s, d in events))
    by: Dict[str, float] = {}
    for gs, ge in gaps:
        best, name = 0.0, "(no span)"
        for sn, ss, sd in spans:
            ov = min(ge, ss + sd) - max(gs, ss)
            if ov > best:
                best, name = ov, sn
        by[name] = by.get(name, 0.0) + (ge - gs)
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def exposed_collective_seconds(events: Sequence[Event]) -> float:
    """Collective time on one device during which no other operation ran."""
    coll = union((s, s + d) for n, s, d in events if COLLECTIVE.search(n))
    comp = union((s, s + d) for n, s, d in events if not COLLECTIVE.search(n))
    return total(subtract(coll, comp))


def within(events: Sequence[Event], intervals: Sequence[Tuple[float, float]]) -> List[Event]:
    """The events that start inside one of the (merged, sorted) intervals."""
    out, i = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        while i < len(intervals) and intervals[i][1] <= ev[1]:
            i += 1
        if i < len(intervals) and intervals[i][0] <= ev[1]:
            out.append(ev)
    return out


def most_frequent_module(modules: Sequence[Event]) -> Optional[str]:
    """The compiled program that ran most often in the window: in a serving
    trace the decode step (every tick runs it; a prefill chunk only some)."""
    counts: Dict[str, int] = {}
    for name, _, _ in modules:
        counts[name] = counts.get(name, 0) + 1
    return max(counts, key=lambda k: (counts[k], k)) if counts else None


def reduce_trace(devices: Dict[str, List[Event]], spans: Sequence[Event],
                 t0: float, t1: float,
                 modules: Optional[Dict[str, List[Event]]] = None) -> Dict:
    """Busy seconds averaged over the devices, the window, the top device
    operations and the longest idle gaps (of the first device)."""
    if not devices:
        raise ValueError("the trace holds no device plane")
    clipped = {k: clip(v, t0, t1) for k, v in sorted(devices.items())}
    first = next(iter(clipped.values()))
    busy = [busy_seconds(v) for v in clipped.values()]
    merged = [e for v in clipped.values() for e in v]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": t1 - t0,
        "device_ops": [[k, v / len(clipped)] for k, v in top_ops(merged)],
        "idle_gaps": idle_gaps(first, clip(spans, t0, t1), t0, t1),
        "events": clipped,
        # the first device's program executions that lie wholly inside
        "modules": [m for m in (modules or {}).get(next(iter(clipped)), [])
                    if m[1] >= t0 and m[1] + m[2] <= t1],
    }


# ------------------------------------------------------------ the profiler


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str):
    """(device -> its XLA operations, the benchmark's host spans, device ->
    its program executions), seconds on the trace's own clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev and line.name == "XLA Ops":
                devices[plane.name] = [
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events]
            elif is_dev and line.name == "XLA Modules":
                modules[plane.name] = [
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events]
            elif not is_dev:
                spans += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                          for e in line.events
                          if e.name.startswith("bm:")]
    return devices, spans, modules
