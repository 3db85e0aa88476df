"""The built-in readers of per-layer metrics.  Each takes the run's
observations (``spans``: name -> list of seconds; ``values``: name ->
number; ``costs``: name -> {flops, bytes} of ONE call; ``peaks``; ``trace``:
the reduced device trace or None) and its arguments from the metric's own
JSON file.  A reader that finds nothing to read returns None, and the
harness leaves the metric out of the line."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks import costs as C
from benchmarks import stats
from benchmarks import trace_reduce as R


def span_median(obs: Dict[str, Any], span: str, scale: float = 1.0) -> Optional[float]:
    values = obs["spans"].get(span)
    return stats.median(values) * scale if values else None


def span_percentile(obs: Dict[str, Any], span: str, q: float,
                    scale: float = 1.0) -> Optional[float]:
    values = obs["spans"].get(span)
    return stats.percentile(values, q) * scale if values else None


def value(obs: Dict[str, Any], key: str, scale: float = 1.0) -> Optional[float]:
    v = obs["values"].get(key)
    return None if v is None else v * scale


def ratio(obs: Dict[str, Any], num: str, den: str, scale: float = 1.0) -> Optional[float]:
    n, d = obs["values"].get(num), obs["values"].get(den)
    return None if n is None or not d else n / d * scale


def mfu(obs: Dict[str, Any]) -> Optional[float]:
    """Tokens/s/chip x operations per token (recomputation not counted)
    over one chip's peak, in percent."""
    v = obs["values"]
    need = ("tokens_per_s_per_chip", "flops_per_token", "peak_flops")
    if any(v.get(k) is None for k in need):
        return None
    return 100.0 * v["tokens_per_s_per_chip"] * v["flops_per_token"] / v["peak_flops"]


def _first_device(obs):
    tr = obs.get("trace")
    if not tr or not tr["events"]:
        return None
    return next(iter(tr["events"].values()))


def roofline(obs: Dict[str, Any], pattern: str, cost: str) -> Optional[float]:
    """The least time the chip could take for a kernel's calls over the time
    they took in the trace, percent.  Calls are counted from the program,
    not from the trace's events: the executions of the window's most
    frequent program (the train step; in a serving trace the decode step)
    that lie wholly inside the traced window, times the cost's
    ``calls_per_execution`` (one per layer).  Operations and bytes of one
    call come from benchmarks/costs.py; the time is that of the operations
    matching ``pattern`` inside those executions."""
    ev = _first_device(obs)
    one = obs["costs"].get(cost)
    mods = (obs.get("trace") or {}).get("modules") or []
    if ev is None or one is None or not mods:
        return None
    name = R.most_frequent_module(mods)
    runs = [(s, s + d) for n, s, d in mods if n == name]
    took = R.op_seconds(R.within(ev, R.union(runs)), pattern)
    if took <= 0:
        return None
    least = (C.roofline_seconds(one, obs["peaks"])["seconds"]
             * len(runs) * one["calls_per_execution"])
    return 100.0 * least / took


def exposed_collectives(obs: Dict[str, Any]) -> Optional[float]:
    """Collective time with no compute running on that device, as a
    percentage of the traced window."""
    ev = _first_device(obs)
    if ev is None:
        return None
    t = R.exposed_collective_seconds(ev)
    return 100.0 * t / obs["trace"]["window_s"] if t > 0 else None
