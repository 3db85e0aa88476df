"""Percentile, lateness and spread arithmetic, in plain Python."""

from __future__ import annotations

import math
import statistics
from typing import Dict, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the two
    nearest ranks, as numpy's default has it."""
    if not values:
        raise ValueError("percentile of no samples")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def lateness(due: Sequence[float], sent: Sequence[float]) -> Dict[str, float]:
    """How late an open-loop generator ran: sent minus due, seconds."""
    late = [max(0.0, s - d) for d, s in zip(due, sent)]
    if not late:
        return {"n": 0, "median_s": 0.0, "max_s": 0.0}
    return {"n": len(late), "median_s": median(late), "max_s": max(late)}
