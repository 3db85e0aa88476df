"""Profiling ranges + trace capture gating.

Analogue of the reference's NVTX toolkit (``dist/utils.py:11-69``):

- ``cu_prof_start/stop`` (nsys capture window)  -> :func:`prof_start` /
  :func:`prof_stop` around ``jax.profiler`` trace collection (view in
  TensorBoard / Perfetto instead of nsys).
- ``nvtx_decorator``                            -> :func:`scope_decorator`
  using ``jax.named_scope`` (names flow into XLA HLO metadata and show up in
  the TPU trace viewer — the XLA-native equivalent of an NVTX range) plus a
  host :class:`span`.
- ``NVTXContext`` (timing context)              -> :class:`span`, the ONE
  way the package opens a host span: a ``TraceAnnotation`` (so that under a
  ``jax.profiler`` capture the span lies on the device trace's clock) whose
  ``perf_counter`` start and end also go to the process-wide ring
  :data:`spans`, which outlives whatever object opened the span.  It does
  not wait for the device: a span around a jitted call measures the
  DISPATCH; put the fetch of the result in a span of its own.

The ring's clock and a capture's.  The profiler stamps its events with the
wall clock (``CLOCK_REALTIME``, what ``time.time_ns()`` reads) and writes
them LESS the capture's own start (the ``profile_start_time`` stat of the
trace's ``Task Environment`` plane), so no clock that Python can read gives
an event's ``start_ns`` as it stands.  The ring keeps what the program can
know: :attr:`SpanRing.anchors`, pairs of ``(perf_counter seconds, wall-clock
ns)`` taken at most once a second as spans close, and
:meth:`SpanRing.to_trace_clock`, which puts any ``perf_counter`` reading on
the wall clock through them.  A reader that holds the trace's file subtracts
``profile_start_time``; one that holds only its events finds that one
constant from what physics demands (no fetch returns before its program
has ended: benchmarks/layer_metrics/idle_by_phase.py).

Garbage collections are spans too: ``gc.callbacks`` closes a
``tdp:host.gc`` record (attr ``generation``) into the ring for each one,
under whatever span was open, and nothing when none runs.
"""

from __future__ import annotations

import bisect
import collections
import functools
import gc
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax


def prof_start(logdir: str = "/tmp/jax-trace") -> None:
    """Begin a profiler capture window (TensorBoard/Perfetto trace).

    Like ``cu_prof_start`` (dist/utils.py:11-21) this is meant to bracket a
    few steady-state steps, not a whole run.
    """
    jax.profiler.start_trace(logdir)


def prof_stop() -> None:
    jax.profiler.stop_trace()


def scope_decorator(fn: Callable = None, *, name: Optional[str] = None) -> Callable:
    """Wrap ``fn`` in a named scope visible in both device (HLO metadata) and
    host (TraceAnnotation) timelines — analogue of ``nvtx_decorator``
    (dist/utils.py:35-44)."""

    def deco(f: Callable) -> Callable:
        scope = name or f.__qualname__

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            with jax.named_scope(scope), span(scope):
                return f(*args, **kwargs)

        return wrapper

    if fn is not None:
        return deco(fn)
    return deco


#: One closed span: ``(id, parent_id, name, t0, t1, attrs)``; ``t0``/``t1``
#: are ``perf_counter`` seconds, ``parent_id`` is None at the top.
SpanRecord = Tuple[int, Optional[int], str, float, float, Dict[str, Any]]


#: seconds between two anchors of the ring's clock to the wall clock
ANCHOR_EVERY_S = 1.0


class SpanRing(collections.deque):
    """The closed spans of this process, newest last; the oldest drop off
    a full ring.  ``clear()`` empties it (the anchors stay: they describe
    the clocks, not the spans)."""

    def __init__(self, maxlen: int) -> None:
        super().__init__(maxlen=maxlen)
        #: ``(perf_counter seconds, wall-clock ns)``, oldest first, one a
        #: second while spans close (the last hour's: a reader asks about
        #: the run it has just made)
        self.anchors: collections.deque = collections.deque(maxlen=1 << 12)
        self._anchor_due = 0.0

    def snapshot(self) -> List[SpanRecord]:
        return list(self)

    def anchor(self) -> None:
        """Read both clocks now.  The wall clock between two readings of
        ``perf_counter``, paired with their middle: a pair is good to half
        the bracket, ~0.1 us."""
        a = time.perf_counter()
        # the package's one wall-clock read for timing (tests/test_repo_lint
        # names it): the profiler's events are on this clock, not on ours
        wall = time.time_ns()
        b = time.perf_counter()
        self.anchors.append((0.5 * (a + b), wall))
        self._anchor_due = b + ANCHOR_EVERY_S

    def to_trace_clock(self, t: float) -> Optional[int]:
        """``perf_counter`` seconds -> whole ns on the clock a
        ``jax.profiler`` capture stamps its events with (the wall clock; a
        capture writes them less its own ``profile_start_time``).  Between
        two anchors the line through them, outside them the nearest one at
        the rate of ``perf_counter``; None before the first span has
        closed."""
        anchors = self.anchors
        if not anchors:
            return None
        i = bisect.bisect_right(anchors, (t, float("inf")))
        if 0 < i < len(anchors):
            (t0, w0), (t1, w1) = anchors[i - 1], anchors[i]
            return w0 + round((t - t0) * (w1 - w0) / (t1 - t0))
        t0, w0 = anchors[min(i, len(anchors) - 1)]
        return w0 + round((t - t0) * 1e9)


#: The process-wide ring every :class:`span` closes into.
spans = SpanRing(maxlen=1 << 17)
_ids = itertools.count(1)
_open = threading.local()  # .top: the calling thread's innermost open span


def _gc_span(phase: str, info: Dict[str, Any]) -> None:
    """``gc.callbacks``: one ``tdp:host.gc`` record a collection, a child of
    the span that was open when it ran (the record alone, no
    ``TraceAnnotation``: a generation-0 collection takes tens of
    microseconds)."""
    if phase == "start":
        _open.gc_t0 = time.perf_counter()
        return
    t1 = time.perf_counter()
    parent = getattr(_open, "top", None)
    spans.append((next(_ids), parent.id if parent is not None else None,
                  "tdp:host.gc", getattr(_open, "gc_t0", t1), t1,
                  {"generation": info["generation"]}))


gc.callbacks.append(_gc_span)


class span:
    """``with span("tdp:engine.sched", tick=7) as sp: ...`` — a named host
    range.  ``attrs`` are the identifiers and counts of that boundary; more
    may be put into ``sp.attrs`` until the span closes.  On exit the record
    goes to :data:`spans` and to the enclosing span's ``children``."""

    __slots__ = ("name", "attrs", "id", "parent", "t0", "t1", "children",
                 "_annot")

    def __init__(self, name: str, **attrs: Any) -> None:
        self.name = name
        self.attrs = attrs
        self.children: List[SpanRecord] = []

    def __enter__(self) -> "span":
        self.id = next(_ids)
        self.parent = getattr(_open, "top", None)
        _open.top = self
        self._annot = jax.profiler.TraceAnnotation(self.name)
        self._annot.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._annot.__exit__(*exc)
        parent = _open.top = self.parent
        rec = (self.id, parent.id if parent is not None else None, self.name,
               self.t0, self.t1, self.attrs)
        spans.append(rec)
        if parent is not None:
            parent.children.append(rec)
        if self.t1 >= spans._anchor_due:
            spans.anchor()
