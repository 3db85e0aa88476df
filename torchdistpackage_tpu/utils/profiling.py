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
"""

from __future__ import annotations

import collections
import functools
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


class SpanRing(collections.deque):
    """The closed spans of this process, newest last; the oldest drop off
    a full ring.  ``clear()`` empties it."""

    def snapshot(self) -> List[SpanRecord]:
        return list(self)


#: The process-wide ring every :class:`span` closes into.
spans = SpanRing(maxlen=1 << 17)
_ids = itertools.count(1)
_open = threading.local()  # .top: the calling thread's innermost open span


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
