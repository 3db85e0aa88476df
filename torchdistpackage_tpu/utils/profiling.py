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

Scopes inside the compiled programs.  The model's code opens
``jax.named_scope`` with the names of ONE closed vocabulary,
:data:`SCOPES` (``tdp:mixer``, ``tdp:ffn.experts``, ...: no layer index, no
shape), so every device operation of a decode, prefill or train call
carries the component that made it in its compiled ``op_name``; in
TensorBoard / Perfetto / xprof the operation's name-stack field shows it.
:func:`note_program` keeps, for each program the engine or the train step
has made ready, its jitted callable and the SHAPES of its arguments, and
:func:`op_scopes` gives the same offline: instruction name -> ``op_name``
of that program as it was compiled, read on the first ask from the
executable JAX already holds (no compile) and never on the hot path.
"""

from __future__ import annotations

import bisect
import collections
import functools
import gc
import itertools
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np


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


# ------------------------------------------- scopes inside compiled programs

#: The closed vocabulary of ``jax.named_scope`` names the package opens
#: inside its compiled programs (docs/profiling.md says what each covers).
#: A backward operation keeps its forward scope inside
#: ``transpose(jvp(...))``, a recomputed one inside ``checkpoint`` /
#: ``rematted_computation``: the names are not doubled for either.
EMBED = "tdp:embed"
MIXER = "tdp:mixer"
KV_WRITE = "tdp:mixer.kv_write"
ATTEND = "tdp:mixer.attend"
SCAN = "tdp:mixer.scan"
STATE = "tdp:state"
FFN = "tdp:ffn"
ROUTE = "tdp:ffn.route"
DISPATCH = "tdp:ffn.dispatch"
EXPERTS = "tdp:ffn.experts"
COMBINE = "tdp:ffn.combine"
HEAD = "tdp:head"
SAMPLE = "tdp:sample"
LOSS = "tdp:loss"
OPTIMIZER = "tdp:optimizer"
GRAD_REDUCE = "tdp:grad_reduce"
SCOPES = (EMBED, MIXER, KV_WRITE, ATTEND, SCAN, STATE, FFN, ROUTE, DISPATCH,
          EXPERTS, COMBINE, HEAD, SAMPLE, LOSS, OPTIMIZER, GRAD_REDUCE)


def scoped(name: str) -> Callable[[Callable], Callable]:
    """Decorator: the function's operations are traced under
    ``jax.named_scope(name)``, a name of :data:`SCOPES`.  (A scope object
    used as a decorator itself is ONE context manager for every call: a
    function that calls itself leaves its name on the stack behind it.)"""
    def deco(f: Callable) -> Callable:
        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)

        return wrapper

    return deco


#: key -> (the jitted callable, its arguments with every array a shape)
_programs: Dict[str, Tuple[Any, Tuple[Any, ...]]] = {}
_op_scopes: Dict[str, Dict[str, str]] = {}

# a compiled program's text, line by line: an instruction (its name, its
# opcode, what follows), the header of a computation, and inside an
# instruction where its operands end, the names it mentions, the
# computation it applies and the name it was traced under
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\((.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_OPERANDS_END = re.compile(r"\), [a-z_]+=")
_MENTIONS = re.compile(r"%([\w.\-]+)")
_APPLIES = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_SCOPE = re.compile(r"tdp:[\w.]+")
#: what stands between an instruction's own ``op_name`` and the one it is
#: credited under, where the compiler gave it none of the program's
OWNER = "=>"


def note_program(key: str, jitted: Callable, args: Tuple[Any, ...]) -> None:
    """Remember the program that ``jitted(*args)`` makes ready, under
    ``key`` (``decode[64,1]``, ``prefill[2,512]``, ``train``; the newest of a
    key stands).  Called at the ONE call of a signature that compiles or
    loads it.  Of ``args`` only the form is kept: every array leaf as a
    ``jax.ShapeDtypeStruct`` (a committed array's with its sharding, as the
    call saw it), any other leaf as it is; no device buffer is held."""
    def form(x: Any) -> Any:
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if x.committed else None)
        if isinstance(x, np.ndarray):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    _programs[key] = (jitted, jax.tree.map(form, args))
    _op_scopes.pop(key, None)


def parse_op_scopes(text: str) -> Dict[str, str]:
    """A compiled program's text -> instruction name (no ``%``) -> its
    ``op_name`` ('' where the compiler gave none), for every instruction a
    device trace can show: those of the entry, of while bodies and
    conditions, of conditional branches and of called computations, not
    those inside a fusion or a reducer.

    What the compiler makes itself has no name of the program's: a weight's
    prefetch (``copy-start`` / ``slice-done``), a copy into another layout,
    a scan's slice of its stacked operand, a ``ragged-dot`` custom call.
    Such an instruction (no ``tdp:`` token of its own) is credited to what
    CONSUMES its result, the first of its users in the program's order that
    has a scope (through further nameless ones), else to the scope under
    which the ``while`` / ``conditional`` / ``call`` that runs its
    computation was traced, and its entry reads ``<its own op_name>=><its
    owner's>``; one with no such owner (a scan's stacking of its results)
    keeps its own."""
    inside = set()   # computations that a fusion, a reduce, a sort ... applies
    found: Dict[str, List[Tuple[str, str, List[str], List[str]]]] = {}
    rows = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                rows = found.setdefault(head.group(1), [])
            continue
        name, opcode, rest = m.groups()
        applies = _APPLIES.search(rest)
        if applies is not None and opcode != "call":
            inside.add(applies.group(1))
        if rows is not None:
            end = _OPERANDS_END.search(rest)
            cut = end.start() if end else len(rest)
            op = _OP_NAME.search(rest)
            # the computations a while, a conditional or a call runs
            runs = (_MENTIONS.findall(rest[cut:])
                    if opcode in ("while", "conditional", "call") else [])
            rows.append((name, op.group(1) if op else "",
                         _MENTIONS.findall(rest[:cut]), runs))
    out: Dict[str, str] = {}
    caller: Dict[str, Optional[str]] = {}   # computation -> its owner
    # callers stand behind what they call, users behind what they use:
    # backwards, every owner is known before it is asked for
    for comp in reversed(list(found)):
        if comp in inside:
            continue
        owner: Dict[str, Optional[str]] = {}
        users: Dict[str, List[str]] = {}
        for name, _, operands, _ in found[comp]:
            for operand in operands:
                users.setdefault(operand, []).append(name)
        for name, op_name, _, runs in reversed(found[comp]):
            owner[name] = op_name if _SCOPE.search(op_name) else next(
                (owner[u] for u in users.get(name, ()) if owner.get(u)),
                None)
            for callee in runs:   # by where the caller was TRACED alone
                caller.setdefault(callee, op_name if _SCOPE.search(op_name)
                                  else caller.get(comp))
        for name, op_name, _, _ in found[comp]:
            got = owner[name] or caller.get(comp)
            out[name] = (op_name if not got or got == op_name
                         else op_name + OWNER + got.rpartition(OWNER)[2])
    return out


def op_scopes(key: str) -> Dict[str, str]:
    """Instruction name (``fusion.237``) -> ``op_name``
    (``jit(step)/tdp:ffn/tdp:ffn.experts/dot_general``) of the program noted
    under ``key``; empty where none was, or where it was no jitted callable
    (a host stub).  The first ask lowers the callable for the noted shapes
    and takes the executable that JAX's in-memory caches hold since the
    call (no compile request); the answer is kept."""
    got = _op_scopes.get(key)
    if got is None:
        jitted, args = _programs.get(key, (None, ()))
        got = _op_scopes[key] = (
            parse_op_scopes(jitted.lower(*args).compile().as_text())
            if hasattr(jitted, "lower") else {})
    return got
