"""Device time inside the compiled programs, by the model component that
made each operation: the program's ``jax.named_scope`` names
(``torchdistpackage_tpu.utils.profiling.SCOPES``: ``tdp:mixer``,
``tdp:ffn.experts``, ...) as the compiled ``op_name`` of every instruction
carries them.

The reduced trace keeps an operation's name, start and duration and no
stat, so the name stack comes from the PROGRAM: ``profiling.op_scopes(key)``
gives instruction name -> ``op_name`` for a program the process has run
(``key``: the ``program`` attr of the engine's dispatch spans,
``decode[64,1]`` / ``prefill[2,512]``; ``train`` for ``DataParallel``'s
step).  Serving: the traced executions are matched to the ring's calls as
``idle_by_phase`` matches them (the j-th whole execution is the j-th call
dispatched; the two laws of ``in_order`` hold the match), so each has its
kind and its table.  Training: the executions of the window's most frequent
program, against ``train``.

An operation (wrappers left out: a ``while``'s time is its body's) is
credited to the INNERMOST ``tdp:`` token of its ``op_name`` (the LAST in the
string: what the compiler made without a name reads ``<its own>=><its
consumer's>`` in the table, and goes to the consumer's scope), '' where it
has none; a metric sums the scopes under its prefixes (``tdp:mixer`` holds
``tdp:mixer.attend``) and gives the mean over the traced executions of its
kind, ms an execution.  A backward operation keeps its forward scope inside
``transpose(jvp(...))`` and a recomputed one inside
``rematted_computation``: ``holds`` reads such tokens of the same name.

Pure functions over tuples first, as ``idle_by_phase.py``; the tests drive
them with synthetic lists.  Every reader gives None where there is nothing
to read: no trace, no registry (a parent commit), dispatch spans without
``program``, a match that breaks a law, or a table that names under 99% of
the executions' operation time (a table of another program)."""

from __future__ import annotations

import bisect
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmarks import trace_reduce as R
from benchmarks.layer_metrics import idle_by_phase as I
from benchmarks.layer_metrics import program_spans as P

TOKEN = re.compile(r"tdp:[\w.]+")
#: the least share of a kind's operation time whose instructions the table
#: must name
NAMED = 0.99
KIND = {P.DISPATCH[0]: "prefill", P.DISPATCH[1]: "decode"}
TRAIN = "train"

#: one traced execution: (kind, the key of its program's table, its
#: operations)
Execution = Tuple[str, str, Sequence[R.Event]]


def instruction(name: str) -> str:
    """A device event's name (the whole HLO line, ``%fusion.3 = bf16[...]
    fusion(...)``) -> the instruction's, ``fusion.3``."""
    return name.partition(" = ")[0].strip().lstrip("%")


def scope_of(op_name: str) -> str:
    """The innermost ``tdp:`` token of an ``op_name``; '' without one."""
    found = TOKEN.findall(op_name)
    return found[-1] if found else ""


def under(scope: str, prefixes: Sequence[str]) -> bool:
    return any(scope == p or scope.startswith(p + ".") for p in prefixes)


def by_kind(executions: Sequence[Execution],
            tables: Dict[str, Dict[str, str]]) -> Dict[str, Dict[str, Any]]:
    """kind -> ``{'n': its executions, 'total': their operations' seconds,
    'named': the part whose instruction its table holds, 'ops': op_name ->
    seconds of that part}``, wrappers left out."""
    out: Dict[str, Dict[str, Any]] = {}
    # an event's name -> its instruction, None for a wrapper: the names of
    # one execution come again in the next
    seen: Dict[str, Optional[str]] = {}
    for kind, key, ops in executions:
        row = out.setdefault(kind, {"n": 0, "total": 0.0, "named": 0.0,
                                    "ops": {}})
        row["n"] += 1
        table = tables.get(key) or {}
        for name, _, d in ops:
            if name not in seen:
                seen[name] = (None if R.is_wrapper(name)
                              else instruction(name))
            if seen[name] is None:
                continue
            row["total"] += d
            op_name = table.get(seen[name])
            if op_name is not None:
                row["named"] += d
                row["ops"][op_name] = row["ops"].get(op_name, 0.0) + d
    return out


def read_ms(rows: Dict[str, Dict[str, Any]], kind: str,
            scopes: Optional[Sequence[str]] = None,
            holds: Optional[str] = None) -> Optional[float]:
    """ms an execution of ``kind`` under the ``scopes`` prefixes (None: any)
    in operations whose ``op_name`` matches ``holds`` (None: any).  None
    where no execution of the kind was read or its table is another
    program's."""
    row = rows.get(kind)
    if not row or not row["total"] or row["named"] < NAMED * row["total"]:
        return None
    rx = re.compile(holds) if holds else None
    took = sum(d for op_name, d in row["ops"].items()
               if (scopes is None or under(scope_of(op_name), scopes))
               and (rx is None or rx.search(op_name)))
    return 1e3 * took / row["n"]


def unscoped_percent(rows: Dict[str, Dict[str, Any]],
                     kinds: Sequence[str]) -> Optional[float]:
    """The operation time of the executions of ``kinds`` under NO ``tdp:``
    token, percent of all of it."""
    got = [rows[k] for k in kinds if k in rows]
    total = sum(r["total"] for r in got)
    if not total or any(r["named"] < NAMED * r["total"] for r in got):
        return None
    bare = sum(d for r in got for op_name, d in r["ops"].items()
               if not scope_of(op_name))
    return 100.0 * (bare + total - sum(r["named"] for r in got)) / total


def split(ops: Sequence[R.Event], runs: Sequence[Tuple[float, float]],
          ) -> List[List[R.Event]]:
    """For each of the disjoint intervals ``runs`` the operations that
    start inside it."""
    order = sorted(range(len(runs)), key=lambda i: runs[i])
    starts = [runs[i][0] for i in order]
    out: List[List[R.Event]] = [[] for _ in runs]
    for ev in R.within(ops, R.union(runs)):
        j = bisect.bisect_right(starts, ev[1]) - 1
        if j >= 0 and ev[1] < runs[order[j]][1]:
            out[order[j]].append(ev)
    return out


def programs_of(ticks: Sequence[Sequence[I.HostSpan]]) -> Optional[
        Dict[int, Tuple[str, str]]]:
    """call id -> (kind, ``program``) from the ticks' dispatch spans, as
    ``idle_by_phase.calls_of`` numbers the calls; None where one carries no
    ``program`` (a program from before the attr)."""
    out: Dict[int, Tuple[str, str]] = {}
    for kids in ticks:
        for name, _, _, attrs in kids:
            if name not in KIND:
                continue
            if "program" not in attrs or "call" not in attrs:
                return None
            k = int(attrs.get("calls", 1))
            for j in range(k):
                out[attrs["call"] - k + 1 + j] = (KIND[name], attrs["program"])
    return out


def matched_executions(modules: Sequence[R.Event], ops: Sequence[R.Event],
                       wall: Sequence[I.HostSpan]) -> Optional[
        List[Execution]]:
    """The traced executions ``modules`` with the operations ``ops`` of
    each, every one given the kind and the program of the call it is:
    ``wall`` are the ring's spans on the wall clock, the capture's start is
    found as ``idle_by_phase`` finds it, and the match must hold both of
    ``in_order``'s laws."""
    ticks = I.by_tick(wall)[1]
    found, programs = I.calls_of(ticks), programs_of(ticks)
    if found is None or programs is None:
        return None
    calls, fetched = found
    matched = I.match(modules, calls)
    zero = I.trace_zero(matched, fetched) if matched else None
    if zero is None:
        return None
    # on the executions' clock now, as idle_by_phase holds them
    calls = [(c, s - zero, e - zero, i) for c, s, e, i in calls]
    fetched = {c: t - zero for c, t in fetched.items()}
    matched = I.match(modules, calls, fetched)
    if matched is None or not I.in_order(matched, calls, fetched):
        return None
    cids = [c for c in matched if c in programs]
    inside = split(ops, [matched[c] for c in cids])
    return [programs[c] + (evs,) for c, evs in zip(cids, inside)]


# ------------------------------------------------------ what a run hands over


def _tables() -> Optional[Callable[[str], Dict[str, str]]]:
    try:
        from torchdistpackage_tpu.utils import profiling
    except ImportError:
        return None
    return getattr(profiling, "op_scopes", None)


def _serving(obs: Dict[str, Any]) -> Optional[List[Execution]]:
    tr = obs.get("trace") or {}
    win, ev = P.window(obs), P._first_device(obs)
    if win is None or ev is None or not tr.get("modules"):
        return None
    opened = win[0][0][3]   # as idle_by_phase.traced_split cuts the ring
    on_wall = I._on_wall_clock([
        r for r in P.ring() or () if r[2].startswith("tdp:")
        and opened <= r[3] < opened + tr["window_s"] + 2.0])
    if on_wall is None:
        return None
    return matched_executions(tr["modules"], ev, on_wall[1])


def _training(obs: Dict[str, Any]) -> Optional[List[Execution]]:
    ev = P._first_device(obs)
    mods = (obs.get("trace") or {}).get("modules") or []
    if ev is None or not mods:
        return None
    name = R.most_frequent_module(mods)
    runs = [(s, s + d) for n, s, d in mods if n == name]
    return [(TRAIN, TRAIN, evs) for evs in split(ev, runs)]


_last: Tuple[Any, Any] = (None, None)


def rows_of(obs: Dict[str, Any]) -> Optional[Dict[str, Dict[str, Any]]]:
    """:func:`by_kind` of the run's traced executions (kept for the run's
    other metrics: fifteen read it)."""
    global _last
    if _last[0] is obs:
        return _last[1]
    op_scopes = _tables()
    rows = None
    if op_scopes is not None:
        runs = (_serving(obs) if obs["spans"].get("engine_step")
                else _training(obs))
        if runs:
            rows = by_kind(runs, {key: op_scopes(key)
                                  for key in {r[1] for r in runs}})
    _last = (obs, rows)
    return rows


def read(obs: Dict[str, Any], kind: str,
         scopes: Optional[Sequence[str]] = None,
         holds: Optional[str] = None) -> Optional[float]:
    rows = rows_of(obs)
    return None if rows is None else read_ms(rows, kind, scopes, holds)


def unscoped_share(obs: Dict[str, Any],
                   kinds: Sequence[str]) -> Optional[float]:
    rows = rows_of(obs)
    return None if rows is None else unscoped_percent(rows, kinds)
