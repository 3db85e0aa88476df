"""What the roofline readers of the windowed-attention cell share
(``swa_decode_roofline.batch``, ``swa_full_decode_roofline.batch``,
``swa_chunk_roofline.batch``, ``swa_step_roofline.batch``).

Two things a built-in reader gets wrong in a cell whose traced seconds lie
in the first wave's prefill.  ``readers.roofline`` counts a kernel's calls
by the executions of the window's MOST FREQUENT program: here the prefill
call, which holds no ``swa_decode`` at all; these readers name a program by
the kernel it runs, as ``dsa_kernels.decode_program`` does for its own.  And
the runner's costs are the WHOLE window's means (32 slots of ~5k), while the
traced decode calls are the window's first, a few slots that have just left
prefill: held to the window's mean, their kernels read over 100%.  So each
traced execution is held to the work of the call that it IS: executions and
the program's dispatch spans are brought to one clock and matched as the
idle metrics match them (``idle_by_phase``: the device runs calls in the
order they were dispatched, no execution starts before its dispatch span
opens, no fetch returns before its execution ends; a trace that breaks
either law gives no number), and the call's cost is counted from its own
spans' counters at the family's unit costs
(``families/afmoe.paged_decode``'s ``window_unit`` and ``step_unit``):
nothing is an expectation or an upper bound."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmarks import costs as C
from benchmarks import trace_reduce as R
from benchmarks.layer_metrics import idle_by_phase as I
from benchmarks.layer_metrics import program_spans as P
from benchmarks.layer_metrics.readers import _first_device

DECODE_KERNEL = "^%swa_decode[.0-9]* "
CHUNK_KERNEL = "^%swa_chunk[.0-9]* "

#: one dispatch span with the whole executions it made: (the span's attrs,
#: the attrs of the fetch span that waited for its last call, the runs)
Call = Tuple[Dict[str, Any], Dict[str, Any], List[I.Interval]]


def program(obs: Dict[str, Any], kernel: str) -> Optional[str]:
    """The name of the program whose executions run ``kernel``; None without
    a trace or where no program runs it (a parent commit, another model)."""
    ev = _first_device(obs)
    mods = (obs.get("trace") or {}).get("modules") or []
    if ev is None or not mods:
        return None
    best = None
    for name in sorted({n for n, _s, _d in mods}):
        runs = [(s, s + d) for n, s, d in mods if n == name]
        took = R.op_seconds(R.within(ev, R.union(runs)), kernel)
        if took > 0 and (best is None or took > best[0]):
            best = (took, name)
    return None if best is None else best[1]


def matched_calls(modules: List[R.Event], wall: List[I.HostSpan],
                  name: str, decode: bool) -> Optional[List[Call]]:
    """The dispatch spans (decode, or prefill) of ``wall`` (host spans in
    seconds on the wall clock) whose calls ALL ran as whole executions of
    program ``name`` in the trace and were fetched, each with those
    executions; None where spans and executions cannot be brought to one
    clock in order."""
    found = I.calls_of(I.by_tick(wall)[1])
    if found is None:
        return None
    calls, fetched = found
    matched = I.match(modules, calls)
    zero = I.trace_zero(matched, fetched) if matched else None
    if zero is None or not I.in_order(
            matched, [(c, s - zero, e - zero, t) for c, s, e, t in calls],
            {c: f - zero for c, f in fetched.items()}):
        return None
    ran = {(s, s + d) for n, s, d in modules if n == name}
    waited = {a["call"]: a for n, _s, _e, a in wall
              if n == I.FETCH and "call" in a}
    out: List[Call] = []
    for n, _s, _e, attrs in wall:
        if n != P.DISPATCH[decode]:
            continue
        ids = range(attrs["call"] - int(attrs.get("calls", 1)) + 1,
                    attrs["call"] + 1)
        runs = [matched[c] for c in ids if matched.get(c) in ran]
        if len(runs) == len(ids) and attrs["call"] in waited:
            out.append((attrs, waited[attrs["call"]], runs))
    return out


def traced_calls(obs: Dict[str, Any], kernel: str) -> Optional[List[Call]]:
    """:func:`matched_calls` of the run's traced part for the program that
    runs ``kernel``, the spans that carry the window attrs alone; None where
    there is nothing to read (no trace, no such program, a program without
    the ring's anchors or the attrs: a parent commit, a model with one
    pool)."""
    tr = obs.get("trace") or {}
    win = P.window(obs)
    name = program(obs, kernel)
    if win is None or name is None:
        return None
    # from the window's first tick (the capture runs) to a little past the
    # capture's end, on the ring's own clock: as idle_by_phase.traced_split
    opened = win[0][0][3]
    on_wall = I._on_wall_clock([
        r for r in P.ring() or () if r[2].startswith("tdp:")
        and opened <= r[3] < opened + tr["window_s"] + 2.0])
    if on_wall is None:
        return None
    got = matched_calls(tr["modules"], on_wall[1], name,
                        kernel == DECODE_KERNEL)
    return [c for c in got or () if "window_positions" in c[0]] or None


def call_costs(costs: Dict[str, Any], calls: List[Call],
               decode: bool) -> Optional[Dict[str, Dict[str, float]]]:
    """What ``calls`` must do at least, summed, from their own counters:
    ``window`` (ONE window layer's calls), ``global`` (one global layer's)
    and, for decode calls, ``step`` (the whole program).  A decode row
    attends the positions its layer holds for its slot (``window_positions``:
    exactly ``min(window, context)`` a slot, summed; ``live_tokens`` in a
    global layer); a prefill call's real rows attend ``window_pairs`` and
    ``live_pairs`` (row, key) pairs (``min(window, position + 1)`` and
    ``position + 1`` a row, summed by the engine).  The positions held are
    read once as K and V, a row's query read and its output written.  The
    step: every weight but the routed experts', the held experts each call
    TOUCHED (its fetch span's ``experts_touched``), and both kinds' K and V.
    None where the family gives no unit costs or a span lacks a counter."""
    cost = costs.get("paged_decode") or {}
    unit, step = cost.get("window_unit"), cost.get("step_unit")
    need = (("window_positions", "live_tokens", "slots") if decode else
            ("window_positions", "live_tokens", "tokens", "window_pairs",
             "live_pairs"))
    if not unit or not step or not calls or not all(
            k in c[0] for c in calls for k in need):
        return None
    total = lambda key: sum(c[0][key] for c in calls)

    def attend(pairs, held, rows):
        return {"flops": unit["flops_per_pair"] * pairs,
                "bytes": (unit["bytes_per_position"] * held
                          + unit["bytes_per_row"] * rows)}

    held, live = total("window_positions"), total("live_tokens")
    if not decode:
        rows = total("tokens")
        return {"window": attend(total("window_pairs"), held, rows),
                "global": attend(total("live_pairs"), live, rows)}
    slots = total("slots")
    out = {"window": attend(held, held, slots),
           "global": attend(live, live, slots)}
    if all("experts_touched" in c[1] for c in calls):
        touched = sum(c[1]["experts_touched"] for c in calls)
        layers = (cost.get("window_layers", 0),
                  cost.get("calls_per_execution", 0))
        out["step"] = {
            "flops": (step["flops_per_slot"] * slots
                      + layers[0] * out["window"]["flops"]
                      + layers[1] * out["global"]["flops"]),
            "bytes": (step["fixed_bytes"] * len(calls)
                      + step["bytes_per_slot"] * slots
                      + step["expert_bytes"] * touched
                      + layers[0] * out["window"]["bytes"]
                      + layers[1] * out["global"]["bytes"])}
    return out


def roofline(obs: Dict[str, Any], kernel: str, pattern: str, which: str,
             layers: str) -> Optional[float]:
    """The least time the chip could take for the traced calls of the
    program that runs ``kernel`` (``layers`` layers of cost ``which`` each:
    a key of ``costs['paged_decode']``, or 1) over the time the operations
    matching ``pattern`` took inside their executions, percent."""
    calls = traced_calls(obs, kernel)
    if calls is None:
        return None
    costs = call_costs(obs["costs"], calls, kernel == DECODE_KERNEL)
    ev = _first_device(obs)
    took = R.op_seconds(
        R.within(ev, R.union(r for c in calls for r in c[2])), pattern)
    n = obs["costs"]["paged_decode"].get(layers, 1) if costs else 0
    if not n or took <= 0 or which not in costs:
        return None
    least = C.roofline_seconds(costs[which], obs["peaks"])["seconds"]
    return 100.0 * least * n / took
