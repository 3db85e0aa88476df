"""What the readers of the MiMo cell share (``sink_decode_roofline.batch``,
``sink_chunk_roofline.batch``, ``wide_decode_roofline.batch``,
``wide_chunk_roofline.batch``, ``mimo_step_roofline.batch``,
``pool_padding_ratio.batch``).

The model's two kinds of attention layer differ in what a position costs:
a window layer keeps 8 KV heads and reads at most 128 positions a row under
a sink, a global layer keeps 4 and reads every live position, and in both a
key is 192 wide over a value of 128.  ``swa_kernels.call_costs`` has ONE
unit cost for both kinds; here each kind is held to its own
(``families/mimo_v2.paged_decode``'s ``window_unit`` and ``global_unit``).
Everything else is ``swa_kernels``': a program is named by the kernel it
runs (``swa_kernels.program``), and each traced execution is held to the
work of the call that it IS, from that call's own dispatch and fetch spans
(``swa_kernels.traced_calls``, which names the program by
``swa_kernels.program`` and matches by ``swa_kernels.matched_calls``:
executions and spans brought to one clock and matched in dispatch order; a
trace that breaks the order gives no number).  Costs count positions INSIDE the window
and live positions, whatever the kernels fetch, so no share can read over
100%.  Every reader returns None where there is nothing to read: a parent
commit, another family, an untraced run."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks import costs as C
from benchmarks import trace_reduce as R
from benchmarks.layer_metrics import program_spans as P
from benchmarks.layer_metrics.readers import _first_device
from benchmarks.layer_metrics.swa_kernels import (  # noqa: F401 (readers')
    CHUNK_KERNEL, DECODE_KERNEL, Call, traced_calls)

#: the global layers' kernels, by name
WIDE_DECODE = "^%paged_decode[.0-9]* "
WIDE_CHUNK = "^%paged_chunk[.0-9]* "


def call_costs(costs: Dict[str, Any], calls: List[Call],
               decode: bool) -> Optional[Dict[str, Dict[str, float]]]:
    """What ``calls`` must do at least, summed, from their own counters:
    ``window`` (ONE window layer's calls, at ``window_unit``), ``global``
    (one global layer's, at ``global_unit``) and, for decode calls, ``step``
    (the whole program).  A decode row attends the positions its layer
    holds for its slot (``window_positions``: exactly ``min(window,
    context)`` a slot, summed; ``live_tokens`` in a global layer); a prefill
    call's real rows attend ``window_pairs`` and ``live_pairs`` (row, key)
    pairs.  The positions held are read once as K and V, a row's query read
    and its output written.  The step: every weight but the routed experts'
    once a call, the held experts that call TOUCHED (its fetch span's
    ``experts_touched``), and both kinds' K and V.  None where the family
    gives no unit cost a kind or a span lacks a counter."""
    cost = costs.get("paged_decode") or {}
    units = {"window": cost.get("window_unit"),
             "global": cost.get("global_unit")}
    step = cost.get("step_unit")
    need = (("window_positions", "live_tokens", "slots") if decode else
            ("window_positions", "live_tokens", "tokens", "window_pairs",
             "live_pairs"))
    if not all(units.values()) or not step or not calls or not all(
            k in c[0] for c in calls for k in need):
        return None
    total = lambda key: sum(c[0][key] for c in calls)

    def attend(kind, pairs, held, rows):
        unit = units[kind]
        return {"flops": unit["flops_per_pair"] * pairs,
                "bytes": (unit["bytes_per_position"] * held
                          + unit["bytes_per_row"] * rows)}

    held, live = total("window_positions"), total("live_tokens")
    if not decode:
        rows = total("tokens")
        return {"window": attend("window", total("window_pairs"), held, rows),
                "global": attend("global", total("live_pairs"), live, rows)}
    slots = total("slots")
    out = {"window": attend("window", held, held, slots),
           "global": attend("global", live, live, slots)}
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
    took = R.op_seconds(
        R.within(_first_device(obs), R.union(r for c in calls for r in c[2])),
        pattern)
    n = obs["costs"]["paged_decode"].get(layers, 1) if costs else 0
    if not n or took <= 0 or which not in costs:
        return None
    least = C.roofline_seconds(costs[which], obs["peaks"])["seconds"]
    return 100.0 * least * n / took


def padding_ratio(obs: Dict[str, Any]) -> Optional[float]:
    """``device_bytes`` over ``bytes`` of the ``tdp:engine.init.pool`` span
    of the engine the window drove, both pools together: what the leaves
    took of the device over their logical bytes.  None where the span says
    nothing of a head's widths (``key_width``: a parent commit) or the
    backend counts no memory."""
    win = P.window(obs)
    pools = [r[5] for r in win[2] if r[2] == "tdp:engine.init.pool"
             ] if win else []
    if not pools or not all(k in pools[-1] for k in (
            "key_width", "device_bytes", "bytes")) or not pools[-1]["bytes"]:
        return None
    return pools[-1]["device_bytes"] / pools[-1]["bytes"]
