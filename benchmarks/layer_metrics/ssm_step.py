"""What the readers of the state-space cell share
(``ssm_step_roofline.batch``, ``ssm_state_share.batch``,
``cache_padding_ratio.batch``).

The decode call of a model whose layers are mostly recurrent moves more
state than weights, and how much of either depends on the call: the
DECODING slots' state is read and written, the live positions' K and V are
read.  So a call's cost is put together from ITS OWN dispatch span's
counters (``slots``, ``live_tokens``) at the family's unit costs
(``families/granite_hybrid.step_unit``, handed over under
``costs['paged_decode']['step_unit']``), and a traced execution is held to
the call that it is: executions and the program's dispatch and fetch spans
are brought to one clock and matched in dispatch order
(``swa_kernels.matched_calls``, which says why the window's mean cost reads
over 100% where the traced seconds are the first wave's).  Every reader
returns None where there is nothing to read: a parent commit, another
family, an untraced run."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks import costs as C
from benchmarks import stats
from benchmarks import trace_reduce as R
from benchmarks.layer_metrics import idle_by_phase as I
from benchmarks.layer_metrics import program_spans as P
from benchmarks.layer_metrics import swa_kernels as K
from benchmarks.layer_metrics.readers import _first_device

DECODE_KERNEL = "^%paged_decode[.0-9]* "
DECODE = P.DISPATCH[1]
_NEED = ("fixed_bytes", "state_bytes_per_slot", "kv_bytes_per_position",
         "qo_bytes_per_slot", "attention_layers", "flops_per_slot",
         "flops_per_position")


def step_unit(obs: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The family's unit costs of one decode call; None where it gives
    none (another family)."""
    unit = (obs.get("costs", {}).get("paged_decode") or {}).get("step_unit")
    return unit if unit and all(k in unit for k in _NEED) else None


def call_cost(unit: Dict[str, float], attrs: Dict[str, Any]) -> Dict[str, float]:
    """One decode call, from its dispatch span's counters: every weight
    once, its decoding slots' state read and written, its live positions'
    K and V read in each attention layer."""
    slots, live = attrs["slots"], attrs["live_tokens"]
    state = slots * unit["state_bytes_per_slot"]
    attn = unit["attention_layers"] * (
        live * unit["kv_bytes_per_position"]
        + slots * unit["qo_bytes_per_slot"])
    return {"flops": (slots * unit["flops_per_slot"]
                      + unit["attention_layers"] * live
                      * unit["flops_per_position"]),
            "bytes": unit["fixed_bytes"] + state + attn,
            "state_bytes": state}


def _counted(attrs: Dict[str, Any]) -> bool:
    return "slots" in attrs and "live_tokens" in attrs


def window_decode_calls(obs: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The attrs of the window's decode dispatch spans that carry both
    counters."""
    win = P.window(obs)
    return [k[5] for kids in win[1] for k in kids
            if k[2] == DECODE and _counted(k[5])] if win else []


def traced_decode_calls(obs: Dict[str, Any]) -> Optional[List[K.Call]]:
    """``swa_kernels.matched_calls`` of the run's traced part for the
    program that runs ``paged_decode``: each decode dispatch span with the
    whole execution it made."""
    tr = obs.get("trace") or {}
    win = P.window(obs)
    name = K.program(obs, DECODE_KERNEL)
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
    got = K.matched_calls(tr["modules"], on_wall[1], name, True)
    return [c for c in got or () if _counted(c[0])] or None


def step_roofline(obs: Dict[str, Any]) -> Optional[float]:
    """The least time the chip could take for the traced decode calls, each
    at its own cost, over the time EVERY operation took inside their
    executions, percent."""
    unit = step_unit(obs)
    calls = traced_decode_calls(obs) if unit else None
    if not calls:
        return None
    took = R.op_seconds(
        R.within(_first_device(obs), R.union(r for c in calls for r in c[2])),
        "^%")
    least = sum(C.roofline_seconds(call_cost(unit, c[0]), obs["peaks"])
                ["seconds"] for c in calls)
    return 100.0 * least / took if took > 0 else None


def state_share(obs: Dict[str, Any]) -> Optional[float]:
    """The recurrent state's part of a decode call's bytes, median over the
    window's decode calls, percent."""
    unit = step_unit(obs)
    calls = window_decode_calls(obs) if unit else []
    shares = []
    for attrs in calls:
        cost = call_cost(unit, attrs)
        shares.append(100.0 * cost["state_bytes"] / cost["bytes"])
    return stats.median(shares) if shares else None


def padding_ratio(obs: Dict[str, Any]) -> Optional[float]:
    """What the pool and the state took of the device over their logical
    bytes, from the two init spans of the engine the window drove."""
    win = P.window(obs)
    if win is None:
        return None
    took = logical = 0
    for name in ("tdp:engine.init.pool", "tdp:engine.init.state"):
        found = [r[5] for r in win[2] if r[2] == name]
        if not found or "device_bytes" not in found[-1] or (
                "bytes" not in found[-1]):
            return None
        took += found[-1]["device_bytes"]
        logical += found[-1]["bytes"]
    return took / logical if logical else None
