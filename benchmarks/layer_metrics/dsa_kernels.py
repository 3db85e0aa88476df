"""What the two roofline readers of the indexed-attention kernels share
(``dsa_decode_roofline.batch``, ``dsa_index_roofline.batch``).

``readers.roofline`` counts a kernel's calls by the executions of the
window's MOST FREQUENT program.  In a cell whose traced seconds lie in the
first wave's prefill (eight compact prefill calls a tick beside one decode
call), that is the prefill program, which holds no ``dsa_decode`` at all and
a ``dsa_index`` of another shape.  These readers name their program by what
it runs: the one whose executions hold the ``dsa_decode`` kernel."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks import costs as C
from benchmarks import trace_reduce as R
from benchmarks.layer_metrics.readers import _first_device

DECODE_KERNEL = "^%dsa_decode[.0-9]* "


def decode_program(obs: Dict[str, Any]):
    """``(device events, the decode program's executions)``; None without a
    trace or where no program runs the kernel (a parent commit, another
    model)."""
    ev = _first_device(obs)
    mods = (obs.get("trace") or {}).get("modules") or []
    if ev is None or not mods:
        return None
    best = None
    for name in sorted({n for n, _s, _d in mods}):
        runs = [(s, s + d) for n, s, d in mods if n == name]
        took = R.op_seconds(R.within(ev, R.union(runs)), DECODE_KERNEL)
        if took > 0 and (best is None or took > best[0]):
            best = (took, runs)
    return None if best is None else (ev, best[1])


def roofline(obs: Dict[str, Any], pattern: str,
             cost: Optional[Dict[str, float]], calls: float) -> Optional[float]:
    """The least time the chip could take for ``calls`` calls of ``cost`` an
    execution over the time the operations matching ``pattern`` took inside
    the decode program's executions, percent."""
    found = decode_program(obs)
    if found is None or cost is None:
        return None
    ev, runs = found
    took = R.op_seconds(R.within(ev, R.union(runs)), pattern)
    if took <= 0:
        return None
    least = C.roofline_seconds(cost, obs["peaks"])["seconds"] * len(runs) * calls
    return 100.0 * least / took
