"""Per-step collective ledger, parsed from the compiled step's HLO.

PR 1's telemetry can say a step is slow; nothing could say *where the
bytes go*: how much traffic the dp grad sync moves vs the tp activation
collectives vs the MoE all-to-all.  This module answers that from the
compiler's own output — ``compiled.as_text()`` of the AOT-compiled step
that :class:`~.telemetry.Telemetry` already captures (no second compile,
no profiler run):

1. every collective instruction (``all-reduce``, ``all-gather``,
   ``reduce-scatter``, ``all-to-all``, ``collective-permute``, plus their
   async ``-start`` forms) is enumerated with its payload bytes and
   replica groups;
2. each instruction's replica groups are mapped back onto the mesh: the
   set of mesh axes whose coordinate varies within a group is the set of
   axes the collective spans;
3. each axis set is classified into a parallelism dimension —
   ``dp`` / ``tp`` / ``pp`` / ``moe`` / ``other`` — from the topology's
   canonical axis names, yielding a per-dimension byte-and-op ledger.

Payload convention (matches ``dist.comm_bench``'s nccl-tests-style
``bytes``): the FULL logical payload of the collective — the sum of the
operand bytes, times the group size for all-gather (whose operand is the
local shard).  The per-link *wire* bytes (the ``(n-1)/n`` bus factors)
are applied by :mod:`.comm_model` when predicting time, not here.

Known limitation: the ledger counts each HLO instruction ONCE.  A
collective inside a ``while`` loop body (e.g. the pipeline schedules'
scan) executes once per trip but is still one instruction — pipeline p2p
traffic is therefore under-counted by the microbatch count.  The
instruction is still *detected* and classified, so the per-dim op list
remains complete.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

LEDGER_SCHEMA = "tdp-comm-ledger/v1"

# One record shape for every comm measurement/annotation in the repo:
# dist.comm_bench emits these per (op, size) cell, CommModel.calibrate
# consumes them, and the ledger's table renderer understands the same keys.
COMM_RECORD_SCHEMA = "tdp-comm-record/v1"


def comm_record(
    op: str,
    axis: str,
    nbytes: float,
    axis_size: int = 0,
    time_s: Optional[float] = None,
    algbw_GBps: Optional[float] = None,
    busbw_GBps: Optional[float] = None,
    **extra: Any,
) -> Dict[str, Any]:
    """The shared comm record: ``{type, schema, op, axis, bytes, ...}``.

    ``op`` uses comm_bench's underscore spelling (``all_reduce``); ``axis``
    is the mesh-axis name (join multiple with '+').  Measurement fields
    (``time_s`` / ``algbw_GBps`` / ``busbw_GBps``) are optional — a ledger
    annotation has bytes but no time until the cost model predicts one.
    """
    rec: Dict[str, Any] = {
        "type": "comm",
        "schema": COMM_RECORD_SCHEMA,
        "op": str(op),
        "axis": str(axis),
        "axis_size": int(axis_size),
        "bytes": int(nbytes),
    }
    if time_s is not None:
        rec["time_s"] = float(time_s)
    if algbw_GBps is not None:
        rec["algbw_GBps"] = float(algbw_GBps)
    if busbw_GBps is not None:
        rec["busbw_GBps"] = float(busbw_GBps)
    rec.update(extra)
    return rec

# The five collective families the ledger enumerates (issue taxonomy).
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# Mesh-axis name -> parallelism dimension.  Covers the package's canonical
# names (dist.topology) and their view-mesh factorings; anything else (or a
# collective spanning axes of DIFFERENT dimensions) lands in 'other'.
AXIS_DIM: Dict[str, str] = {
    "data": "dp",
    "moe_dp": "dp",
    "data_inter": "dp",
    "data_intra": "dp",
    "batch": "dp",
    "fsdp": "dp",
    "tensor": "tp",
    "model": "tp",
    "pipe": "pp",
    "stage": "pp",
    "moe_ep": "moe",
    "expert": "moe",
    "context": "cp",
}

DTYPE_BITS = {
    "pred": 8, "s2": 2, "u2": 2, "s4": 4, "u4": 4,
    "s8": 8, "u8": 8, "f8e4m3fn": 8, "f8e5m2": 8, "f8e4m3b11fnuz": 8,
    "f8e4m3fnuz": 8, "f8e5m2fnuz": 8, "f8e3m4": 8, "f8e4m3": 8,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64, "c64": 64,
    "c128": 128,
}

# ------------------------------------------------- the one reader of HLO text
#
# Everything in the repo that reads types out of HLO text (this ledger and
# obs.numerics' dtype ledger) goes through the four names below, so a JAX
# that prints HLO differently is met in one place.

# An array type: 'f32[2,16]'.  A tuple type is several of them in parentheses.
_SHAPE_RE = re.compile(r"\b([a-z]\w*)\[([0-9,]*)\]")

# A defining line:
#   %all-reduce.1 = f32[2,16]{1,0} all-reduce(%x), channel_id=1, ...
# Lazy 'res' = the result type, which may be a tuple and on a TPU carries
# parentheses in its layout ('{1,0:T(8,128)}'); the op is the first word
# followed by '(' that stands after whitespace, so a reference such as
# 'get-tuple-element(%all-to-all.2)' is an operand, never a definition.
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+)\s+=\s+(?P<res>.+?)\s+"
    r"(?P<op>[\w-]+)\((?P<rest>.*)$"
)
_OPERAND_NAME_RE = re.compile(r"%([^\s,)]+)")

_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[0-9,{} ]*\}\}|\{\}|\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)"
)
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def hlo_instructions(
    hlo_text: str,
) -> Tuple[List["re.Match[str]"], Dict[str, str]]:
    """Every defining line of an HLO module, in order, as a match with the
    groups ``name``, ``res`` (result type), ``op`` and ``rest`` (the text
    after the op's opening parenthesis: operands, then attributes); and the
    module's ``name -> res`` table, which :func:`operand_shapes` resolves
    a named operand in."""
    instrs = [m for m in map(_DEF_RE.match, hlo_text.splitlines()) if m]
    return instrs, {m.group("name"): m.group("res") for m in instrs}


def type_shapes(type_text: str) -> List[Tuple[str, str]]:
    """``(dtype, dims)`` of every array in a type: one for ``f32[2,16]{1,0}``,
    one per element for a tuple (the ``-start`` forms, variadic results)."""
    return _SHAPE_RE.findall(type_text)


def shape_elems(dims: str) -> int:
    return math.prod(int(d) for d in dims.split(",") if d)


def operand_shapes(
    rest: str, result_types: Dict[str, str]
) -> List[Tuple[str, str]]:
    """``(dtype, dims)`` of every array an instruction takes as an operand.

    ``rest`` is a defining line's text after the op's ``(``; the first
    unmatched ``)`` ends the operand list.  An operand's type is the shape
    printed beside it where the text has one (``f32[2,16]{1,0} %x``: older
    JAX) and otherwise the result type of the instruction it names
    (``%x``: jax 0.9), looked up in ``result_types`` (the table of
    :func:`hlo_instructions`).  An operand that is neither raises
    ``ValueError``: a size nobody can read must not become 0."""
    operands: List[str] = []
    depth, start, end = 0, 0, len(rest)
    for i, c in enumerate(rest):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            if depth == 0:
                end = i
                break
            depth -= 1
        elif c == "," and depth == 0:
            operands.append(rest[start:i])
            start = i + 1
    operands.append(rest[start:end])
    shapes: List[Tuple[str, str]] = []
    for operand in operands:
        if not operand.strip():
            continue
        found = type_shapes(operand)
        if not found:
            nm = _OPERAND_NAME_RE.search(operand)
            found = type_shapes(result_types.get(nm.group(1), "")) if nm else []
            if not found:
                raise ValueError(
                    f"HLO operand {operand.strip()!r} has no shape beside it "
                    "and names no instruction of this module")
        shapes += found
    return shapes


def _operand_bytes(m: "re.Match[str]", result_types: Dict[str, str]) -> int:
    """Bytes of a collective's operands (arrays, so whole bytes each pass
    through; sub-byte dtypes are summed in bits first)."""
    try:
        shapes = operand_shapes(m.group("rest"), result_types)
        bits = sum(DTYPE_BITS[dt] * shape_elems(dims) for dt, dims in shapes)
    except (KeyError, ValueError) as e:
        raise ValueError(
            f"comm ledger cannot size the operands of: {m.string.strip()}"
        ) from e
    return bits // 8


def _expand_replica_groups(text: str) -> List[List[int]]:
    """Decode both replica-group syntaxes:

    - literal:  ``{{0,2,4,6},{1,3,5,7}}``
    - iota v2:  ``[2,4]<=[8]`` or ``[2,4]<=[4,2]T(1,0)`` — reshape an iota
      over the source dims (transposed by T's permutation) into
      [n_groups, group_size].
    """
    text = text.strip()
    if text.startswith("{"):
        groups = []
        for grp in re.findall(r"\{([0-9, ]+)\}", text):
            groups.append([int(x) for x in grp.replace(" ", "").split(",") if x])
        return groups
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", text)
    if not m:
        return []
    out_shape = [int(x) for x in m.group(1).split(",")]
    src_shape = [int(x) for x in m.group(2).split(",")]
    n = math.prod(src_shape)
    ids: Any = list(range(n))
    if m.group(3):
        perm = [int(x) for x in m.group(3).split(",")]
        # transpose without numpy: index arithmetic over the source shape
        import numpy as np

        ids = np.arange(n).reshape(src_shape).transpose(perm).reshape(-1)
        ids = [int(x) for x in ids]
    if len(out_shape) == 1:
        return [ids[: out_shape[0]]]
    g, s = out_shape[0], out_shape[1]
    return [ids[i * s:(i + 1) * s] for i in range(g)]


def parse_hlo_collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """Enumerate collective instructions from HLO text (mesh-independent).

    Returns one record per instruction: ``{op, bytes, groups, group_size,
    n_groups, pairs, channel_id, op_name, async, sched_distance}`` —
    ``groups`` is the decoded replica-group list (device ids), ``pairs``
    the source-target pairs for collective-permute.

    ``sched_distance`` (async ops only, else None): the number of
    instructions the scheduler placed between the ``-start`` and its
    matching ``-done`` — the direct HLO-level measure of how much
    independent compute the transfer can hide behind.  0 means the
    ``-done`` immediately follows the ``-start`` (async in name only);
    the latency-hiding presets of ``dist/overlap.py`` exist to push this
    number up.

    ``overlapped_idx`` (async ops only, else None): indices (into the
    returned list) of OTHER collective instructions issued inside this
    op's start->done window — the instruction-level evidence of
    collective-under-collective overlap (e.g. a TP all-gather issuing
    inside a pipeline ppermute's slack, the synergy-paper ordering
    ``zero_bubble.py`` arranges; :func:`tp_pp_overlap` summarizes it).
    """
    out: List[Dict[str, Any]] = []
    starts: Dict[str, Dict[str, Any]] = {}
    open_starts: List[Dict[str, Any]] = []
    instrs, result_types = hlo_instructions(hlo_text)
    # instr_idx counts defining lines: the unit of the scheduling distance
    for instr_idx, m in enumerate(instrs, 1):
        line = m.string
        op = m.group("op").removesuffix("-start")
        if op.endswith("-done") and op[:-len("-done")] in COLLECTIVE_OPS:
            # the first %name in the operand list is the -start instruction
            onm = _OPERAND_NAME_RE.search(m.group("rest"))
            rec = starts.get(onm.group(1)) if onm else None
            if rec is not None:
                rec["sched_distance"] = max(0, instr_idx - rec["_idx"] - 1)
                if rec in open_starts:
                    open_starts.remove(rec)
            continue
        if op not in COLLECTIVE_OPS:
            continue
        is_start = op != m.group("op")
        operand_bytes = _operand_bytes(m, result_types)
        gm = _REPLICA_GROUPS_RE.search(line)
        groups = _expand_replica_groups(gm.group(1)) if gm else []
        pairs: List[Tuple[int, int]] = []
        pm = _PAIRS_RE.search(line)
        if pm:
            pairs = [
                (int(a), int(b))
                for a, b in re.findall(r"\{(\d+),(\d+)\}", pm.group(1))
            ]
        group_size = max((len(g) for g in groups), default=0)
        nbytes = operand_bytes
        if op == "all-gather" and group_size:
            nbytes = operand_bytes * group_size  # operand is the local shard
        cm = _CHANNEL_RE.search(line)
        nm = _OPNAME_RE.search(line)
        rec = {
            "op": op,
            "bytes": int(nbytes),
            "groups": groups,
            "n_groups": len(groups),
            "group_size": int(group_size),
            "pairs": pairs,
            "channel_id": int(cm.group(1)) if cm else None,
            "op_name": nm.group(1) if nm else None,
            "async": is_start,
            "sched_distance": None,
            "overlapped_idx": None,
            "_idx": instr_idx,
        }
        # this collective was issued inside every currently-open async
        # window — record it as overlapped work those transfers can hide
        for open_rec in open_starts:
            open_rec["overlapped_idx"].append(len(out))
        if rec["async"]:
            rec["overlapped_idx"] = []
            starts[m.group("name")] = rec
            open_starts.append(rec)
        out.append(rec)
    for rec in out:
        rec.pop("_idx", None)
    return out


def classify_axes(axes: Sequence[str]) -> str:
    """Axis-name set -> parallelism dimension.  One unanimous dimension
    wins; an empty set or a mix (e.g. a psum over ('data', 'tensor'))
    is 'other'."""
    dims = {AXIS_DIM.get(a, "other") for a in axes}
    return dims.pop() if len(dims) == 1 else "other"


def _device_coords(mesh) -> Dict[int, Tuple[int, ...]]:
    """device id -> mesh coordinates, from the mesh's device ndarray."""
    import numpy as np

    coords: Dict[int, Tuple[int, ...]] = {}
    arr = np.asarray(mesh.devices, dtype=object)
    for idx in np.ndindex(arr.shape):
        coords[int(arr[idx].id)] = tuple(int(i) for i in idx)
    return coords


def _axes_of_group(
    group: Sequence[int], coords: Dict[int, Tuple[int, ...]], names: Sequence[str]
) -> Tuple[str, ...]:
    """Mesh axes whose coordinate varies across the group's members."""
    cs = [coords[d] for d in group if d in coords]
    if len(cs) < 2:
        return ()
    return tuple(
        names[k] for k in range(len(names))
        if len({c[k] for c in cs}) > 1
    )


def ledger_from_hlo(hlo_text: str, mesh=None) -> Dict[str, Any]:
    """The per-step comm ledger: every collective with payload bytes, the
    mesh axes it spans, and its parallelism dimension, plus per-dimension
    aggregates.

    ``mesh`` defaults to the :data:`~..dist.topology.tpc` base mesh when the
    topology is initialized; without any mesh the instructions are still
    enumerated but axes/dimension fall back to ``()`` / ``'other'``.
    """
    if mesh is None:
        try:
            from ..dist.topology import tpc

            mesh = tpc.mesh  # None when not initialized
        except Exception:
            mesh = None

    coords: Dict[int, Tuple[int, ...]] = {}
    names: Tuple[str, ...] = ()
    if mesh is not None:
        try:
            coords = _device_coords(mesh)
            names = tuple(str(a) for a in mesh.axis_names)
        except Exception:
            coords, names = {}, ()

    collectives: List[Dict[str, Any]] = []
    per_dim: Dict[str, Dict[str, int]] = {}
    total = 0
    for rec in parse_hlo_collectives(hlo_text):
        axes: Tuple[str, ...] = ()
        if coords:
            if rec["groups"]:
                union: set = set()
                for g in rec["groups"]:
                    union.update(_axes_of_group(g, coords, names))
                axes = tuple(a for a in names if a in union)
            elif rec["pairs"]:
                union = set()
                for s, t in rec["pairs"]:
                    union.update(_axes_of_group((s, t), coords, names))
                axes = tuple(a for a in names if a in union)
        dim = classify_axes(axes) if axes else "other"
        entry = {
            "op": rec["op"],
            "bytes": rec["bytes"],
            "axes": list(axes),
            "dim": dim,
            "group_size": rec["group_size"] or (
                math.prod(mesh.shape[a] for a in axes)
                if (axes and mesh is not None) else 0
            ),
            "channel_id": rec["channel_id"],
            "op_name": rec["op_name"],
            "async": rec["async"],
            "sched_distance": rec["sched_distance"],
            "overlapped_idx": rec["overlapped_idx"],
        }
        collectives.append(entry)
        d = per_dim.setdefault(dim, {"bytes": 0, "ops": 0})
        d["bytes"] += entry["bytes"]
        d["ops"] += 1
        total += entry["bytes"]
    async_recs = [c for c in collectives if c["async"]]
    distances = [
        c["sched_distance"] for c in async_recs
        if c["sched_distance"] is not None
    ]
    return {
        "schema": LEDGER_SCHEMA,
        "collectives": collectives,
        "per_dim": per_dim,
        "total_bytes": int(total),
        "n_collectives": len(collectives),
        # async scheduling summary: how many collectives the compiler
        # emitted in split -start/-done form, the bytes they carry, and
        # the mean instruction distance the scheduler achieved between
        # start and done (the latency-hiding evidence comm_model's
        # ``overlap`` report section is computed from)
        "async": {
            "ops": len(async_recs),
            "bytes": int(sum(c["bytes"] for c in async_recs)),
            "sync_ops": len(collectives) - len(async_recs),
            "sync_bytes": int(total - sum(c["bytes"] for c in async_recs)),
            "mean_sched_distance": (
                round(sum(distances) / len(distances), 2) if distances else None
            ),
        },
        "mesh_axes": (
            {str(a): int(mesh.shape[a]) for a in mesh.axis_names}
            if mesh is not None else None
        ),
    }


def tp_pp_overlap(ledger: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """TP-under-PP overlap evidence from a ledger: for every async
    pipeline collective-permute, which tensor-dimension collectives were
    issued inside its start->done window.

    The synergy schedule (``zero_bubble.py``, arXiv 2510.27257) orders
    each boundary ``ppermute`` so a TP stage's SP all-gather/
    reduce-scatter pairs are the independent work between its start and
    done; this report reads the achieved ordering back out of the
    compiled HLO.  On backends whose scheduler never splits the permute
    into -start/-done (the CPU sim), ``pp_async_ops`` is 0 and the rest
    is vacuously 0 — the structure is only *provable* where async
    collectives exist (TPU with the ``dist/overlap.py`` presets).
    """
    out = {
        "pp_async_ops": 0,
        "pp_windows_with_tp": 0,
        "tp_ops_in_pp_windows": 0,
        "tp_bytes_in_pp_windows": 0,
        "mean_pp_sched_distance": None,
    }
    if not ledger or not ledger.get("collectives"):
        return out
    colls = ledger["collectives"]
    distances = []
    for c in colls:
        if c["dim"] != "pp" or not c["async"]:
            continue
        out["pp_async_ops"] += 1
        if c["sched_distance"] is not None:
            distances.append(c["sched_distance"])
        inside = [colls[i] for i in (c.get("overlapped_idx") or [])
                  if i < len(colls)]
        tp_inside = [o for o in inside if o["dim"] == "tp"]
        if tp_inside:
            out["pp_windows_with_tp"] += 1
        out["tp_ops_in_pp_windows"] += len(tp_inside)
        out["tp_bytes_in_pp_windows"] += sum(o["bytes"] for o in tp_inside)
    if distances:
        out["mean_pp_sched_distance"] = round(
            sum(distances) / len(distances), 2)
    return out


def cp_ring_overlap(ledger: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Ring-paged-prefill overlap evidence from a ledger (the PR-20
    analogue of :func:`tp_pp_overlap`): the CP ring's hops are
    python-unrolled ppermutes (ops/ring_paged.py), so every hop is its
    own ``collective-permute`` over the ``context`` axis — ``cp_hops``
    counting them from HLO is the no-while-body-undercount evidence.
    For every hop the scheduler split into -start/-done, the report
    records which non-cp ops (the next sub-chunk's flash accumulation,
    projections, gathers) were issued inside its window — hops hidden
    under chunk compute.  On backends whose scheduler never splits the
    permute (the CPU sim), ``cp_async_hops`` is 0 and the overlap fields
    are vacuously 0; the hop COUNT is backend-independent.
    """
    out = {
        "cp_hops": 0,
        "cp_hop_bytes": 0,
        "cp_async_hops": 0,
        "cp_windows_with_compute_comm": 0,
        "ops_in_cp_windows": 0,
        "mean_cp_sched_distance": None,
    }
    if not ledger or not ledger.get("collectives"):
        return out
    colls = ledger["collectives"]
    distances = []
    for c in colls:
        if c["dim"] != "cp" or c["op"] != "collective-permute":
            continue
        out["cp_hops"] += 1
        out["cp_hop_bytes"] += c["bytes"]
        if not c["async"]:
            continue
        out["cp_async_hops"] += 1
        if c["sched_distance"] is not None:
            distances.append(c["sched_distance"])
        inside = [colls[i] for i in (c.get("overlapped_idx") or [])
                  if i < len(colls)]
        other_inside = [o for o in inside if o["dim"] != "cp"]
        if other_inside:
            out["cp_windows_with_compute_comm"] += 1
        out["ops_in_cp_windows"] += len(inside)
    if distances:
        out["mean_cp_sched_distance"] = round(
            sum(distances) / len(distances), 2)
    return out


def ledger_from_compiled(compiled, mesh=None) -> Optional[Dict[str, Any]]:
    """Ledger from a compiled executable (``jit(f).lower(...).compile()``);
    None when the backend can't render HLO text."""
    try:
        text = compiled.as_text()
    except Exception:
        return None
    if not isinstance(text, str) or not text:
        return None
    return ledger_from_hlo(text, mesh=mesh)


def render_table(ledger: Optional[Dict[str, Any]]) -> str:
    """Human summary table of one ledger."""
    if not ledger or not ledger.get("n_collectives"):
        return "comm ledger: no collectives in the compiled step (single-device program?)"
    L = ["comm ledger (per compiled step):",
         f"{'dim':>6} {'ops':>4} {'bytes':>12}  breakdown"]
    by_dim: Dict[str, Dict[str, Any]] = {}
    for c in ledger["collectives"]:
        d = by_dim.setdefault(c["dim"], {})
        key = (c["op"], tuple(c["axes"]))
        e = d.setdefault(key, {"ops": 0, "bytes": 0})
        e["ops"] += 1
        e["bytes"] += c["bytes"]
    order = ("dp", "tp", "pp", "cp", "moe", "other")
    for dim in sorted(by_dim, key=lambda d: order.index(d) if d in order else 99):
        stats = ledger["per_dim"][dim]
        parts = ", ".join(
            f"{op}x{e['ops']}@{_fmt_bytes(e['bytes'])}"
            f"{('[' + ','.join(ax) + ']') if ax else ''}"
            for (op, ax), e in sorted(by_dim[dim].items())
        )
        L.append(
            f"{dim:>6} {stats['ops']:>4} {_fmt_bytes(stats['bytes']):>12}  {parts}")
    L.append(f"{'total':>6} {ledger['n_collectives']:>4} "
             f"{_fmt_bytes(ledger['total_bytes']):>12}")
    return "\n".join(L)


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"
