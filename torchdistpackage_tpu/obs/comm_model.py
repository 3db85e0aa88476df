"""Alpha–beta (Hockney) cost model for the comm ledger.

Predicts the time of every collective in a :mod:`.comm_ledger` ledger from
per-axis link parameters — ``t = steps(op, n) * alpha + wire_bytes / beta``
where ``steps`` is the latency-term count of the ring algorithm and
``wire_bytes`` applies the same nccl-tests bus factors as
``dist.comm_bench``:

====================  ==============  =====================
op                    steps(n)        wire_bytes / payload
====================  ==============  =====================
all_reduce            ``2(n-1)``      ``2(n-1)/n``
all_gather            ``n-1``         ``(n-1)/n``
reduce_scatter        ``n-1``         ``(n-1)/n``
all_to_all            ``n-1``         ``(n-1)/n``
ppermute              ``1``           ``1``
====================  ==============  =====================

Two parameter sources:

- **tables** (:data:`GENERATION_DEFAULTS`): public per-chip aggregate ICI
  bandwidth and DCN defaults per TPU generation (v4/v5e/v5p/v6) — the
  zero-measurement prior, looked up from ``device_kind``;
- **calibration** (:meth:`CommModel.calibrate`): runs
  ``dist.comm_bench.bench_collective`` over each mesh axis and least-squares
  fits measured (steps, wire_bytes, time) samples to per-axis alpha/beta —
  ground truth for THIS fabric, including the CPU sim (where the tables
  would be fiction).

:func:`comm_report` combines a ledger, the model, and Telemetry's measured
step time + XLA cost analysis into the RUNREPORT ``comm`` section: modeled
comm time per dimension, a comm-bound vs compute-bound verdict, and the
overlap-headroom estimate (how much step time perfect compute/comm overlap
could recover).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# Public interconnect specs per TPU generation: per-chip aggregate ICI
# bandwidth (one direction, all links), and conservative DCN defaults
# (per-host NIC).  Latencies are order-of-magnitude link latencies — the
# alpha prior; calibrate() replaces both with measurements.
GENERATION_DEFAULTS: List[Tuple[str, Dict[str, float]]] = [
    ("v6", {"ici_bw_GBps": 448.0, "ici_lat_us": 1.0}),
    ("v5p", {"ici_bw_GBps": 600.0, "ici_lat_us": 1.0}),
    ("v5e", {"ici_bw_GBps": 200.0, "ici_lat_us": 1.0}),
    ("v5 lite", {"ici_bw_GBps": 200.0, "ici_lat_us": 1.0}),
    ("v4", {"ici_bw_GBps": 300.0, "ici_lat_us": 1.0}),
    ("v3", {"ici_bw_GBps": 140.0, "ici_lat_us": 1.5}),
    ("v2", {"ici_bw_GBps": 62.5, "ici_lat_us": 2.0}),
    # the CPU sim has no fabric: a placeholder so table-based planning runs
    # in tests; calibrate() gives the sim's real numbers
    ("cpu", {"ici_bw_GBps": 100.0, "ici_lat_us": 1.0}),
]
DCN_DEFAULTS = {"dcn_bw_GBps": 25.0, "dcn_lat_us": 10.0}

# Steps (latency terms) and wire-bytes factor of the ring algorithms;
# op names in comm_bench's underscore convention.
_STEPS = {
    "all_reduce": lambda n: 2 * (n - 1),
    "all_gather": lambda n: n - 1,
    "reduce_scatter": lambda n: n - 1,
    "all_to_all": lambda n: n - 1,
    "ppermute": lambda n: 1,
}
_WIRE_FACTOR = {
    "all_reduce": lambda n: 2 * (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
}

# HLO instruction name (comm_ledger) -> model op name.
_HLO_OP = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "all_to_all",
    "collective-permute": "ppermute",
}

# ------------------------------------------------- compressed-ring costing
# The int8 ring collectives (dist/compressed.py) carry 1 byte/elem payload
# plus one f32 scale per COMPRESS_GROUP elements.  obs is a leaf subsystem
# (imports nothing from the package), so the group size is mirrored here;
# tests/test_compression.py pins the two constants together.

COMPRESS_GROUP = 256

COMPRESSION_SCHEMA = "tdp-compression/v1"

#: ops the int8 rings implement (model-op spelling)
_COMPRESSIBLE_OPS = ("all_reduce", "reduce_scatter", "all_gather")

#: comm_bench's int8 arm names -> the exact op each one replaces
INT8_BENCH_OPS = {
    "int8_all_reduce": "all_reduce",
    "int8_reduce_scatter": "reduce_scatter",
    "int8_all_gather": "all_gather",
}


def compressed_payload_bytes(
    payload_bytes: float, elem_bytes: int = 4, group: int = COMPRESS_GROUP
) -> float:
    """Quantized logical payload: 1 byte/elem + the f32 scale sideband."""
    elems = payload_bytes / max(1, elem_bytes)
    return elems * (1.0 + 4.0 / group)


def compressed_wire_bytes(
    op: str, payload_bytes: float, n: int,
    elem_bytes: int = 4, group: int = COMPRESS_GROUP,
) -> float:
    """Per-link bytes the int8 ring serializes for a full ``payload_bytes``
    collective (the compressed analogue of :func:`wire_bytes`):

    - ``reduce_scatter`` / ``all_gather`` — one ring pass: ``(n-1)/n``
      of the quantized payload;
    - ``all_reduce`` (the ``int8_ring_pmean`` decomposition) — ring pass
      + invariance-typed int8 psum gather: ``3(n-1)/n`` (the psum leg is
      an all-reduce of the quantized payload, ``2(n-1)/n``).
    """
    op = _HLO_OP.get(op, op)
    if op not in _COMPRESSIBLE_OPS:
        raise ValueError(f"no int8 ring for {op!r}")
    if n <= 1:
        return 0.0
    q = compressed_payload_bytes(payload_bytes, elem_bytes, group)
    factor = 3.0 if op == "all_reduce" else 1.0
    return factor * q * (n - 1) / n


def compressed_ledger_bytes(
    op: str, payload_bytes: float, n: int,
    elem_bytes: int = 4, group: int = COMPRESS_GROUP,
) -> float:
    """Bytes the HLO comm ledger counts for one int8 ring collective —
    per-INSTRUCTION operand payloads of the unrolled rings (s8 chunks +
    f32 scales), the apples-to-apples prediction for the ledger's
    measured per-axis bytes (RUNREPORT ``compression`` section):

    - ring pass: n-1 ppermutes of a 1/n quantized chunk = ``(n-1)/n * q``;
    - ``all_reduce`` adds the masked psum of the full quantized payload
      (counted once, by the ledger's payload convention) = ``+ q``.

    The exact arm's ledger bytes are simply ``payload_bytes`` for all
    three ops (all-gather: operand x group size = the full payload).
    """
    op = _HLO_OP.get(op, op)
    if op not in _COMPRESSIBLE_OPS:
        raise ValueError(f"no int8 ring for {op!r}")
    if n <= 1:
        return 0.0
    q = compressed_payload_bytes(payload_bytes, elem_bytes, group)
    extra = q if op == "all_reduce" else 0.0
    return q * (n - 1) / n + extra


def steps_for(op: str, n: int) -> int:
    return int(_STEPS[op](max(2, n))) if n > 1 else 0


def wire_bytes(op: str, payload_bytes: float, n: int) -> float:
    """Per-link bytes actually serialized for a full ``payload_bytes``
    collective over ``n`` participants (nccl-tests bus convention)."""
    if n <= 1:
        return 0.0
    return payload_bytes * _WIRE_FACTOR[op](n)


def fit_alpha_beta(
    samples: Sequence[Tuple[float, float, float]],
) -> Tuple[float, float]:
    """Least-squares fit of ``t = steps * alpha + wire / beta``.

    ``samples`` rows are ``(steps, wire_bytes, time_s)``.  Returns
    ``(alpha_s, beta_Bps)``; alpha is clipped at 0 (a negative latency is a
    fit artifact) and beta refit under that constraint.

    The fit minimizes RELATIVE residuals (rows weighted by ``1/t``):
    absolute least squares would let timing noise on the large
    bandwidth-dominated samples (milliseconds) swamp the microsecond-scale
    alpha that only the small samples constrain.
    """
    arr = np.asarray(list(samples), dtype=np.float64)
    if arr.ndim != 2 or arr.shape[0] < 1 or arr.shape[1] != 3:
        raise ValueError(f"need rows of (steps, wire_bytes, time_s); got {arr.shape}")
    A = arr[:, :2]
    t = arr[:, 2]
    w = np.where(t > 0, 1.0 / np.maximum(t, 1e-12), 1.0)
    sol, *_ = np.linalg.lstsq(A * w[:, None], t * w, rcond=None)
    alpha, inv_beta = float(sol[0]), float(sol[1])
    if alpha < 0 or inv_beta <= 0:
        alpha = max(0.0, alpha)
        resid = (t - alpha * A[:, 0]) * w
        wired = A[:, 1] * w
        denom = float(wired @ wired)
        inv_beta = float(wired @ resid) / denom if denom > 0 else 0.0
    if inv_beta <= 0:
        # degenerate timings (all latency): infinite bandwidth, pure alpha
        alpha = float(np.mean(t / np.maximum(A[:, 0], 1.0)))
        return alpha, float("inf")
    return alpha, 1.0 / inv_beta


@dataclasses.dataclass
class AxisCost:
    """Per-mesh-axis link parameters: startup latency + bus bandwidth."""

    alpha_s: float
    beta_Bps: float
    kind: str = "table"  # 'table' | 'dcn-table' | 'calibrated'

    def as_dict(self) -> Dict[str, Any]:
        return {
            "alpha_s": self.alpha_s,
            "beta_GBps": (
                self.beta_Bps / 1e9 if math.isfinite(self.beta_Bps) else None
            ),
            "kind": self.kind,
        }


class CommModel:
    """Per-axis alpha–beta model over a mesh.

    ``axis_costs`` maps mesh-axis name -> :class:`AxisCost`; ``default``
    covers collectives whose axis set is unknown (no mesh at parse time) or
    spans several axes (the bottleneck — slowest beta, largest alpha — of
    the involved axes is used when they ARE known).
    """

    def __init__(
        self,
        axis_costs: Dict[str, AxisCost],
        default: Optional[AxisCost] = None,
        chip: str = "unknown",
        source: str = "table",
        compressed_axis_costs: Optional[Dict[str, AxisCost]] = None,
    ) -> None:
        self.axis_costs = dict(axis_costs)
        self.default = default or AxisCost(1e-6, 100e9, "table")
        self.chip = chip
        self.source = source
        #: per-axis alpha/beta fitted from the int8-ring bench arms
        #: (``calibrate(compressed_ops=...)``) — the effective parameters
        #: of the QUANTIZED rings, quant/dequant FLOPs folded into the
        #: measured bandwidth.  Empty -> predictions fall back to the
        #: exact-axis parameters (table optimism: same link, fewer bytes).
        self.compressed_axis_costs = dict(compressed_axis_costs or {})

    # ------------------------------------------------------------- builders

    @classmethod
    def from_defaults(
        cls,
        mesh=None,
        device_kind: Optional[str] = None,
        dcn_axes: Sequence[str] = (),
    ) -> "CommModel":
        """Table-based model: every mesh axis gets the generation's ICI
        parameters except ``dcn_axes`` (multi-slice axes), which get DCN
        defaults.  ``device_kind`` defaults to the first jax device."""
        if device_kind is None:
            import jax

            device_kind = jax.devices()[0].device_kind
        dk = device_kind.lower()
        gen = next(
            (params for sub, params in GENERATION_DEFAULTS if sub in dk), None
        )
        if gen is None:
            raise ValueError(
                f"no comm-table row for device_kind={device_kind!r}; add one "
                "to GENERATION_DEFAULTS or calibrate()")
        ici = AxisCost(
            alpha_s=gen["ici_lat_us"] * 1e-6,
            beta_Bps=gen["ici_bw_GBps"] * 1e9,
            kind="table",
        )
        dcn = AxisCost(
            alpha_s=DCN_DEFAULTS["dcn_lat_us"] * 1e-6,
            beta_Bps=DCN_DEFAULTS["dcn_bw_GBps"] * 1e9,
            kind="dcn-table",
        )
        costs: Dict[str, AxisCost] = {}
        if mesh is not None:
            for a in mesh.axis_names:
                costs[str(a)] = dcn if str(a) in dcn_axes else ici
        return cls(costs, default=ici, chip=device_kind, source="table")

    @classmethod
    def calibrate(
        cls,
        mesh=None,
        axes: Optional[Sequence[str]] = None,
        sizes: Sequence[int] = (1 << 16, 1 << 20, 1 << 23),
        ops: Sequence[str] = ("all_reduce", "all_gather", "ppermute"),
        iters: int = 5,
        warmup: int = 1,
        compressed_ops: Sequence[str] = (),
    ) -> "CommModel":
        """Measure alpha/beta per mesh axis with ``bench_collective``.

        Each (op, size) cell contributes one ``(steps, wire_bytes, time)``
        sample; the per-axis fit is :func:`fit_alpha_beta`.  Axes of size 1
        are skipped (nothing to time).  This is a collective — call it on
        every process of a multi-host job.

        ``compressed_ops``: additionally time the int8-ring arms (names
        from :data:`INT8_BENCH_OPS`, e.g. ``("int8_all_reduce",
        "int8_reduce_scatter")``) and fit a SEPARATE per-axis alpha/beta
        against their *compressed* wire bytes — so
        :meth:`predict_compressed` scores the quantized rings from
        measurement (quant/dequant cost folded into the fitted busbw)
        instead of assuming the exact link parameters at a quarter of the
        bytes.
        """
        from ..dist.comm_bench import bench_collective
        from ..dist.topology import tpc

        if mesh is None:
            mesh = tpc.get_view()
        names = [str(a) for a in (axes if axes is not None else mesh.axis_names)]
        costs: Dict[str, AxisCost] = {}
        q_costs: Dict[str, AxisCost] = {}
        for axis in names:
            n = int(mesh.shape[axis])
            if n <= 1:
                continue
            samples: List[Tuple[float, float, float]] = []
            for op in ops:
                for nbytes in sizes:
                    row = bench_collective(
                        op, axis, nbytes=nbytes, mesh=mesh,
                        warmup=warmup, iters=iters,
                    )
                    samples.append((
                        float(steps_for(op, n)),
                        wire_bytes(op, row["bytes"], n),
                        row["time_s"],
                    ))
            alpha, beta = fit_alpha_beta(samples)
            costs[axis] = AxisCost(alpha, beta, kind="calibrated")
            q_samples: List[Tuple[float, float, float]] = []
            for op in compressed_ops:
                base = INT8_BENCH_OPS[op]
                for nbytes in sizes:
                    row = bench_collective(
                        op, axis, nbytes=nbytes, mesh=mesh,
                        warmup=warmup, iters=iters,
                    )
                    q_samples.append((
                        float(steps_for(base, n)),
                        compressed_wire_bytes(
                            base, row["bytes"], n,
                            elem_bytes=row.get("elem_bytes", 4)),
                        row["time_s"],
                    ))
            if q_samples:
                qa, qb = fit_alpha_beta(q_samples)
                q_costs[axis] = AxisCost(qa, qb, kind="calibrated-int8")
        try:
            import jax

            chip = jax.devices()[0].device_kind
        except Exception:
            chip = "unknown"
        default = next(iter(costs.values()), None)
        return cls(costs, default=default, chip=chip, source="calibrated",
                   compressed_axis_costs=q_costs)

    # ------------------------------------------------------------ prediction

    def _cost_for(self, axes: Sequence[str]) -> AxisCost:
        known = [self.axis_costs[a] for a in axes if a in self.axis_costs]
        if not known:
            return self.default
        # multi-axis collective: the slowest link is the bottleneck
        return AxisCost(
            alpha_s=max(c.alpha_s for c in known),
            beta_Bps=min(c.beta_Bps for c in known),
            kind=known[0].kind,
        )

    def _compressed_cost_for(self, axes: Sequence[str]) -> Tuple[AxisCost, str]:
        """(link params for the int8 rings over ``axes``, basis tag).
        Calibrated compressed parameters win; otherwise the exact-axis
        parameters serve (same link, quarter the bytes — optimistic: the
        quant FLOPs are then unmodeled, which is exactly what
        ``calibrate(compressed_ops=...)`` exists to fix)."""
        known = [self.compressed_axis_costs[a] for a in axes
                 if a in self.compressed_axis_costs]
        if known:
            return AxisCost(
                alpha_s=max(c.alpha_s for c in known),
                beta_Bps=min(c.beta_Bps for c in known),
                kind=known[0].kind,
            ), "calibrated-int8"
        return self._cost_for(axes), "exact-params"

    def predict_compressed(
        self,
        op: str,
        payload_bytes: float,
        n: int,
        axes: Sequence[str] = (),
        elem_bytes: int = 4,
        group: int = COMPRESS_GROUP,
    ) -> Dict[str, Any]:
        """Score the int8 ring against the exact collective for one
        payload — the ``grad_compress='auto'`` decision primitive.

        The quantized ring keeps the exact op's LATENCY term (same hop
        count — requantization doesn't change the ring length) while the
        bytes quarter (``compressed_wire_bytes``); quant/dequant FLOPs
        don't shrink either, and enter the prediction only through
        calibrated compressed parameters (:meth:`calibrate` with
        ``compressed_ops``) — table-based predictions are optimistic for
        latency-bound payloads, which is why callers keep a
        ``min_size`` floor on top (``dist.compressed.auto_compress_policy``).

        Returns ``{exact_s, compressed_s, speedup, compress,
        wire_bytes_exact, wire_bytes_compressed, ledger_bytes_exact,
        ledger_bytes_compressed, basis}``.
        """
        op = _HLO_OP.get(op, op)
        if op not in _COMPRESSIBLE_OPS:
            raise ValueError(
                f"no int8 ring for {op!r}; compressible: {_COMPRESSIBLE_OPS}")
        exact_s = self.predict(op, payload_bytes, n, axes=axes)
        out: Dict[str, Any] = {
            "op": op, "n": int(n), "axes": list(axes),
            "payload_bytes": payload_bytes,
            "exact_s": exact_s,
            "wire_bytes_exact": wire_bytes(op, payload_bytes, n),
            "ledger_bytes_exact": payload_bytes if n > 1 else 0.0,
        }
        if n <= 1:
            out.update(compressed_s=0.0, wire_bytes_compressed=0.0,
                       ledger_bytes_compressed=0.0, speedup=1.0,
                       compress=False, basis="single-member axis")
            return out
        q_wire = compressed_wire_bytes(op, payload_bytes, n, elem_bytes, group)
        c, basis = self._compressed_cost_for(axes)
        t = steps_for(op, n) * c.alpha_s
        if math.isfinite(c.beta_Bps) and c.beta_Bps > 0:
            t += q_wire / c.beta_Bps
        out.update(
            compressed_s=t,
            wire_bytes_compressed=q_wire,
            ledger_bytes_compressed=compressed_ledger_bytes(
                op, payload_bytes, n, elem_bytes, group),
            speedup=(exact_s / t) if t > 0 else float("inf"),
            compress=t < exact_s,
            basis=basis,
        )
        return out

    def predict(
        self,
        op: str,
        payload_bytes: float,
        n: int,
        axes: Sequence[str] = (),
    ) -> float:
        """Predicted seconds for one collective (op in either the ledger's
        hyphenated or comm_bench's underscore spelling)."""
        op = _HLO_OP.get(op, op)
        if op not in _STEPS:
            raise ValueError(f"unknown collective {op!r}")
        if n <= 1:
            return 0.0
        c = self._cost_for(axes)
        wire = wire_bytes(op, payload_bytes, n)
        t = steps_for(op, n) * c.alpha_s
        if math.isfinite(c.beta_Bps) and c.beta_Bps > 0:
            t += wire / c.beta_Bps
        return t

    def predict_ledger(self, ledger: Dict[str, Any]) -> Dict[str, Any]:
        """Per-collective and per-dimension predicted times for a
        :func:`~.comm_ledger.ledger_from_hlo` ledger (serialized — no
        overlap assumed)."""
        per_dim: Dict[str, float] = {}
        rows: List[Dict[str, Any]] = []
        total = 0.0
        for c in ledger.get("collectives", []):
            n = int(c.get("group_size") or 0)
            t = self.predict(c["op"], c["bytes"], n, axes=c.get("axes", ()))
            rows.append({
                "op": c["op"], "dim": c["dim"], "axes": c.get("axes", []),
                "bytes": c["bytes"], "pred_s": t,
            })
            per_dim[c["dim"]] = per_dim.get(c["dim"], 0.0) + t
            total += t
        return {
            "per_collective": rows,
            "per_dim_s": {k: round(v, 9) for k, v in per_dim.items()},
            "total_s": total,
            "params": {a: c.as_dict() for a, c in self.axis_costs.items()},
            "source": self.source,
            "chip": self.chip,
        }


def comm_report(
    ledger: Optional[Dict[str, Any]],
    step_time_s: Optional[float],
    model: Optional[CommModel] = None,
    xla_flops: Optional[float] = None,
    peak_flops: Optional[float] = None,
    mesh=None,
) -> Optional[Dict[str, Any]]:
    """The RUNREPORT ``comm`` section: ledger aggregates + modeled comm
    time vs the measured step + bound verdict and overlap headroom.

    - ``t_comm``  — modeled serialized collective time (:meth:`predict_ledger`)
    - ``t_comp``  — XLA-counted FLOPs / peak FLOP/s (None off-accelerator)
    - verdict     — ``comm-bound`` when even perfectly-overlapped comm
      exceeds compute (``t_comm > t_comp``); with no compute estimate the
      comm fraction of the measured step decides (> 0.5)
    - ``overlap_headroom_s`` — measured step minus ``max(t_comm, t_comp)``:
      what a perfectly-overlapped schedule could still recover
    - ``overlap``  — the ACHIEVED side, from real HLO scheduling
      distances: which collectives the compiler emitted async
      (``-start``/``-done`` with instructions between), what (modeled)
      fraction of the comm time they carry, and the effective exposed
      comm time under that achieved overlap — so the headroom number is
      labeled with how much of it the schedule already banked instead of
      assuming zero overlap.
    """
    if ledger is None:
        return None
    if model is None:
        model = CommModel.from_defaults(mesh=mesh)
    pred = model.predict_ledger(ledger)
    t_comm = pred["total_s"]
    out: Dict[str, Any] = {
        "ledger": {
            "per_dim": ledger.get("per_dim", {}),
            "total_bytes": ledger.get("total_bytes", 0),
            "n_collectives": ledger.get("n_collectives", 0),
            "mesh_axes": ledger.get("mesh_axes"),
            "collectives": ledger.get("collectives", []),
        },
        "model": {
            "per_dim_s": pred["per_dim_s"],
            "total_s": t_comm,
            "params": pred["params"],
            "source": pred["source"],
            "chip": pred["chip"],
        },
        "modeled_comm_s": t_comm,
    }
    # achieved overlap from the HLO scheduling distances: a collective is
    # counted as hidden when the compiler split it async AND placed at
    # least one instruction between -start and -done.  Time-weight by the
    # model's per-collective predictions so one big hidden all-gather
    # outweighs many tiny sync permutes.
    colls = ledger.get("collectives", [])
    t_hidden = 0.0
    n_async = n_hidden = 0
    distances: List[float] = []
    for c, row in zip(colls, pred["per_collective"]):
        if not c.get("async"):
            continue
        n_async += 1
        d = c.get("sched_distance")
        if d is not None:
            distances.append(d)
        if d is not None and d > 0:
            n_hidden += 1
            t_hidden += row["pred_s"]
    achieved = (t_hidden / t_comm) if t_comm > 0 else 0.0
    effective_comm_s = max(0.0, t_comm - t_hidden)
    out["overlap"] = {
        "async_ops": n_async,
        "sync_ops": len(colls) - n_async,
        "hidden_ops": n_hidden,
        "mean_sched_distance": (
            round(sum(distances) / len(distances), 2) if distances else None
        ),
        "achieved_fraction": round(achieved, 4),
        "hidden_comm_s": t_hidden,
        "effective_comm_s": effective_comm_s,
        "basis": "HLO async -start/-done scheduling distances, time-weighted "
                 "by the alpha-beta model",
    }
    t_comp = None
    if xla_flops and peak_flops:
        t_comp = xla_flops / peak_flops
        out["modeled_compute_s"] = t_comp
    if step_time_s and step_time_s > 0:
        out["measured_step_s"] = step_time_s
        out["comm_fraction"] = round(min(1.0, t_comm / step_time_s), 4)
        # the exposed fraction under the ACHIEVED schedule — the honest
        # companion to comm_fraction's zero-overlap assumption
        out["comm_fraction_effective"] = round(
            min(1.0, effective_comm_s / step_time_s), 4)
        floor = max(t_comm, t_comp) if t_comp else t_comm
        out["overlap_headroom_s"] = max(0.0, step_time_s - floor)
    if t_comp is not None:
        out["verdict"] = "comm-bound" if t_comm > t_comp else "compute-bound"
        out["verdict_basis"] = "modeled comm vs modeled compute"
    elif step_time_s and step_time_s > 0:
        out["verdict"] = (
            "comm-bound" if out["comm_fraction"] > 0.5 else "compute-bound"
        )
        out["verdict_basis"] = "modeled comm fraction of measured step"
    else:
        out["verdict"] = "unknown"
        out["verdict_basis"] = "no measured step time"
    return out


def compression_report(
    mode: str,
    policy_events: Sequence[Dict[str, Any]] = (),
    ledger: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """The RUNREPORT ``compression`` section: the compress-policy choices
    next to predicted-vs-ledger-measured wire bytes per axis.

    ``policy_events``: ``compress_policy`` event records (or bare
    ``{leaves: [...]}`` dicts) as emitted by ``DataParallel`` /
    ``ZeroOptimizer`` when ``grad_compress='auto'`` builds a step — each
    leaf row carries its choice and both ledger-convention byte
    predictions (``CommModel.predict_compressed``).  ``ledger``: the
    compiled step's comm ledger; measured bytes aggregate its collectives
    by the axis set they span.  The measured number covers the WHOLE
    step's traffic on that axis (loss reductions, param gathers ride the
    same axis), so ``rel_err`` is a reconciliation aid, not a bound —
    ``Telemetry.record_compression`` attaches the section and
    ``validate_runreport`` checks its structure."""
    leaves: List[Dict[str, Any]] = []
    for ev in policy_events:
        leaves.extend(ev.get("leaves") or [])
    predicted: Dict[str, float] = {}
    for l in leaves:
        key = "+".join(l.get("axes") or []) or "?"
        b = (l["ledger_bytes_compressed"] if l.get("compress")
             else l["ledger_bytes_exact"])
        predicted[key] = predicted.get(key, 0.0) + float(b)
    measured: Dict[str, int] = {}
    for c in (ledger or {}).get("collectives", []):
        key = "+".join(c.get("axes") or []) or "?"
        measured[key] = measured.get(key, 0) + int(c.get("bytes", 0))
    rows: List[Dict[str, Any]] = []
    for key in sorted(set(predicted) | set(measured)):
        pred = predicted.get(key)
        meas = measured.get(key)
        row: Dict[str, Any] = {"axes": key}
        if pred is not None:
            row["predicted_bytes"] = int(round(pred))
        if meas is not None:
            row["measured_bytes"] = meas
        if pred and meas is not None:
            row["rel_err"] = round((meas - pred) / pred, 4)
        rows.append(row)
    return {
        "schema": COMPRESSION_SCHEMA,
        "mode": str(mode),
        "policy": {
            "n_leaves": len(leaves),
            "n_compressed": sum(1 for l in leaves if l.get("compress")),
            # the artifact keeps a bounded table; full records live on the
            # event timeline
            "leaves": [dict(l) for l in leaves[:64]],
        },
        "per_axis": rows,
    }
