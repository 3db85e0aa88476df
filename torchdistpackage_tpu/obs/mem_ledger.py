"""Memory observability: static HLO buffer ledger, live HBM timeline,
and OOM-headroom verdicts.

The obs stack closes the loop on *time* (Telemetry spans, cost_analysis
MFU) and on *bytes-on-the-wire* (comm ledger + alpha-beta CommModel);
this module closes it on *bytes-resident* — the resource that decides
whether a config runs at all.  Three layers of truth, symmetric to
:mod:`.comm_ledger` / :mod:`.comm_model`:

1. **Static ledger** (:func:`static_ledger`): parse
   ``compiled.memory_analysis()`` of the AOT-compiled step — the same
   no-second-compile :class:`~.telemetry.Telemetry` hook that captures
   cost_analysis and the comm ledger — into a per-compiled-program
   breakdown: argument / output / temp / generated-code bytes and the
   alias (donation) savings, proving ``donate_argnums`` actually bought
   the in-place update.  Argument bytes are attributed to pytree leaves
   through the compiled input shardings (:func:`_leaf_rows`), so
   FSDP/ZeRO-3 sharding is *evidenced*: a sharded leaf's resident bytes
   scale ~1/N with the shard count, and replicated leaves are flagged.
2. **Live timeline** (:func:`live_memory`): the ONE ``memory_stats()``
   reader in the repo (``tests/test_repo_lint.py`` bans the raw call
   everywhere else) — per-device live/peak/limit plus host-level sums,
   polled per step by Telemetry into ``mem_snapshot`` samples and
   exported to the Perfetto trace as a counter track.
3. **Verdict** (:func:`headroom_verdict` / :func:`mem_report`): modeled
   (static) and measured peaks against device capacity ->
   ``ok | tight | oom_risk`` — the memory mirror of the comm section's
   comm-bound/compute-bound verdict.  An ``oom_risk`` verdict also lands
   on the event timeline.

On top, :class:`MemoryModel` is the planner-facing half: estimate a
config's per-device resident bytes from (config, mesh, specs) *without
compiling* — the third cost model (compute = cost_analysis, comm =
CommModel, memory = this) an auto-sharding planner scores candidate
layouts with before anything compiles (Mesh-TensorFlow 1811.02084,
arxiv 2211.05322 both gate plans on a memory budget first).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence

MEM_LEDGER_SCHEMA = "tdp-mem-ledger/v1"

#: The memory headroom verdicts (RUNREPORT ``memory.verdict``), mirroring
#: the comm section's bound verdicts.  ``unknown`` = no capacity to judge
#: against (the CPU sim reports no memory stats).
MEM_VERDICTS = ("ok", "tight", "oom_risk", "unknown")

# Peak-vs-capacity thresholds: below TIGHT_FRAC the config has real
# headroom; past OOM_RISK_FRAC one allocator hiccup (fragmentation, a
# transient double buffer) plausibly OOMs.  The same numbers govern the
# static (modeled) and measured sides so the two verdicts are comparable.
TIGHT_FRAC = 0.80
OOM_RISK_FRAC = 0.95


# ---------------------------------------------------------------- live side


def live_memory() -> Dict[str, Any]:
    """The repo's one ``memory_stats()`` reader: per-local-device live /
    peak / limit bytes plus process-level aggregates.

    Returns ``{reported, live_bytes, peak_bytes, limit_bytes, peak_frac,
    per_device}`` — sums over local devices for the three byte totals
    (matching what Telemetry historically reported) and ``peak_frac`` =
    the MAX per-device ``peak/limit`` (OOM is a per-device event; summing
    would hide one hot chip behind seven cold ones).  ``reported=False``
    (and zeros) when no device exposes stats — the CPU sim."""
    per_device: List[Dict[str, Any]] = []
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        devices = []
    live = peak = limit = 0
    peak_frac = 0.0
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        row = {
            "device": str(d),
            "bytes_in_use": int(ms.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(ms.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(ms.get("bytes_limit", 0)),
        }
        per_device.append(row)
        live += row["bytes_in_use"]
        peak += row["peak_bytes_in_use"]
        limit += row["bytes_limit"]
        if row["bytes_limit"] > 0:
            peak_frac = max(
                peak_frac, row["peak_bytes_in_use"] / row["bytes_limit"])
    return {
        "reported": bool(per_device),
        "live_bytes": live,
        "peak_bytes": peak,
        "limit_bytes": limit,
        "peak_frac": peak_frac if per_device else None,
        "per_device": per_device,
    }


def device_capacity() -> Optional[int]:
    """Per-device HBM capacity (``bytes_limit`` of the first reporting
    device); None when the backend reports nothing (CPU sim)."""
    mem = live_memory()
    for row in mem["per_device"]:
        if row["bytes_limit"] > 0:
            return row["bytes_limit"]
    return None


# -------------------------------------------------------------- static side


def _leaf_rows(compiled) -> List[Dict[str, Any]]:
    """Attribute the compiled program's argument bytes to pytree leaves.

    Walks ``compiled.in_avals`` (global shapes/dtypes) zipped with
    ``compiled.input_shardings``: each leaf's per-device RESIDENT bytes
    come from ``sharding.shard_shape(global_shape)``, so an FSDP-sharded
    leaf shows ``global/N`` and a replicated one shows ``global`` with
    ``replicated: True`` — the sharding evidence, from the compiler's own
    layout rather than from what the caller intended."""
    import jax
    import numpy as np

    try:
        avals_args, _ = compiled.in_avals
        shard_args, _ = compiled.input_shardings
    except Exception:
        return []
    is_sh = lambda s: hasattr(s, "shard_shape")  # Sharding objects are leaves
    flat_av = jax.tree_util.tree_flatten_with_path(avals_args)[0]
    flat_sh = jax.tree_util.tree_leaves(shard_args, is_leaf=is_sh)
    if len(flat_av) != len(flat_sh):
        return []
    rows: List[Dict[str, Any]] = []
    for (path, aval), sh in zip(flat_av, flat_sh):
        shape = tuple(getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", None)
        itemsize = np.dtype(dtype).itemsize if dtype is not None else 0
        global_bytes = int(np.prod(shape, dtype=np.int64)) * itemsize
        try:
            shard_shape = tuple(sh.shard_shape(shape))
        except Exception:
            shard_shape = shape
        resident = int(np.prod(shard_shape, dtype=np.int64)) * itemsize
        try:
            n_devices = len(sh.device_set)
        except Exception:
            n_devices = 1
        rows.append({
            "path": jax.tree_util.keystr(path),
            "shape": list(shape),
            "dtype": str(dtype),
            "global_bytes": global_bytes,
            "resident_bytes": resident,
            "shard_count": (
                max(1, round(global_bytes / resident)) if resident else 1),
            "spec": str(getattr(sh, "spec", None)),
            "replicated": bool(
                resident == global_bytes and n_devices > 1 and global_bytes),
        })
    return rows


def static_ledger(compiled, label: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """Per-compiled-program static memory ledger from
    ``compiled.memory_analysis()`` (None when the backend reports none).

    All byte counts are PER PARTICIPATING DEVICE of the SPMD program —
    the same convention as ``cost_analysis``.  ``alias_bytes`` is the
    donation evidence: argument bytes the compiler aliased into outputs
    (``donate_argnums`` working as claimed); ``peak_estimate_bytes`` is
    the static upper bound ``args + outputs + temps + generated_code -
    alias`` — an over-estimate of the true liveness-scheduled peak, an
    under-estimate of nothing (every counted buffer exists at some point
    and the aliased ones never double)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    g = lambda name: int(getattr(ma, name, 0) or 0)
    args = g("argument_size_in_bytes")
    out = g("output_size_in_bytes")
    temp = g("temp_size_in_bytes")
    alias = g("alias_size_in_bytes")
    gen = g("generated_code_size_in_bytes")
    host = {
        "argument_bytes": g("host_argument_size_in_bytes"),
        "output_bytes": g("host_output_size_in_bytes"),
        "temp_bytes": g("host_temp_size_in_bytes"),
        "alias_bytes": g("host_alias_size_in_bytes"),
        "generated_code_bytes": g("host_generated_code_size_in_bytes"),
    }
    leaves = _leaf_rows(compiled)
    return {
        "schema": MEM_LEDGER_SCHEMA,
        "label": label,
        "argument_bytes": args,
        "output_bytes": out,
        "temp_bytes": temp,
        "alias_bytes": alias,
        "generated_code_bytes": gen,
        "peak_estimate_bytes": max(0, args + out + temp + gen - alias),
        "host": host if any(host.values()) else None,
        "per_leaf": leaves,
        "n_leaves": len(leaves),
        "replicated_leaves": sum(1 for r in leaves if r["replicated"]),
        "sharded_leaves": sum(
            1 for r in leaves if r["shard_count"] > 1),
    }


def ledger_from_compiled(compiled, label: Optional[str] = None):
    """Alias of :func:`static_ledger`, mirroring
    ``comm_ledger.ledger_from_compiled``'s naming."""
    return static_ledger(compiled, label=label)


# ------------------------------------------------------------------ verdict


def headroom_verdict(
    peak_bytes: Optional[float], capacity_bytes: Optional[float]
) -> Dict[str, Any]:
    """``{verdict, frac, headroom_frac}`` for a peak against a capacity.

    ``frac`` = peak/capacity; verdict thresholds: ``ok`` below
    :data:`TIGHT_FRAC`, ``tight`` up to :data:`OOM_RISK_FRAC`,
    ``oom_risk`` past it (or peak > capacity outright); ``unknown`` when
    either side is missing/non-positive."""
    if not peak_bytes or not capacity_bytes or capacity_bytes <= 0:
        return {"verdict": "unknown", "frac": None, "headroom_frac": None}
    frac = float(peak_bytes) / float(capacity_bytes)
    if frac >= OOM_RISK_FRAC:
        verdict = "oom_risk"
    elif frac >= TIGHT_FRAC:
        verdict = "tight"
    else:
        verdict = "ok"
    return {
        "verdict": verdict,
        "frac": round(frac, 4),
        "headroom_frac": round(1.0 - frac, 4),
    }


def mem_report(
    programs: Sequence[Optional[Dict[str, Any]]] = (),
    measured_peak_bytes: Optional[int] = None,
    measured_peak_frac: Optional[float] = None,
    capacity_bytes: Optional[int] = None,
    timeline: Optional[Sequence[Dict[str, Any]]] = None,
    kv_pool: Optional[Dict[str, Any]] = None,
    emit: bool = True,
) -> Dict[str, Any]:
    """The RUNREPORT ``memory`` section.

    - ``programs`` — the per-compiled-program static ledgers Telemetry
      captured (one per signature; ``per_leaf`` trimmed to the section).
    - modeled vs measured peak: the MAX static ``peak_estimate_bytes``
      across programs vs the polled ``memory_stats`` peak.
    - verdict: measured side wins when both exist (it is ground truth;
      ``measured_peak_frac`` is the per-device max, see
      :func:`live_memory`), else the modeled peak against
      ``capacity_bytes``; ``unknown`` without a capacity.
    - ``kv_pool`` — the serving cross-check: the engine's expected pool
      bytes (shape math) vs the device buffer actually held
      (``paged_cache.pool_bytes``); a mismatch is flagged, not hidden.
    - ``emit`` — an ``oom_risk`` verdict lands on the default event log
      so the timeline shows WHEN the run learned it was at risk.
    """
    progs = [p for p in programs if p]
    modeled_peak = max(
        (p["peak_estimate_bytes"] for p in progs), default=None)
    if measured_peak_frac is not None:
        meas = headroom_verdict(measured_peak_frac, 1.0)
        basis = "measured per-device peak vs device capacity"
    else:
        meas = headroom_verdict(measured_peak_bytes, capacity_bytes)
        basis = "measured peak vs capacity"
    model = headroom_verdict(modeled_peak, capacity_bytes)
    if meas["verdict"] != "unknown":
        verdict, frac, basis = meas["verdict"], meas["frac"], basis
    elif model["verdict"] != "unknown":
        verdict, frac = model["verdict"], model["frac"]
        basis = "modeled (static ledger) peak vs capacity"
    else:
        verdict, frac, basis = "unknown", None, "no device capacity reported"
    section: Dict[str, Any] = {
        "programs": [
            {k: v for k, v in p.items() if k != "schema"} for p in progs],
        "modeled_peak_bytes": modeled_peak,
        "measured_peak_bytes": measured_peak_bytes,
        "capacity_bytes": capacity_bytes,
        "peak_frac": frac,
        "headroom_frac": (
            round(1.0 - frac, 4) if isinstance(frac, (int, float)) else None),
        "verdict": verdict,
        "verdict_basis": basis,
    }
    if timeline:
        # downsampled to <= 64 points like the throughput trajectory
        tl = list(timeline)
        stride = max(1, len(tl) // 64)
        section["timeline"] = tl[::stride]
    if kv_pool is not None:
        expected = kv_pool.get("pool_bytes_expected")
        actual = kv_pool.get("pool_bytes")
        section["kv_pool"] = {
            **kv_pool,
            "accounting_match": (
                expected == actual
                if (expected is not None and actual is not None) else None),
        }
    if emit and verdict == "oom_risk":
        from .events import emit_event

        emit_event(
            "oom_risk", peak_frac=frac, basis=basis,
            modeled_peak_bytes=modeled_peak,
            measured_peak_bytes=measured_peak_bytes)
    return section


# ------------------------------------------------------------ planner model


@dataclasses.dataclass
class MemoryModel:
    """Analytic per-device memory estimate for a (config, mesh, specs)
    candidate — no compile, so a planner can score hundreds of layouts.

    Parameters
    ----------
    capacity_bytes: per-device HBM to judge against; default read from
        the live backend (:func:`device_capacity`), None on the CPU sim.
    optimizer_slots: optimizer moment buffers per param (adam(w) = 2,
        sgd+momentum = 1, sgd = 0).
    opt_itemsize: bytes per moment element (moments are f32 in every
        optimizer this repo ships).
    act_factor: resident activation multiplier per layer boundary — 1.0
        models full remat (one boundary carry per layer), larger values
        model saved intermediates.  The exact number is workload-shaped;
        params/grads/optimizer are exact, activations are labeled an
        estimate.
    """

    capacity_bytes: Optional[int] = None
    optimizer_slots: int = 2
    opt_itemsize: int = 4
    act_factor: float = 1.0

    def estimate(
        self,
        config: Any,
        mesh: Any,
        specs: Any,
        *,
        params: Any = None,
        batch_per_device: Optional[int] = None,
        seq_len: Optional[int] = None,
        with_grads: bool = True,
    ) -> Dict[str, Any]:
        """Per-device resident-bytes estimate for running ``config`` with
        params partitioned by ``specs`` over ``mesh``.

        ``params`` (a pytree of arrays or ``ShapeDtypeStruct``) defaults
        to the config family's init under ``jax.eval_shape`` (GPTConfig /
        TransformerConfig — zero FLOPs, zero bytes).  Per-leaf resident
        bytes = global bytes / the product of the spec'd mesh axis sizes;
        grads follow the param specs (the ZeRO/reduce-scatter layout this
        repo trains with), optimizer moments add ``optimizer_slots`` f32
        copies at the same sharding, activations add
        ``B_local * S * D * nlayers * act_factor`` in the config dtype
        when batch/seq are known.  Returns the breakdown plus an
        ``ok|tight|oom_risk|unknown`` verdict against ``capacity_bytes``.
        """
        import jax
        import numpy as np

        if params is None:
            params = _shapes_for_config(config)
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: s is None or _is_spec(s))
        if len(spec_leaves) == 1 and len(leaves) > 1:
            spec_leaves = spec_leaves * len(leaves)  # one spec for the tree
        if len(spec_leaves) != len(leaves):
            raise ValueError(
                f"specs tree has {len(spec_leaves)} leaves for "
                f"{len(leaves)} param leaves")

        axis_sizes = {str(a): int(mesh.shape[a]) for a in mesh.axis_names}
        per_leaf: List[Dict[str, Any]] = []
        params_bytes = 0
        params_elems_resident = 0
        for (path, leaf), spec in zip(leaves, spec_leaves):
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = getattr(leaf, "dtype", np.float32)
            itemsize = np.dtype(dtype).itemsize
            n_elems = int(np.prod(shape, dtype=np.int64))
            shards = _shard_count(spec, axis_sizes)
            resident = -(-n_elems // shards) * itemsize  # ceil: padded shard
            per_leaf.append({
                "path": jax.tree_util.keystr(path),
                "global_bytes": n_elems * itemsize,
                "resident_bytes": resident,
                "shard_count": shards,
                "replicated": shards == 1 and math.prod(
                    axis_sizes.values()) > 1,
            })
            params_bytes += resident
            params_elems_resident += -(-n_elems // shards)
        grads_bytes = params_bytes if with_grads else 0
        opt_bytes = (
            self.optimizer_slots * params_elems_resident * self.opt_itemsize)

        act_bytes = 0
        dim = getattr(config, "dim", None)
        nlayers = getattr(config, "nlayers", None)
        S = seq_len if seq_len is not None else getattr(config, "max_seq", None)
        if batch_per_device and dim and nlayers and S:
            act_itemsize = np.dtype(
                getattr(config, "dtype", np.float32)).itemsize
            act_bytes = int(
                batch_per_device * S * dim * nlayers
                * self.act_factor * act_itemsize)

        total = params_bytes + grads_bytes + opt_bytes + act_bytes
        capacity = (
            self.capacity_bytes if self.capacity_bytes is not None
            else device_capacity())
        hv = headroom_verdict(total, capacity)
        return {
            "params_bytes": params_bytes,
            "grads_bytes": grads_bytes,
            "opt_bytes": opt_bytes,
            "act_bytes": act_bytes,
            "total_bytes": total,
            "capacity_bytes": capacity,
            "frac": hv["frac"],
            "headroom_frac": hv["headroom_frac"],
            "verdict": hv["verdict"],
            "per_leaf": per_leaf,
            "replicated_leaves": sum(
                1 for r in per_leaf if r["replicated"]),
            "mesh_axes": axis_sizes,
        }


def _is_spec(s: Any) -> bool:
    from jax.sharding import PartitionSpec

    return isinstance(s, PartitionSpec)


def _shard_count(spec: Any, axis_sizes: Dict[str, int]) -> int:
    """Devices a leaf is split across under ``spec`` (1 = replicated)."""
    if spec is None:
        return 1
    n = 1
    for entry in tuple(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for a in axes:
            n *= axis_sizes.get(str(a), 1)
    return n


def _shapes_for_config(config: Any) -> Any:
    """ShapeDtypeStruct param tree for a known config family (GPTConfig /
    TransformerConfig) via ``jax.eval_shape`` of its init — lazy imports
    keep obs a leaf subsystem."""
    import jax

    key = jax.ShapeDtypeStruct((2,), "uint32")
    if hasattr(config, "vocab_size"):
        if getattr(config, "moe_experts", 0):
            from ..models import init_gpt_moe_params as init
        else:
            from ..models import init_gpt_params as init
    elif hasattr(config, "nheads"):
        from ..parallel.tensor_parallel import init_transformer_params as init
    else:
        raise ValueError(
            f"cannot derive param shapes from {type(config).__name__}; "
            f"pass params= explicitly")
    return jax.eval_shape(lambda k: init(k, config), key)
