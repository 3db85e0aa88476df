"""Numerics observability: per-layer training-dynamics stats, the HLO
dtype ledger, and threshold-driven ``numerics_alert`` events.

The obs stack answers "how fast" (Telemetry spans + cost_analysis MFU),
"where do the bytes go on the wire" (:mod:`.comm_ledger` +
:mod:`.comm_model`) and "what is resident" (:mod:`.mem_ledger`); nothing
answered **"is the math healthy"** — a run could train on vanishing
gradients or a silently-f32 matmul for hours and the report would show a
great MFU.  Three layers of truth, symmetric to the comm and memory
stacks:

1. **In-step stats** (:func:`numerics_stats`): a jittable pure function
   over the (grads, params, updates) the train step already holds —
   global and per-layer-group L2 norms, the update ratio
   ``|update| / |param|`` (the classic learning-rate health signal),
   non-finite counts, and low-precision *range-health* fractions (how
   much of the gradient mass would underflow bf16, overflow f16, or
   quantize to zero at int8).  Fused INTO the compiled step — one
   program, donate-friendly, no extra dispatch
   (``DataParallel.make_train_step(numerics=True)``).
2. **HLO dtype ledger** (:func:`dtype_ledger_from_compiled`): per-dtype
   FLOP and byte accounting parsed from the AOT-compiled step's HLO text
   — the same no-second-compile ``Telemetry._compile_entry`` hook as the
   comm/mem ledgers.  This PROVES what actually runs in bf16 vs f32 vs
   int8: the evidence channel quantized collectives / quantized KV are
   verified against (a "quantized" config whose ledger shows zero s8
   bytes is lying).
3. **Alerts + report** (:func:`check_alerts` / :func:`numerics_report`):
   :class:`~.telemetry.Telemetry` promotes the per-step stats to a
   timeline with threshold-driven ``numerics_alert`` events (explosion,
   vanishing, update-ratio out of band, non-finite loss/grads) and
   Perfetto counter tracks (``grad_norm``, ``update_ratio``), and
   ``finalize()`` builds the validated RUNREPORT ``numerics`` section.

The shared-reduction contract: :func:`global_grad_norm` here is THE
global-norm implementation — ``parallel/clip.py`` delegates to it, so a
step that both clips and monitors computes the grouped squared-sum
reduction once (XLA CSEs the identical subgraphs) and the clipped-step
trajectory is bitwise-unchanged vs pre-fold HEAD (parity-tested).
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from .comm_ledger import (
    DTYPE_BITS,
    hlo_instructions,
    operand_shapes,
    shape_elems,
    type_shapes,
)

NUMERICS_SCHEMA = "tdp-numerics/v1"
DTYPE_LEDGER_SCHEMA = "tdp-dtype-ledger/v1"

# Alert thresholds (Telemetry accepts overrides).  The bands are loose on
# purpose: an alert should mean "look at this run", not "tuesday".
DEFAULT_THRESHOLDS: Dict[str, float] = {
    # global grad-norm explosion / vanishing (absolute, post-reduction)
    "grad_norm_explode": 1.0e3,
    "grad_norm_vanish": 1.0e-7,
    # |update| / |param| out of band: >1e-1 means steps rewrite the net,
    # <1e-6 means the optimizer is effectively frozen
    "update_ratio_high": 1.0e-1,
    "update_ratio_low": 1.0e-6,
}

# Low-precision range constants: bf16 shares f32's exponent range, so its
# underflow line is the f32 smallest normal; f16's max is famously 65504.
BF16_TINY = 1.17549435e-38
F16_MAX = 65504.0


# ----------------------------------------------------------- shared norms


def _vma_axes(x) -> Tuple[str, ...]:
    """Mesh axes a traced value varies over (sorted; empty outside
    shard_map)."""
    from jax import typeof

    return tuple(sorted(getattr(typeof(x), "vma", frozenset())))


def _psum_grouped(pairs: Iterable[Tuple[Tuple[str, ...], Any]]):
    """Sum ``(axes, scalar)`` pairs: accumulate per distinct axes-set in
    encounter order, psum each set ONCE, then total — one scalar psum per
    distinct sharding instead of one per leaf.  This is the exact
    accumulation order ``parallel/clip.py`` used pre-fold, so the global
    norm (and thus clipping) stays bitwise-identical."""
    import jax
    import jax.numpy as jnp

    by_axes: Dict[Tuple[str, ...], Any] = {}
    for axes, s in pairs:
        by_axes[axes] = by_axes.get(axes, 0.0) + s
    total = jnp.zeros((), dtype=jnp.float32)
    for axes, s in by_axes.items():
        total = total + (jax.lax.psum(s, axes) if axes else s)
    return total


def _sq_pairs(tree) -> List[Tuple[Tuple[str, ...], Any]]:
    import jax
    import jax.numpy as jnp

    out = []
    for g in jax.tree.leaves(tree):
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        out.append((_vma_axes(sq), sq))
    return out


def global_grad_norm(tree) -> Any:
    """True global L2 norm of a (possibly mixed-sharded) pytree — traced;
    inside shard_map each leaf's squared sum is psum-ed over exactly the
    mesh axes it varies on.  The one implementation ``parallel/clip.py``
    and :func:`numerics_stats` share."""
    import jax.numpy as jnp

    return jnp.sqrt(_psum_grouped(_sq_pairs(tree)))


# ------------------------------------------------------------- step stats


def default_group_fn(path) -> str:
    """Leaf path -> layer-group name: the first path component, plus the
    index when the model is a list of blocks (``blocks/0``, ``blocks/3``)
    — coarse enough to stay a handful of scalars, fine enough to say
    WHICH layer's gradients died."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    if not parts:
        return "params"
    if len(parts) >= 2 and parts[1].isdigit():
        return f"{parts[0]}/{parts[1]}"
    return parts[0]


def _grouped_sq(tree, group_fn) -> Dict[str, List[Tuple[Tuple[str, ...], Any]]]:
    import jax
    import jax.numpy as jnp

    groups: Dict[str, List[Tuple[Tuple[str, ...], Any]]] = {}
    for path, g in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        groups.setdefault(group_fn(path), []).append((_vma_axes(sq), sq))
    return groups


def numerics_stats(
    grads,
    params=None,
    updates=None,
    group_fn: Optional[Callable] = None,
    eps: float = 1e-12,
) -> Dict[str, Any]:
    """Training-dynamics stats over one step's (grads, params, updates).

    Pure and jittable — call it INSIDE the train step (after the grad
    reduction, before the param update) so monitoring rides in the same
    compiled program as training: no extra dispatch, no second fetch, and
    the norms see exactly the grads the optimizer sees.  Returns a dict
    of f32 scalars (fetch with the step outputs):

    - ``grad_norm`` / ``param_norm`` / ``update_norm`` — global L2 norms
      (param/update only when the trees are passed).
    - ``update_ratio`` — ``update_norm / (param_norm + eps)``.
    - ``nonfinite_grads`` — count of NaN/Inf gradient elements.
    - ``bf16_underflow_frac`` / ``f16_overflow_frac`` / ``int8_zero_frac``
      — fraction of nonzero grad elements below bf16's smallest normal,
      above f16's max, and (per leaf, against its own amax) inside the
      dead zone a symmetric int8 quantizer rounds to zero.  The health
      gauges for running grads/collectives at low precision.
    - ``groups`` — per-layer-group sub-dicts of the same norms
      (:func:`default_group_fn` grouping unless ``group_fn`` is given).

    Under shard_map every reduction psums over exactly the axes each leaf
    varies on, so TP/FSDP-sharded trees report true global values.
    """
    import jax
    import jax.numpy as jnp

    gf = group_fn or default_group_fn
    out: Dict[str, Any] = {"grad_norm": global_grad_norm(grads)}

    groups: Dict[str, Dict[str, Any]] = {}
    for name, pairs in _grouped_sq(grads, gf).items():
        groups[name] = {"grad_norm": jnp.sqrt(_psum_grouped(pairs))}
    if params is not None:
        out["param_norm"] = global_grad_norm(params)
        for name, pairs in _grouped_sq(params, gf).items():
            groups.setdefault(name, {})["param_norm"] = jnp.sqrt(
                _psum_grouped(pairs))
    if updates is not None:
        out["update_norm"] = global_grad_norm(updates)
        for name, pairs in _grouped_sq(updates, gf).items():
            groups.setdefault(name, {})["update_norm"] = jnp.sqrt(
                _psum_grouped(pairs))
    if params is not None and updates is not None:
        out["update_ratio"] = out["update_norm"] / (out["param_norm"] + eps)
        for g in groups.values():
            if "update_norm" in g and "param_norm" in g:
                g["update_ratio"] = g["update_norm"] / (g["param_norm"] + eps)
    out["groups"] = groups

    # non-finite + low-precision range fractions over the gradient mass
    nonfinite, under, over, dead, total = [], [], [], [], []
    for g in jax.tree.leaves(grads):
        if not jnp.issubdtype(g.dtype, jnp.floating):
            continue
        a = jnp.abs(g.astype(jnp.float32))
        axes = _vma_axes(a)
        nonfinite.append((axes, jnp.sum(~jnp.isfinite(g)).astype(jnp.float32)))
        nz = a > 0
        under.append((axes, jnp.sum(nz & (a < BF16_TINY)).astype(jnp.float32)))
        over.append((axes, jnp.sum(a > F16_MAX).astype(jnp.float32)))
        # per-leaf symmetric int8 scale: values under amax/(2*127) round
        # to the zero bucket — the quantizer's dead zone
        amax = jnp.max(a)
        if axes:
            amax = jax.lax.pmax(amax, axes)
        dead.append((axes, jnp.sum(nz & (a < amax / 254.0)).astype(jnp.float32)))
        total.append((axes, jnp.asarray(g.size, jnp.float32)))
    if total:
        n = _psum_grouped(total)
        out["nonfinite_grads"] = _psum_grouped(nonfinite)
        out["bf16_underflow_frac"] = _psum_grouped(under) / n
        out["f16_overflow_frac"] = _psum_grouped(over) / n
        out["int8_zero_frac"] = _psum_grouped(dead) / n
    return out


# ----------------------------------------------------------------- alerts


def check_alerts(
    rec: Dict[str, Any], thresholds: Optional[Dict[str, float]] = None
) -> List[Dict[str, Any]]:
    """Threshold checks over one HOST-side step record (floats, as built
    by ``Telemetry.end_step``).  Returns ``[{reason, value, threshold}]``
    — empty when healthy.  Reasons: ``nonfinite_loss``,
    ``nonfinite_grads``, ``grad_explosion``, ``grad_vanishing``,
    ``update_ratio_high``, ``update_ratio_low``."""
    import math

    th = dict(DEFAULT_THRESHOLDS)
    if thresholds:
        th.update(thresholds)
    alerts: List[Dict[str, Any]] = []

    def add(reason, value, threshold=None):
        alerts.append({
            "reason": reason, "value": value, "threshold": threshold})

    loss = rec.get("loss")
    if isinstance(loss, (int, float)) and not math.isfinite(loss):
        add("nonfinite_loss", loss)
    nf = rec.get("nonfinite_grads")
    if isinstance(nf, (int, float)) and nf > 0:
        add("nonfinite_grads", nf)
    gn = rec.get("grad_norm")
    if isinstance(gn, (int, float)):
        if not math.isfinite(gn):
            if not any(a["reason"] == "nonfinite_grads" for a in alerts):
                add("nonfinite_grads", gn)
        elif gn >= th["grad_norm_explode"]:
            add("grad_explosion", gn, th["grad_norm_explode"])
        elif 0.0 < gn <= th["grad_norm_vanish"]:
            add("grad_vanishing", gn, th["grad_norm_vanish"])
    ur = rec.get("update_ratio")
    if isinstance(ur, (int, float)) and math.isfinite(ur):
        if ur >= th["update_ratio_high"]:
            add("update_ratio_high", ur, th["update_ratio_high"])
        elif 0.0 < ur <= th["update_ratio_low"]:
            add("update_ratio_low", ur, th["update_ratio_low"])
    return alerts


# ----------------------------------------------------------- dtype ledger

_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

# Result buffers of these ops alias/bookkeep rather than compute — they
# would double-count the producing instruction's bytes.
_NO_ALLOC_OPS = frozenset({
    "parameter", "get-tuple-element", "tuple", "bitcast", "constant",
    "after-all", "partition-id", "replica-id",
})


def dtype_ledger_from_hlo(
    hlo_text: str, label: Optional[str] = None
) -> Dict[str, Any]:
    """Per-dtype byte/FLOP/op accounting of an HLO module's instructions.

    - ``bytes``: sum of result-buffer bytes per result dtype over every
      compute-defining instruction (bookkeeping ops — parameter, tuple,
      get-tuple-element, bitcast, constant — excluded).  A traffic-mix
      proxy, not a liveness peak (that is :mod:`.mem_ledger`'s job).
    - ``flops``: matmul FLOPs per OPERAND dtype, ``2 * |result| * K``
      from each ``dot``'s result shape and lhs contracting dims — the
      precision the MXU actually multiplies in.  Elementwise/conv FLOPs
      are not attributed (cost_analysis owns the total; this ledger owns
      the *mix*).
    - ``ops``: instruction count per result dtype.

    The quantization evidence channel: an int8-collective or int8-KV arm
    must show s8 bytes here, and a "bf16 training" run whose dot FLOPs
    sit in f32 has a silent upcast.
    """
    per: Dict[str, Dict[str, float]] = {}

    def bucket(dt: str) -> Dict[str, float]:
        return per.setdefault(dt, {"bytes": 0, "ops": 0, "flops": 0})

    instrs, result_types = hlo_instructions(hlo_text)
    for m in instrs:
        op = m.group("op")
        if op in _NO_ALLOC_OPS:
            continue
        # a tuple result '(f32[2]{0}, s8[4]{0})': every array is counted
        shapes = type_shapes(m.group("res"))
        for i, (dt, dims) in enumerate(shapes):
            bits = DTYPE_BITS.get(dt)
            if bits is None:
                continue
            b = bucket(dt)
            b["bytes"] += shape_elems(dims) * bits // 8
            if i == 0:
                b["ops"] += 1
        cm = _CONTRACT_RE.search(m.string) if op == "dot" else None
        if cm is not None:
            lhs_dt, lhs_dims = operand_shapes(m.group("rest"), result_types)[0]
            lhs_shape = [int(d) for d in lhs_dims.split(",") if d]
            k = 1
            for idx in cm.group(1).split(","):
                if idx and int(idx) < len(lhs_shape):
                    k *= lhs_shape[int(idx)]
            out_elems = sum(shape_elems(dims) for _, dims in shapes)
            bucket(lhs_dt)["flops"] += 2 * out_elems * k
    total_bytes = sum(b["bytes"] for b in per.values())
    total_flops = sum(b["flops"] for b in per.values())
    ledger: Dict[str, Any] = {
        "schema": DTYPE_LEDGER_SCHEMA,
        "label": label,
        "per_dtype": {
            dt: {k: int(v) for k, v in b.items()}
            for dt, b in sorted(per.items())
        },
        "total_bytes": int(total_bytes),
        "total_flops": int(total_flops),
    }
    if total_bytes:
        ledger["byte_frac"] = {
            dt: round(b["bytes"] / total_bytes, 4)
            for dt, b in sorted(per.items()) if b["bytes"]}
    if total_flops:
        ledger["flop_frac"] = {
            dt: round(b["flops"] / total_flops, 4)
            for dt, b in sorted(per.items()) if b["flops"]}
    return ledger


def dtype_ledger_from_compiled(
    compiled, label: Optional[str] = None
) -> Optional[Dict[str, Any]]:
    """Dtype ledger from a compiled executable; None when the backend
    can't render HLO text (mirrors ``comm_ledger.ledger_from_compiled``)."""
    try:
        text = compiled.as_text()
    except Exception:
        return None
    if not isinstance(text, str) or not text:
        return None
    return dtype_ledger_from_hlo(text, label=label)


# ---------------------------------------------------------- report section


def numerics_report(
    timeline: Sequence[Dict[str, Any]] = (),
    dtype_ledgers: Sequence[Optional[Dict[str, Any]]] = (),
    events: Iterable[Dict[str, Any]] = (),
    parity: Optional[Dict[str, Any]] = None,
    thresholds: Optional[Dict[str, float]] = None,
) -> Dict[str, Any]:
    """The RUNREPORT ``numerics`` section: timeline summary + alert roll-up
    + dtype ledger(s) (+ the optional A/B :mod:`.parity` verdict)."""
    import math

    import numpy as np

    tl = [dict(t) for t in timeline]
    summary: Dict[str, Any] = {"steps": len(tl)}
    gns = [t["grad_norm"] for t in tl
           if isinstance(t.get("grad_norm"), (int, float))
           and math.isfinite(t["grad_norm"])]
    if gns:
        summary["grad_norm_final"] = gns[-1]
        summary["grad_norm_mean"] = float(np.mean(gns))
        summary["grad_norm_max"] = float(np.max(gns))
    urs = [t["update_ratio"] for t in tl
           if isinstance(t.get("update_ratio"), (int, float))
           and math.isfinite(t["update_ratio"])]
    if urs:
        summary["update_ratio_final"] = urs[-1]
        summary["update_ratio_mean"] = float(np.mean(urs))
    summary["nonfinite_steps"] = sum(
        1 for t in tl if t.get("nonfinite_grads"))

    alert_events = [e for e in events if e.get("kind") == "numerics_alert"]
    by_reason: Dict[str, int] = {}
    for e in alert_events:
        by_reason[str(e.get("reason"))] = by_reason.get(
            str(e.get("reason")), 0) + 1
    alerts: Dict[str, Any] = {"count": len(alert_events),
                              "by_reason": by_reason}
    if alert_events:
        first = alert_events[0]
        alerts["first"] = {
            "step": first.get("step"), "reason": first.get("reason"),
            "value": first.get("value")}

    stride = max(1, len(tl) // 64)
    section: Dict[str, Any] = {
        "schema": NUMERICS_SCHEMA,
        "summary": summary,
        "alerts": alerts,
        "timeline": tl[::stride],
        "dtype_ledgers": [
            {k: v for k, v in d.items() if k != "schema"}
            for d in dtype_ledgers if d],
        "thresholds": dict(DEFAULT_THRESHOLDS, **(thresholds or {})),
    }
    if parity is not None:
        section["parity"] = dict(parity)
    return section
