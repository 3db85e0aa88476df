"""Auto-sharding planner — close the loop from cost models to a plan.

The repo owns three cost models that were, until now, only ever consulted
one at a time: the calibrated per-axis alpha-beta :class:`~..obs.comm_model.
CommModel` (including the int8-ring ``predict_compressed`` arms), the HLO
``cost_analysis`` FLOP count captured by the Telemetry AOT hook, and
:class:`~..obs.mem_ledger.MemoryModel` (per-leaf resident bytes from
spec x mesh math, no compile).  This module is the consumer that uses all
three at once: given a model config and a chip count it

1. **enumerates** candidate plans — mesh factorizations ``dp x tp x pp``
   of the chip count (constrained to what the model family can actually
   shard: ``tp | nheads/dim/vocab``, ``pp | nlayers``), each crossed with
   the layer layout for the data axis (``dp`` = replicated params,
   ``fsdp`` = ZeRO-3 param sharding via the same first-free-divisible-dim
   rule ``parallel.zero.zero_partition_spec`` applies) and with per-axis
   int8 compression arms (grad collectives on the data axis, SP boundary
   activations on the tensor axis — exactly the knobs
   ``DataParallel(grad_compress=...)`` / ``TransformerConfig(ag_compress=
   ...)`` expose) — MoE GPT configs additionally cross in an
   expert-parallel factor ``ep | gcd(dp, experts)`` (expert stacks
   sharded over a dedicated ``ep`` mesh axis, the batch over
   ``("data", "ep")``, the dispatch all_to_all priced per MoE layer);
2. **prunes** candidates whose modeled per-device resident bytes exceed
   the HBM budget — ``MemoryModel.estimate`` is the judge when jax is
   importable (``memory='model'``), a byte-identical pure-python mirror
   (``memory='analytic'``, pinned to the model by tests) serves the
   jax-free CLI; every pruned plan emits a ``plan_rejected_oom`` event
   **before anything compiles**;
3. **scores** the survivors with a modeled step time: an HLO-FLOP (or
   6N+12LSD formula) compute term over a sustained per-device FLOP/s
   basis, plus every per-step collective the plan implies priced through
   the CommModel (grad reduce / ZeRO param gathers over ``data``, SP
   boundary gathers+scatters over ``tensor``, pipeline p2p over ``pipe``
   with the 1F1B bubble on the compute term) — compressed arms priced by
   ``predict_compressed``, so an int8 arm can only win when the
   (calibrated) model approves it;
4. **emits** an executable plan: mesh axes, per-leaf param PartitionSpecs
   (:func:`plan_param_specs` builds the real ``jax.sharding.
   PartitionSpec`` tree for the winning candidate), the compress policy,
   and the ranked alternatives with per-term score breakdowns — plus a
   ``plan_selected`` event and the validated RUNREPORT ``autoplan``
   section (``Telemetry.record_autoplan``), so every selection is
   auditable after the fact.

Known gaps vs measured (docs/autoplan.md spells these out): the comm
terms assume zero compute/comm overlap (the same serialized convention as
the RUNREPORT comm section's ``modeled_comm_s``), the vocab-parallel
cross-entropy reductions and optimizer-update traffic are unmodeled, and
TP compute is assumed to scale perfectly.  The ranking is validated
against measured CPU-sim steps in ``tests/test_autoplan.py``;
disagreements are disclosed in the section's ``modeled_vs_measured``
record rather than hidden.

Module scope is deliberately jax-free (``tools/autoplan.py`` is a
login-node CLI over a JSON model config): jax is
imported lazily and only by the executable-side helpers and the
``memory='model'`` estimator.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.comm_model import CommModel
from ..obs.mem_ledger import headroom_verdict
# the schema vocabulary lives in obs (the leaf subsystem) so the RUNREPORT
# validator never has to import dist; re-exported here for callers
from ..obs.report import AUTOPLAN_SCHEMA, PLAN_VERDICTS  # noqa: F401

#: Default sustained per-device FLOP/s when nothing better is known (no
#: measured step, no recognized chip) — only relative comm terms order
#: plans in that regime, and the basis is recorded so the report says so.
ASSUMED_FLOPS = 1e12


# --------------------------------------------------------- model description


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """Normalized, jax-free view of a model config — everything the shape
    table and the FLOP formula need.  Built by :func:`model_dims` from a
    ``GPTConfig``, a ``TransformerConfig``, or a plain dict (the CLI's
    JSON config)."""

    family: str  # 'gpt' (embed + stacked blocks + head) | 'transformer'
    dim: int
    nheads: int
    nlayers: int
    seq: int
    vocab: Optional[int] = None
    ffn: int = 0
    kv_heads: Optional[int] = None
    act: str = "gelu"
    norm: str = "layer"
    pos: str = "learned"
    dtype_size: int = 4
    # MoE (0 experts = dense).  Every ``moe_every``-th block's FFN is an
    # expert layer; top_k routing with the Switch capacity bound inflates
    # the expert FLOP term by ``top_k * capacity_factor / experts``.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2
    moe_capacity_factor: float = 1.25

    @property
    def n_moe_layers(self) -> int:
        """MoE blocks in the stack — ``is_moe_block`` places one at every
        ``moe_every``-th position, so exactly ``L // moe_every``."""
        if not self.moe_experts:
            return 0
        return self.nlayers // max(self.moe_every, 1)


def model_dims(config: Any) -> ModelDims:
    """Normalize a GPTConfig / TransformerConfig / dict into
    :class:`ModelDims`.  MoE GPT configs carry the expert dims through
    (the planner prices the EP all_to_all and the capacity-inflated
    expert FLOPs); the transformer family has no MoE variant."""
    get = (config.get if isinstance(config, dict)
           else lambda k, d=None: getattr(config, k, d))
    moe_experts = int(get("moe_experts", 0) or 0)
    if moe_experts and not get("vocab_size"):
        raise ValueError(
            "MoE planning needs the gpt family (gpt_moe) — the "
            "transformer family has no expert blocks")
    dim = int(get("dim"))
    ffn = get("ffn_hidden") or dim * int(get("ffn_mult", 4))
    dtype = get("dtype", "float32")
    try:
        dtype_size = int(np.dtype(dtype).itemsize)
    except TypeError:
        dtype_size = int(np.dtype(str(dtype).split(".")[-1]).itemsize)
    vocab = get("vocab_size")
    seq = get("max_seq") or get("seq") or 0
    kv = get("kv_heads")
    return ModelDims(
        family="gpt" if vocab else "transformer",
        dim=dim,
        nheads=int(get("nheads")),
        nlayers=int(get("nlayers")),
        seq=int(seq),
        vocab=int(vocab) if vocab else None,
        ffn=int(ffn),
        kv_heads=int(kv) if kv else None,
        act=str(get("act", "gelu")),
        norm=str(get("norm", "layer")),
        pos=str(get("pos", "learned")),
        dtype_size=dtype_size,
        moe_experts=moe_experts,
        moe_top_k=int(get("moe_top_k", 2) or 2),
        moe_every=int(get("moe_every", 2) or 2),
        moe_capacity_factor=float(get("moe_capacity_factor", 1.25) or 1.25),
    )


@dataclasses.dataclass(frozen=True)
class LeafRow:
    """One param leaf of the analytic shape table.  ``tp_dim`` /
    ``stack_dim`` name the dims the tensor / pipe axes shard (None =
    replicated on that axis); ``count`` multiplies the leaf (the
    transformer family keeps per-layer block lists where GPT stacks)."""

    path: str
    shape: Tuple[int, ...]
    tp_dim: Optional[int] = None
    stack_dim: Optional[int] = None
    count: int = 1
    matmul: bool = True  # counted by the 6N FLOP formula
    ep_dim: Optional[int] = None  # dim the expert-parallel axis shards
    #: FLOP multiplier vs a dense leaf — expert leaves carry
    #: ``top_k * capacity_factor / experts`` (each token visits top_k of
    #: E experts, padded to the Switch capacity bound).
    flop_weight: float = 1.0


def _block_rows(d: ModelDims) -> List[LeafRow]:
    """Unstacked per-block leaves with their TP dims — the analytic mirror
    of ``tensor_parallel.block_param_specs`` + ``init_block_params``."""
    D, F = d.dim, d.ffn
    rows: List[LeafRow] = []
    norm_leaves = [("scale", (D,))] + (
        [("bias", (D,))] if d.norm == "layer" else [])
    for ln in ("ln1", "ln2"):
        rows += [LeafRow(f"{ln}.{k}", s) for k, s in norm_leaves]
    if d.kv_heads and d.kv_heads != d.nheads:
        dkv = d.kv_heads * (D // d.nheads)
        rows += [
            LeafRow("attn.wq", (D, D), tp_dim=1),
            LeafRow("attn.bq", (D,), tp_dim=0),
            LeafRow("attn.wkv", (2, D, dkv), tp_dim=2),
            LeafRow("attn.bkv", (2, dkv), tp_dim=1),
        ]
    else:
        rows += [
            LeafRow("attn.wqkv", (3, D, D), tp_dim=2),
            LeafRow("attn.bqkv", (3, D), tp_dim=1),
        ]
    rows += [
        LeafRow("attn.wo", (D, D), tp_dim=0),
        LeafRow("attn.bo", (D,)),
    ]
    if d.act == "swiglu":
        rows += [
            LeafRow("mlp.w1", (2, D, F), tp_dim=2),
            LeafRow("mlp.b1", (2, F), tp_dim=1),
        ]
    else:
        rows += [
            LeafRow("mlp.w1", (D, F), tp_dim=1),
            LeafRow("mlp.b1", (F,), tp_dim=0),
        ]
    rows += [
        LeafRow("mlp.w2", (F, D), tp_dim=0),
        LeafRow("mlp.b2", (D,)),
    ]
    return rows


def _moe_rows(d: ModelDims, count: int) -> List[LeafRow]:
    """The expert-layer leaves of one MoE block — the analytic mirror of
    ``parallel.moe.init_moe_params`` / ``moe_param_specs``: router
    replicated, stacked expert arrays EP-sharded on dim 0.  Expert leaves
    carry the capacity-inflated FLOP weight (a token runs top_k of E
    experts, each padded to the Switch capacity bound)."""
    D, F, E = d.dim, d.ffn, d.moe_experts
    w = d.moe_top_k * d.moe_capacity_factor / E
    rows = [LeafRow("moe.router.w", (D, E), count=count)]
    if d.act == "swiglu":
        rows += [
            LeafRow("moe.experts.w1", (E, 2, D, F), ep_dim=0, count=count,
                    flop_weight=w),
            LeafRow("moe.experts.b1", (E, 2, F), ep_dim=0, count=count,
                    flop_weight=w),
        ]
    else:
        rows += [
            LeafRow("moe.experts.w1", (E, D, F), ep_dim=0, count=count,
                    flop_weight=w),
            LeafRow("moe.experts.b1", (E, F), ep_dim=0, count=count,
                    flop_weight=w),
        ]
    rows += [
        LeafRow("moe.experts.w2", (E, F, D), ep_dim=0, count=count,
                flop_weight=w),
        LeafRow("moe.experts.b2", (E, D), ep_dim=0, count=count,
                flop_weight=w),
    ]
    return rows


def param_table(d: ModelDims) -> List[LeafRow]:
    """The model's full analytic shape table.  GPT stacks block leaves on
    a leading [L] dim (``stack_dim=0`` — the dim ``pipe`` shards, and a
    legal FSDP dim, exactly as in the real spec tree); the transformer
    family keeps per-layer leaves (``count=nlayers``).  MoE GPT blocks
    are a heterogeneous LIST in the real tree (``init_gpt_moe_params``),
    so they are counted per-layer too: dense blocks x (L - n_moe), MoE
    blocks' attention/norm leaves + expert leaves x n_moe."""
    rows: List[LeafRow] = []
    if d.family == "gpt":
        assert d.vocab
        rows.append(LeafRow("tok_emb", (d.vocab, d.dim), tp_dim=0,
                            matmul=False))
        if d.pos == "learned":
            rows.append(LeafRow("pos_emb", (d.seq, d.dim), matmul=False))
        if d.moe_experts:
            n_moe = d.n_moe_layers
            n_dense = d.nlayers - n_moe
            brows = _block_rows(d)
            if n_dense:
                rows += [dataclasses.replace(
                    r, path=f"blocks[dense].{r.path}", count=n_dense)
                    for r in brows]
            rows += [dataclasses.replace(
                r, path=f"blocks[moe].{r.path}", count=n_moe)
                for r in brows if not r.path.startswith("mlp.")]
            rows += [dataclasses.replace(r, path=f"blocks[moe].{r.path}")
                     for r in _moe_rows(d, count=n_moe)]
        else:
            for r in _block_rows(d):
                rows.append(LeafRow(
                    f"blocks.{r.path}", (d.nlayers, *r.shape),
                    tp_dim=None if r.tp_dim is None else r.tp_dim + 1,
                    stack_dim=0))
        rows.append(LeafRow("head", (d.dim, d.vocab), tp_dim=1))
    else:
        for r in _block_rows(d):
            rows.append(dataclasses.replace(
                r, path=f"blocks.{r.path}", count=d.nlayers))
    norm_leaves = [("scale", (d.dim,))] + (
        [("bias", (d.dim,))] if d.norm == "layer" else [])
    rows += [LeafRow(f"ln_f.{k}", s) for k, s in norm_leaves]
    return rows


def flops_per_token(d: ModelDims) -> float:
    """The 6N+12LSD accounting: 6 FLOPs per matmul param per
    token (embedding tables excluded — gathers, not matmuls) plus the
    attention score/value matmuls.  A caller with a compiled step passes
    its ``cost_analysis`` count as ``fpt`` to :func:`plan` in its place.
    Expert leaves count at their capacity-inflated ``flop_weight`` — a
    token runs ``top_k`` of ``E`` experts, padded to capacity — so a MoE
    stack prices its *activated* FLOPs, not the full parameter count."""
    n_matmul = sum(
        r.count * r.flop_weight * int(np.prod(r.shape, dtype=np.int64))
        for r in param_table(d) if r.matmul)
    return 6.0 * n_matmul + 12.0 * d.nlayers * d.seq * d.dim


# --------------------------------------------------------------- candidates


def _divisors(n: int) -> List[int]:
    return [k for k in range(1, n + 1) if n % k == 0]


def _tp_ok(d: ModelDims, tp: int) -> bool:
    if tp == 1:
        return True
    if d.nheads % tp or d.dim % tp or d.ffn % tp:
        return False
    if d.vocab and d.vocab % tp:
        return False
    if d.kv_heads and d.kv_heads % tp:
        return False
    return True


def candidate_key(c: Dict[str, Any]) -> str:
    parts = [f"{'fsdp' if c['layout'] == 'fsdp' else 'dp'}{c['dp']}"]
    if c.get("ep", 1) > 1:
        parts.append(f"ep{c['ep']}")
    if c["tp"] > 1:
        parts.append(f"tp{c['tp']}")
    if c["pp"] > 1:
        parts.append(f"pp{c['pp']}")
    key = "·".join(parts)
    if c["compress"]["grads"]:
        key += "+gc8"
    if c["compress"]["acts"]:
        key += "+ac8"
    return key


def enumerate_candidates(
    d: ModelDims,
    n_chips: int,
    global_batch: int,
    allow_pp: bool = True,
    executable_only: bool = False,
    compression: bool = True,
    layouts: Sequence[str] = ("dp", "fsdp"),
) -> List[Dict[str, Any]]:
    """Every legal ``dp x tp x pp`` factorization of ``n_chips`` crossed
    with layer layout and compression arms — deterministic order.  Awkward
    chip counts still always yield at least pure DP (``dp = n_chips``
    divides any batch multiple of it; batch-indivisible dp values are
    skipped).  ``executable_only`` restricts to plans bench's timed
    runners can execute: compression only on the pure-dp ``pp == 1`` arm
    (``DataParallel(grad_compress='int8')`` — the GSPMD jit runner for
    tp/fsdp plans cannot express the int8 rings), and ``pp > 1`` plans
    restricted to the ``dp`` layout (bench's pipeline runner drives the
    1F1B/ZB schedules through ``DataParallel``, which replicates params
    over ``data`` — the fsdp spec insertion has no pipelined runner).

    MoE configs additionally cross each ``dp x tp`` point with an
    expert-parallel factor ``ep`` (every common divisor of ``dp`` and the
    expert count): the data axis splits into ``data = dp/ep`` x ``ep``,
    the batch shards over both, and expert stacks shard over ``ep``
    (``moe_param_specs``).  MoE candidates are restricted to ``pp == 1``
    (MoE blocks are a heterogeneous list — no stacked [L] dim for pipe to
    shard), the ``dp`` layout (the ZeRO insertion has no MoE runner), and
    no compression arms (the int8 rings have no expert-dispatch runner)."""
    out: List[Dict[str, Any]] = []
    moe = d.moe_experts > 0
    for pp in _divisors(n_chips):
        if pp > 1 and (
                not allow_pp or d.family != "gpt" or d.nlayers % pp or moe):
            continue
        for tp in _divisors(n_chips // pp):
            if not _tp_ok(d, tp):
                continue
            dp = n_chips // pp // tp
            if global_batch % dp:
                continue
            arm_layouts = [
                l for l in layouts if l == "dp" or (l == "fsdp" and dp > 1)]
            if moe or (executable_only and pp > 1):
                arm_layouts = [l for l in arm_layouts if l == "dp"]
            ep_arms = [
                e for e in _divisors(dp) if d.moe_experts % e == 0
            ] if moe else [1]
            for layout in arm_layouts:
                can_gq = compression and dp > 1 and not moe and not (
                    executable_only and (tp > 1 or pp > 1
                                         or layout == "fsdp"))
                grad_arms = (False, True) if can_gq else (False,)
                act_arms = (False, True) if (
                    compression and tp > 1 and not moe
                    and not executable_only) else (False,)
                for gq in grad_arms:
                    for aq in act_arms:
                        for ep in ep_arms:
                            c: Dict[str, Any] = {
                                "dp": dp, "tp": tp, "pp": pp,
                                "layout": layout,
                                "mesh_axes": {"pipe": pp, "data": dp,
                                              "tensor": tp},
                                "compress": {"grads": gq, "acts": aq},
                            }
                            if moe:
                                c["ep"] = ep
                                c["mesh_axes"] = {
                                    "pipe": pp, "data": dp // ep,
                                    "ep": ep, "tensor": tp}
                            out.append(c)
    for c in out:
        c["key"] = candidate_key(c)
    return out


# ----------------------------------------------------------------- sharding


def _axis_assignment(
    row: LeafRow, c: Dict[str, Any]
) -> List[Optional[str]]:
    """Per-dim mesh-axis assignment for one leaf under candidate ``c`` —
    the analytic mirror of ``plan_param_specs``: tp/pipe dims from the
    table, then (fsdp layout) the data axis on the first free dim whose
    size divides dp, exactly ``parallel.zero.zero_partition_spec``'s rule."""
    entries: List[Optional[str]] = [None] * len(row.shape)
    if c["pp"] > 1 and row.stack_dim is not None:
        entries[row.stack_dim] = "pipe"
    if c.get("ep", 1) > 1 and row.ep_dim is not None:
        entries[row.ep_dim] = "ep"
    if c["tp"] > 1 and row.tp_dim is not None:
        entries[row.tp_dim] = "tensor"
    if c["layout"] == "fsdp" and c["dp"] > 1:
        for dim, (size, used) in enumerate(zip(row.shape, entries)):
            if used is None and size > 0 and size % c["dp"] == 0:
                entries[dim] = "data"
                break
    return entries


def _leaf_shards(row: LeafRow, c: Dict[str, Any]) -> int:
    n = 1
    for axis in _axis_assignment(row, c):
        if axis is not None:
            n *= c["mesh_axes"][axis]
    return n


def _spec_str(entries: Sequence[Optional[str]]) -> str:
    trimmed = list(entries)
    while trimmed and trimmed[-1] is None:
        trimmed.pop()
    return "P(" + ", ".join(a or "None" for a in trimmed) + ")"


def spec_table(d: ModelDims, c: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per-leaf spec rows of a candidate (rendered, audit-friendly) —
    the ``param_specs`` payload of an emitted plan."""
    rows = []
    for r in param_table(d):
        entries = _axis_assignment(r, c)
        rows.append({
            "path": r.path,
            "shape": list(r.shape),
            "spec": _spec_str(entries),
            "shard_count": _leaf_shards(r, c),
        })
    return rows


# ------------------------------------------------------------------- memory


def estimate_memory_analytic(
    d: ModelDims,
    c: Dict[str, Any],
    global_batch: int,
    seq_len: Optional[int] = None,
    capacity_bytes: Optional[int] = None,
    optimizer_slots: int = 2,
    act_factor: float = 1.0,
) -> Dict[str, Any]:
    """Pure-python per-device resident-bytes estimate — byte-identical to
    ``MemoryModel.estimate`` over the real (config, mesh, specs) triple
    (``tests/test_autoplan.py`` pins the two): per-leaf ceil over the
    spec'd shard product, grads at param sharding, f32 optimizer moments,
    the same B_local*S*D*L activation term."""
    params_bytes = 0
    elems_resident = 0
    for r in param_table(d):
        n_elems = int(np.prod(r.shape, dtype=np.int64))
        shards = _leaf_shards(r, c)
        resident = -(-n_elems // shards)
        params_bytes += r.count * resident * d.dtype_size
        elems_resident += r.count * resident
    grads_bytes = params_bytes
    opt_bytes = optimizer_slots * elems_resident * 4
    S = seq_len if seq_len is not None else d.seq
    batch_per_device = global_batch // c["dp"]
    act_bytes = int(
        batch_per_device * S * d.dim * d.nlayers * act_factor * d.dtype_size)
    total = params_bytes + grads_bytes + opt_bytes + act_bytes
    hv = headroom_verdict(total, capacity_bytes)
    return {
        "params_bytes": params_bytes,
        "grads_bytes": grads_bytes,
        "opt_bytes": opt_bytes,
        "act_bytes": act_bytes,
        "total_bytes": total,
        "capacity_bytes": capacity_bytes,
        "frac": hv["frac"],
        "headroom_frac": hv["headroom_frac"],
        "verdict": hv["verdict"],
        "basis": "analytic",
    }


class _MiniMesh:
    """Duck-typed mesh for ``MemoryModel.estimate`` (it reads only
    ``axis_names`` + ``shape``) — scores mesh shapes no device has to
    back."""

    def __init__(self, sizes: Dict[str, int]) -> None:
        self.shape = dict(sizes)
        self.axis_names = tuple(sizes)


def estimate_memory_model(
    config: Any,
    c: Dict[str, Any],
    global_batch: int,
    seq_len: Optional[int] = None,
    capacity_bytes: Optional[int] = None,
    optimizer_slots: int = 2,
    act_factor: float = 1.0,
) -> Dict[str, Any]:
    """``MemoryModel.estimate`` over the candidate's REAL spec tree — the
    acceptance path: the same model that judges compiled layouts judges
    the plan, before anything compiles."""
    from ..obs.mem_ledger import MemoryModel

    specs = plan_param_specs(c, config)
    est = MemoryModel(
        capacity_bytes=capacity_bytes,
        optimizer_slots=optimizer_slots,
        act_factor=act_factor,
    ).estimate(
        config, _MiniMesh(c["mesh_axes"]), specs,
        batch_per_device=global_batch // c["dp"],
        seq_len=seq_len,
    )
    est = {k: est[k] for k in (
        "params_bytes", "grads_bytes", "opt_bytes", "act_bytes",
        "total_bytes", "capacity_bytes", "frac", "headroom_frac",
        "verdict")}
    est["basis"] = "memory-model"
    return est


# ------------------------------------------------------------------ scoring


def _grad_payload_bytes(d: ModelDims, c: Dict[str, Any]) -> float:
    """Per-device grad bytes entering the data-axis collective: each
    leaf's bytes after the NON-data shards (tp/pp/ep — each ep shard owns
    different experts, so its grads never cross the ep boundary) — the
    fsdp data shard is the collective's OUTPUT, not its payload."""
    total = 0
    for r in param_table(d):
        n_elems = int(np.prod(r.shape, dtype=np.int64))
        shards = 1
        for axis in _axis_assignment(r, c):
            if axis in ("tensor", "pipe", "ep"):
                shards *= c["mesh_axes"][axis]
        total += r.count * -(-n_elems // shards) * d.dtype_size
    return float(total)


def comm_terms(
    d: ModelDims,
    c: Dict[str, Any],
    global_batch: int,
    model: CommModel,
    seq_len: Optional[int] = None,
    microbatches: int = 8,
) -> List[Dict[str, Any]]:
    """The per-step collectives candidate ``c`` implies, priced through
    the CommModel.  Per term: op, axes, full-payload bytes (the same
    nccl-tests convention ``CommModel.predict`` expects), op count per
    step, per-op and total predicted seconds, and — for compressed arms —
    the ``predict_compressed`` record (so the report shows whether the
    calibrated model actually approved the ring)."""
    S = seq_len if seq_len is not None else d.seq
    dp, tp, pp = c["dp"], c["tp"], c["pp"]
    terms: List[Dict[str, Any]] = []

    def price(name, op, axes, n, payload, count, compressed):
        if n <= 1 or payload <= 0 or count <= 0:
            return
        row: Dict[str, Any] = {
            "name": name, "op": op, "axes": list(axes), "n": int(n),
            "payload_bytes": int(payload), "count": int(count),
            "compressed": bool(compressed),
        }
        if compressed:
            rec = model.predict_compressed(
                op, payload, n, axes=axes, elem_bytes=d.dtype_size)
            row["per_op_s"] = rec["compressed_s"]
            row["model_approves"] = rec["compress"]
            row["basis"] = rec["basis"]
            row["exact_s"] = rec["exact_s"]
        else:
            row["per_op_s"] = model.predict(op, payload, n, axes=axes)
        row["total_s"] = row["per_op_s"] * count
        terms.append(row)

    gq = c["compress"]["grads"]
    grad_bytes = _grad_payload_bytes(d, c)
    if dp > 1:
        if c["layout"] == "fsdp":
            # ZeRO-3: param all-gather fwd + bwd re-gather, grad
            # reduce-scatter inside the backward
            price("fsdp-param-gather", "all_gather", ("data",), dp,
                  grad_bytes, 2, gq)
            price("fsdp-grad-scatter", "reduce_scatter", ("data",), dp,
                  grad_bytes, 1, gq)
        else:
            price("dp-grad-reduce", "all_reduce", ("data",), dp,
                  grad_bytes, 1, gq)
    if tp > 1:
        # SP boundaries: 2 gathers + 2 scatters per block forward, the
        # transposed pair in the backward -> 4 of each per layer per step
        act_bytes = (global_batch // dp) * S * d.dim * d.dtype_size
        n_each = 4 * d.nlayers
        aq = c["compress"]["acts"]
        price("sp-act-gather", "all_gather", ("tensor",), tp,
              act_bytes, n_each, aq)
        price("sp-act-scatter", "reduce_scatter", ("tensor",), tp,
              act_bytes, n_each, aq)
    if pp > 1:
        # 1F1B critical path: ~2(M + pp - 2) boundary transfers of one
        # microbatch's boundary activation
        micro_act = ((global_batch // dp) / microbatches) * S * d.dim \
            * d.dtype_size
        price("pp-boundary", "ppermute", ("pipe",), pp, micro_act,
              2 * (microbatches + pp - 2), False)
    ep = c.get("ep", 1)
    if ep > 1:
        # EP dispatch: each MoE layer all_to_alls the capacity-padded
        # token buffer (T_local * top_k * cf rows of D) to its experts
        # and back, forward and backward -> 4 per MoE layer per step.
        tok_local = (global_batch // dp) * S
        a2a_bytes = int(
            tok_local * d.moe_top_k * d.moe_capacity_factor
            * d.dim * d.dtype_size)
        price("moe-all-to-all", "all_to_all", ("ep",), ep, a2a_bytes,
              4 * d.n_moe_layers, False)
    return terms


def score_candidate(
    d: ModelDims,
    c: Dict[str, Any],
    global_batch: int,
    model: CommModel,
    effective_flops: float,
    fpt: float,
    seq_len: Optional[int] = None,
    microbatches: int = 8,
) -> Dict[str, Any]:
    """Modeled step time = compute term (HLO/formula FLOPs over the
    sustained per-device FLOP/s, inflated by the pipeline schedule's
    modeled wall-clock multiplier for pp plans) + the serialized comm
    terms.  Returned dict is the ranked-row payload (per-term breakdown
    included).

    pp plans are priced under BOTH pipeline schedules the executable side
    can drive — classic 1F1B and the zero-bubble split
    (``obs.aggregate.pipeline_time_inflation``, which charges zb's extra
    dgrad/wgrad recompute honestly) — and the row records the cheaper one
    as ``pp_schedule`` plus its slot-accounting ``bubble_fraction``
    (``obs.aggregate.pipeline_bubble_fraction``), so the planner's
    schedule choice is auditable against a measured pair."""
    from ..obs.aggregate import (
        pipeline_bubble_fraction,
        pipeline_time_inflation,
    )

    S = seq_len if seq_len is not None else d.seq
    n_chips = c["dp"] * c["tp"] * c["pp"]
    flops_step = fpt * global_batch * S
    if c["pp"] > 1:
        inflations = {
            sched: pipeline_time_inflation(microbatches, c["pp"],
                                           schedule=sched)
            for sched in ("1f1b", "zb")
        }
        pp_schedule = min(inflations, key=inflations.get)
        inflation = inflations[pp_schedule]
        bubble = pipeline_bubble_fraction(
            microbatches, c["pp"], schedule=pp_schedule)
    else:
        pp_schedule, inflation, bubble = None, 1.0, 0.0
    compute_s = flops_step / n_chips / effective_flops * inflation
    terms = comm_terms(d, c, global_batch, model, seq_len=S,
                       microbatches=microbatches)
    comm_s = sum(t["total_s"] for t in terms)
    return {
        "compute_s": compute_s,
        "comm_s": comm_s,
        "step_s": compute_s + comm_s,
        "bubble_fraction": round(bubble, 4),
        "pp_schedule": pp_schedule,
        "terms": terms,
    }


# --------------------------------------------------------------- the planner


def plan(
    config: Any,
    n_chips: int,
    global_batch: int,
    seq_len: Optional[int] = None,
    comm_model: Optional[CommModel] = None,
    capacity_bytes: Optional[int] = None,
    effective_flops: Optional[float] = None,
    fpt: Optional[float] = None,
    optimizer_slots: int = 2,
    act_factor: float = 1.0,
    microbatches: int = 8,
    allow_pp: bool = True,
    executable_only: bool = False,
    compression: bool = True,
    layouts: Sequence[str] = ("dp", "fsdp"),
    memory: str = "auto",
    device_kind: Optional[str] = None,
    top: int = 8,
    emit: bool = True,
) -> Dict[str, Any]:
    """Plan the parallelism for ``config`` on ``n_chips`` chips.

    Returns the RUNREPORT-shaped ``autoplan`` section: ``chosen`` (the
    executable winner: mesh axes, layout, compress policy, rendered
    per-leaf specs, score + memory breakdowns), ``ranked`` (top
    alternatives), ``pruned`` + ``n_pruned_oom`` (the OOM evidence), the
    scoring ``basis``, and ``verdict`` (``ok`` | ``all_oom``).

    - ``comm_model``: a calibrated :class:`CommModel` grounds the comm
      terms (and the int8 arms) in measurement; default = the
      per-generation table model for ``device_kind`` (no kind named =
      the table's ``cpu`` placeholder row; an unlisted kind raises).
    - ``effective_flops``: sustained per-device FLOP/s.  Feed the value a
      measured step implies (HLO FLOPs / measured step time) to close
      the loop; default = 40% of the chip's
      table peak when recognized, else :data:`ASSUMED_FLOPS`.
    - ``fpt``: FLOPs/token for the compute term — pass the compiled
      step's ``cost_analysis`` count when one exists; default = the
      6N+12LSD formula.
    - ``memory``: ``'model'`` (``MemoryModel.estimate`` over the real
      spec tree — needs jax importable), ``'analytic'`` (the pure-python
      mirror, for the jax-free CLI), ``'auto'`` = model when config is a
      real config object and jax imports, else analytic.
    - ``emit``: a ``plan_rejected_oom`` event per pruned candidate and one
      ``plan_selected`` event for the winner land on the default event
      timeline.
    """
    d = model_dims(config)
    if global_batch < 1:
        raise ValueError(f"global_batch must be >= 1, got {global_batch}")
    if memory not in ("auto", "model", "analytic"):
        raise ValueError(f"memory must be auto|model|analytic, got {memory!r}")
    use_model = memory == "model"
    if memory == "auto":
        use_model = not isinstance(config, dict) and _jax_importable()
    model = comm_model or CommModel.from_defaults(
        device_kind=device_kind or "cpu")
    fpt_val = float(fpt) if fpt else flops_per_token(d)
    eff, compute_basis = _resolve_effective_flops(
        effective_flops, device_kind)

    cands = enumerate_candidates(
        d, n_chips, global_batch, allow_pp=allow_pp,
        executable_only=executable_only, compression=compression,
        layouts=layouts)
    ranked: List[Dict[str, Any]] = []
    pruned: List[Dict[str, Any]] = []
    for c in cands:
        if use_model:
            mem = estimate_memory_model(
                config, c, global_batch, seq_len=seq_len,
                capacity_bytes=capacity_bytes,
                optimizer_slots=optimizer_slots, act_factor=act_factor)
        else:
            mem = estimate_memory_analytic(
                d, c, global_batch, seq_len=seq_len,
                capacity_bytes=capacity_bytes,
                optimizer_slots=optimizer_slots, act_factor=act_factor)
        if mem["verdict"] == "oom_risk":
            row = {"key": c["key"], "total_bytes": mem["total_bytes"],
                   "capacity_bytes": mem["capacity_bytes"],
                   "frac": mem["frac"]}
            pruned.append(row)
            if emit:
                from ..obs.events import emit_event

                emit_event("plan_rejected_oom", **row)
            continue
        score = score_candidate(
            d, c, global_batch, model, eff, fpt_val,
            seq_len=seq_len, microbatches=microbatches)
        ranked.append({**c, **score, "memory": mem})
    ranked.sort(key=lambda r: (r["step_s"], r["key"]))

    chosen = None
    if ranked:
        chosen = dict(ranked[0])
        chosen["param_specs"] = spec_table(d, chosen)[:64]
        if emit:
            from ..obs.events import emit_event

            emit_event(
                "plan_selected", key=chosen["key"],
                modeled_step_s=chosen["step_s"],
                n_candidates=len(cands), n_pruned_oom=len(pruned))
    return {
        "schema": AUTOPLAN_SCHEMA,
        "verdict": "ok" if chosen else "all_oom",
        "n_candidates": len(cands),
        "n_pruned_oom": len(pruned),
        "pruned": pruned[:16],
        "chosen": chosen,
        "ranked": [
            {k: v for k, v in r.items() if k != "terms"}
            if i else r  # full per-term breakdown on the winner only
            for i, r in enumerate(ranked[:top])
        ],
        "params": {
            "n_chips": n_chips, "global_batch": global_batch,
            "seq_len": seq_len if seq_len is not None else d.seq,
            "family": d.family, "microbatches": microbatches,
        },
        "basis": {
            "comm": model.source,
            "compute": compute_basis,
            "memory": ("memory-model" if use_model else "analytic"),
            "flops_per_token": fpt_val,
            "effective_flops": eff,
        },
    }


PREFILL_PLAN_SCHEMA = "autoplan-prefill-v1"


def plan_prefill_tier(
    config: Any,
    *,
    context_len: int,
    chunk: int,
    block_size: int,
    num_blocks: Optional[int] = None,
    cp_widths: Sequence[int] = (1, 2, 4, 8),
    batch: int = 1,
    comm_model: Optional[CommModel] = None,
    device_kind: Optional[str] = None,
    capacity_bytes: Optional[int] = None,
    effective_flops: Optional[float] = None,
    emit: bool = True,
) -> Dict[str, Any]:
    """Size a CP prefill tier (docs/long_context.md "CP prefill
    serving"): for each candidate ring width price the modeled TTFT of
    one ``context_len``-token prompt — the chunk compute split ``cp``
    ways plus every ring hop through the CommModel's ``ppermute`` row,
    the same per-hop payloads the engine's HLO ledger shows
    (``ring_hops_per_chunk`` / ``ring_chunk_bytes`` in
    ops/ring_paged.py) — and the per-rank memory verdict: pool slice
    (``pool/cp``) + ring working set against ``capacity_bytes``
    (``headroom_verdict``).  Ranked by modeled ``ttft_s`` among
    non-OOM arms; widths that don't divide ``chunk`` (each rank
    prefills ``chunk/cp`` rows) are skipped as non-executable.

    The hop and compute terms are summed SERIALLY — the honest model
    until the ring's overlap is measured on the chip; the returned
    ``basis`` says so.  ``emit`` lands ``plan_rejected_oom`` /
    ``plan_selected`` events like :func:`autoplan`."""
    from ..obs.mem_ledger import headroom_verdict
    from ..ops.ring_paged import (
        modeled_cp_working_set_bytes,
        ring_chunk_bytes,
        ring_hops_per_chunk,
    )

    if context_len < 1 or chunk < 1 or block_size < 1:
        raise ValueError(
            f"context_len/chunk/block_size must be >= 1, got "
            f"{context_len}/{chunk}/{block_size}")
    d = model_dims(config)
    kv_heads = d.kv_heads or d.nheads
    head_dim = d.dim // d.nheads
    model = comm_model or CommModel.from_defaults(
        device_kind=device_kind or "cpu")
    eff, compute_basis = _resolve_effective_flops(
        effective_flops, device_kind)
    # forward-only prefill: the 6N+12LSD accounting is fwd+bwd, and the
    # backward is 2x the forward
    fpt = flops_per_token(d) / 3.0
    n_chunks = -(-context_len // chunk)
    nb_base = num_blocks if num_blocks is not None \
        else 1 + batch * -(-context_len // block_size)

    ranked: List[Dict[str, Any]] = []
    pruned: List[Dict[str, Any]] = []
    skipped: List[int] = []
    for cp in sorted(set(int(w) for w in cp_widths)):
        if cp < 1 or chunk % cp:
            skipped.append(cp)
            continue
        nb = -(-nb_base // cp) * cp  # the engine's rounding
        nb_local = nb // cp
        pool = 2 * d.nlayers * nb * kv_heads * block_size * head_dim \
            * d.dtype_size
        mem_bytes = pool // cp + modeled_cp_working_set_bytes(
            kv_heads=kv_heads, head_dim=head_dim, block_size=block_size,
            nb_local=nb_local, chunk=chunk, cp=cp, batch=batch,
            itemsize=d.dtype_size)
        verdict = headroom_verdict(mem_bytes, capacity_bytes)
        compute_s = fpt * context_len / (cp * eff)
        terms: List[Dict[str, Any]] = [{
            "name": "prefill-compute", "op": "matmul", "axes": [],
            "n": cp, "count": n_chunks, "total_s": compute_s,
        }]
        ring_s = 0.0
        if cp > 1:
            fresh = batch * kv_heads * (chunk // cp) * head_dim \
                * d.dtype_size
            pool_slice = nb_local * kv_heads * block_size * head_dim \
                * d.dtype_size
            for name, payload in (("cp-ring-fresh", fresh),
                                  ("cp-ring-pool", pool_slice)):
                per_op = model.predict(
                    "ppermute", payload, cp, axes=("context",))
                count = n_chunks * 2 * (cp - 1) * d.nlayers
                terms.append({
                    "name": name, "op": "ppermute", "axes": ["context"],
                    "n": cp, "payload_bytes": int(payload),
                    "count": count, "per_op_s": per_op,
                    "total_s": per_op * count,
                })
                ring_s += per_op * count
        row = {
            "key": f"cp{cp}",
            "cp": cp,
            "num_blocks": nb,
            "ttft_s": compute_s + ring_s,
            "compute_s": compute_s,
            "ring_s": ring_s,
            "ring_hops": n_chunks * ring_hops_per_chunk(d.nlayers, cp),
            "ring_bytes": n_chunks * ring_chunk_bytes(
                nlayers=d.nlayers, cp=cp, batch=batch, kv_heads=kv_heads,
                head_dim=head_dim, chunk=chunk, nb_local=nb_local,
                block_size=block_size, itemsize=d.dtype_size),
            "mem_bytes": mem_bytes,
            "memory": verdict,
            "terms": terms,
        }
        if verdict["verdict"] == "oom_risk":
            prow = {"key": row["key"], "total_bytes": mem_bytes,
                    "capacity_bytes": capacity_bytes,
                    "frac": verdict["frac"]}
            pruned.append(prow)
            if emit:
                from ..obs.events import emit_event

                emit_event("plan_rejected_oom", **prow)
            continue
        ranked.append(row)
    ranked.sort(key=lambda r: (r["ttft_s"], r["key"]))

    chosen = dict(ranked[0]) if ranked else None
    if chosen and emit:
        from ..obs.events import emit_event

        emit_event(
            "plan_selected", key=chosen["key"],
            modeled_step_s=chosen["ttft_s"],
            n_candidates=len(ranked) + len(pruned),
            n_pruned_oom=len(pruned))
    return {
        "schema": PREFILL_PLAN_SCHEMA,
        "verdict": "ok" if chosen else "all_oom",
        "n_candidates": len(ranked) + len(pruned),
        "n_pruned_oom": len(pruned),
        "skipped_widths": skipped,
        "pruned": pruned,
        "chosen": chosen,
        "ranked": [
            {k: v for k, v in r.items() if k != "terms"} if i else r
            for i, r in enumerate(ranked)
        ],
        "params": {
            "context_len": context_len, "chunk": chunk,
            "block_size": block_size, "batch": batch,
            "family": d.family,
        },
        "basis": {
            "comm": model.source,
            "compute": compute_basis,
            "flops_per_token_fwd": fpt,
            "effective_flops": eff,
            "overlap": "serial (compute + ring summed)",
        },
    }


def _jax_importable() -> bool:
    try:
        import jax  # noqa: F401

        return True
    except Exception:
        return False


def _resolve_effective_flops(
    effective_flops: Optional[float], device_kind: Optional[str]
) -> Tuple[float, str]:
    if effective_flops:
        return float(effective_flops), "measured"
    if device_kind:
        from ..obs.telemetry import peak_flops_for

        peak = peak_flops_for(device_kind)
        if peak:
            # sustained ~= 40% of peak: the repo's own measured MFU band
            return 0.4 * peak, "peak-table@0.4"
    return ASSUMED_FLOPS, "assumed"


def attach_measured(
    result: Dict[str, Any], rows: Sequence[Dict[str, Any]]
) -> Dict[str, Any]:
    """Record measured step times for (some of) the ranked plans into the
    section's ``modeled_vs_measured`` — the audit record the acceptance
    reads: per-plan modeled vs measured with rel err, and whether the
    measured ordering agrees with the modeled one.  ``rows``: dicts with
    ``key``, ``modeled_step_s``, ``measured_step_s``; pp rows may carry
    the bubble audit alongside (``pp_schedule``,
    ``modeled_bubble_fraction`` from the slot accounting,
    ``measured_bubble_fraction`` estimated from the timed 1F1B/ZB pair)
    — passed through verbatim so the RUNREPORT shows the bubble term's
    modeled-vs-measured agreement, not just the step time's."""
    out_rows = []
    for r in rows:
        mo, me = float(r["modeled_step_s"]), float(r["measured_step_s"])
        out_rows.append({
            "key": r["key"], "modeled_step_s": mo, "measured_step_s": me,
            "rel_err": round((mo - me) / me, 4) if me > 0 else None,
        })
        for extra in ("pp_schedule", "modeled_bubble_fraction",
                      "measured_bubble_fraction", "microbatches"):
            if extra in r:
                out_rows[-1][extra] = r[extra]
    modeled_order = [r["key"] for r in sorted(
        out_rows, key=lambda r: r["modeled_step_s"])]
    measured_order = [r["key"] for r in sorted(
        out_rows, key=lambda r: r["measured_step_s"])]
    result["modeled_vs_measured"] = {
        "rows": out_rows,
        "modeled_order": modeled_order,
        "measured_order": measured_order,
        "ordering_agrees": modeled_order == measured_order,
    }
    return result


# ---------------------------------------------------------- executable side


def build_mesh(c: Dict[str, Any], devices: Optional[Sequence[Any]] = None):
    """A real ``jax.sharding.Mesh`` for a candidate/chosen plan: the
    plan's axis sizes over the attached (or given) devices, ICI-aware via
    ``mesh_utils`` when more than one axis is non-trivial."""
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    sizes = c["mesh_axes"]
    names = tuple(sizes)
    shape = tuple(sizes[a] for a in names)
    devs = list(devices) if devices is not None else jax.devices()
    n = int(np.prod(shape))
    if len(devs) != n:
        raise ValueError(
            f"plan wants {n} chips ({dict(sizes)}), have {len(devs)}")
    try:
        arr = mesh_utils.create_device_mesh(shape, devices=devs)
    except Exception:
        arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, axis_names=names)


def plan_param_specs(c: Dict[str, Any], config: Any):
    """The candidate's REAL per-leaf PartitionSpec tree (jax side): the
    family's TP/PP specs composed with the ZeRO first-free-divisible-dim
    data-axis insertion for the fsdp layout.  ``tests/test_autoplan.py``
    pins this against the analytic :func:`spec_table`."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..obs.mem_ledger import _shapes_for_config
    from ..parallel.zero import zero_partition_spec

    d = model_dims(config)
    tp_axis = "tensor" if c["tp"] > 1 else None
    pipe_axis = "pipe" if c["pp"] > 1 else None
    shapes = _shapes_for_config(config)
    if d.family == "gpt" and d.moe_experts:
        from ..models.gpt_moe import gpt_moe_param_specs

        base = gpt_moe_param_specs(
            config, tp_axis=tp_axis,
            ep_axis="ep" if c.get("ep", 1) > 1 else None)
    elif d.family == "gpt":
        from ..models.gpt import gpt_param_specs

        base = gpt_param_specs(config, tp_axis=tp_axis, pipe_axis=pipe_axis)
    else:
        if tp_axis:
            from ..parallel.tensor_parallel import transformer_param_specs

            base = transformer_param_specs(config, axis=tp_axis)
        else:
            base = jax.tree.map(lambda _: P(), shapes)
    if c["layout"] != "fsdp" or c["dp"] <= 1:
        return base
    flat_p, treedef = jax.tree_util.tree_flatten(shapes)
    flat_s = treedef.flatten_up_to(base)
    out = [
        zero_partition_spec(tuple(p.shape), s, "data", c["dp"])[0]
        for p, s in zip(flat_p, flat_s)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_partition_spec(c: Dict[str, Any]):
    """Batch leaves shard their leading dim over the data axis — over
    ``("data", "ep")`` for MoE plans, whose data axis splits in two (the
    batch still shards ``dp`` ways; experts shard over the ep factor)."""
    from jax.sharding import PartitionSpec as P

    if "ep" in c["mesh_axes"]:
        return P(("data", "ep")) if c["dp"] > 1 else P()
    return P("data") if c["dp"] > 1 else P()
