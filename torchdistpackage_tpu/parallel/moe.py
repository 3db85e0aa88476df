"""Mixture-of-Experts: expert parallelism (EP) + MoE data parallelism.

Analogue of the reference's MoE support — ``tpc.build_moe_groups``
(process_topo.py:118-143) plus ``MoEDP``/``create_moe_dp_hooks``
(naive_ddp.py:233-441, moe_dp.md) — but **first-class**: the reference
delegates the actual expert all-to-all dispatch to DeepSpeed/fastmoe forks
(explore/moe/ds_fmoe_main.py:19-25); here token dispatch is implemented
natively with ``lax.all_to_all`` over the ``'moe_ep'`` mesh axis, with two
interchangeable dispatch materializations: dense [T, E, C] one-hot einsums
(MXU-friendly, the GShard/Switch pattern — fine at small scale) and an
index-based gather/scatter-add path (O(T*k + E*C*D) memory) that 'auto'
selects once the dense tensors pass :data:`_DENSE_DISPATCH_MAX` elements —
the routing DECISION (priorities, drops, gates) is shared code either way.

Design mirrors the package's TP layers: parameters are global-array pytrees;
``ep_axis=None`` runs serially on full weights, while inside ``shard_map``
each device holds ``num_experts / ep`` stacked experts (leading expert dim
sharded over the EP axis — see :func:`moe_param_specs`) and the forward
inserts the all-to-alls.  Static shapes are kept through capacity-factor
padding (SURVEY.md §7 "hard parts"): each expert processes a fixed
``capacity`` slots per device; overflowing tokens are dropped (contribute
zero, i.e. pass through the residual), underfull slots are zero-padded.

MoE-DP (replicated-expert data parallelism) composes through
:class:`~..parallel.data_parallel.DataParallel`'s ``grad_reduce_overrides``:
expert grads reduce over ``'moe_dp'`` only, everything else over the full
data group — exactly the reference's hook split (naive_ddp.py:269-441).
:func:`moe_grad_reduce_overrides` returns the right override dict.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax

from jax.lax import axis_size
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dist.topology import EXPERT_AXIS, MOE_DATA_AXIS
from ..utils import profiling as prof
from .tensor_parallel.layers import rms_norm

PyTree = Any


# How moe_forward materializes dispatch and combine (MoEConfig.dispatch).
MOE_DISPATCHES = ("dense", "sorted", "auto")


def check_moe_dispatch(dispatch: str) -> str:
    if dispatch not in MOE_DISPATCHES:
        raise ValueError(
            f"unknown MoE dispatch {dispatch!r}: it is one of "
            f"{MOE_DISPATCHES}")
    return dispatch


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    dim: int
    ffn_dim: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    # jitter / z-loss knobs kept minimal; aux load-balance loss is standard
    aux_loss_weight: float = 1e-2
    dtype: Any = jnp.float32
    # 'topk' (token-choice, GShard/Switch: each token picks top_k experts,
    # overflow dropped, aux loss balances) | 'expert_choice' (EC: each
    # EXPERT picks its top-capacity tokens — perfectly balanced by
    # construction, no drops, aux loss identically 0; Zhou et al. 2022).
    # EC capacity is ceil(T * capacity_factor / E) per the paper — top_k
    # does NOT scale it (top_k is a token-choice concept).  EC routing is
    # non-causal by construction (an expert ranks the WHOLE sequence), so
    # moe_forward(causal=True) rejects it — see _expert_choice_dispatch.
    router: str = "topk"
    # How dispatch/combine are MATERIALIZED (the routing decision is
    # identical — outputs agree to summation-order rounding):
    #   'dense'  — [T, E, C] one-hot einsums.  MXU-friendly but O(T*E*C)
    #              memory; dominant at real scale (VERDICT r3 weak #4).
    #   'sorted' — index-based gather / scatter-add, O(T*k + E*C*D): each
    #              kept (token, choice) writes its token row into flat slot
    #              e*C + c, dropped choices write to a discarded dumpster
    #              row; combine gathers the slot outputs back per token.
    #   'auto'   — 'sorted' on the TPU backend (the path with a chip
    #              record); elsewhere 'sorted' when the dense tensors would
    #              exceed _DENSE_DISPATCH_MAX elements.
    dispatch: str = "auto"
    # Expert FFN activation: 'gelu' | 'swiglu' (stacked [E, 2, D, F]
    # gate/up — the Mixtral-style expert; structural dispatch on w1.ndim,
    # mirroring the dense MLP's convention in tensor_parallel/layers.py;
    # a 3-dim ``w1`` [E, D, 2F] under 'swiglu' is the gated expert WITHOUT
    # biases, gate and up side by side in one matmul: serving path only) |
    # 'relu2' (non-gated squared ReLU, no biases: serving path only).
    act: str = "gelu"
    # --- the sigmoid-router / latent-expert family (serving path only,
    # :func:`moe_serve_forward`; docs/serving.md "State models"):
    # 'softmax' (Mixtral: probabilities over all experts, top-k kept and
    # renormalized) | 'sigmoid' (one score an expert in float32; the top-k
    # of score + the router's ``bias`` leaf is chosen, the bias does not
    # enter the weights; weights = score / sum of the chosen scores x
    # ``routed_scale``) | 'mlp' (the router is a small network over a
    # stream of its own that runs from expert layer to expert layer,
    # :func:`_mlp_route`; softmax in float32, the top-k of probability +
    # ``bias`` chosen, weights = the chosen probabilities as they are)
    score: str = "softmax"
    routed_scale: float = 1.0
    # experts work in a latent of this width: ``latent.down`` [D, latent]
    # before the dispatch, ``latent.up`` [latent, D] after the combine
    latent_dim: Optional[int] = None
    # a shared expert at full width (0 = none), same activation, every token
    shared_ffn: int = 0
    # ``(first, count)``: the experts THIS device holds.  The router keeps
    # its ``num_experts`` outputs and its top-k; the stacked expert leaves
    # are ``[count, ...]``; assignments to experts not held are left out
    # and the partial result goes on (one chip's share of an EP layer, run
    # without its exchange).  None = all of them.
    held: Optional[Tuple[int, int]] = None
    # the 'mlp' router's RMSNorm
    norm_eps: float = 1e-5

    def __post_init__(self):
        if self.router not in ("topk", "expert_choice"):
            raise ValueError(f"unknown MoE router {self.router!r}")
        check_moe_dispatch(self.dispatch)
        if self.act not in ("gelu", "swiglu", "relu2"):
            raise ValueError(f"unknown MoE act {self.act!r}")
        if self.score not in ("softmax", "sigmoid", "mlp"):
            raise ValueError(f"unknown MoE router score {self.score!r}")
        if self.held is not None:
            first, count = self.held
            if not (0 <= first and count >= 1
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"held experts {self.held} outside 0..{self.num_experts}")

    @property
    def held_range(self) -> Tuple[int, int]:
        return (0, self.num_experts) if self.held is None else self.held


# ------------------------------------------------------------------ dispatch


# Above this many dense-dispatch elements (T*E*C), dispatch='auto' switches
# to the index-based path: 2^24 f32 elements = 64 MB for EACH of
# dispatch/combine, and the einsums' [T, E*C] matmul views grow as T^2 —
# the measured crossover territory on v5e-class HBM.
_DENSE_DISPATCH_MAX = 1 << 24


def resolve_moe_dispatch(dispatch: Optional[str]) -> str:
    """``'auto'``/None -> ``'sorted'`` on TPU (the one dispatch with a chip
    record: it beat ``'dense'`` there), ``'auto'`` (the size-based
    dense/sorted selection of :func:`_use_sorted`) elsewhere.  Explicit
    values pass through validated.  The choice is recorded on the event
    timeline (``moe_dispatch_selected``)."""
    if dispatch in (None, "auto"):
        chosen = "sorted" if jax.default_backend() == "tpu" else "auto"
        from ..obs.events import emit_event

        emit_event("moe_dispatch_selected", requested="auto", chosen=chosen,
                   backend=jax.default_backend())
        return chosen
    return check_moe_dispatch(dispatch)


def _use_sorted(dispatch: str, T: int, E: int, capacity: int) -> bool:
    """``dispatch``: cfg.dispatch after :func:`resolve_moe_dispatch`."""
    if dispatch == "auto":
        return T * E * capacity > _DENSE_DISPATCH_MAX
    return dispatch == "sorted"


@prof.scoped(prof.ROUTE)
def _top_k_route(
    probs: jnp.ndarray, k: int, capacity: int, priority: str = "choice"
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The ROUTING DECISION shared by both dispatch materializations.

    probs: [T, E].  Returns ``gate_vals`` [T, k] (renormalized over the kept
    choices of each token), ``gate_idx`` [T, k] (expert of each choice),
    ``slot`` [T, k] (capacity slot within that expert), ``keep`` [T, k, E]
    (one-hot of choices that fit under capacity).

    ``priority`` orders the capacity ranking:

    - ``'choice'`` (Switch/GShard): all 1st choices rank before any 2nd
      choice (token order within a choice), so low-index tokens don't
      starve later experts of their primary assignments.  NOT causal-safe
      under drops: a future token's 1st choice can evict an earlier
      token's 2nd-choice slot, leaking future information backward.
    - ``'token'``: all of token t's choices rank before any of token
      t+1's — token t's keep/slot then depends only on tokens <= t, so the
      layer is leak-free for autoregressive models even when capacity
      drops occur.  :func:`moe_forward` selects this under ``causal=True``.
    """
    T, E = probs.shape
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [T, k]
    # renormalize the kept gates so the combine weights sum to 1 per token
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    onehot = jax.nn.one_hot(gate_idx, E, dtype=probs.dtype)  # [T, k, E]
    if priority == "choice":
        # rank choice-major: flatten to [k*T, E], all 1st choices first
        flat = onehot.transpose(1, 0, 2).reshape(k * T, E)
        pos = jnp.cumsum(flat, axis=0) - flat  # slot position in its expert
        pos = pos.reshape(k, T, E).transpose(1, 0, 2)  # [T, k, E]
    elif priority == "token":
        # rank token-major: [T*k, E] in natural order — causally safe
        flat = onehot.reshape(T * k, E)
        pos = jnp.cumsum(flat, axis=0) - flat
        pos = pos.reshape(T, k, E)
    else:
        raise ValueError(f"unknown routing priority {priority!r}")
    within_cap = (pos < capacity).astype(probs.dtype)

    keep = onehot * within_cap  # [T, k, E]
    slot = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [T, k] slot index
    return gate_vals, gate_idx, slot, keep


def _dense_topk_tensors(
    gate_vals: jnp.ndarray,
    slot: jnp.ndarray,
    keep: jnp.ndarray,
    capacity: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense [T, E, C] dispatch/combine from an already-computed
    :func:`_top_k_route` — ``dispatch[t, e, c]`` one-hot of token t
    occupying slot c of expert e, ``combine`` the gate weight there (0 for
    dropped tokens)."""
    slot_oh = jax.nn.one_hot(slot, capacity, dtype=keep.dtype)  # [T, k, C]
    dispatch = jnp.einsum("tke,tkc->tec", keep, slot_oh)
    combine = jnp.einsum("tk,tke,tkc->tec", gate_vals, keep, slot_oh)
    return dispatch, combine


def _expert_choice_dispatch(
    probs: jnp.ndarray, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-choice dispatch/combine (Zhou et al., "Mixture-of-Experts with
    Expert Choice Routing", 2022): each EXPERT selects its top-``capacity``
    tokens by router probability.  Every expert is exactly full (perfect
    load balance, nothing dropped by overflow), at the price of a token
    possibly being picked by 0 or many experts — fine under the residual
    use ``y = x + moe(x)``.

    **Not causal.** Each expert ranks its top-C over the ENTIRE sequence,
    so whether token t is picked (hence its output) depends on tokens > t.
    In an autoregressive LM that leaks future information through the
    router; :func:`moe_forward` refuses ``causal=True`` with this router
    (tests/test_moe.py has the leak detector proving the dependency).
    EC is an encoder / non-autoregressive technique.

    probs: [T, E].  Returns ``dispatch``/``combine`` [T, E, C] like
    :func:`_top_k_dispatch`; combine carries the raw router prob of each
    pick (EC does not renormalize per token)."""
    T = probs.shape[0]
    gate_vals, tok_idx = jax.lax.top_k(probs.T, capacity)  # [E, C] over tokens
    tok_oh = jax.nn.one_hot(tok_idx, T, dtype=probs.dtype)  # [E, C, T]
    dispatch = tok_oh.transpose(2, 0, 1)  # [T, E, C]
    combine = (tok_oh * gate_vals[..., None]).transpose(2, 0, 1)
    return dispatch, combine


def _load_balance_loss(probs: jnp.ndarray, dispatched: jnp.ndarray) -> jnp.ndarray:
    """Switch-style aux loss: E * sum_e mean_t(dispatched_e) * mean_t(p_e).

    ``dispatched``: [T, E] count of kept choices of token t on expert e
    (``keep.sum(axis=1)`` from :func:`_top_k_route` — dispatch-
    materialization-independent)."""
    E = probs.shape[-1]
    frac_tokens = jnp.mean(dispatched, axis=0)  # [E]
    frac_probs = jnp.mean(probs, axis=0)  # [E]
    return E * jnp.sum(frac_tokens * frac_probs)


# ------------------------------------------------------------------- experts


@prof.scoped(prof.EXPERTS)
def _expert_ffn(p: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    """Per-expert MLP on stacked experts.  x: [E, G, D] -> [E, G, D].
    A 4-dim ``w1`` ([E, 2, D, F]) is the stacked gate/up SwiGLU expert
    (``MoEConfig.act='swiglu'``): silu(gate) * up -> w2."""
    if p["w1"].ndim == 4:
        gu = jnp.einsum("egd,etdf->tegf", x, p["w1"]) + p["b1"].transpose(1, 0, 2)[:, :, None, :]
        h = jax.nn.silu(gu[0]) * gu[1]
    else:
        h = jax.nn.gelu(jnp.einsum("egd,edf->egf", x, p["w1"]) + p["b1"][:, None, :])
    return jnp.einsum("egf,efd->egd", h, p["w2"]) + p["b2"][:, None, :]


def _router_metrics(
    probs: jnp.ndarray, keep: Optional[jnp.ndarray], top_k: int,
    ec_tok_idx: Optional[jnp.ndarray] = None, capacity: int = 0,
) -> Dict[str, jnp.ndarray]:
    """Observability counters (stop_gradient — they must not perturb
    training).  Token-choice: ``keep`` [T, k, E] from :func:`_top_k_route`
    gives per-expert kept counts and the overflow-drop rate.  Expert-choice:
    ``ec_tok_idx`` [E, C] gives coverage (every expert is exactly full, so
    the "dropped" quantity is tokens picked by NO expert).

    Per-device locals under EP/shard_map — aggregate across shards (psum or
    host-side sum) before reporting pod-wide balance.  Consumed by
    ``obs.aggregate.moe_load_stats`` / ``Telemetry.record_counters``."""
    probs = jax.lax.stop_gradient(probs)
    T, E = probs.shape
    # mean per-token router entropy, normalized to [0, 1] by log E
    plogp = jnp.where(probs > 0, probs * jnp.log(probs), 0.0)
    entropy = -jnp.sum(plogp, axis=-1).mean() / math.log(max(E, 2))
    if keep is not None:
        keep = jax.lax.stop_gradient(keep)
        expert_tokens = jnp.sum(keep, axis=(0, 1))  # [E] kept choices
        dropped = 1.0 - jnp.sum(keep) / (T * top_k)
    else:
        ec_tok_idx = jax.lax.stop_gradient(ec_tok_idx)
        expert_tokens = jnp.full((E,), float(capacity), probs.dtype)
        covered = (
            jnp.zeros((T,), jnp.int32).at[ec_tok_idx.reshape(-1)].add(1) > 0
        )
        dropped = 1.0 - jnp.mean(covered.astype(probs.dtype))
    return {
        "router_entropy": entropy.astype(jnp.float32),
        "expert_tokens": expert_tokens.astype(jnp.float32),
        "dropped_token_rate": dropped.astype(jnp.float32),
    }


#: Dropped-token rate above which :func:`check_expert_overflow` records an
#: ``expert_overflow`` event — 5% sustained drops is the point where the
#: "dropped tokens contribute zero, callers use the output additively"
#: contract starts to cost model quality rather than just efficiency.
EXPERT_OVERFLOW_THRESHOLD = 0.05


def check_expert_overflow(
    metrics: Dict[str, Any],
    threshold: float = EXPERT_OVERFLOW_THRESHOLD,
    where: str = "",
) -> bool:
    """Host-side overflow tripwire over concrete router metrics (a
    :func:`_router_metrics` dict, or any mapping with a
    ``dropped_token_rate``).  Traced code can't emit events, so the
    training loop / serving engine call this with materialized stats; past
    ``threshold`` it records an ``expert_overflow`` event (the capacity
    alarm the timeline replays) and returns True."""
    rate = metrics.get("dropped_token_rate")
    rate = 0.0 if rate is None else float(rate)
    if rate > threshold:
        from ..obs.events import emit_event

        emit_event(
            "expert_overflow",
            dropped_token_rate=rate,
            threshold=threshold,
            where=where,
        )
        return True
    return False


@prof.scoped(prof.FFN)
def moe_forward(
    params: Dict[str, PyTree],
    x: jnp.ndarray,
    cfg: MoEConfig,
    ep_axis: Optional[str] = None,
    causal: bool = False,
    return_metrics: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN layer.  x: [B, S, D] (the device-local tokens under EP).

    Returns ``(y, aux_loss)``; add ``cfg.aux_loss_weight * aux_loss`` to the
    training loss.  ``return_metrics=True`` appends a third element — the
    :func:`_router_metrics` observability counters (router entropy,
    per-expert kept-token counts, dropped-token rate; all
    ``stop_gradient``-ed), for ``obs.Telemetry`` wiring.  With ``ep_axis`` set (inside shard_map) the stacked expert
    params hold only the local shard of experts and tokens are exchanged with
    two ``all_to_all`` collectives over the EP axis; dropped tokens contribute
    zero so callers should use the output additively (residual).

    ``causal=True`` declares that the surrounding model is autoregressive.
    It (a) rejects the ``expert_choice`` router, whose whole-sequence top-C
    pick leaks future tokens into token t's output (see
    :func:`_expert_choice_dispatch`), and (b) switches token-choice routing
    to token-major capacity priority: the default choice-major Switch
    ranking lets a future token's 1st choice evict an earlier token's
    2nd-choice slot whenever drops occur, which is the same leak in a
    subtler form (see :func:`_top_k_route`).  Under ``causal=True`` token
    t's output is a function of tokens <= t only, drops or not.
    """
    B, S, D = x.shape
    T = B * S
    if (cfg.score != "softmax" or cfg.latent_dim or cfg.shared_ffn
            or cfg.held is not None or cfg.act == "relu2"):
        raise NotImplementedError(
            "the sigmoid-router / latent / held-range expert layer has a "
            "serving path only (moe_serve_forward); its training-side "
            "router and aux loss are ROADMAP queue 2 A1")
    E = cfg.num_experts
    tokens = x.reshape(T, D)

    probs = jax.nn.softmax(
        (tokens @ params["router"]["w"]).astype(jnp.float32), axis=-1
    )  # [T, E] in fp32 for routing stability
    # 'auto' -> the backend's choice, recorded as a ``moe_dispatch_selected``
    # event at trace time
    dispatch = resolve_moe_dispatch(cfg.dispatch)
    if cfg.router == "expert_choice":
        if causal:
            raise ValueError(
                "router='expert_choice' is incompatible with causal=True: "
                "each expert picks its top-capacity tokens over the WHOLE "
                "sequence, so token t's routing depends on tokens > t — a "
                "future-information leak in an autoregressive model (Zhou "
                "et al. 2022 define EC for encoder/non-AR settings). Use "
                "router='topk' for causal LMs."
            )
        # Zhou et al. convention: capacity = T * cf / E — top_k is a
        # token-choice concept and deliberately does NOT scale EC capacity
        capacity = max(1, int(math.ceil(T * cfg.capacity_factor / E)))
        capacity = min(capacity, T)  # an expert cannot pick more than T tokens
        # every expert exactly full: balanced by construction, no aux needed
        aux = jnp.zeros((), jnp.float32)
        metrics = (
            _router_metrics(
                probs, None, cfg.top_k,
                ec_tok_idx=jax.lax.top_k(probs.T, capacity)[1],
                capacity=capacity,
            )
            if return_metrics else None
        )
        if _use_sorted(dispatch, T, E, capacity):
            # index path: the EC pick IS a gather spec — tok_idx[e, c] names
            # the token in slot c of expert e; no [T, E, C] tensors exist
            gate_ec, tok_idx = jax.lax.top_k(probs.T, capacity)  # [E, C]
            expert_in = tokens[tok_idx]  # [E, C, D] pure gather

            def combine_out(expert_out: jnp.ndarray) -> jnp.ndarray:
                w = gate_ec.astype(expert_out.dtype)[..., None] * expert_out
                # scatter-add: a token picked by several experts sums their
                # outputs, one picked by none stays 0 — EC semantics
                return jnp.zeros((T, D), expert_out.dtype).at[
                    tok_idx.reshape(-1)
                ].add(w.reshape(E * capacity, D))
        else:
            dispatch, combine = _expert_choice_dispatch(probs, capacity)
            dispatch = dispatch.astype(x.dtype)
            combine = combine.astype(x.dtype)
            expert_in = jnp.einsum("tec,td->ecd", dispatch, tokens)

            def combine_out(expert_out: jnp.ndarray) -> jnp.ndarray:
                return jnp.einsum("tec,ecd->td", combine, expert_out)
    else:
        capacity = max(1, int(math.ceil(T * cfg.top_k * cfg.capacity_factor / E)))
        # causal models use token-major capacity priority: with the default
        # choice-major ranking a FUTURE token's 1st choice can evict an
        # earlier token's 2nd-choice slot — a future-information leak
        # whenever drops occur.  Token-major makes token t's routing a
        # function of tokens <= t only (leak-free by construction).
        gate_vals, gate_idx, slot, keep = _top_k_route(
            probs, cfg.top_k, capacity,
            priority="token" if causal else "choice",
        )
        aux = _load_balance_loss(probs, jnp.sum(keep, axis=1))
        metrics = (
            _router_metrics(probs, keep, cfg.top_k) if return_metrics else None
        )
        if _use_sorted(dispatch, T, E, capacity):
            kept = jnp.sum(keep, axis=-1)  # [T, k] 1 iff the choice fit
            # flat destination slot e*C + c; dropped choices go to a
            # dumpster row (index E*C) that is sliced off / zeroed
            dest = jnp.where(
                kept > 0, gate_idx * capacity + slot, E * capacity
            )  # [T, k]
            src = jnp.broadcast_to(
                tokens[:, None, :], (T, cfg.top_k, D)
            ).reshape(T * cfg.top_k, D)
            expert_in = (
                jnp.zeros((E * capacity + 1, D), x.dtype)
                .at[dest.reshape(-1)]
                .add(src)[: E * capacity]  # each kept slot receives one token
                .reshape(E, capacity, D)
            )
            gates = (gate_vals * kept).astype(x.dtype)  # [T, k]

            def combine_out(expert_out: jnp.ndarray) -> jnp.ndarray:
                out_flat = jnp.concatenate(
                    [
                        expert_out.reshape(E * capacity, D),
                        jnp.zeros((1, D), expert_out.dtype),  # dumpster -> 0
                    ],
                    axis=0,
                )
                picked = out_flat[dest]  # [T, k, D] gather
                return jnp.sum(gates[..., None] * picked, axis=1)
        else:
            dispatch, combine = _dense_topk_tensors(
                gate_vals, slot, keep, capacity)
            dispatch = dispatch.astype(x.dtype)
            combine = combine.astype(x.dtype)
            expert_in = jnp.einsum("tec,td->ecd", dispatch, tokens)

            def combine_out(expert_out: jnp.ndarray) -> jnp.ndarray:
                return jnp.einsum("tec,ecd->td", combine, expert_out)

    if ep_axis is None:
        expert_out = _expert_ffn(params["experts"], expert_in)  # [E, C, D]
    else:
        ep = axis_size(ep_axis)
        if E % ep != 0:
            raise ValueError(f"num_experts {E} not divisible by EP size {ep}")
        e_loc = E // ep
        # [E, C, D] -> [ep, e_loc, C, D]; exchange: dim0 becomes source device
        send = expert_in.reshape(ep, e_loc, capacity, D)
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0)
        # my local experts now see ep*C slots (C from every EP peer)
        grouped = recv.transpose(1, 0, 2, 3).reshape(e_loc, ep * capacity, D)
        out = _expert_ffn(params["experts"], grouped)
        back = out.reshape(e_loc, ep, capacity, D).transpose(1, 0, 2, 3)
        expert_out = jax.lax.all_to_all(
            back, ep_axis, split_axis=0, concat_axis=0
        ).reshape(E, capacity, D)

    y = combine_out(expert_out)
    out = (y.reshape(B, S, D), aux.astype(jnp.float32))
    return out + (metrics,) if return_metrics else out


@prof.scoped(prof.ROUTE)
def _mlp_route(router: Dict[str, PyTree], tokens: jnp.ndarray,
               cfg: MoEConfig, depth: Optional[jnp.ndarray]):
    """The router that is a network (ZAYA1, arXiv:2511.17127): tokens [T, D]
    go down to the router's width, ``u = x W_d + b_d``; from the second
    expert layer on the stream of the layer before is mixed in, ``u <- u +
    gamma * depth`` (a learned ``gamma`` a channel: an average over depth);
    the probabilities are ``softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(u) + b_1)
    + b_2))`` in float32.  The top k of probability + ``bias`` are chosen
    (the bias balances load and stays out of the weight); a chosen expert
    weighs by its probability, NOT renormalised.  Returns ``(probs,
    gate_vals, gate_idx, u)``: ``u`` [T, R] float32 is the next expert
    layer's ``depth`` (it runs through the layers of ONE call and is never
    cached: a position's stream needs no other position)."""
    f32 = jnp.float32
    down = router["down"]
    u = jnp.dot(tokens, down["w"], preferred_element_type=f32) + down[
        "b"].astype(f32)
    if depth is not None:
        u = u + router["gamma"].astype(f32) * depth.astype(f32)
    h = rms_norm(u, router["norm"], cfg.norm_eps)
    for w, b in (("w1", "b1"), ("w2", "b2")):
        h = jax.nn.gelu(h @ router[w].astype(f32) + router[b].astype(f32),
                        approximate=False)
    probs = jax.nn.softmax(h @ router["w3"].astype(f32), axis=-1)
    _, gate_idx = jax.lax.top_k(
        probs + router["bias"].astype(f32), cfg.top_k)
    return probs, jnp.take_along_axis(probs, gate_idx, axis=-1), gate_idx, u


@prof.scoped(prof.ROUTE)
def _serve_route(router: Dict[str, jnp.ndarray], tokens: jnp.ndarray,
                 cfg: MoEConfig):
    """tokens [T, D] -> (probs [T, E], gate_vals [T, k], gate_idx [T, k]).
    ``score='softmax'`` is the Mixtral decision (kept operation for
    operation); ``'sigmoid'`` scores in float32, chooses by score + bias and
    weighs by the chosen scores alone.  (``'mlp'`` carries a stream between
    layers and so has a signature of its own: :func:`_mlp_route`.)"""
    k = cfg.top_k
    if cfg.score == "softmax":
        probs = jax.nn.softmax(
            (tokens @ router["w"]).astype(jnp.float32), axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [T, k]
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
        return probs, gate_vals, gate_idx
    scores = jax.nn.sigmoid(jnp.dot(
        tokens, router["w"], preferred_element_type=jnp.float32))
    _, gate_idx = jax.lax.top_k(
        scores + router["bias"].astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, gate_idx, axis=-1)
    gate_vals = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return scores, gate_vals * cfg.routed_scale, gate_idx


def _unbiased_act(h: jnp.ndarray, act: str) -> jnp.ndarray:
    """What an expert WITHOUT biases does between its two matmuls: 'relu2'
    squares the positive part; 'swiglu' takes ``h`` as gate and up side by
    side (``w1`` [..., D, 2F]) and gives silu(gate) * up."""
    if act == "relu2":
        return jnp.square(jax.nn.relu(h))
    F = h.shape[-1] // 2
    return jax.nn.silu(h[..., :F]) * h[..., F:]


#: The most rows ONE held expert may get for the unbiased experts to run as
#: ONE batched matmul over every held expert (each expert ``C`` slots, the
#: exact no-drop capacity of the call) and not as ``ragged_dot`` groups: the
#: capacity ``C`` of :func:`_batched_experts`.  The batched form reads every
#: held expert's weights once (89% of the HBM peak: 1.9 ms for 128 experts of
#: [1024, 2688], both GEMMs, where the grouped GEMM took 4.2-5.1 ms AND as
#: long as the experts its rows touch, which moved a run's rate by 2.4%
#: between seeds: PERF.md, PR 26) and does ``C`` flop a byte of weight.  The
#: v5e's ridge is 197e12 / 819e9 = 240 flop a byte, so up to 128 the form
#: stays bound by that one pass for ANY expert shape; at 256 it no longer
#: does.  A call of at most this many tokens (a decode call: one a slot)
#: cannot give an expert more (a token takes an expert at most once); a
#: wider call (a prefill call, mostly padding) asks its largest group.
_BATCHED_EXPERTS_MAX_ROWS = 128


@prof.scoped(prof.EXPERTS)
def _batched_experts(ex, rows, sorted_expert, group_sizes, C: int, act: str,
                     fetch: bool = False):
    """``rows`` [R, d] sorted by expert (``sorted_expert`` [R]; values past
    the last group are no expert's) -> the experts' outputs, row for row
    ([R, d]; zero for a row of no expert).  Every expert gets ``C`` slots
    (no group may be larger); row r sits in slot ``r - start of its
    group``.  A small call PUTS each of its few rows into its slot; a wide
    one, most of whose rows are padding's or absent experts', has each slot
    ``fetch`` its row (a gather of ``n * C`` rows, not a scatter of ``R``:
    0.2-1.1 ms a layer less on a v5e at every wide shape measured, PERF.md
    section 6, PR 40)."""
    n, (R, d) = ex["w1"].shape[0], rows.shape
    starts = jnp.cumsum(group_sizes) - group_sizes
    pos = jnp.arange(R) - starts[jnp.minimum(sorted_expert, n - 1)]
    slot = jnp.where(sorted_expert < n, sorted_expert * C + pos,
                     n * C)                                   # n * C: nowhere
    if fetch:
        live = jnp.arange(C) < group_sizes[:, None]           # [n, C]
        at = jnp.where(live, starts[:, None] + jnp.arange(C), 0)
        xe = jnp.where(live[..., None], rows[at], 0)
    else:
        xe = jnp.zeros((n * C, d), rows.dtype).at[slot].set(
            rows, mode="drop").reshape(n, C, d)
    h = _unbiased_act(jnp.einsum("ecd,edf->ecf", xe, ex["w1"]), act)
    out = jnp.einsum("ecf,efd->ecd", h, ex["w2"]).reshape(n * C, d)
    return out.at[slot].get(mode="fill", fill_value=0)


@prof.scoped(prof.EXPERTS)
def _grouped_experts(ex, rows, group_sizes, act: str):
    """The same experts as ``ragged_dot`` groups: time follows the experts
    touched, whatever their rows; rows past the last group are undefined."""
    h = _unbiased_act(jax.lax.ragged_dot(rows, ex["w1"], group_sizes), act)
    return jax.lax.ragged_dot(h, ex["w2"], group_sizes)


@prof.scoped(prof.FFN)
def moe_serve_forward(
    params: Dict[str, PyTree],
    x: jnp.ndarray,
    cfg: MoEConfig,
    return_metrics: bool = False,
    valid: Optional[jnp.ndarray] = None,
    depth: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Serving-time MoE FFN: EXACT no-drop routing with ragged grouped
    matmuls — zero capacity padding (VERDICT r4 weak #5: training-style
    no-drop dispatch pays ``ceil(T*k*(E/k)/E) = T`` slots PER EXPERT, an
    ``E/top_k``-fold padded-compute tax at prefill; this path pays exactly
    ``T*top_k`` rows total).

    Route-then-group: the ``T*k`` (token, choice) assignments are sorted by
    expert (stable, so ties stay in token order), ``jax.lax.ragged_dot``
    runs every expert's FFN over its contiguous row group against the
    stacked ``[E, ...]`` weights — the TPU-native grouped GEMM, no
    ``[T, E, C]`` dispatch tensors, no slack slots — and the gated outputs
    scatter-add back per token.

    No capacity ⇒ no cross-token routing interaction ⇒ causally safe by
    construction and exactly equal to the no-drop capacity path (golden:
    tests/test_moe.py::test_serve_forward_matches_nodrop).  Token-choice
    (``router='topk'``) only — expert-choice is a training-time,
    non-causal technique with no serving analogue here.  Runs per device
    on full expert weights (``ep_axis=None`` serving); EP-sharded decode
    goes through :func:`moe_forward`'s exchange path instead
    (models/generate.forward_cached_moe wires both).

    ``cfg.dispatch`` chooses between :func:`moe_forward`'s capacity
    materializations and has no say here: there is no capacity to
    materialize.  ``return_metrics=True`` appends the per-expert
    routed-token counts ({'expert_tokens', 'dropped_token_rate'} — rate
    identically 0 here, the path is no-drop) for the engine's live
    ``moe`` load signal.

    The sigmoid-router / latent family (``MoEConfig.score`` /
    ``latent_dim`` / ``shared_ffn`` / ``held`` / ``act='relu2'`` or the
    gated ``'swiglu'`` with a 3-dim ``w1`` [E, D, 2F]; no biases) rides the
    same ragged path: the router scores all ``num_experts``, the rows go
    down to the latent before the sort, the ``held`` experts' groups run,
    rows assigned to an expert that is not held sort behind every group
    and count for nothing, the combine goes up from the latent, and the
    shared expert adds its full-width part.  Its metrics count the held
    experts only and add ``rows_routed`` / ``rows_held`` /
    ``experts_touched`` and ``gate_idx`` ([B, S, k]: the experts each
    position chose, of all ``num_experts``); ``valid`` [B, S] names the
    real positions: a padding row's choices are held by no expert here, so
    they sort behind every group with the absent experts' and cost the
    grouped matmul nothing (a compact prefill call is mostly padding, all of
    it the same token: its rows fell on the same few experts, a different
    few with every seed's weights).  These unbiased experts run as ONE
    batched matmul over every held expert wherever no held expert got more
    than ``_BATCHED_EXPERTS_MAX_ROWS`` rows, which a call of at most that
    many tokens cannot and a wider call asks of its group sizes on the
    device (``lax.cond``; the ``ragged_dot`` pair otherwise: the same
    operands, the same rows out); the metrics say which under
    ``layers_batched`` (1.0 / 0.0).

    ``score='mlp'`` (:func:`_mlp_route`) is the same layer behind a router
    that is a network: ``depth`` [B, S, R] is the router's stream as the
    expert layer before left it (None at the first), and the result gains
    this layer's as its LAST element."""
    if cfg.router != "topk":
        raise NotImplementedError(
            f"moe_serve_forward supports router='topk' (got {cfg.router!r})")
    B, S, D = x.shape
    T, E, k = B * S, cfg.num_experts, cfg.top_k
    first, n_held = cfg.held_range
    tokens = x.reshape(T, D)

    if cfg.score == "mlp":
        probs, gate_vals, gate_idx, depth = _mlp_route(
            params["router"], tokens, cfg,
            None if depth is None else depth.reshape(T, -1))
        depth = depth.reshape(B, S, -1)
    else:
        probs, gate_vals, gate_idx = _serve_route(
            params["router"], tokens, cfg)
    # the expert of each choice as this device numbers the ones it holds;
    # ``n_held`` = not held (sorts last, belongs to no group)
    local_idx = gate_idx
    if cfg.held is not None:
        with jax.named_scope(prof.DISPATCH):
            here = (gate_idx >= first) & (gate_idx < first + n_held)
            if valid is not None:
                here &= valid.reshape(T, 1)
            local_idx = jnp.where(here, gate_idx - first, n_held)

    def _with_metrics(y: jnp.ndarray):
        out = (y, _counters()) if return_metrics else (y,)
        if cfg.score == "mlp":
            out += (depth,)
        return out if len(out) > 1 else y

    @prof.scoped(prof.DISPATCH)
    def _counters():
        if cfg.held is None and valid is None:
            counts = jnp.bincount(gate_idx.reshape(-1), length=E)
        else:
            w = (jnp.ones((T,), jnp.int32) if valid is None
                 else valid.reshape(T).astype(jnp.int32))
            counts = jnp.bincount(
                local_idx.reshape(-1), weights=jnp.repeat(w, k),
                length=n_held + 1)[:n_held]
        metrics = {
            "expert_tokens": counts.astype(jnp.float32),
            "dropped_token_rate": jnp.zeros((), jnp.float32),
        }
        if cfg.held is not None:
            metrics["rows_routed"] = (
                jnp.float32(T * k) if valid is None
                else jnp.sum(valid).astype(jnp.float32) * k)
            metrics["rows_held"] = jnp.sum(counts).astype(jnp.float32)
            metrics["experts_touched"] = jnp.sum(counts > 0).astype(
                jnp.float32)
            metrics["gate_idx"] = gate_idx.reshape(B, S, k)
        if batched is not None:
            metrics["layers_batched"] = batched
        return metrics

    # the GEMMs of the layer (the latent's two projections, the experts,
    # the shared expert) are ``experts``; what moves rows to them and back
    # is ``dispatch`` and ``combine``
    src = tokens
    if cfg.latent_dim:
        with jax.named_scope(prof.EXPERTS):
            src = tokens @ params["latent"]["down"]
    with jax.named_scope(prof.DISPATCH):
        flat_expert = local_idx.reshape(-1)  # [T*k] token-major
        order = jnp.argsort(flat_expert, stable=True)
        sorted_tok = (order // k).astype(jnp.int32)  # token of a sorted row
        sorted_expert = flat_expert[order]
        rows = src[sorted_tok]  # [T*k, D] gather, expert-grouped
        # with a held range there is one more bin, for the rows of no expert
        # here; it is counted and cut off, so the groups end before those
        # rows
        group_sizes = jnp.bincount(
            flat_expert, length=n_held + (cfg.held is not None)
        )[:n_held].astype(jnp.int32)

    ex = params["experts"]
    batched = None   # the unbiased path alone: 1.0 where it ran batched
    if ex["w1"].ndim == 4:  # swiglu: [E, 2, D, F] stacked gate/up
        with jax.named_scope(prof.EXPERTS):
            F = ex["w1"].shape[-1]
            w1 = ex["w1"].transpose(0, 2, 1, 3).reshape(-1, D, 2 * F)
            gu = jax.lax.ragged_dot(rows, w1, group_sizes)
            gu = gu + ex["b1"].reshape(-1, 2 * F)[sorted_expert]
            h = jax.nn.silu(gu[:, :F]) * gu[:, F:]
            out = jax.lax.ragged_dot(h, ex["w2"], group_sizes)
    elif cfg.act in ("relu2", "swiglu"):  # no biases; swiglu: [E, D, 2F]
        C = _BATCHED_EXPERTS_MAX_ROWS
        if T <= C:  # a small call: no expert can get more than T rows
            batched = jnp.ones((), jnp.float32)
            out = _batched_experts(ex, rows, sorted_expert, group_sizes, T,
                                   cfg.act)
        else:  # by the largest group, which the padding rows are not in
            fits = jnp.max(group_sizes) <= C
            batched = fits.astype(jnp.float32)
            out = jax.lax.cond(
                fits,
                lambda r: _batched_experts(ex, r, sorted_expert, group_sizes,
                                           C, cfg.act, fetch=True),
                lambda r: _grouped_experts(ex, r, group_sizes, cfg.act),
                rows)
    else:
        with jax.named_scope(prof.EXPERTS):
            h = jax.lax.ragged_dot(rows, ex["w1"], group_sizes)
            h = jax.nn.gelu(h + ex["b1"][sorted_expert])
            out = jax.lax.ragged_dot(h, ex["w2"], group_sizes)
    if "b2" in ex:
        with jax.named_scope(prof.EXPERTS):
            out = out + ex["b2"][sorted_expert]

    with jax.named_scope(prof.COMBINE):
        g = gate_vals.reshape(-1)[order].astype(out.dtype)
        if cfg.held is not None:
            # rows behind the last group are no expert's: whatever the
            # grouped matmul left there counts for nothing
            kept = sorted_expert < n_held
            out = jnp.where(kept[:, None], out, 0)
            g = jnp.where(kept, g, 0)
        y = jnp.zeros((T, out.shape[-1]), out.dtype).at[sorted_tok].add(
            g[:, None] * out)
    with jax.named_scope(prof.EXPERTS):
        if cfg.latent_dim:
            y = y @ params["latent"]["up"]
        if cfg.shared_ffn:
            sh = params["shared"]
            y = y + _unbiased_act(tokens @ sh["w1"], cfg.act) @ sh["w2"]
    return _with_metrics(y.reshape(B, S, D).astype(x.dtype))


# ---------------------------------------------------------------------- init


def init_moe_params(key, cfg: MoEConfig) -> Dict[str, PyTree]:
    kr, k1, k2 = jax.random.split(key, 3)
    D, F, E = cfg.dim, cfg.ffn_dim, cfg.num_experts
    dt = cfg.dtype
    if cfg.act == "swiglu":
        experts = {
            "w1": (jax.random.normal(k1, (E, 2, D, F)) / math.sqrt(D)).astype(dt),
            "b1": jnp.zeros((E, 2, F), dt),
            "w2": (jax.random.normal(k2, (E, F, D)) / math.sqrt(F)).astype(dt),
            "b2": jnp.zeros((E, D), dt),
        }
    else:
        experts = {
            "w1": (jax.random.normal(k1, (E, D, F)) / math.sqrt(D)).astype(dt),
            "b1": jnp.zeros((E, F), dt),
            "w2": (jax.random.normal(k2, (E, F, D)) / math.sqrt(F)).astype(dt),
            "b2": jnp.zeros((E, D), dt),
        }
    return {
        "router": {"w": (jax.random.normal(kr, (D, E)) / math.sqrt(D)).astype(dt)},
        "experts": experts,
    }


def moe_param_specs(ep_axis: str = EXPERT_AXIS, act: str = "gelu") -> Dict[str, PyTree]:
    """Router replicated; stacked expert arrays sharded on the expert dim over
    the EP axis.  Sharding *is* the expert placement — no manual scatter.
    ``act='swiglu'`` matches the [E, 2, D, F] stacked gate/up leaves."""
    w1 = P(ep_axis, None, None, None) if act == "swiglu" else P(ep_axis, None, None)
    b1 = P(ep_axis, None, None) if act == "swiglu" else P(ep_axis, None)
    return {
        "router": {"w": P()},
        "experts": {
            "w1": w1,
            "b1": b1,
            "w2": P(ep_axis, None, None),
            "b2": P(ep_axis, None),
        },
    }


def moe_grad_reduce_overrides(
    moe_dp_axis: str = MOE_DATA_AXIS,
) -> Dict[str, Tuple[str, ...]]:
    """Override dict for :class:`DataParallel`: expert grads reduce over the
    ``moe_dp`` axis only (replicated-expert DP, naive_ddp.py:269-441); the EP
    dimension must NOT be reduced — each EP shard owns different experts.
    Router and all dense params use the DataParallel default (full data group).
    """
    return {"experts": (moe_dp_axis,)}
