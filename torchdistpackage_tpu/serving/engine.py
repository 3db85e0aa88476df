"""Continuous-batching serving engine over the paged KV cache.

``generate()`` is a *batch* API: every sequence in a call shares one
prompt length and one decode budget, and a new request waits for the whole
batch to drain.  Serving traffic is nothing like that — requests arrive
staggered, prompts and output lengths vary wildly, and throughput comes
from keeping a fixed-size decode batch FULL (Orca/vLLM continuous
batching).  This engine is that scheduler, built TPU-first:

- **Fixed slots, compiled once.**  The decode batch is ``num_slots`` rows
  forever.  A request occupies a slot from admission to retirement; freed
  slots are refilled from the queue on the next tick.  Because every
  device-side shape is static (``[num_slots, 1]`` tokens, ``[num_slots,
  max_blocks]`` int32 tables, the block pool), the hot loop is exactly TWO
  compiled programs — one decode step, one prefill-chunk step — and host
  code between ticks only rewrites small int32 tables.  No shape ever
  depends on which requests are in flight, so there is no per-request
  retrace (``serving_summary()['decode_signatures']`` is the evidence).
- **Chunked prefill, and the tick's order.**  Prompts enter through the
  same paged forward in ``chunk``-token slices, one a tick for every
  prefilling slot, in compact ``[dp * prefill_width, chunk]`` calls
  (``ceil(n / W)`` of the ONE signature back to back: ``_prefill_batches``;
  W is 1 or 2 slots, ``PREFILL_WIDTH``), so an admission costs its own
  rows.  A tick dispatches
  ALL of its calls before it fetches any: the prefill calls, the decode call
  behind them, then the fetches, so the device runs both while results
  travel and the host walks.  A prompt's last slice samples the first token
  (``last_idx``; TTFT stops there); that slot's first DECODE step is the NEXT
  tick's call, this tick's having been built before the fetch: its second
  token comes one tick later, once (``late_joins``; :meth:`step`).
- **Per-slot sampling.**  Temperature / top-k / top-p and the PRNG key are
  ``[num_slots]`` arrays, so every request keeps its own sampling policy
  and stream inside one compiled sampler (temperature 0 = greedy, exactly
  ``generate()``'s argmax).
- **Retirement.**  EOS or the request's ``max_new_tokens`` frees the slot
  and returns its blocks to the pool the same tick — no token of decode
  compute is spent on finished rows beyond the step that finished them.
- **Prefix cache** (``prefix_cache=True``).  ``BlockAllocator`` carries
  per-block refcounts and a content-hash index chained over FULL token
  blocks (vLLM automatic-prefix-caching); admission maps the longest
  resident prefix of a prompt into the new slot's table at ZERO prefill
  cost (``prefix_hit`` event — chunked prefill starts after the cached
  boundary), a whole-prompt hit copy-on-writes its last block
  (``block_cow``) so the final token's logits can be recomputed without
  touching a shared block, retirement/preemption decrement rather than
  free, and refcount-0 cached blocks are retained on an LRU and evicted
  (``cache_evict``) only under allocator pressure.  Shared system-prompt
  traffic prefills once per PREFIX, not once per request.
- **Speculative decoding** (``spec_k=K``).  A host-side self-speculative
  drafter (n-gram / prompt-lookup — no second model) proposes a STATIC
  ``K`` tokens per decoding slot each tick (``spec_draft``), and one
  compiled verify program scores all K+1 positions in a single
  paged-attention step (``spec_verify``): greedy rows accept while the
  draft equals the model's argmax — temp-0 output is BIT-identical to
  non-speculative decode — and sampled rows run residual rejection
  sampling off the slot's own key stream.  Accepted prefixes advance the
  block tables 1..K+1 tokens per tick; rejections truncate host-side
  (the stale KV tail is overwritten before it can be attended).  The hot
  loop stays at one decode-signature: the verify program at fixed K.
- **TP/DP come from the mesh, not the code.**  With a mesh, the step runs
  inside shard_map: KV heads and the vocab-parallel head shard over
  ``axis`` (tp) exactly as in training/`generate()`, and slots + block
  pool shard over ``dp_axis`` — each data group runs its own slice of the
  slot batch against its own pool shard, so a ``tp_dp`` mesh serves with
  zero engine changes.

Overload and faults are first-class, not exceptional (docs/serving.md
"Serving under stress").  Everything below is HOST-side scheduler state —
no priority, deadline, or fault bit is ever a traced value, so the
two-compiled-programs invariant survives every path:

- **Priorities + preemption.**  ``Request.priority`` orders the queue
  (higher first; FIFO within a class).  When the head of the queue cannot
  be admitted, the lowest-priority running slot strictly below it is
  *evicted*: blocks freed, accumulated output discarded, request requeued
  for prompt replay through the ordinary chunked prefill (replay is
  deterministic — greedy rows trivially, sampled rows because the slot
  key restarts from the same seed — so a preempted request's final tokens
  equal its unpreempted ones).
- **Deadlines, shedding, cancel.**  ``Request.deadline_s`` is a TTFT
  budget from submit: admission estimates TTFT from the queue's unstarted
  prefill work x the engine's own measured tick time
  (:meth:`ServingEngine.estimate_ttft`) and *sheds* requests that cannot
  make it — a structured rejection verdict in ``engine.rejected`` plus a
  ``request_shed`` event, never unbounded queue growth (``max_queue``
  bounds the queue the same way).  A queued request whose deadline passes
  expires (``request_expired``); :meth:`ServingEngine.cancel` retires a
  queued or in-flight request and frees its blocks the same tick.
- **Invariant audit + self-healing.**  Every tick starts with a block-
  conservation audit (:meth:`ServingEngine.audit` over
  ``BlockAllocator.audit``): allocator in_use must equal the live slots'
  owned blocks, no table row may disagree with its slot's ownership, no
  entry may point at a freed block.  A violated slot is poisoned —
  retired with an ``engine_fault_detected`` event, its blocks reclaimed,
  the request requeued for replay — and orphaned blocks are reclaimed;
  the rest of the batch continues bit-identically (``engine_recovered``).
  Sampled tokens are validity-checked on fetch (an out-of-range token is
  the host-visible face of a NaN logit row) with the same retire-and-
  replay recovery.  ``chaos=`` accepts a
  :class:`~..resilience.ChaosMonkey` whose engine fault kinds
  (``slot_stall`` / ``alloc_exhaust`` / ``table_corrupt`` /
  ``nan_logits``) drive exactly these paths; ``watchdog=`` beats a
  :class:`~..resilience.Watchdog` each tick so a wedged tick escalates
  to ``hang_suspected``/abort.
- **Preemption-safe drain.**  :meth:`ServingEngine.drain` (the
  ``GracefulShutdown`` SIGTERM contract) stops admission and unwinds the
  queue + in-flight slots into restartable descriptors — prompt, emitted
  tokens, sampling state, the carried PRNG key — optionally persisted
  with a SHA-256 manifest (the ``ckpt_guard`` verify-before-restore
  idiom).  A restarted engine's :meth:`ServingEngine.resume` replays
  prompt+emitted-prefix through chunked prefill and continues the stream
  exactly: temp-0 requests resume to exact token parity
  (``tools/parity_diff.py``-gated in tests), sampled ones continue their
  key stream.

Observability (docs/serving.md "Serving observability"): every lifecycle
transition is a structured event (``request_submitted`` /
``request_admitted`` / ``prefill_chunk`` / ``request_retired`` /
``slots_snapshot`` plus the stress kinds ``request_preempted`` /
``request_shed`` / ``request_expired`` / ``request_cancelled`` /
``engine_fault_detected`` / ``engine_recovered`` / ``engine_drained`` /
``request_resumed``), decode ticks are Telemetry steps when a session is
wired in, and every tick leaves a host-side accounting record — the
:data:`~.tracing.TICK_PHASES` decomposition (audit / sched / prefill /
draft / decode / fetch / host) plus queue/occupancy/utilization gauges —
on ``tick_records``, the ``engine_tick`` timeline (with per-rid
attribution, from which serving/tracing.py reconstructs each request's
full lifecycle as a Perfetto flow track), and the optional
``metrics_sink=`` live export (``serving_metrics`` schema through the
obs exporter sinks).  :meth:`ServingEngine.serving_summary` is the
RUNREPORT ``serving`` section — per-priority TTFT/TPOT percentiles,
shed/preempt/expire counts, the ``slo`` block (per-priority deadline
attainment, goodput counting only deadline-meeting tokens, and the
predicted-vs-actual TTFT calibration whose EWMA bias feeds back into
:meth:`estimate_ttft`), and a ``healthy | degraded | overloaded``
verdict that cites its evidence, next to the PR-5 aggregates.  All of
it is host arithmetic around the same compiled calls:
``decode_signatures == 1`` survives every traced/metered path.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import _full_logits
from ..models.gpt import GPTConfig
from ..obs.aggregate import percentiles
from ..obs.events import EventLog, default_event_log
from ..ops import paged_attention as paged_attention_ops
from ..utils import profiling as prof
from ..utils.profiling import scope_decorator, span
from .paged_cache import (
    BlockAllocator,
    chain_block_hashes,
    copy_blocks,
    expected_pool_bytes,
    index_bytes,
    init_paged_kv,
    paged_forward,
    paged_forward_moe,
    pool_bytes,
    keys_transposed,
    window_bytes,
    window_reach,
)
from .tracing import TICK_PHASES, serving_metrics_record

# slot lifecycle
FREE, PREFILL, DECODE = "free", "prefill", "decode"

#: Drain-payload schema tag (ServingEngine.drain / .resume).
DRAIN_SCHEMA = "tdp-engine-drain/v1"

#: Slots (a dp group) that one compiled prefill call carries, at most (an
#: engine with fewer slots a group carries them all): a tick with n slots
#: prefilling makes ceil(n / W) calls of this ONE signature, queued back to
#: back.  A call costs a fixed part F (one pass over the weights, an expert
#: layer's experts at their form's price, a launch and a fetch) and p a
#: slot, and a steady tick of a full engine admits ONE prompt (74-96% of
#: the ticks that prefill in the benchmark's eight serving cells, two in
#: 4-18%): every row beyond its own is computed for nobody.  What a
#: narrower call costs is F once more in the ticks with more prompts than
#: W, and the first wave of a full engine, once: ceil(num_slots / W) calls
#: a tick while every first prompt prefills, inside the set-up.  On a v5e
#: one steady call beside a decode call reads, at W = 4 / 2 / 1, ms
#: (PERF.md section 6, PR 50, has the grid): 45 / 23 / 13 (a dense 7B at
#: half depth, chunk 256) and 53 / 27 / 14 (a state model under a dense
#: MLP, chunk 256): F is small, every halving pays (the rate +9% and +5%
#: at 1 over 2) and the set-up does not move.  With expert layers F is a
#: pass over the held experts: 24 / 17 / 15, 34 / 22 / 18 and 23 / 17 / 14
#: (chunk 128 and 256, 64-128 slots), where W = 1 gives the rate 0 to +2%
#: over W = 2 and its wave of 64-128 calls adds 3-10% to the set-up; 42 /
#: 26 / 16, 72 / 45 / 29 and 106 / 55 / 31 (chunk 512, 32 slots), where
#: W = 1 would give 9-23% more for 2-4% of set-up: that takes a rule that
#: reads the chunk and the slots too (ROADMAP queue 1 item 1(b)).  So the
#: engine takes its width from the one thing that decides F and that it
#: can see of its own model, whether it has expert layers: 1 without, 2
#: with.
PREFILL_WIDTH = 1
PREFILL_WIDTH_EXPERTS = 2


#: What a state model's calls report of their expert layers, in ``stats``
#: and on every tick record: rows routed, the rows among them that fell on
#: held experts (a held range is one chip's share), the held experts the
#: DECODE calls touched, the expert layers executed and those among them
#: whose experts ran as one batched matmul (``moe_serve_forward`` chooses by
#: the largest group), the last two again for the PREFILL calls alone, where
#: the choice is open.  All summed over the expert layers and the calls.
_MOE_CALL_STATS = ("moe_rows_routed", "moe_rows_held", "experts_touched",
                   "moe_layers_run", "moe_layers_batched",
                   "prefill_moe_layers_run", "prefill_moe_layers_batched")
#: engine counters (``stats``, ``serving_summary()['tick_accounting']``) that
#: every tick record carries as the tick's own count
_TICK_COUNTS = ("late_joins", "ahead_rows", "flight_dropped")


@dataclasses.dataclass
class Request:
    """One serving request.  ``temperature=0`` is greedy (bit-identical to
    ``generate()``'s argmax); otherwise ``seed`` starts the slot's private
    sampling stream.  ``eos_id`` retires the request early — a serving-
    layer concern ``generate()`` deliberately doesn't have.

    ``priority`` (host-side scheduler state, never traced) orders the
    queue and arms preemption: a waiting request may evict a running slot
    of strictly lower priority.  ``deadline_s`` is a TTFT budget measured
    from submit: admission sheds the request when the engine's own
    latency model says it cannot make the deadline, and a queued request
    whose budget lapses expires without service."""

    tokens: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_id: Optional[int] = None
    seed: int = 0
    priority: int = 0
    deadline_s: Optional[float] = None
    rid: int = -1  # assigned at submit()

    def __post_init__(self) -> None:
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")
        if len(self.tokens) < 1:
            raise ValueError("empty prompt")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be > 0, got {self.deadline_s}")


def _device_bytes_in_use() -> Optional[int]:
    """What the local devices hold now, by their own count; None where the
    backend gives none (the CPU)."""
    from ..obs.mem_ledger import live_memory

    mem = live_memory()
    return mem["live_bytes"] if mem["reported"] else None


def _device_bytes_taken(before: Optional[int], tree: Any) -> Dict[str, int]:
    """``{'device_bytes': ...}`` for an init span: what allocating ``tree``
    took of the devices since ``before`` (:func:`_device_bytes_in_use`),
    tiling and padding included, to stand beside the arrays' logical
    ``bytes``; nothing where the backend gives no count."""
    if before is None:
        return {}
    jax.block_until_ready(tree)
    return {"device_bytes": _device_bytes_in_use() - before}


def _pool_shape_attrs(cache: Dict[str, Any], quantized: bool) -> Dict[str, int]:
    """What a K/V pool's leaves say of a head and of each pool, for the
    ``tdp:engine.init.pool`` span: ``key_width`` / ``value_width`` (a
    head's; a packed pool's row) and ``kv_heads``, with ``window_kv_heads``
    where window layers have their own pool (of another block shape where
    the model gives them another count)."""
    k, v = ((leaf[0] if quantized else leaf).shape
            for leaf in (cache["k"], cache["v"]))
    # a K leaf of another width than V's lies transposed, [.., width, bs]
    out = {"key_width": k[3 if keys_transposed(cache["k"], cache["v"]) else 4],
           "value_width": v[4], "kv_heads": v[2]}
    if "win" in cache:
        out["window_kv_heads"] = cache["win"]["v"].shape[2]
    return out


@prof.scoped(prof.SAMPLE)
def _split_keys(keys: jnp.ndarray):
    """[B, 2] uint32 -> (carried keys, this step's sample keys)."""
    ks = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return ks[:, 0], ks[:, 1]


@prof.scoped(prof.SAMPLE)
def _take_prev(tokens: jnp.ndarray, keys: jnp.ndarray, prev: Optional[Tuple]):
    """``run_ahead``'s decode call: ``prev = (tok, keys, take)`` is the call
    before's sampled tokens and advanced keys as they lie on the device, and
    the rows that take theirs from there (``take > 0``); every other row's
    come from the host.  None (a prefill call): all of them do."""
    if prev is None:
        return tokens, keys
    take = prev[2][:, None] > 0
    return (jnp.where(take, prev[0][:, None], tokens),
            jnp.where(take, prev[1], keys))


def _filtered_logits(
    x: jnp.ndarray,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> jnp.ndarray:
    """Per-row temperature -> top-k -> top-p filter chain on f32 [N, V]
    logits (the `_sample` semantics, including the rank-0-always-kept
    nucleus edge): masked entries become -inf, survivors are scaled by
    1/temperature.  Shared by :func:`_slot_sample` and the speculative
    verify step, which applies the SAME chain at every drafted position —
    acceptance is judged against the distribution the slot would actually
    have sampled from."""
    V = x.shape[-1]
    neg = jnp.float32(-jnp.inf)
    xs = x / jnp.maximum(temperature, 1e-6)[:, None]
    k = jnp.clip(top_k, 1, V)[:, None]
    sorted_x = jnp.sort(xs, axis=-1)[:, ::-1]  # ONE descending sort
    kth = jnp.take_along_axis(sorted_x, k - 1, axis=-1)
    xs = jnp.where(xs < kth, neg, xs)
    sorted_x = jnp.where(jnp.arange(V)[None, :] < k, sorted_x, neg)
    probs = jax.nn.softmax(sorted_x, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = jnp.roll(cum, 1, axis=-1).at[:, 0].set(0.0) < top_p[:, None]
    keep = keep.at[:, 0].set(True)  # argmax always survives (top_p -> 0)
    cutoff = jnp.min(jnp.where(keep, sorted_x, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(xs < cutoff, neg, xs)


@prof.scoped(prof.SAMPLE)
def _slot_sample(
    logits: jnp.ndarray,
    keys: jnp.ndarray,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> jnp.ndarray:
    """Vectorized per-slot sampler on full [B, V] logits: each row applies
    ITS OWN temperature -> top-k -> top-p filter chain
    (:func:`_filtered_logits`) and draws from its own key;
    ``temperature <= 0`` rows take the plain f32 argmax — bitwise the
    ``generate()`` greedy choice.

    What a call costs follows what its rows ask for.  The argmax is always
    computed; the filter chain (one descending sort of all ``[B, V]``
    logits: 9 ms of a 30 ms decode tick at ``[128, 65536]`` on a v5e) and
    the draw sit behind one ``lax.cond`` on ``any(temperature > 0)``, so a
    call whose rows are all greedy runs neither.  ONE sampling row brings
    the whole ``[B, V]`` chain back for that call: a ``cond`` chooses for
    the call, not for the row.  The tokens are the same either way, and
    the keys are split by the caller on every call, outside the ``cond``,
    so a row's draws do not depend on what its neighbours asked for."""
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)

    def draw() -> jnp.ndarray:
        # widened HERE, from the logits in the model's dtype: handed an f32
        # copy made outside, XLA's TPU compiler fuses the widening into the
        # head's GEMM, the bf16 rounding goes, and the ARGMAX above breaks
        # its near-ties differently (59 of 61 requests of sarvam105b.reason
        # ended on other tokens: my chip run, PR 32)
        xs = _filtered_logits(logits.astype(jnp.float32), temperature,
                              top_k, top_p)
        sampled = jax.vmap(jax.random.categorical)(keys, xs)
        return jnp.where(temperature <= 0.0, greedy,
                         sampled.astype(jnp.int32))

    return jax.lax.cond(jnp.any(temperature > 0.0), draw, lambda: greedy)


class _SlotState:
    """Host-side bookkeeping for one slot (device state lives in the
    engine's int32/f32 arrays; this carries the request identity).
    ``orig_prompt_len``/``pre_gen`` account for resumed requests whose
    admitted prompt includes an already-emitted prefix (drain/resume)."""

    __slots__ = ("state", "rid", "req", "blocks", "wblocks", "prompt", "off",
                 "generated", "t_submit", "t_admit", "t_last", "ttft_s",
                 "tpot_s", "orig_prompt_len", "pre_gen", "routing")

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.state = FREE
        self.rid = -1
        self.req: Optional[Request] = None
        self.blocks: List[int] = []
        #: the blocks it holds of the window layers' pool (a model with
        #: window layers; which column names which changes as they are
        #: handed on, the set does not)
        self.wblocks: List[int] = []
        self.prompt: Optional[np.ndarray] = None
        self.off = 0
        self.generated: List[int] = []
        self.t_submit = self.t_admit = self.t_last = 0.0
        self.ttft_s: Optional[float] = None
        self.tpot_s: List[float] = []
        self.orig_prompt_len = 0
        self.pre_gen = 0
        #: ``record_routing``: one [positions, E-layers, k] piece a call
        self.routing: List[np.ndarray] = []


class ServingEngine:
    """Paged-KV continuous-batching engine — see the module docstring for
    the design.  Typical driver::

        eng = ServingEngine(params, cfg, num_slots=8, block_size=16,
                            telemetry=tel)
        eng.submit(Request(prompt_ids, max_new_tokens=64))
        eng.run_until_idle()
        out = eng.finished[0]["tokens"]          # prompt + generated
        tel.record_serving(eng.serving_summary())

    Parameters
    ----------
    params: the model tree — plain arrays (serial) or device_put with the
        training TP specs when a ``mesh`` is given.
    num_slots: decode-batch width (divisible by the dp size).
    block_size: KV positions per pool block.
    num_blocks: pool blocks PER DP GROUP (incl. the reserved NULL block);
        default sizes the pool so every slot can hold ``max_ctx``.
    max_ctx: per-request ceiling on prompt + generated tokens; sets the
        block-table width.  Default ``cfg.max_seq``.
    chunk: prefill tokens per slot per tick.
    mesh / axis / dp_axis / ep_axis: the serving mesh and its tp / dp /
        expert axes; all None = single-device.  ``param_specs`` overrides
        the auto-derived (``gpt_param_specs`` family) in_specs.
    kv_quant: int8 block pool (``_kv_quant`` per-vector scales).
    telemetry: an ``obs.Telemetry`` — decode ticks become steps (recompile
        detection guards the compile-once contract) and events land on its
        timeline.
    max_queue: bound on the waiting queue; a submit past it is SHED with a
        structured verdict (``engine.rejected``) instead of growing the
        queue without bound.  None = unbounded (the PR-5 behavior).
    chaos: a :class:`~..resilience.ChaosMonkey` driven each tick
        (``before_engine_tick`` + ``perturb_engine_tokens``) — the fault-
        injection seam the recovery paths are proven against.
    watchdog: a :class:`~..resilience.Watchdog`; the engine beats it once
        per tick so a wedged tick escalates to ``hang_suspected``/abort.
    attn_impl: paged attention implementation (docs/serving.md "Paged
        attention kernel"): ``'pallas'`` walks the block table inside the
        fused TPU kernel (per-tick attention HBM scales with live
        context), ``'gather'`` materializes the dense per-slot view (the
        parity oracle), ``'auto'`` (default) picks pallas on TPU and
        gather on CPU (the interpreter-mode kernel is correct but slow —
        tests opt in explicitly).  Recorded in
        ``serving_summary()['attn_impl']``.
    metrics_sink: any obs exporter sink (``write(record)`` — e.g.
        :class:`~..obs.exporters.PrometheusTextfileSink` or ``JsonlSink``);
        every tick writes a ``serving_metrics``
        record (:data:`~.tracing.SERVING_METRICS_SCHEMA`) so an external
        scraper can watch queue depth, slot occupancy, batch utilization,
        and the per-phase tick breakdown of a RUNNING engine.
    tick_history: bound on the in-memory per-tick accounting records
        (``tick_records``; oldest dropped first, like the event log).
    device_step: a :class:`~.sim.DeviceStep` supplying the engine's
        device programs (pool init, the shared prefill/decode step, the
        verify step, COW, per-request PRNG keys).  ``None`` (default)
        builds the real :class:`~.sim.CompiledDeviceStep` — identical to
        the engine before the seam existed.  Pass
        :class:`~.sim.StubDeviceStep` for the host-only double
        (``params`` may then be ``None``): same scheduler, allocator,
        audit, and event timeline, zero compilation — what
        ``tools/trace_replay.py`` and the compile-free policy tests run
        on.  A host-only step cannot be combined with a mesh.
    record_routing: a state model with expert layers only: every call also
        returns the experts each position chose, and a finished request
        carries them (``finished[rid]['routing']``: int16 [fed positions,
        expert layers, top_k], the last token is never fed).  A top-k
        choice is discontinuous, so a reference in another precision can
        only be held to the program's logits along the program's own
        choices; this is what lets it follow them.  Where attention is
        INDEXED the positions a row keeps are such a choice too: a fed
        position's record is then flat, its ``expert layers x top_k``
        experts and behind them, for each indexed layer, ``ceil(max_ctx /
        16)`` words of the kept positions as bits
        (``ops.dsa_attention.selection_words``; 14 KB a position at 8
        layers x 14,336, fetched for the live rows only), and
        ``finished[rid]['routing']`` is the LIST of the calls' pieces
        ``[positions, width]`` as they were fetched: putting 145 MB a
        request together is the reader's, not a serving tick's.
    run_ahead: the decode discipline, which the engine chooses itself
        (``None``, the default).  Ahead: the decode call of a tick is
        dispatched BEFORE the call of the tick before it is fetched.  A slot
        whose newest token is still on the device is fed it (and its
        sampling key) from there (:func:`_take_prev`), so the host's walk
        over the slots, its tick record, the caller's loop, audit,
        admission and the building of the next call's arrays run while the
        device computes and not between its calls.  Every sequence's
        tokens, greedy or sampled, are the ones the serial engine gives;
        what the host sees (``finished``, the tick records'
        ``emitted_tokens``) lags one decode call, so a slot freed by a
        retirement is filled one tick later.  A slot whose in-flight token
        is its last by count sits the next call out.  One that leaves
        DECODE with a token in flight (it ends on ``eos_id``, is cancelled,
        preempted, requeued after a poisoned token or a failed audit,
        exported, drained) has that token dropped when it arrives
        (``stats['flight_dropped']``; ``stats['ahead_rows']`` counts the
        rows that took their token from the device) and goes on from what
        the host had: a descriptor carries the tokens booked so far and the
        key that samples the next, so the importer or the replay computes
        the dropped token again, the same one.  The pool is donated and
        chained call to call, so what is dispatched behind a call in flight
        (a prefill into a freed slot's blocks, a copy-on-write, a
        migration's copy) runs behind it on the device; a retired slot's
        last in-flight row writes at a position past its prompt, never into
        a block the prefix cache has registered.
        ``None`` means ahead wherever the decode step takes ``prev`` and
        runs beside the host: every single-device engine, dense, MoE,
        window, ``kv_quant``, ``prefix_cache`` and state model alike.  The
        engine keeps the SERIAL order (dispatch, fetch, walk inside one
        tick), chosen from its own constructor arguments, with ``spec_k``
        (the next draft needs this tick's tokens), with a ``mesh`` or
        ``cp_axis`` (the ``shard_map``'d steps' specs carry no ``prev``)
        and with a ``host_only`` ``device_step`` (its step runs in the
        caller's thread, so nothing can run beside it); ``hold_decode`` has
        no decode call at all.  An explicit ``True`` there raises; an
        explicit ``False`` is the serial engine, the oracle that the tests
        hold the other to.  (The keyword stays because the benchmark's
        family runner passes it: ROADMAP queue 3 item 4(i).)
    """

    @scope_decorator(name="tdp:engine.init")
    def __init__(
        self,
        params: Any,
        cfg: GPTConfig,
        *,
        num_slots: int = 4,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        max_ctx: Optional[int] = None,
        chunk: int = 16,
        mesh: Optional[Any] = None,
        axis: Optional[str] = None,
        dp_axis: Optional[str] = None,
        ep_axis: Optional[str] = None,
        cp_axis: Optional[str] = None,
        param_specs: Optional[Any] = None,
        kv_quant: bool = False,
        telemetry: Optional[Any] = None,
        snapshot_every: int = 16,
        max_queue: Optional[int] = None,
        chaos: Optional[Any] = None,
        watchdog: Optional[Any] = None,
        prefix_cache: bool = False,
        spec_k: int = 0,
        attn_impl: str = "auto",
        metrics_sink: Optional[Any] = None,
        tick_history: int = 4096,
        device_step: Optional[Any] = None,
        record_routing: bool = False,
        run_ahead: Optional[bool] = None,
    ) -> None:
        if (axis is not None or dp_axis is not None) and mesh is None:
            raise ValueError("axis/dp_axis need a mesh")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if cfg.attn_impl in ("ring", "ulysses"):
            raise NotImplementedError(
                "the training-side ring/Ulysses attn_impl does not apply to "
                "serving: pass cp_axis= for sequence-sharded (ring paged) "
                "prefill over the block pool, or decode a CP-trained "
                "checkpoint with attn_impl='flash', context_axis=None")
        #: the hybrid family (models/hybrid.py), some of whose layers may keep
        #: a recurrent state per sequence instead of keys and values, or a
        #: tail of the rows before a position BESIDE its keys and values:
        #: its step carries that state beside the pool (none at all where
        #: the pattern has no such layer); docs/serving.md "State models"
        self.state_model = hasattr(cfg, "state_layers")
        if self.state_model:
            # a window pool keeps a sequence's last window alone: a block
            # that fell behind it is handed on to a column ahead and
            # overwritten (docs/serving.md "Two pools")
            for on, what, why in (
                    (prefix_cache, "prefix_cache",
                     "a handed-on block is no prefix block: the window "
                     "layers' keys of a shared prefix are overwritten as "
                     "its first owner goes on, so a later prompt would map "
                     "the other layers' blocks and find no window ones"),
                    (spec_k, "spec_k",
                     "a rejected draft's rows may already have overwritten "
                     "a handed-on block that the accepted length still "
                     "reads"),
                    (cp_axis, "cp_axis",
                     "the ring rotates ONE pool's slices through one table"),
                    (mesh, "a mesh (tp/dp/ep)",
                     "the window pool has no sharded form and one "
                     "allocator"),
                    (kv_quant, "kv_quant",
                     "the window pool has no int8 form")):
                if on and getattr(cfg, "window_layers", 0):
                    raise NotImplementedError(
                        f"{what} with a window pool is not supported: "
                        f"{why} (ROADMAP queue 2 A2)")
            for on, what in ((prefix_cache and cfg.state_layers,
                              "prefix_cache"),
                             (spec_k, "spec_k"), (cp_axis, "cp_axis"),
                             (mesh, "a mesh (tp/dp/ep)")):
                if not on:
                    continue
                if not cfg.state_layers:
                    raise NotImplementedError(
                        f"{what} with the hybrid family is not supported: "
                        f"its step has no verify or mesh form (a model of "
                        f"attention layers alone keeps nothing outside its "
                        f"blocks, so nothing else stands in the way; where "
                        f"attention is indexed, the verify rows would each "
                        f"select for themselves: ROADMAP queue 2 A4)")
                raise NotImplementedError(
                    f"{what} with a state model is not supported: a "
                    f"recurrent state, or the tail an attention layer "
                    f"keeps of the rows before a position, cannot be "
                    f"shared by prefix, rolled back after a rejected "
                    f"draft or split over devices without per-position "
                    f"SNAPSHOTS of it, which the engine does not keep "
                    f"yet, and the family's step has no verify or mesh "
                    f"form (ROADMAP queue 2 A4)")
            if record_routing and not cfg.moe_experts:
                raise ValueError("record_routing: the model has no "
                                 "expert layers")
            q = cfg.ssm_chunk
            if cfg.ssm_layers and chunk > q and chunk % q:
                raise ValueError(
                    f"chunk ({chunk}) must be at most the model's "
                    f"recurrence chunk ({q}) or a multiple of it")
        elif record_routing:
            raise NotImplementedError(
                "record_routing is written for the state model's step only")
        self.record_routing = bool(record_routing)
        #: run_ahead: the decode call whose outputs are still on the device
        #: (:meth:`_absorb_decode` books them one tick later)
        self._flight: Optional[Dict[str, Any]] = None
        if cp_axis is not None:
            if mesh is None:
                raise ValueError("cp_axis needs a mesh")
            if dp_axis is not None:
                raise NotImplementedError(
                    "cp_axis cannot be combined with dp_axis: the pool's "
                    "block dim carries exactly one mesh axis (run a CP "
                    "prefill tier as its own replica behind the Router)")
            if spec_k:
                raise NotImplementedError(
                    "cp_axis + speculative decoding is not supported (a CP "
                    "prefill tier hands off before decode; run spec_k on "
                    "the decode replica)")
            if prefix_cache:
                raise NotImplementedError(
                    "cp_axis + prefix_cache is not supported (block hashes "
                    "would need cross-rank content)")
            if kv_quant:
                raise NotImplementedError(
                    "cp_axis + kv_quant is not supported (the ring rotates "
                    "fp pool slices)")
            if cfg.moe_experts:
                raise NotImplementedError(
                    "cp_axis + MoE serving is not supported yet")
            cp = int(mesh.shape[cp_axis])
            if chunk % cp:
                raise ValueError(
                    f"chunk ({chunk}) must be divisible by the context axis "
                    f"size ({cp}) — each rank prefills chunk/cp rows")
        else:
            cp = 1
        #: context-parallel width: >1 = ring paged prefill, the pool's
        #: block dim sharded over ``cp_axis`` (ops/ring_paged.py,
        #: docs/long_context.md "CP prefill serving")
        self.cp = cp
        self.cp_axis = cp_axis
        if num_slots < 1 or chunk < 1 or block_size < 1:
            raise ValueError(
                f"num_slots/chunk/block_size must be >= 1, got "
                f"{num_slots}/{chunk}/{block_size}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1 or None, got {max_queue}")
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.block_size = block_size
        self.chunk = chunk
        self.mesh, self.axis, self.dp_axis = mesh, axis, dp_axis
        self.ep_axis = ep_axis
        self.kv_quant = kv_quant
        self.telemetry = telemetry
        self.snapshot_every = snapshot_every
        self.max_queue = max_queue
        self.chaos = chaos
        self.watchdog = watchdog
        #: host-side scheduler bit for a DISAGGREGATED prefill tier
        #: (serving/router.py): True = the decode tick is skipped, so a
        #: slot that finishes prefill PARKS in the DECODE state (first
        #: token sampled, KV complete) until the router exports it to a
        #: decode replica — this engine's compiled decode program is then
        #: never dispatched at all.  Plain scheduler state: flipping it
        #: traces nothing.
        self.hold_decode = False
        self.prefix_cache = bool(prefix_cache)
        self.spec_k = int(spec_k)
        from ..ops.paged_attention import resolve_attn_impl

        #: 'pallas' (in-kernel block-table walk — the TPU default) or
        #: 'gather' (dense gathered view — the parity oracle and the CPU
        #: default; interpreter-mode pallas on CPU is correct but slow).
        #: docs/serving.md "Paged attention kernel".
        self.attn_impl = resolve_attn_impl(attn_impl)
        self.metrics_sink = metrics_sink
        self.tick_history = int(tick_history)
        self._ev: EventLog = (
            telemetry.events if telemetry is not None else default_event_log())

        self.max_ctx = int(max_ctx if max_ctx is not None else cfg.max_seq)
        # spec slack: a verify step writes up to spec_k positions past the
        # committed length, so the table must cover max_ctx + spec_k
        # positions: a write past the table's width goes to the NULL block
        # (paged_cache._write_blocks), and the later drafts of the same
        # call would attend to keys that were never stored
        self.max_blocks = -(-(self.max_ctx + self.spec_k) // block_size)
        self.dp = int(mesh.shape[dp_axis]) if (mesh is not None and dp_axis) else 1
        if num_slots % self.dp:
            raise ValueError(
                f"num_slots {num_slots} not divisible by dp {self.dp}")
        self.slots_per_group = num_slots // self.dp
        #: slots of a dp group in one compiled prefill call: by whether the
        #: model has expert layers, never more than the group holds
        self.prefill_width = min(
            PREFILL_WIDTH_EXPERTS if cfg.moe_experts else PREFILL_WIDTH,
            self.slots_per_group)
        if num_blocks is None:
            num_blocks = 1 + self.slots_per_group * self.max_blocks
            if self.cp > 1:  # pool shards evenly over the context axis
                num_blocks = -(-num_blocks // self.cp) * self.cp
        elif self.cp > 1 and num_blocks % self.cp:
            raise ValueError(
                f"num_blocks ({num_blocks}) must be divisible by the "
                f"context axis size ({self.cp}) — the pool's block dim is "
                f"sharded over cp_axis")
        self.num_blocks = num_blocks  # per dp group
        self._allocs = [BlockAllocator(num_blocks) for _ in range(self.dp)]
        #: a model with window layers (models/hybrid.py kind 'W'): the
        #: SECOND pool, its own allocator and table.  A slot holds at most
        #: ``window_reach`` of its blocks at a time (the columns from the
        #: first key inside the window of a call's first row to the call's
        #: last row), so the pool is every slot's reach and the NULL block,
        #: whatever ``num_blocks`` says of the pool that keeps everything
        self.window = int(getattr(cfg, "window_layers", 0) and cfg.window)
        self.window_reach = (window_reach(self.window, chunk, block_size)
                             if self.window else 0)
        self.window_blocks = (1 + num_slots * self.window_reach
                              if self.window else 0)
        self._walloc = (BlockAllocator(self.window_blocks)
                        if self.window else None)
        self._param_specs = param_specs

        from .sim import CompiledDeviceStep

        if device_step is None:
            device_step = CompiledDeviceStep()
        host_only = getattr(device_step, "host_only", False)
        if host_only and mesh is not None:
            raise ValueError(
                "a host-only DeviceStep cannot shard a pool over a mesh")
        # the decode discipline is the engine's own choice (docstring,
        # ``run_ahead``): ahead wherever the decode step has a ``prev`` form
        # that runs beside the host
        serial = next((why for on, why in (
            (spec_k, "spec_k (the next draft needs this tick's tokens)"),
            (cp_axis is not None, "cp_axis (the ring step takes no prev)"),
            (mesh is not None, "a mesh (the shard_map'd step's specs carry "
                               "no prev)"),
            (host_only, "a host-only DeviceStep (its step runs in the "
                        "caller's thread: nothing can run beside it)"),
        ) if on), None)
        if run_ahead and serial:
            raise NotImplementedError(
                f"run_ahead with {serial} is not supported: such an engine "
                f"keeps the serial order")
        self.run_ahead = serial is None if run_ahead is None else bool(
            run_ahead)
        #: the device-program seam (serving/sim.py): compiled pair or
        #: host-only stub — every device touch below goes through it
        self.device_step = device_step
        device_step.bind(self)
        with span("tdp:engine.init.pool") as sp:
            before = _device_bytes_in_use()
            self.cache = device_step.init_cache()
            sp.attrs.update(bytes=pool_bytes(self.cache), **self._walk_attrs(),
                            **_device_bytes_taken(before, self.cache))
            if "idx" in self.cache:  # of which the indexer's keys
                sp.attrs.update(index_bytes=index_bytes(self.cache))
            if self.window:  # of which the window layers' pool
                sp.attrs.update(window_bytes=window_bytes(self.cache),
                                window_blocks=self.window_blocks)
            if "v" in self.cache:  # a head's widths and each pool's heads
                sp.attrs.update(_pool_shape_attrs(self.cache, self.kv_quant))
        #: state models: the recurrent state, one row a slot, beside the
        #: pool (``models.hybrid.init_state``); like the pool, the compiled
        #: step is handed it as a donated argument and the engine keeps
        #: what comes back
        self.state = None
        self.state_bytes = 0
        if self.state_model:
            self.state_bytes = int(cfg.state_bytes(num_slots))
            with span("tdp:engine.init.state", bytes=self.state_bytes) as sp:
                before = _device_bytes_in_use()
                self.state = device_step.init_state()
                # of which the recurrent state, and the convolutions' rows
                sp.attrs.update(
                    ssm_bytes=pool_bytes(self.state.get("ssm", ())),
                    conv_bytes=pool_bytes(self.state.get("conv", ())),
                    **_device_bytes_taken(before, self.state))
        #: run_ahead's first decode call: no call before it to take from
        self._no_flight = {"out": (jnp.zeros(num_slots, jnp.int32),
                                   jnp.zeros((num_slots, 2), jnp.uint32))
                           } if self.run_ahead else None

        # host-visible device state, one row per slot
        V = cfg.vocab_size
        self._tables = np.zeros((num_slots, self.max_blocks), np.int32)
        #: the window pool's table: absolute columns as the other's, a
        #: column behind the window NULL once its block was handed on
        self._wtables = (np.zeros_like(self._tables) if self.window
                         else None)
        self._lengths = np.zeros(num_slots, np.int32)
        self._last_tok = np.zeros(num_slots, np.int32)
        self._temps = np.zeros(num_slots, np.float32)
        self._top_k = np.full(num_slots, V, np.int32)
        self._top_p = np.ones(num_slots, np.float32)
        self._keys = np.zeros((num_slots, 2), np.uint32)

        self._slots = [_SlotState() for _ in range(num_slots)]
        self.queue: List[Tuple[Request, float]] = []
        self.finished: Dict[int, Dict[str, Any]] = {}
        self.rejected: Dict[int, Dict[str, Any]] = {}
        # completion/rejection rids in arrival order — lets a collector
        # (the Router, every tick) consume just the tail instead of
        # re-scanning the whole dict, which goes quadratic at replay scale
        self._finished_order: List[int] = []
        self._rejected_order: List[int] = []
        self._next_rid = 0
        self._seq: Dict[int, int] = {}  # rid -> FIFO age (survives requeue)
        self._inject: Dict[int, Dict[str, Any]] = {}  # resume key/prefix
        self._draining = False
        self._tick_ewma: Optional[float] = None
        #: EWMA of measured-TTFT / raw-estimate — the calibration factor
        #: estimate_ttft applies (None until a prediction resolved; like
        #: _tick_ewma it is measurement state, NOT reset by reset_metrics)
        self._ttft_bias: Optional[float] = None
        #: signatures whose first call (the one that compiles or loads) is
        #: behind us; like _tick_ewma, NOT reset by reset_metrics
        self._called_sigs: set = set()
        self._tick_prefill_rids: List[int] = []
        self._tick_decode_rids: List[int] = []
        self._tick_emitted = 0
        self._tick_moe = dict.fromkeys(_MOE_CALL_STATS, 0.0)
        #: indexed attention (``cfg.index_width``): how many positions a
        #: query keeps at most (0: attention is not indexed), and this
        #: tick's (query, position) pairs scored and selected
        self._idx_topk = (int(cfg.idx_topk)
                          if getattr(cfg, "index_width", 0) else 0)
        self._tick_dsa = [0, 0]
        #: a window pool: this tick's [positions the window layers hold for
        #: the calls' slots, blocks handed on]
        self._tick_window = [0, 0]
        self._pending_cow: List[Tuple[int, int, int]] = []  # slot, src, dst
        wrap = (telemetry is not None
                and getattr(device_step, "wrap_steps", True))
        self._step_fn = device_step.step_fn()
        self._decode_fn = (
            telemetry.wrap_step(self._step_fn) if wrap else self._step_fn)
        self._cow_fn = device_step.cow_fn() if self.prefix_cache else None
        self._verify_jit = device_step.verify_fn() if self.spec_k else None
        self._verify_fn = (telemetry.wrap_step(self._verify_jit)
                           if wrap and self.spec_k else self._verify_jit)
        self.reset_metrics()

    # ------------------------------------------------------------ compiled step

    def _cache_specs(self, cache):
        from jax.sharding import PartitionSpec as P

        def spec(leaf):
            # the pool's block dim carries dp groups OR the cp ring slices
            # (mutually exclusive, validated in __init__); heads carry tp
            lead = (None, self.dp_axis or self.cp_axis, self.axis)
            return P(*lead, *([None] * (leaf.ndim - 3)))

        return jax.tree.map(spec, cache)

    def _fwd(self, moe_stats: bool = False) -> Callable:
        import functools

        if self.cfg.moe_experts:
            return functools.partial(paged_forward_moe, ep_axis=self.ep_axis,
                                     attn_impl=self.attn_impl,
                                     moe_stats=moe_stats)
        return functools.partial(paged_forward, attn_impl=self.attn_impl)

    def _build_step(self) -> Callable:
        """ONE python step serves both phases: ``[num_slots, 1]`` calls are
        the decode step, ``[dp * prefill_width, chunk]`` calls the
        prefill-chunk step (:meth:`_prefill_batches`: only slots that are
        prefilling) — two signatures of the same program, compiled once
        each.  The row count comes from ``tokens.shape[0]`` and the pool
        is reached through ``tables`` alone, so nothing here is
        ``num_slots`` wide.  A decode call with ``run_ahead`` also takes
        ``prev`` (:func:`_take_prev`: always, ``_no_flight``'s zeros on the
        first, so the decode signature stays one program); a prefill call
        never does.

        The pool is a DONATED argument of every program that takes it
        (this one, the state, mesh, ring, verify and copy-on-write
        programs): the forward carries it whole through the layers and
        writes a call's rows at ``[layer, block, :, row]``, so the buffer
        that goes in is the buffer that comes out, held once and never
        copied.  The array handed in is dead after the call: the engine
        keeps what comes back (:meth:`_dispatch`), and so must anyone who
        calls a step by hand."""
        cfg, axis = self.cfg, self.axis
        moe = bool(cfg.moe_experts)
        if self.cp_axis is not None:
            return self._build_cp_step()
        if self.state_model:
            return self._build_state_step()
        fwd = self._fwd(moe_stats=moe)

        def step(params, cache, tokens, tables, offsets, last_idx, samp, keys,
                 prev=None):
            tokens, keys = _take_prev(tokens, keys, prev)
            if moe:
                cache, logits, mstats = fwd(
                    params, tokens, cfg, cache, tables, offsets,
                    axis=axis, last_idx=last_idx)
            else:
                cache, logits = fwd(params, tokens, cfg, cache, tables,
                                    offsets, axis=axis, last_idx=last_idx)
            full = _full_logits(logits, cfg, axis)
            keys, sub = _split_keys(keys)
            tok = _slot_sample(full, sub, samp["temperature"], samp["top_k"],
                               samp["top_p"])
            if axis is not None:
                # every tp shard sampled the identical token (full logits
                # are psum-assembled, keys replicated); pmax re-types it
                # axis-invariant for the replicated out_spec
                tok = jax.lax.pmax(tok, axis)
            if moe:
                # live expert-load signal, [1, E] / [1] per dp group so the
                # host can sum shards; pmax re-types tp-replicated values
                # axis-invariant (the routing inputs are identical per tp
                # shard) without changing them
                et = mstats["expert_tokens"][None, :]
                dr = mstats["dropped_token_rate"][None]
                if axis is not None:
                    et = jax.lax.pmax(et, axis)
                    dr = jax.lax.pmax(dr, axis)
                return cache, tok, keys, et, dr
            return cache, tok, keys

        if self.mesh is None:
            return jax.jit(step, donate_argnums=(1,))
        return self._mesh_step(step)

    def _build_state_step(self) -> Callable:
        """:meth:`_build_step` for a state model: the same two signatures,
        with the per-sequence ``state`` as a second DONATED argument after
        the pool (each layer's array, a Mamba layer's state or a convolved
        attention layer's tail, is updated where it lies and never held
        twice, exactly as the pool) and two more row vectors at the
        end:
        ``rows`` (None: row b is slot b, the decode call; else the slot
        whose state each compact prefill row carries) and ``n_valid`` (the
        real positions of each row: padding advances no state).  The
        expert layers' counters always ride along, as the MoE family's
        do, plus ``[rows routed, rows on held experts, experts touched,
        expert layers that ran batched]`` (one vector: a transfer costs by
        the array) and, with ``record_routing``, every position's chosen
        experts.
        ``prev``: as in :meth:`_build_step` (:func:`_take_prev`)."""
        from .paged_cache import paged_forward_hybrid

        cfg, attn_impl = self.cfg, self.attn_impl
        record = self.record_routing
        held = cfg.moe.held_range[1] if cfg.moe_experts else 1

        def step(params, cache, state, tokens, tables, offsets, last_idx,
                 samp, keys, rows, n_valid, prev=None):
            tokens, keys = _take_prev(tokens, keys, prev)
            cache, state, logits, m = paged_forward_hybrid(
                params, tokens, cfg, cache, state, tables, offsets, n_valid,
                rows=rows, last_idx=last_idx, attn_impl=attn_impl)
            keys, sub = _split_keys(keys)
            tok = _slot_sample(logits, sub, samp["temperature"],
                               samp["top_k"], samp["top_p"])
            if m is None:
                m = {"expert_tokens": jnp.zeros((held,), jnp.float32),
                     "dropped_token_rate": jnp.zeros((), jnp.float32)}
            share = jnp.stack([m.get(k, jnp.zeros((), jnp.float32)) for k in
                               ("rows_routed", "rows_held",
                                "experts_touched", "layers_batched")])
            out = (cache, state, tok, keys, m["expert_tokens"][None, :],
                   m["dropped_token_rate"][None], share)
            if record:
                chose = m["routing"].astype(jnp.int16)
                if "selection" in m:
                    # behind a position's experts, the positions it kept;
                    # a compact prefill row is a leaf of its own, so that
                    # the host fetches the live rows alone
                    chose = jnp.concatenate(
                        [a.reshape(*chose.shape[:2], -1)
                         for a in (chose, m["selection"])], axis=-1)
                    chose = chose if rows is None else tuple(chose)
                out += (chose,)
            return out

        return jax.jit(step, donate_argnums=(1, 2))

    def _walk_attrs(self) -> Dict[str, int]:
        """How the paged kernel walks this engine's calls, which follows
        from their shapes alone (``ops.paged_attention.call_walk``).  A
        decode (or verify) call: the KV heads a program carries and the pool
        blocks of one key tile (0: the grid walks the table's columns).  A
        prefill chunk's call: the query rows of one program, the keys of its
        one key tile a grid step (one online-softmax step), and the programs
        a slot's call is dealt to.  Nothing for the gather path and for a
        latent pool."""
        k = self.cache.get("k")
        if self.attn_impl != "pallas" or k is None:
            return {}
        tp = int(self.mesh.shape[self.axis]) if (
            self.mesh is not None and self.axis) else 1
        blk, ops = self.cfg.block, paged_attention_ops

        def walk(pool, s_in, window):  # as the wrapper asks
            """``shape_walk`` of a call on ``pool``, with its KV heads and
            its block: by the pool's own head axis (narrow heads lie several
            to a row; window layers may have another count), its block size
            the V leaf's (a K leaf of another width lies transposed), a
            head's block of K and of V their mean."""
            ka, va = ((leaf[0] if self.kv_quant else leaf)
                      for leaf in (pool["k"], pool["v"]))
            hkv, bs = va.shape[2] // tp, va.shape[3]
            keys = ka.shape[3 if keys_transposed(ka, va) else 4]
            return (hkv, bs) + ops.shape_walk(
                blk.nheads // va.shape[2], s_in, hkv, self.max_blocks, bs,
                bs * (keys + va.shape[4]) // 2 * va.dtype.itemsize, window,
                self.kv_quant)

        window = getattr(blk, "sliding_window", None)
        *_, hb, T = walk(self.cache, self.spec_k + 1, window)
        hkv, bs, split, _cols, rows, fw, chb, cT = walk(
            self.cache, self.chunk, window)
        attrs = {"kv_heads_per_step": hb, "kv_tile_blocks": T,
                 "chunk_rows": rows, "chunk_tile_keys": (cT or fw) * bs,
                 "chunk_programs": hkv * split // chb}
        if self.window:  # the window layers' chunk walks the window's columns
            *_, fw, _hb, cT = walk(self.cache["win"], self.chunk, self.window)
            attrs["window_chunk_tile_keys"] = (cT or fw) * bs
        return attrs

    def _dispatch(self, fn: Callable, args: Tuple[Any, ...]) -> Tuple[Any, ...]:
        """One call of a compiled step.  The call consumes the pool (and a
        state model's state): both are donated, so what is kept here is
        the only live handle to either.  Hands back the rest: ``(tok,
        keys)`` and, where the model has expert layers, their counters."""
        if self.state is None:
            out = fn(self.params, self.cache, *args)
        else:
            out = fn(self.params, self.cache, self.state, *args)
            self.state, out = out[1], out[:1] + out[2:]
        self.cache = out[0]
        return out[1:]

    def _note_program(self, attrs: Dict[str, Any], jitted: Callable,
                      args: Tuple[Any, ...]) -> None:
        """Before the ``first`` call of a signature (``attrs``: its
        dispatch span's): the program it makes ready goes into
        ``utils.profiling``'s table under the span's ``program``, as the
        jitted step and the SHAPES of what the call hands it."""
        if attrs.get("first"):
            held = (self.params, self.cache) + (
                () if self.state is None else (self.state,))
            prof.note_program(attrs["program"], jitted, held + tuple(args))

    def _needs_snapshots(self, what: str) -> None:
        """A state model's requests cannot leave the engine mid-flight: the
        recurrent state would have to travel with them."""
        if not self.state_model:
            return
        if self.window:
            raise NotImplementedError(
                f"{what} with a window pool is not supported: a request "
                f"would travel with two tables, and the window pool's "
                f"blocks stand at columns that depend on where its last "
                f"call stood (ROADMAP queue 2 A2)")
        if not self.cfg.state_layers:
            raise NotImplementedError(
                f"{what} with the hybrid family is not supported: a model "
                f"of attention layers alone keeps nothing outside its "
                f"blocks (K, V and an indexer's keys travel with them), but "
                f"the family's engine path has no export, import or drain "
                f"form yet (ROADMAP queue 2 A4)")
        raise NotImplementedError(
            f"{what} with a state model is not supported: the "
            f"request's recurrent state (or its attention layers' "
            f"tails) would have to be snapshotted and carried, which "
            f"the engine does not do yet (ROADMAP queue 2 A4)")

    def _build_cp_step(self) -> Callable:
        """The ring-paged step (docs/long_context.md "CP prefill
        serving"): the same two-signature program as :meth:`_build_step`
        — ``cp_paged_forward`` branches on S_in at TRACE time, so the
        S_in=chunk signature compiles the python-unrolled ring and the
        S_in=1 signature compiles the local-slice + psum-combine decode.
        ``decode_signatures`` stays 1."""
        from .paged_cache import cp_paged_forward

        cfg, axis, cp_axis = self.cfg, self.axis, self.cp_axis
        attn_impl = self.attn_impl

        def step(params, cache, tokens, tables, offsets, last_idx, samp, keys):
            cache, logits = cp_paged_forward(
                params, tokens, cfg, cache, tables, offsets,
                cp_axis=cp_axis, axis=axis, last_idx=last_idx,
                attn_impl=attn_impl)
            full = _full_logits(logits, cfg, axis)
            keys, sub = _split_keys(keys)
            tok = _slot_sample(full, sub, samp["temperature"], samp["top_k"],
                               samp["top_p"])
            if axis is not None:
                tok = jax.lax.pmax(tok, axis)
            # every cp rank sampled the identical token (prefill logits
            # are psum-assembled over cp, decode logits psum-combined,
            # keys replicated); pmax re-types for the replicated out_spec
            tok = jax.lax.pmax(tok, cp_axis)
            return cache, tok, keys

        return self._mesh_step(step)

    def _mesh_step(self, step):
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        dp = self.dp_axis
        row = P(dp) if dp else P()
        in_specs = (
            self.param_specs_cached(),
            self._cache_specs(self.cache),
            row, row, row, row,
            {"temperature": row, "top_k": row, "top_p": row},
            row,
        )
        out_specs = (self._cache_specs(self.cache), row, row)
        if self.cfg.moe_experts:
            # [1, E] expert counts / [1] drop rate per dp group -> stacked
            # [dp, E] / [dp] globally; the host sums / means the groups
            out_specs = out_specs + (row, row)
        # The Pallas INTERPRETER evaluates a kernel's index maps as plain
        # jaxprs, and shard_map's type check refuses to index a
        # scalar-prefetch operand that varies over a mesh axis (tables,
        # offsets) by a grid position that does not.  Mosaic has no such
        # check, so the check goes exactly when the kernels are
        # interpreted; every other engine program keeps it.
        interpreted = (self.attn_impl == "pallas"
                       and paged_attention_ops._interpret())
        return jax.jit(shard_map(
            step, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=not interpreted),
            donate_argnums=(1,))

    def _build_verify_step(self) -> Callable:
        """The speculative verify program — ONE compiled step at a STATIC
        draft width: feed ``[last_tok, d_1..d_K]`` per slot at offsets
        ``length..length+K`` through the same paged forward
        (``all_logits=True``: every position's distribution in one
        paged-attention pass), then judge each draft against the
        distribution its slot would have sampled from.

        Greedy rows (``temperature <= 0``): accept while the draft equals
        the model's argmax — EXACT, so temp-0 output is bit-identical to
        non-speculative decode whatever the drafter proposes.  Sampled
        rows: standard residual rejection sampling against the filtered
        distribution (the drafter is deterministic, a point mass, so the
        acceptance test is ``u < p(draft)`` and the rejection draw comes
        from p with the draft's mass removed) off the slot's own key
        stream — distributionally exact.  Returns ``(cache, verify[B,
        K+1], accept[B, K], keys)``: ``verify[:, i]`` is the token the
        model emits when draft ``i`` is the first rejection (column K =
        the bonus token when every draft survives); the host walks the
        accept bits."""
        cfg, axis = self.cfg, self.axis
        K = self.spec_k
        fwd = self._fwd()

        def step(params, cache, tokens, tables, offsets, samp, keys):
            cache, logits = fwd(params, tokens, cfg, cache, tables, offsets,
                                axis=axis, all_logits=True)
            x = _full_logits(logits, cfg, axis).astype(jnp.float32)
            B, K1, V = x.shape
            greedy = jnp.argmax(x, axis=-1).astype(jnp.int32)  # [B, K+1]
            temp = samp["temperature"]
            carry, sub = _split_keys(keys)
            # a fixed 2K+1 keys per slot per tick: K acceptance uniforms,
            # K residual draws, 1 bonus draw — static key plumbing
            subs = jax.vmap(lambda k: jax.random.split(k, 2 * K + 1))(sub)
            rep = lambda a: jnp.repeat(a, K1)
            xf = _filtered_logits(
                x.reshape(B * K1, V), rep(temp), rep(samp["top_k"]),
                rep(samp["top_p"])).reshape(B, K1, V)
            probs = jax.nn.softmax(xf, axis=-1)
            drafts = tokens[:, 1:]  # [B, K]
            p_draft = jnp.take_along_axis(
                probs[:, :K], drafts[..., None], axis=-1)[..., 0]
            u = jax.vmap(jax.vmap(jax.random.uniform))(subs[:, :K])
            acc = jnp.where(temp[:, None] <= 0.0,
                            drafts == greedy[:, :K], u < p_draft)
            # residual: p with the draft's (point) mass removed; when the
            # draft was the whole support the residual is empty — fall
            # back to the filtered argmax (measure-zero guard)
            neg = jnp.float32(-jnp.inf)
            onehot = jax.nn.one_hot(drafts, V, dtype=jnp.bool_)
            xr = jnp.where(onehot, neg, xf[:, :K])
            has = jnp.max(xr, axis=-1) > neg
            resid = jax.vmap(jax.vmap(jax.random.categorical))(
                subs[:, K:2 * K], xr)
            resid = jnp.where(has, resid, jnp.argmax(xf[:, :K], axis=-1))
            bonus = jax.vmap(jax.random.categorical)(subs[:, 2 * K], xf[:, K])
            ver = jnp.where(
                temp[:, None] <= 0.0, greedy,
                jnp.concatenate([resid, bonus[:, None]], axis=1),
            ).astype(jnp.int32)
            acc = acc.astype(jnp.int32)
            if axis is not None:
                # every tp shard judged the identical verdict (full logits
                # psum-assembled, keys replicated); pmax re-types for the
                # replicated out_spec, as in the ordinary decode step
                ver = jax.lax.pmax(ver, axis)
                acc = jax.lax.pmax(acc, axis)
            return cache, ver, acc, carry

        if self.mesh is None:
            return jax.jit(step, donate_argnums=(1,))
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        dp = self.dp_axis
        row = P(dp) if dp else P()
        in_specs = (
            self.param_specs_cached(),
            self._cache_specs(self.cache),
            row, row, row,
            {"temperature": row, "top_k": row, "top_p": row},
            row,
        )
        out_specs = (self._cache_specs(self.cache), row, row, row)
        return jax.jit(shard_map(
            step, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs),
            donate_argnums=(1,))

    def _build_cow(self) -> Callable:
        """The copy-on-write program: one fixed-signature block copy
        (``[num_slots]`` src/dst lanes, NULL-padded) applied between host
        scheduling and the next prefill call — admission-path only, never
        part of the per-tick hot loop."""
        def cow(cache, src, dst):
            return copy_blocks(cache, src, dst)

        if self.mesh is None:
            return jax.jit(cow, donate_argnums=(0,))
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        row = P(self.dp_axis) if self.dp_axis else P()
        cache_specs = self._cache_specs(self.cache)
        return jax.jit(shard_map(
            cow, mesh=self.mesh, in_specs=(cache_specs, row, row),
            out_specs=cache_specs), donate_argnums=(0,))

    def param_specs_cached(self):
        if getattr(self, "_param_specs", None) is None:
            from ..models import gpt_moe_param_specs, gpt_param_specs

            fn = gpt_moe_param_specs if self.cfg.moe_experts else gpt_param_specs
            kw = {"ep_axis": self.ep_axis} if (
                self.cfg.moe_experts and self.ep_axis) else {}
            self._param_specs = fn(self.cfg, tp_axis=self.axis, **kw)
        return self._param_specs

    # ---------------------------------------------------------------- admission

    def _blocks_needed(self, req: Request) -> int:
        # spec_k slack: a verify step writes drafts up to spec_k positions
        # past the committed length, so every request's table must cover
        # them (mirrors speculative_generate's overshoot slack)
        return -(-(len(req.tokens) + req.max_new_tokens + self.spec_k)
                 // self.block_size)

    def _window_blocks_needed(self, req: Request) -> int:
        """What a request reserves of the window pool: its whole length's
        blocks, or the reach of a window (``window_reach``) where it is
        longer (the rest is handed on, :meth:`_hand_on`)."""
        return min(self._blocks_needed(req), self.window_reach)

    def _prefix_hashes(self, tokens) -> List[Any]:
        return (chain_block_hashes(tokens, self.block_size)
                if self.prefix_cache else [])

    def _prefill_chunks(self, tokens) -> int:
        """Prefill ticks a prompt costs, NET of prefix-cache hits: full
        blocks already resident prefill for free (a whole-prompt hit
        still recomputes the last token — the COW admission), so warm
        shared-prefix traffic is not spuriously shed by the deadline
        gate."""
        p_len = len(tokens)
        cached = 0
        if self.prefix_cache:
            hashes = self._prefix_hashes(tokens)
            if hashes:
                n_hit = max(len(a.match(hashes)) for a in self._allocs)
                cached = min(n_hit * self.block_size, p_len - 1)
        return -(-(p_len - cached) // self.chunk)

    def _queue_sort(self) -> None:
        """Priority order, FIFO within a class: the sort key is
        (-priority, submit age) and ages survive requeue, so a preempted
        request rejoins ahead of younger peers of its own class."""
        self.queue.sort(key=lambda e: (-e[0].priority, self._seq[e[0].rid]))

    def _slo_row(self, priority: int) -> Dict[str, int]:
        """Per-priority SLO accumulator: completed/met/missed service plus
        the demand the engine refused (shed/expired) — the attainment
        denominator counts refusals as misses, because a shed request's
        deadline was not met however principled the refusal was."""
        return self._slo_by_prio.setdefault(int(priority), {
            "completed": 0, "met": 0, "missed": 0,
            "shed": 0, "expired": 0, "goodput_tokens": 0})

    def _resolve_ttft(self, rid: int, actual: float, priority: int) -> None:
        """Close the loop on one admission-time TTFT prediction: update
        the calibration bias EWMA (measured / RAW estimate — the raw one,
        so the feedback converges to the true factor instead of its
        square root) and record the relative error of the estimate
        admission actually used (the biased one) for the RUNREPORT
        ``serving.slo.calibration`` percentiles."""
        pred = self._ttft_pred.pop(rid, None)
        if pred is None or actual <= 0 or pred["raw"] <= 0:
            return
        ratio = actual / pred["raw"]
        self._ttft_bias = (
            ratio if self._ttft_bias is None
            else 0.8 * self._ttft_bias + 0.2 * ratio)
        self._calib_n += 1
        self._calib_by_prio.setdefault(int(priority), []).append(
            abs(actual - pred["est"]) / actual)

    def estimate_ttft(self, prompt_len: int,
                      tokens: Optional[Sequence[int]] = None) -> Optional[float]:
        """Estimated seconds until a request of ``prompt_len`` submitted
        NOW samples its first token, from the engine's own measured tick
        time (an EWMA over decode-carrying ticks): the request's own
        prefill chunks + the queue's unstarted prefill work + (when every
        slot is busy) the ticks until the earliest busy slot can retire.
        ``None`` until a tick has been measured — an unmeasured engine
        admits everything (there is no evidence to shed on yet).

        With the prefix cache on and ``tokens`` given, prefill chunks
        already RESIDENT are subtracted (for the candidate and for every
        queued request) — a warm shared-prefix request costs what it will
        actually cost, not its cold estimate, so the PR-9 deadline gate
        does not shed warm traffic spuriously.

        The raw (ticks x tick-EWMA) estimate is multiplied by the
        engine's TTFT calibration bias — the EWMA of measured-TTFT /
        raw-estimate over resolved predictions (``_resolve_ttft``), the
        RUNREPORT ``serving.slo.calibration`` record — so admission
        stops trusting a systematically miscalibrated model instead of
        shedding (or admitting) on it forever."""
        if self._tick_ewma is None:
            return None
        if self.prefix_cache and tokens is not None:
            ticks = self._prefill_chunks(tokens)
        else:
            ticks = -(-prompt_len // self.chunk)
        for q, _t in self.queue:
            ticks += self._prefill_chunks(q.tokens)
        if not any(s.state == FREE for s in self._slots):
            remaining = []
            for s in self._slots:
                if s.state == FREE or s.req is None:
                    continue
                pre = (-(-(len(s.prompt) - s.off) // self.chunk)
                       if s.state == PREFILL else 0)
                remaining.append(
                    max(0, pre + s.req.max_new_tokens - len(s.generated)))
            if remaining:
                ticks += min(remaining)
        raw = ticks * self._tick_ewma
        return raw * (self._ttft_bias if self._ttft_bias is not None else 1.0)

    def _shed(self, req: Request, t_submit: float, reason: str,
              **extra: Any) -> None:
        """Refuse admission with a structured verdict: the record lands in
        ``self.rejected[rid]`` and on the timeline as ``request_shed`` —
        bounded, observable degradation instead of unbounded queueing."""
        verdict = {
            "rid": req.rid, "reason": reason, "priority": req.priority,
            "deadline_s": req.deadline_s, "queue_depth": len(self.queue),
            **extra,
        }
        self.rejected[req.rid] = verdict
        self._rejected_order.append(req.rid)
        self.stats["shed"] += 1
        self._slo_row(req.priority)["shed"] += 1
        self._ttft_pred.pop(req.rid, None)
        self._ev.emit("request_shed", **verdict)

    def submit(self, req: Request) -> int:
        """Enqueue; returns the request id.  Raises if the request can
        never fit the engine's context/pool ceilings (a too-long request
        must fail loudly at the door, not deadlock the queue).  A request
        the engine COULD serve but currently cannot afford — queue at
        ``max_queue``, estimated TTFT past ``deadline_s``, engine draining
        — is SHED: the rid is still returned, with the structured
        rejection verdict in ``self.rejected[rid]`` and a ``request_shed``
        event on the timeline."""
        P, N = len(req.tokens), req.max_new_tokens
        need = self._blocks_needed(req)
        if P + N > self.max_ctx:
            raise ValueError(
                f"prompt {P} + max_new {N} exceeds max_ctx {self.max_ctx}")
        if need > self._allocs[0].n_usable:
            raise ValueError(
                f"request needs {need} blocks, pool has "
                f"{self._allocs[0].n_usable} per group")
        if self.cfg.pos == "learned" and P + N > self.cfg.max_seq:
            raise ValueError(
                f"P + max_new_tokens = {P + N} exceeds the learned position "
                f"table ({self.cfg.max_seq})")
        req = dataclasses.replace(req, rid=self._next_rid)
        self._next_rid += 1
        self._seq[req.rid] = req.rid  # submit order IS the FIFO age
        t_submit = time.perf_counter()
        self._ev.emit(
            "request_submitted", rid=req.rid, prompt_len=int(P),
            max_new_tokens=int(N), priority=req.priority,
            deadline_s=req.deadline_s)
        # the admission model's prediction, recorded for calibration: the
        # biased estimate is what the deadline gate trusts, the raw one is
        # what the bias EWMA learns against (_resolve_ttft at first token)
        est = self.estimate_ttft(P, tokens=req.tokens)
        if est is not None and est > 0:
            self._ttft_pred[req.rid] = {
                "est": est,
                "raw": est / (self._ttft_bias
                              if self._ttft_bias is not None else 1.0),
            }
        if self._draining:
            self._shed(req, t_submit, "draining")
            return req.rid
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self._shed(req, t_submit, "queue_full", max_queue=self.max_queue)
            return req.rid
        if req.deadline_s is not None:
            if est is not None and est > req.deadline_s:
                self._shed(req, t_submit, "deadline_unmeetable",
                           est_ttft_s=round(est, 6))
                return req.rid
        self.queue.append((req, t_submit))
        self._queue_sort()
        return req.rid

    def _expire_queue(self, now: float) -> int:
        """Drop queued requests whose TTFT deadline already passed — they
        cannot be served in time, so holding a queue spot only delays
        requests that still can."""
        keep, expired = [], 0
        for req, t_submit in self.queue:
            if req.deadline_s is not None and now - t_submit > req.deadline_s:
                expired += 1
                self.stats["expired"] += 1
                self._slo_row(req.priority)["expired"] += 1
                verdict = {
                    "rid": req.rid, "reason": "expired",
                    "priority": req.priority, "deadline_s": req.deadline_s,
                    "waited_s": round(now - t_submit, 6),
                }
                self.rejected[req.rid] = verdict
                self._rejected_order.append(req.rid)
                self._inject.pop(req.rid, None)
                self._ttft_pred.pop(req.rid, None)
                self._ev.emit("request_expired", **verdict)
            else:
                keep.append((req, t_submit))
        self.queue = keep
        return expired

    def _pick_victim(self, req: Request) -> Optional[int]:
        """The slot to evict so ``req`` can run: lowest priority strictly
        below ``req``'s; among equals, the most recently admitted (the
        discard-and-replay loses the least work)."""
        best = None
        for i, s in enumerate(self._slots):
            if s.state == FREE or s.req is None:
                continue
            if s.req.priority >= req.priority:
                continue
            key = (s.req.priority, -s.t_admit)
            if best is None or key < best[0]:
                best = (key, i)
        return None if best is None else best[1]

    def _preempt(self, i: int, by: Request) -> None:
        s = self._slots[i]
        self.stats["preempted"] += 1
        self._ev.emit(
            "request_preempted", rid=s.rid, slot=i,
            priority=s.req.priority, by_rid=by.rid, by_priority=by.priority,
            discarded_tokens=len(s.generated), blocks_freed=len(s.blocks))
        self._requeue_slot(i)

    def _requeue_slot(self, i: int) -> int:
        """Evict slot ``i`` back to the queue: blocks freed (tolerantly —
        a poisoned slot's ownership may already be inconsistent),
        accumulated output discarded, the request requeued at its ORIGINAL
        FIFO age for prompt replay.  Replay is deterministic: the slot key
        restarts from the request seed (or the drain-injected key), so the
        eventual tokens equal the uninterrupted run's."""
        s = self._slots[i]
        rid, req, t_submit = s.rid, s.req, s.t_submit
        alloc = self._allocs[i // self.slots_per_group]
        self._release_blocks(alloc, s.blocks)
        if self.window:
            self._release_blocks(self._walloc, s.wblocks)
        self._clear_slot_rows(i)
        s.reset()
        # the admission-time TTFT prediction's premise (the queue as it
        # stood at submit) was invalidated by SCHEDULING, not by tick-time
        # misestimation — resolving it would teach the bias the wrong
        # lesson, so it is dropped instead
        self._ttft_pred.pop(rid, None)
        self.queue.append((req, t_submit))
        self._queue_sort()
        return rid

    @staticmethod
    def _release_blocks(alloc: BlockAllocator, blocks: List[int]) -> None:
        """Fault-path block release, PER BLOCK and refcount-aware: a
        clean ownership reference decrements via ``free`` (a shared
        block's co-owner keeps it — preempting or retiring one sharer
        must never free a block another slot still references), and only
        a block ``free`` refuses (the inconsistency a fault created) is
        force-reclaimed."""
        for b in blocks:
            try:
                alloc.free([b])
            except ValueError:
                alloc.reclaim([b])

    def _clear_slot_rows(self, i: int) -> None:
        self._tables[i] = 0
        if self.window:
            self._wtables[i] = 0
        self._lengths[i] = 0
        self._last_tok[i] = 0
        self._temps[i] = 0.0
        self._top_k[i] = self.cfg.vocab_size
        self._top_p[i] = 1.0

    def _try_place(self, req: Request):
        """Find a slot + blocks for ``req``.  With the prefix cache on,
        the longest RESIDENT prefix of the prompt's full blocks (content-
        hash chained) is mapped into the table at zero prefill cost —
        each matched block's refcount bumps via ``share`` — and only the
        remainder is freshly allocated (evicting refcount-0 cached blocks
        LRU-first, only under pressure).  A whole-prompt hit keeps all
        but its last block and schedules a copy-on-write of that one:
        first-token sampling needs the last prompt position's LOGITS, and
        its KV write may not land in a block other slots read.  Returns
        ``(slot, shared, cow_src, fresh)`` or None (back-pressure)."""
        P = len(req.tokens)
        need = self._blocks_needed(req)
        hashes = self._prefix_hashes(req.tokens)
        for i, s in enumerate(self._slots):
            if s.state != FREE:
                continue
            alloc = self._allocs[i // self.slots_per_group]
            hit = alloc.match(hashes) if hashes else []
            cow_src = None
            if hit and len(hit) * self.block_size >= P:
                cow_src = hit[-1]
                hit = hit[:-1]
            for b in hit:
                alloc.share(b)
            if cow_src is not None:
                alloc.share(cow_src)  # pin: eviction must not take the src
            fresh = alloc.alloc(need - len(hit))
            if fresh is None:
                # revert the shares: nothing partially admitted
                for b in hit:
                    alloc.free([b])
                if cow_src is not None:
                    alloc.free([cow_src])
                continue
            if cow_src is not None:
                # unpin — the copy is scheduled before the next device
                # call, and the cache threading orders it before any write
                alloc.free([cow_src])
            if self.window:
                # every slot's reach is in the pool: a free slot finds it
                self._slots[i].wblocks = self._walloc.alloc(
                    self._window_blocks_needed(req))
            return i, hit, cow_src, fresh
        return None

    def _admit(self) -> int:
        """Priority admission: the head of the (priority-ordered) queue
        takes the first free slot whose dp group can cover its blocks
        (shared-prefix blocks mapped, remainder allocated — see
        :meth:`_try_place`).  When it cannot be placed, the lowest-
        priority running slot strictly below it is preempted and
        admission retries; head-of-line blocking WITHIN a priority class
        is deliberate — skipping ahead would starve long requests."""
        admitted = 0
        while self.queue:
            req, t_submit = self.queue[0]
            P, N = len(req.tokens), req.max_new_tokens
            need = self._blocks_needed(req)
            placed = self._try_place(req)
            if placed is None:
                victim = self._pick_victim(req)
                if victim is None:
                    break
                self._preempt(victim, req)
                continue  # blocks and/or a slot freed: retry the head
            self.queue.pop(0)
            slot_idx, shared, cow_src, fresh = placed
            alloc = self._allocs[slot_idx // self.slots_per_group]
            evicted = alloc.pop_evicted()
            blocks = shared + fresh
            s = self._slots[slot_idx]
            s.state, s.rid, s.req, s.blocks = PREFILL, req.rid, req, blocks
            s.prompt = np.asarray(req.tokens, np.int32)
            # chunked prefill starts AFTER the cached boundary (a COW
            # admission recomputes only the last prompt token)
            s.off = (P - 1) if cow_src is not None else (
                len(shared) * self.block_size)
            s.generated = []
            s.t_submit, s.t_admit = t_submit, time.perf_counter()
            s.ttft_s, s.tpot_s = None, []
            s.orig_prompt_len, s.pre_gen = len(req.tokens), 0
            self._tables[slot_idx] = 0
            self._tables[slot_idx, :need] = blocks
            if self.window:
                self._wtables[slot_idx] = 0
                self._wtables[slot_idx, :len(s.wblocks)] = s.wblocks
            self._lengths[slot_idx] = 0
            if evicted:
                self.stats["cache_evictions"] += len(evicted)
                self._ev.emit(
                    "cache_evict", tick=self._tick, n_blocks=len(evicted),
                    group=slot_idx // self.slots_per_group)
            if self.prefix_cache:
                self.stats["prefix_prompt_tokens"] += P
            if s.off:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_cached_tokens"] += int(s.off)
                self._ev.emit(
                    "prefix_hit", rid=req.rid, slot=slot_idx,
                    blocks=len(shared) + (1 if cow_src is not None else 0),
                    cached_tokens=int(s.off), cow=cow_src is not None)
            if cow_src is not None:
                self._pending_cow.append(
                    (slot_idx, int(cow_src), int(fresh[0])))
                self.stats["cow_copies"] += 1
                self._ev.emit(
                    "block_cow", rid=req.rid, slot=slot_idx,
                    src_block=int(cow_src), dst_block=int(fresh[0]))
            self._temps[slot_idx] = req.temperature
            self._top_k[slot_idx] = (
                req.top_k if req.top_k is not None else self.cfg.vocab_size)
            self._top_p[slot_idx] = (
                req.top_p if req.top_p is not None else 1.0)
            self._keys[slot_idx] = self.device_step.prng_key(req.seed)
            inj = self._inject.get(req.rid)
            if inj is not None:
                # drain/resume: the admitted prompt carries the already-
                # emitted prefix; the carried key continues the stream
                if inj.get("key") is not None:
                    self._keys[slot_idx] = np.asarray(inj["key"], np.uint32)
                s.orig_prompt_len = int(inj["orig_prompt_len"])
                s.pre_gen = int(inj["pre_gen"])
            self._ev.emit(
                "request_admitted", rid=req.rid, slot=slot_idx,
                prompt_len=int(P), max_new_tokens=int(N), blocks=need,
                priority=req.priority,
                queue_wait_s=round(s.t_admit - t_submit, 6))
            admitted += 1
        self._apply_cow()
        return admitted

    def _apply_cow(self) -> None:
        """Flush this admission wave's copy-on-write list as ONE compiled
        block-copy call (NULL-padded fixed-width lanes).  The cache object
        is threaded through, so the copy is device-ordered before any
        subsequent prefill write to the copied block."""
        if not self._pending_cow:
            return
        src = np.zeros(self.num_slots, np.int32)
        dst = np.zeros(self.num_slots, np.int32)
        for slot, s_blk, d_blk in self._pending_cow:
            src[slot], dst[slot] = s_blk, d_blk
        self._pending_cow.clear()
        self.cache = self._cow_fn(self.cache, src, dst)
        self._cow_sigs.add(("cow", self.num_slots))

    # -------------------------------------------------------------------- ticks

    #: device calls dispatched so far, an engine's own count from 0: the
    #: ``call`` attr that ties a dispatch span to the ``tdp:engine.fetch``
    #: that waits for it.  (Set here and not in ``__init__``: the line
    #: numbers above the step builders are part of every compiled kernel's
    #: cache key, PERF.md section 6, PR 34.)
    _call = 0

    def _still(self, slots: List[Tuple[int, Tuple[int, float]]], state: str):
        """Of a dispatched call's ``slots`` (``(slot, (rid, t_admit))`` as
        it was built), those that are still their request's and in
        ``state`` when its results arrive, as ``(slot, its state)``: a slot
        retired, cancelled, preempted or requeued meanwhile, and whoever
        was admitted into it since, gets nothing of the call's."""
        for i, who in slots:
            s = self._slots[i]
            if s.state == state and (s.rid, s.t_admit) == who:
                yield i, s

    def _masked(self, state: str) -> np.ndarray:
        """Table rows for slots NOT in ``state`` zeroed (NULL block) so a
        phase's step can never touch another phase's cache blocks."""
        m = np.array([s.state == state for s in self._slots], bool)
        t = np.where(m[:, None], self._tables, 0).astype(np.int32)
        return m, t

    def _samp(self) -> Dict[str, np.ndarray]:
        return {"temperature": self._temps, "top_k": self._top_k,
                "top_p": self._top_p}

    def _sig(self, tokens: np.ndarray) -> tuple:
        return (tokens.shape, str(tokens.dtype), self.num_slots,
                self.max_blocks)

    def _first_call(self, kind: str, tokens: np.ndarray) -> Tuple[
            str, Dict[str, bool]]:
        """Count the call's signature.  Returns the dispatch span's
        ``program`` (``decode[64,1]``: the key of the call's compiled
        program in ``utils.profiling.op_scopes``) and the ``first=True``
        attr of the dispatch span and of the fetch after it on the one call
        of each signature that compiles or loads its program, else nothing:
        ``_called_sigs`` outlives :meth:`reset_metrics`, which forgets the
        counted ones."""
        sig = (kind,) + self._sig(tokens)
        (self._prefill_sigs if kind == "prefill" else self._decode_sigs).add(sig)
        program = f"{kind}[{','.join(map(str, tokens.shape))}]"
        if sig in self._called_sigs:
            return program, {}
        self._called_sigs.add(sig)
        return program, {"first": True}

    def _token_poisoned(self, tok: int) -> bool:
        """An out-of-range sampled token is the host-visible face of a
        poisoned logit row (NaN/garbage logits cannot be told apart from a
        legitimate argmax on the host, so chaos injects the sentinel the
        real failure would need anyway — see resilience/chaos.py)."""
        return not (0 <= tok < self.cfg.vocab_size)

    def _poisoned_token_recover(self, i: int, tok: int) -> None:
        s = self._slots[i]
        self.stats["faults_detected"] += 1
        self._ev.emit(
            "engine_fault_detected", fault="invalid_token", slot=i,
            rid=s.rid, token=int(tok), tick=self._tick)
        rid = self._requeue_slot(i)
        self.stats["faults_healed"] += 1
        self._ev.emit(
            "engine_recovered", fault="invalid_token", slot=i, rid=rid,
            action="requeued", tick=self._tick)

    def _prefill_batches(self, pre: List[int]) -> List[Tuple[Any, ...]]:
        """The prefilling slots ``pre`` packed into compact batches of
        ``prefill_width`` slots a dp group: ``[dp * W, chunk]`` rows,
        group g's at ``g*W..(g+1)*W`` (a slot's table indexes its own
        group's pool shard), as many batches as the fullest group needs.  A
        row with no slot is padding: NULL table, token 0, greedy, zero key,
        so it writes the NULL block only and nobody reads its output.
        Each batch is ``(slot_of, step_args)``: ``slot_of[r]`` is the slot
        that compact row r carries, -1 for padding."""
        W, C, G = self.prefill_width, self.chunk, self.slots_per_group
        groups = [[i for i in pre if i // G == g] for g in range(self.dp)]
        batches = []
        for c in range(max(-(-len(g) // W) for g in groups)):
            slot_of = np.full(self.dp * W, -1)
            for g, members in enumerate(groups):
                part = members[c * W:(c + 1) * W]
                slot_of[g * W:g * W + len(part)] = part
            live = slot_of >= 0
            slots = slot_of[live]

            def rows(src: np.ndarray, fill: Any = 0) -> np.ndarray:
                out = np.full(live.shape + src.shape[1:], fill, src.dtype)
                out[live] = src[slots]
                return out

            tokens = np.zeros((len(live), C), np.int32)
            offsets = np.zeros(len(live), np.int32)
            last_idx = np.zeros(len(live), np.int32)
            n_valid = np.zeros(len(live), np.int32)
            for r, i in zip(np.flatnonzero(live), slots):
                s = self._slots[i]
                sl = s.prompt[s.off:s.off + C]
                tokens[r, :len(sl)] = sl
                offsets[r] = s.off
                last_idx[r] = min(len(s.prompt) - 1 - s.off, C - 1)
                n_valid[r] = len(sl)
            samp = {"temperature": rows(self._temps),
                    "top_k": rows(self._top_k, self.cfg.vocab_size),
                    "top_p": rows(self._top_p, 1.0)}
            args = (tokens, rows(self._tables), offsets, last_idx, samp,
                    rows(self._keys))
            if self.state_model:
                # the slot whose state each row carries; a padding row
                # names none (num_slots: read clipped, written nowhere)
                args += (np.where(live, slot_of, self.num_slots).astype(
                    np.int32), n_valid)
            batches.append((slot_of, args))
        return batches

    def _prefill_calls(self, pre: List[int]) -> Callable[[], Tuple[
            np.ndarray, np.ndarray, Dict[int, np.ndarray]]]:
        """One ``chunk``-token slice for every slot of ``pre`` through the
        compiled step: ``ceil(n / W)`` calls of the ONE compact signature
        (:meth:`_prefill_batches`), dispatched and NOT waited for.  Returns
        their fetch: called once, behind whatever else the tick dispatches,
        it waits for the calls and gives the sampled tokens and the
        advanced keys by SLOT index (``[num_slots]``; rows of slots not in
        ``pre`` are zero) and, with ``record_routing``, each slot's piece
        of the record."""
        C = self.chunk
        with span("tdp:engine.build"):
            batches = self._prefill_batches(pre)
            program, first = self._first_call("prefill", batches[0][1][0])
            self._call += len(batches)
            # what the fetch says of the calls it waits for: the decode
            # call's build moves the engine's count on before it opens
            waits_for = {"call": self._call, **first}
            # tokens: the real prompt tokens of this tick's slices; rows:
            # what the compiled calls compute, padding included; call: the
            # LAST of the `calls` device calls this span dispatches, by the
            # engine's running count (the fetch that waits for them says
            # the same); state_slots (a state model): the slots whose state
            # the calls gather and scatter; sampled_rows: the slots that
            # asked for temperature > 0 (0: every call took _slot_sample's
            # greedy branch, no sort and no draw)
            attrs = dict(
                tokens=sum(
                    min(C, len(self._slots[i].prompt) - self._slots[i].off)
                    for i in pre),
                calls=len(batches),
                rows=sum(args[0].size for _, args in batches),
                sampled_rows=np.count_nonzero(self._temps[pre] > 0),
                rids=self._tick_prefill_rids, program=program, **waits_for)
            if self.state_model:
                attrs["state_slots"] = len(pre)
            if self._idx_topk:
                attrs.update(self._indexed_attrs(
                    np.concatenate([args[2] for _, args in batches]),
                    np.concatenate([args[-1] for _, args in batches])))
        if self.window:
            with span("tdp:engine.handon"):
                batches = [(slot_of, self._hand_on(args, slot_of, attrs))
                           for slot_of, args in batches]
        with span("tdp:engine.prefill", **attrs):
            # back to back: the pool is donated and chained call to call,
            # so a queued call holds its small inputs only
            self._note_program(attrs, self._step_fn, batches[0][1])
            outs = [self._dispatch(self._step_fn, args)
                    for _, args in batches]
        self.stats["prefill_calls"] += len(batches)

        def fetch():
            tok = np.zeros(self.num_slots, np.int32)
            keys = np.zeros_like(self._keys)
            routing: Dict[int, np.ndarray] = {}
            with span("tdp:engine.fetch", **waits_for):
                for (slot_of, args), out in zip(batches, outs):
                    live = slot_of >= 0
                    tok[slot_of[live]] = np.asarray(out[0])[live]
                    keys[slot_of[live]] = np.asarray(out[1])[live]
                    if len(out) > 2:  # expert layers: load stats ride along
                        self._absorb_moe_stats(*out[2:5])
                    if len(out) > 5:  # record_routing: the real positions' own
                        n_valid, by_row = args[-1], isinstance(out[5], tuple)
                        whole = None if by_row else np.asarray(out[5])
                        for r in np.flatnonzero(live):
                            piece = (np.asarray(out[5][r]) if by_row
                                     else whole[r])
                            routing[slot_of[r]] = piece[:n_valid[r]]
            return tok, keys, routing

        return fetch

    def _book_prefill(self, n_calls: int) -> None:
        """The events of a tick's ``n_calls`` prefill calls, once their
        tokens are on the host."""
        C, rids = self.chunk, self._tick_prefill_rids
        self._ev.emit("prefill_chunk", rids=rids, chunk=C, n_slots=len(rids),
                      calls=n_calls)
        if self.cp > 1:
            # modeled ring accounting (host math, ops/ring_paged.py): each
            # compiled call issued 4*(cp-1) unrolled ppermutes per layer
            # — the comm-ledger test prices the same count from HLO
            from ..ops.ring_paged import ring_chunk_bytes, ring_hops_per_chunk

            hops = n_calls * ring_hops_per_chunk(self.cfg.nlayers, self.cp)
            bts = n_calls * ring_chunk_bytes(
                nlayers=self.cfg.nlayers, cp=self.cp,
                batch=self.dp * self.prefill_width,
                kv_heads=self.cfg.block.kv_head_count,
                head_dim=self.cfg.block.head_dim, chunk=C,
                nb_local=self.num_blocks // self.cp,
                block_size=self.block_size,
                itemsize=jnp.dtype(self.cfg.dtype).itemsize)
            self.stats["cp_ring_hops"] += hops
            self.stats["cp_ring_bytes"] += bts
            self._ev.emit("cp_prefill_chunk", rids=rids, chunk=C,
                          cp=self.cp, sub_chunk=C // self.cp)
            self._ev.emit("cp_ring_hop", tick=self._tick, hops=hops,
                          bytes=bts)

    def _dispatch_prefill(self) -> Optional[Dict[str, Any]]:
        """One ``chunk``-token slice for EVERY prefilling slot, dispatched
        (:meth:`_prefill_calls`: only those slots' rows are computed) and
        left on the device: what :meth:`_land_prefill` takes once the
        tick's decode call is queued behind it.  None: nobody prefills."""
        pre = [i for i, s in enumerate(self._slots) if s.state == PREFILL]
        if not pre:
            return None
        self._tick_prefill_rids = [self._slots[i].rid for i in pre]
        # how many calls it took, by the engine's own count (a test stands
        # another function of the same results in for _prefill_calls)
        calls_before = self.stats["prefill_calls"]
        slots = [(i, (self._slots[i].rid, self._slots[i].t_admit))
                 for i in pre]
        fetch = self._prefill_calls(pre)
        return {"slots": slots, "fetch": fetch,
                "calls": self.stats["prefill_calls"] - calls_before}

    def _land_prefill(self, call: Optional[Dict[str, Any]]) -> None:
        """Fetch what a tick's prefill calls returned and book it.  Slots
        whose slice covers the last prompt row have sampled their first
        token (TTFT) and move to DECODE: the NEXT tick's decode call is
        their first, since this tick's was dispatched before this fetch."""
        if call is None:
            return
        tok, keys, routing = call["fetch"]()
        with span("tdp:engine.absorb"):
            self._book_prefill(call["calls"])
            self._walk_prefilled(call["slots"], tok, keys, routing)
        self.stats["prefill_chunks"] += 1

    def _walk_prefilled(self, slots: List[Tuple[int, Tuple[int, float]]],
                        tok: np.ndarray, keys: np.ndarray,
                        routing: Dict[int, np.ndarray]) -> None:
        """Book one fetched prefill slice a slot of ``slots`` (``(slot,
        (rid, t_admit))`` as the calls were dispatched: a slot that was
        cancelled, preempted or requeued meanwhile has its slice dropped,
        as ``run_ahead``'s flight drops a token)."""
        C = self.chunk
        if self.chaos is not None:
            tok = self.chaos.perturb_engine_tokens(self._tick, tok)
        now = time.perf_counter()
        for i, s in self._still(slots, PREFILL):
            if i in routing:  # record_routing
                s.routing.append(routing[i])
            s.off += C
            if s.off >= len(s.prompt):  # final slice: first token sampled
                if self._token_poisoned(int(tok[i])):
                    self._poisoned_token_recover(i, int(tok[i]))
                    continue
                self._keys[i] = keys[i]
                s.state = DECODE
                if self.prefix_cache:
                    # every FULL prompt block is now fully written: bind
                    # it to its chain hash so later admissions with the
                    # same prefix map it instead of re-prefilling (first
                    # registration wins; a COW copy of an already-
                    # registered block stays unregistered)
                    alloc = self._allocs[i // self.slots_per_group]
                    for bh, blk in zip(
                            chain_block_hashes(s.prompt, self.block_size),
                            s.blocks):
                        alloc.register(blk, bh)
                s.ttft_s = now - s.t_submit
                self._resolve_ttft(s.rid, s.ttft_s, int(s.req.priority))
                s.t_last = now
                self._lengths[i] = len(s.prompt)
                self._last_tok[i] = tok[i]
                s.generated.append(int(tok[i]))
                self._tick_emitted += 1
                self._maybe_retire(i, int(tok[i]), now)
                if s.state == DECODE and not self.hold_decode:
                    # not retired by its first token: it sat this tick's
                    # decode call out and joins the next
                    self.stats["late_joins"] += 1

    def _dispatch_decode(self) -> Optional[Dict[str, Any]]:
        """The tick's decode call (a speculative engine's verify call),
        built from the slots that are in DECODE now and dispatched behind
        the tick's prefill calls, whose results are still on the device: a
        slot whose prompt they end is not among them.  Returns the call for
        :meth:`_absorb_decode`: its outputs, its slots as ``(slot, (rid,
        t_admit))`` and what its fetch span says of it.  None: no slot
        decodes, or decoding is another replica's."""
        if self.hold_decode:
            # disaggregated prefill tier: decoding is another replica's
            # job — parked slots wait for the router's export
            return None
        if self.spec_k:
            return self._dispatch_verify()
        with span("tdp:engine.build"):
            built = self._build_decode(self._flight)
        if built is None:
            return None
        args, slots, attrs = built
        if self.window:
            with span("tdp:engine.handon"):
                args = self._hand_on(
                    args, np.where(args[7] > 0, np.arange(self.num_slots),
                                   -1), attrs)
        with span("tdp:engine.decode", **attrs):
            self._note_program(attrs, self._step_fn, args)
            out = self._dispatch(self._decode_fn, args)
        self.stats["decode_steps"] += 1
        self.stats["decode_slot_steps"] += len(slots)
        return {"out": out, "slots": slots,
                "waits_for": {k: attrs[k] for k in ("call", "first")
                              if k in attrs}}

    def _build_decode(self, flight: Optional[Dict[str, Any]]) -> Optional[
            Tuple[Tuple[Any, ...], List[Tuple[int, Tuple[int, float]]],
                  Dict[str, Any]]]:
        """The decode call's host side: ``(the compiled call's arguments,
        the decoding slots as (slot, (rid, t_admit)), the dispatch span's
        attrs)``; None when no slot decodes this tick.  ``call`` among the
        attrs is this device call by the engine's running count: the
        ``tdp:engine.fetch`` that waits for it (with ``run_ahead`` in the
        NEXT tick) carries the same."""
        mask, tables = self._masked(DECODE)
        ahead = np.zeros(self.num_slots, bool)
        if flight is not None:
            # run_ahead: the slots whose newest token is still on the device
            for i, s in self._still(flight["slots"], DECODE):
                if len(s.generated) + 1 >= s.req.max_new_tokens:
                    mask[i], tables[i] = False, 0  # that token is its last
                else:
                    ahead[i] = True
        n_active = int(mask.sum())
        if n_active == 0:
            return None
        tokens = np.where(mask & ~ahead, self._last_tok,
                          0).astype(np.int32)[:, None]
        offsets = np.where(mask, self._lengths + ahead, 0).astype(np.int32)
        last_idx = np.zeros(self.num_slots, np.int32)
        slots = [(int(i), (self._slots[i].rid, self._slots[i].t_admit))
                 for i in np.flatnonzero(mask)]
        self._tick_decode_rids = [who[0] for _, who in slots]
        args = (tokens, tables, offsets, last_idx, self._samp(), self._keys)
        if self.state_model:
            # row b is slot b: the state is updated where it lies, and a
            # masked slot (0 real positions) keeps its own
            args += (None, mask.astype(np.int32))
        if self.run_ahead:
            args += ((flight or self._no_flight)["out"][:2]
                     + (ahead.astype(np.int32),),)
            self.stats["ahead_rows"] += int(ahead.sum())
        self._call += 1
        program, first = self._first_call("decode", tokens)
        attrs = dict(slots=n_active, rids=self._tick_decode_rids,
                     live_tokens=int(offsets.sum()) + n_active,
                     sampled_rows=np.count_nonzero(self._temps > 0),
                     call=self._call, program=program, **first)
        if self._idx_topk:
            attrs.update(self._indexed_attrs(offsets, mask))
        return args, slots, attrs

    def _indexed_attrs(self, offsets: np.ndarray,
                       n_valid: np.ndarray) -> Dict[str, int]:
        """What a call's indexed attention layers each do, from its rows'
        offsets and real positions: ``indexed_positions`` (the (query,
        cached position) pairs the indexer scores) and
        ``selected_positions`` (those attention then reads: ``min(topk,
        context)`` a query); booked on the tick as well."""
        from ..ops.dsa_attention import position_counts

        pair = position_counts(offsets, n_valid, self._idx_topk)
        self._tick_dsa[0] += pair[0]
        self._tick_dsa[1] += pair[1]
        return {"indexed_positions": pair[0], "selected_positions": pair[1]}

    def _hand_on(self, args: Tuple[Any, ...], slot_of: np.ndarray,
                 attrs: Dict[str, Any]) -> Tuple[Any, ...]:
        """A call's window table, made just before its dispatch.  Row r of
        the call carries slot ``slot_of[r]`` (-1: none) from position
        ``offsets[r]`` for ``n_valid[r]`` real rows, and will write the
        table columns those fall in.  Where such a column names no block
        yet, it is HANDED one that lies wholly behind the window of the
        call's first row (a later row's window, and every later call's,
        starts later still): the host's table alone changes, old column
        NULL, new column the same block id; no device copy, no allocator
        traffic.  The table keeps absolute columns, so the kernel needs no
        ring arithmetic, and a call in flight keeps the table it was handed.
        A slot's blocks are the columns from its lowest live one on, so one
        look at the call's first and last column says whether any is short.

        Returns ``args`` with ``(tables, window tables)`` in the table's
        place; adds to the dispatch span's ``attrs`` (and the tick)
        ``window_positions``, the positions a window layer holds for the
        call's slots at its last row, beside ``live_tokens``, those the
        other layers hold (a decode span counts its own), and
        ``blocks_handed_on``; a prefill span also gets ``window_pairs`` and
        ``live_pairs``, the (row, key) pairs its real rows attend in a
        window layer (``min(window, position + 1)`` a row) and in a global
        one (``position + 1``): a decode row's are the positions held."""
        W, bs = self.window, self.block_size
        offsets, n_valid = args[2], args[7]
        live = np.flatnonzero((slot_of >= 0) & (n_valid > 0))
        slots, off, n = slot_of[live], offsets[live], n_valid[live]
        first = off // bs
        last = np.minimum((off + n - 1) // bs, self.max_blocks - 1)
        handed = 0
        for r in np.flatnonzero((self._wtables[slots, first] == 0)
                                | (self._wtables[slots, last] == 0)):
            row = self._wtables[slots[r]]
            want = [c for c in range(first[r], last[r] + 1) if not row[c]]
            behind = max(off[r] - W + 1, 0) // bs
            give = np.flatnonzero(row[:behind])[:len(want)]
            if len(give) < len(want):
                raise RuntimeError(
                    f"slot {slots[r]}: {len(want)} window blocks short at "
                    f"position {off[r]} and {len(give)} behind the window")
            row[want] = row[give]
            row[give] = 0
            handed += len(want)
        wtables = np.zeros((len(slot_of), self.max_blocks), np.int32)
        wtables[live] = self._wtables[slots]
        held = int(np.minimum(off + n, W).sum())
        attrs["window_positions"] = attrs.get("window_positions", 0) + held
        attrs["blocks_handed_on"] = attrs.get("blocks_handed_on", 0) + handed
        if "rows" in attrs:   # a prefill span: summed over the tick's calls
            end = (off + n).astype(np.int64)
            tri = lambda x: x * (x + 1) // 2   # sum of p + 1 over p < x
            inside = lambda x: tri(np.minimum(x, W)) + np.maximum(x - W, 0) * W
            for key, add in (("live_tokens", end.sum()),
                             ("live_pairs", (tri(end) - tri(end - n)).sum()),
                             ("window_pairs",
                              (inside(end) - inside(end - n)).sum())):
                attrs[key] = attrs.get(key, 0) + int(add)
        self._tick_window[0] += held
        self._tick_window[1] += handed
        self.stats["blocks_handed_on"] += handed
        return args[:1] + ((args[1], wtables),) + args[2:]

    def _absorb_decode(self, call: Optional[Dict[str, Any]]) -> None:
        """Fetch what one decode call returned and book it: every slot's
        token, key and chosen experts, its retirement.  With ``run_ahead``
        this is the call of the tick before, and a slot that was retired,
        preempted or requeued meanwhile has its token dropped."""
        if call is None:
            return
        if self.spec_k:
            return self._absorb_verified(call)
        out = call["out"]
        with span("tdp:engine.fetch", **call["waits_for"]) as sp:
            tok = np.asarray(out[0])
            keys = np.asarray(out[1])
            if len(out) > 2:  # expert layers: live load stats ride along
                self._absorb_moe_stats(*out[2:5], decode=True)
            if len(out) > 4:
                # a held range: the held experts THIS call touched, on the
                # span that names the call (its dispatch span closed before
                # it ran)
                sp.attrs["experts_touched"] = float(np.asarray(out[4])[2])
            routing = np.asarray(out[5]) if len(out) > 5 else None
        with span("tdp:engine.absorb"):
            if self.telemetry is not None:
                # run_ahead: the newest wrapped call is the one just
                # dispatched, and nobody waits for that here
                self.telemetry.end_step(active_slots=len(call["slots"]),
                                        wait=not self.run_ahead)
            if self.chaos is not None:
                tok = self.chaos.perturb_engine_tokens(self._tick, tok)
            now = time.perf_counter()
            still = list(self._still(call["slots"], DECODE))
            self.stats["flight_dropped"] += len(call["slots"]) - len(still)
            for i, s in still:
                if routing is not None:  # record_routing
                    s.routing.append(routing[i])
                if self._token_poisoned(int(tok[i])):
                    self._poisoned_token_recover(i, int(tok[i]))
                    continue
                self._keys[i] = keys[i]
                self._lengths[i] += 1
                self._last_tok[i] = tok[i]
                s.generated.append(int(tok[i]))
                self._tick_emitted += 1
                s.tpot_s.append(now - s.t_last)
                s.t_last = now
                self._maybe_retire(i, int(tok[i]), now)

    def _drop_flight(self) -> None:
        """``run_ahead``: forget the decode call in flight once every slot
        it was built from has left; its tokens are never fetched."""
        if self._flight is not None:
            self.stats["flight_dropped"] += len(self._flight["slots"])
            self._flight = None

    # ------------------------------------------------------ speculative decode

    def _draft(self, s: _SlotState) -> List[int]:
        """Host-side self-speculative drafter: prompt-lookup / n-gram
        continuation (no second model, no new weights).  Propose the
        ``spec_k`` tokens that followed the most recent earlier occurrence
        of the slot's last BIGRAM in its own history (prompt + generated),
        falling back to the last unigram, then to repeating the last
        token.  A bad draft costs nothing but acceptance — greedy
        verification is exact whatever this proposes."""
        hist = (list(int(t) for t in s.prompt) + s.generated)[-256:]
        K = self.spec_k
        cand: Optional[List[int]] = None
        if len(hist) >= 3:
            a, b = hist[-2], hist[-1]
            for j in range(len(hist) - 3, -1, -1):
                if hist[j] == a and hist[j + 1] == b:
                    cand = hist[j + 2:j + 2 + K]
                    break
        if not cand:
            last = hist[-1]
            for j in range(len(hist) - 2, -1, -1):
                if hist[j] == last:
                    cand = hist[j + 1:j + 1 + K]
                    break
        cand = list(cand or [])
        while len(cand) < K:
            cand.append(cand[-1] if cand else hist[-1])
        return cand[:K]

    def _dispatch_verify(self) -> Optional[Dict[str, Any]]:
        """The speculative decode tick: the drafter proposes a STATIC
        ``spec_k`` tokens per decoding slot, ONE compiled verify program
        scores all k+1 positions in a single paged-attention step, and
        the host walks the accept bits — the accepted draft prefix plus
        the model's own correction/bonus token advance the slot, a
        rejection truncates host-side (the stale KV tail is overwritten
        before it can ever be attended, exactly the
        ``speculative_generate`` argument).  Emits 1..k+1 tokens per slot
        per tick at one decode-signature — the decode latency floor
        broken without touching the compile-once contract.  This half
        drafts and dispatches; :meth:`_absorb_verified` fetches and walks,
        in the same tick (the next tick's drafts need its tokens)."""
        K = self.spec_k
        with span("tdp:engine.build"):
            mask, tables = self._masked(DECODE)
            n_active = int(mask.sum())
            tokens = np.zeros((self.num_slots, K + 1), np.int32)
            offsets = np.where(mask, self._lengths, 0).astype(np.int32)
        if n_active == 0:
            return None
        slots = []
        with span("tdp:engine.draft"):
            for i in np.flatnonzero(mask):
                s = self._slots[i]
                slots.append((int(i), (s.rid, s.t_admit)))
                tokens[i, 0] = self._last_tok[i]
                tokens[i, 1:] = self._draft(s)
        with span("tdp:engine.build"):  # the draft is a phase of its own
            rids = self._tick_decode_rids = [who[0] for _, who in slots]
            self._ev.emit("spec_draft", k=K, n_slots=len(rids), rids=rids)
            self._call += 1
            program, first = self._first_call("decode", tokens)
            waits_for = {"call": self._call, **first}
            samp = self._samp()
        with span("tdp:engine.decode", slots=n_active, rids=rids,
                  program=program, **waits_for):
            args = (tokens, tables, offsets, samp, self._keys)
            self._note_program({"program": program, **first},
                               self._verify_jit, args)
            self.cache, *out = self._verify_fn(self.params, self.cache, *args)
        return {"out": out, "slots": slots, "tokens": tokens,
                "waits_for": waits_for}

    def _absorb_verified(self, call: Dict[str, Any]) -> None:
        """Fetch one verify call and book it: every slot's accepted draft
        prefix and the model's own token behind it, for the slots the call
        was built from."""
        with span("tdp:engine.fetch", **call["waits_for"]):
            verify, accept, keys = (np.asarray(o) for o in call["out"])
        with span("tdp:engine.absorb"):
            self._walk_verified(call["slots"], call["tokens"], verify,
                                accept, keys)

    def _walk_verified(self, slots: List[Tuple[int, Tuple[int, float]]],
                       tokens: np.ndarray, verify: np.ndarray,
                       accept: np.ndarray, keys: np.ndarray) -> None:
        """Book one fetched verify call: the accepted draft prefix and the
        model's own token behind it for each of ``slots`` (``tokens``: what
        the call was handed, a slot's last token and its drafts)."""
        K, rids, n_active = self.spec_k, self._tick_decode_rids, len(slots)
        if self.telemetry is not None:
            self.telemetry.end_step(active_slots=n_active)
        if self.chaos is not None:
            verify = self.chaos.perturb_engine_tokens(self._tick, verify)
        now = time.perf_counter()
        emitted_total = accepted_total = 0
        for i, s in self._still(slots, DECODE):
            # accepted draft prefix, then the model's correction (or the
            # bonus token when every draft survived)
            emitted: List[int] = []
            for j in range(K):
                if accept[i, j]:
                    emitted.append(int(tokens[i, j + 1]))
                else:
                    emitted.append(int(verify[i, j]))
                    break
            else:
                emitted.append(int(verify[i, K]))
            self.stats["spec_drafted"] += K
            if self._token_poisoned(int(verify[i, 0])) or any(
                    self._token_poisoned(t) for t in emitted):
                self._poisoned_token_recover(i, int(verify[i, 0]))
                continue
            self._keys[i] = keys[i]
            req = s.req
            took, done, reason = 0, False, "max_tokens"
            for t in emitted:
                s.generated.append(t)
                took += 1
                if req.eos_id is not None and t == req.eos_id:
                    done, reason = True, "eos"
                    break
                if len(s.generated) >= req.max_new_tokens:
                    done = True
                    break
            self.stats["spec_accepted"] += max(0, took - 1)
            accepted_total += max(0, took - 1)
            emitted_total += took
            self._tick_emitted += took
            self._lengths[i] += took
            self._last_tok[i] = s.generated[-1]
            per_tok = (now - s.t_last) / took
            s.tpot_s.extend([per_tok] * took)
            s.t_last = now
            if done:
                self._finish_slot(i, reason, now)
        self._ev.emit("spec_verify", k=K, n_slots=len(rids),
                      emitted=emitted_total, accepted=accepted_total)
        self.stats["decode_steps"] += 1
        self.stats["decode_slot_steps"] += n_active

    # --------------------------------------------------------------- retirement

    def _maybe_retire(self, i: int, tok: int, now: float) -> None:
        s = self._slots[i]
        req = s.req
        done_eos = req.eos_id is not None and tok == req.eos_id
        # req.max_new_tokens is the budget remaining at THIS admission (a
        # resumed request's original total lives in the drain descriptor)
        done_len = len(s.generated) >= req.max_new_tokens
        if not (done_eos or done_len):
            return
        self._finish_slot(i, "eos" if done_eos else "max_tokens", now)

    def _finish_slot(self, i: int, reason: str, now: float) -> None:
        """Terminal slot exit (EOS / max-token / cancel): record, free
        blocks, reset — all the same tick.  Only completed requests
        (eos / max_tokens) contribute to the latency percentiles; a
        cancelled request's partial service would skew the SLO evidence."""
        s = self._slots[i]
        completed = reason in ("eos", "max_tokens")
        new_tokens = s.pre_gen + len(s.generated)
        self._finished_order.append(s.rid)
        self.finished[s.rid] = {
            "rid": s.rid,
            "tokens": np.concatenate(
                [s.prompt, np.asarray(s.generated, np.int32)]),
            "prompt_len": int(s.orig_prompt_len),
            "new_tokens": new_tokens,
            "reason": reason,
            "priority": int(s.req.priority),
            "resumed": s.pre_gen > 0,
            "ttft_s": s.ttft_s,
            "tpot_s": list(s.tpot_s),
            "t_submit": s.t_submit,
            "t_done": now,
        }
        if self.record_routing:
            # indexed attention (flat pieces, 14 KB a position): as fetched
            self.finished[s.rid]["routing"] = (
                s.routing if self._idx_topk else np.concatenate(s.routing))
        self._inject.pop(s.rid, None)
        self._ttft_pred.pop(s.rid, None)
        if completed:
            self._ttfts.append(s.ttft_s)
            self._tpots.extend(s.tpot_s)
            prio = int(s.req.priority)
            if s.ttft_s is not None:
                self._ttfts_by_prio.setdefault(prio, []).append(s.ttft_s)
            self._tpots_by_prio.setdefault(prio, []).extend(s.tpot_s)
            # SLO accounting: a request with no deadline meets by
            # definition; only deadline-meeting service counts as goodput
            met = (s.req.deadline_s is None
                   or (s.ttft_s is not None
                       and s.ttft_s <= s.req.deadline_s))
            row = self._slo_row(prio)
            row["completed"] += 1
            row["met" if met else "missed"] += 1
            if met:
                row["goodput_tokens"] += len(s.generated)
            self.stats["generated_tokens"] += len(s.generated)
            self._t_first = min(self._t_first, s.t_submit)
            self._t_last_done = max(self._t_last_done, now)
            self._ev.emit(
                "request_retired", rid=s.rid, slot=i, reason=reason,
                new_tokens=new_tokens, priority=prio,
                ttft_s=round(s.ttft_s, 6) if s.ttft_s is not None else None)
        else:
            self.stats["cancelled"] += 1
            self._ev.emit(
                "request_cancelled", rid=s.rid, slot=i, where="slot",
                emitted_tokens=new_tokens, blocks_freed=len(s.blocks))
        self._allocs[i // self.slots_per_group].free(s.blocks)
        if self.window:
            self._walloc.free(s.wblocks)
        self._clear_slot_rows(i)
        s.reset()

    def cancel(self, rid: int) -> bool:
        """Retire request ``rid`` wherever it is — queued (removed, no
        service) or in-flight (slot retired, blocks freed THIS tick, the
        partial output kept in ``finished[rid]`` with reason
        ``cancelled``).  Returns False when the rid is unknown or already
        terminal."""
        for idx, (req, _t) in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[idx]
                self.stats["cancelled"] += 1
                self._finished_order.append(rid)
                self.finished[rid] = {
                    "rid": rid,
                    "tokens": np.asarray(req.tokens, np.int32),
                    "prompt_len": len(req.tokens),
                    "new_tokens": 0,
                    "reason": "cancelled",
                    "priority": int(req.priority),
                    "resumed": False,
                    "ttft_s": None,
                    "tpot_s": [],
                    "t_submit": _t,
                    "t_done": time.perf_counter(),
                }
                self._inject.pop(rid, None)
                self._ttft_pred.pop(rid, None)
                self._ev.emit("request_cancelled", rid=rid, where="queued",
                              emitted_tokens=0, blocks_freed=0)
                return True
        for i, s in enumerate(self._slots):
            if s.state != FREE and s.rid == rid:
                self._finish_slot(i, "cancelled", time.perf_counter())
                return True
        return False

    # ------------------------------------------------------------ invariant audit

    def audit(self, heal: bool = True) -> Dict[str, Any]:
        """Per-tick block-conservation invariant check, per dp group:

        - every ACTIVE slot's device-bound table row must equal its owned
          block list (padded with NULL) — a drifted row means the next
          compiled step would read/write another request's cache;
        - every owned block must be live in its group's allocator
          (``BlockAllocator.audit``'s ``unknown`` is a use-after-free)
          with refcount-weighted ownership: the number of slots
          referencing a block must EQUAL its refcount (legitimate
          prefix sharing keeps them equal; a mismatch is a scatter
          collision or a lost reference);
        - every refcounted allocator block must be owned by some slot
          (``orphaned`` is a leak);
        - an inactive slot's row must be all-NULL;
        - ``unique in_use + cached + n_free == n_usable`` (conservation
          under sharing — refcount-0 cached blocks are accounted, not
          leaked).

        A model with window layers is audited twice, once a pool: the
        window pool's violations carry ``pool: "window"``, and a slot's row
        of ITS table must name exactly the slot's window blocks, at
        whichever columns (they move as they are handed on).

        ``heal=True`` (the engine's in-``step()`` mode) repairs what it
        finds — poisoned slots are retired + requeued for replay, orphaned
        blocks reclaimed, stale rows zeroed — bracketed by
        ``engine_fault_detected`` / ``engine_recovered`` events.  With
        ``heal=False`` it only reports (the test-side conservation probe).
        Pure host arithmetic: no device call, no new signature.
        """
        violations: List[Dict[str, Any]] = []
        poisoned: List[int] = []
        stale_rows: List[Tuple[np.ndarray, int]] = []
        orphans: List[Tuple[BlockAllocator, List[int]]] = []
        pools = [({}, self._allocs, self._tables, "blocks")]
        if self.window:
            pools.append(({"pool": "window"}, [self._walloc], self._wtables,
                          "wblocks"))
        for pool, allocs, tables, owned in pools:
            for g, alloc in enumerate(allocs):
                # the window pool is one group: a mesh refuses it
                n = self.num_slots // len(allocs)
                lo, hi = g * n, (g + 1) * n
                owned_lists = []
                for i in range(lo, hi):
                    s = self._slots[i]
                    row, mine = tables[i], getattr(s, owned)
                    if s.state == FREE:
                        if row.any():
                            violations.append(
                                {"kind": "stale_table_row", "slot": i, **pool})
                            stale_rows.append((tables, i))
                        continue
                    owned_lists.append(mine)
                    if pool:   # handed on: at whichever columns
                        ok = np.sort(row[row != 0]).tolist() == sorted(mine)
                    else:
                        want = np.zeros(self.max_blocks, np.int32)
                        want[:len(mine)] = mine
                        ok = np.array_equal(row, want)
                    if not ok:
                        violations.append({
                            "kind": "table_mismatch", "slot": i,
                            "rid": s.rid, "row": row.tolist(),
                            "owned": list(mine), **pool})
                        poisoned.append(i)
                rep = alloc.audit(owned_lists)
                for b in rep["shared"]:
                    # refcount-weighted ownership violated: more (or
                    # fewer) slots reference the block than its refcount
                    # records
                    refs = [i for i in range(lo, hi)
                            if b in getattr(self._slots[i], owned)]
                    violations.append({
                        "kind": "shared_block", "block": int(b),
                        "group": g, "slots": refs, **pool})
                    for i in refs:
                        if i not in poisoned:
                            poisoned.append(i)
                if rep["orphaned"]:
                    violations.append({
                        "kind": "orphaned_blocks", "group": g,
                        "blocks": rep["orphaned"], **pool})
                    orphans.append((alloc, rep["orphaned"]))
                for b in rep["unknown"]:
                    violations.append({
                        "kind": "unowned_block", "group": g,
                        "block": int(b), **pool})
                    for i in range(lo, hi):
                        if (b in getattr(self._slots[i], owned)
                                and i not in poisoned):
                            poisoned.append(i)
                if not rep["conserved"]:
                    violations.append({
                        "kind": "conservation", "group": g,
                        "in_use": rep["in_use"], "n_free": rep["n_free"],
                        "n_usable": alloc.n_usable, **pool})
        if violations and heal:
            self.stats["faults_detected"] += len(violations)
            self._ev.emit(
                "engine_fault_detected", fault="invariant_audit",
                tick=self._tick, n_violations=len(violations),
                kinds=sorted({v["kind"] for v in violations}),
                slots=sorted(poisoned))
            requeued = [self._requeue_slot(i) for i in sorted(poisoned)]
            for tables, i in stale_rows:
                tables[i] = 0
            reclaimed = 0
            for alloc, blocks in orphans:
                reclaimed += len(alloc.reclaim(blocks))
            self.stats["faults_healed"] += len(violations)
            self._ev.emit(
                "engine_recovered", fault="invariant_audit",
                tick=self._tick, requeued_rids=requeued,
                blocks_reclaimed=reclaimed)
        return {"ok": not violations, "violations": violations}

    # -------------------------------------------------------------- driver API

    @property
    def n_busy(self) -> int:
        return sum(s.state != FREE for s in self._slots)

    def step(self) -> Dict[str, int]:
        """One engine tick: chaos hook -> invariant audit (heal) -> expiry
        -> admit (with preemption) -> one prefill slice -> one decode
        step.  Returns what happened (all zeros = idle).

        **Every device call of the tick is dispatched before any call of
        the tick is fetched**: build and dispatch the prefill calls P, build
        and dispatch the decode call D behind them (the pool and the state
        are donated and chained on the device, so D needs nothing of P's on
        the host), absorb ``run_ahead``'s flight (the decode call of the
        tick before), fetch P and walk its slots, and without ``run_ahead``
        fetch D and absorb it.  The device runs P and D back to back while
        P's results travel, are walked, and the next tick audits, admits and
        builds.  D is built from the slots that are in DECODE when the
        tick's calls begin, so a slot whose prompt ends in P takes its first
        decode step in the NEXT tick's decode call, its token from the host
        like every newly decoding slot's: the same tokens as before, the
        time to the first token unmoved (it is P's), the SECOND token one
        tick later, once a request, and the slot held one tick longer.
        ``stats['late_joins']`` counts those slots and
        ``stats['ticks_queued_whole']`` the ticks that had both kinds of
        call (``serving_summary()['tick_accounting']`` and every tick
        record carry both).  A first token that ends its request (EOS,
        ``max_new_tokens == 1``) retires at the walk and joins nothing; a
        slot cancelled, preempted or requeued between P's dispatch and its
        fetch has its slice dropped.  ``hold_decode`` has no D and fetches
        P at once; a speculative tick's verify call takes D's place and is
        fetched in its own tick (the next draft needs its tokens), behind
        P's fetch.

        The tick is a ``tdp:engine.tick`` span with one child span a phase
        (``tdp:engine.audit`` / ``sched`` / ``prefill`` / ``draft`` /
        ``decode`` / ``fetch``; utils/profiling.py: the process-wide ring,
        and the profiler's clock under a capture).  Their summed durations
        are the :data:`TICK_PHASES` accounting (``host`` is the remainder);
        three more children divide that remainder in the ring and are no
        phases (``tdp:engine.build`` before a dispatch, ``absorb`` from a
        fetch's end to the end of the slot walk, ``record`` behind the
        decode phase).  The accounting is recorded on ``tick_records``,
        emitted as an ``engine_tick`` timeline event (with the measured
        ``spans`` and per-rid attribution, the raw material of the
        request-lifecycle trace — serving/tracing.py), and exported live
        through ``metrics_sink`` under the ``serving_metrics`` schema.  All
        of it is wall-clock bookkeeping around the SAME two compiled calls:
        zero extra device dispatches, ``decode_signatures`` stays 1.  A
        ``tdp:engine.fetch`` names the call it waits for (``call``): the
        prefill calls' fetch opens after the decode call's dispatch span has
        closed and still carries the PREFILL calls' count."""
        with span("tdp:engine.tick", tick=self._tick + 1) as tick:
            self._tick += 1
            self._tick_prefill_rids = []
            self._tick_decode_rids = []
            self._tick_emitted = 0
            self._tick_moe = dict.fromkeys(_MOE_CALL_STATS, 0.0)
            self._tick_dsa = [0, 0]
            self._tick_window = [0, 0]
            before = {k: self.stats[k] for k in _TICK_COUNTS}
            if self.chaos is not None:
                self.chaos.before_engine_tick(self._tick, self)
            self.stats["audits"] += 1
            with span("tdp:engine.audit"):
                self.audit(heal=True)
            with span("tdp:engine.sched"):
                expired = self._expire_queue(time.perf_counter())
                admitted = self._admit()
            # every call of the tick is on its way before any is waited for
            prefill = self._dispatch_prefill()
            decode = self._dispatch_decode()
            prefilled = len(prefill["slots"]) if prefill else 0
            decoded = len(decode["slots"]) if decode else 0
            queued_whole = bool(prefill and decode)
            self.stats["ticks_queued_whole"] += queued_whole
            if self.run_ahead:
                # the call of the tick before: the device has it done
                decode, self._flight = self._flight, decode
                self._absorb_decode(decode)
                self._land_prefill(prefill)
            else:
                self._land_prefill(prefill)
                self._absorb_decode(decode)
            with span("tdp:engine.record"):
                busy = self.n_busy
                if not busy:
                    self._drop_flight()  # run_ahead: nobody left to take it
                self._occ_sum += busy / self.num_slots
                util = float(np.mean(
                    [a.utilization() for a in self._allocs]))
                self._util_sum += util
                if self.window:
                    self._wutil_sum += self._walloc.utilization()
                self._occ_ticks += 1
                if (self.snapshot_every
                        and self._tick % self.snapshot_every == 0):
                    self._ev.emit(
                        "slots_snapshot", tick=self._tick, busy=busy,
                        queued=len(self.queue),
                        pool_utilization=round(util, 4))
                if self.watchdog is not None:
                    self.watchdog.beat(self._tick)
                t_end = time.perf_counter()
                if decoded:
                    dt = t_end - tick.t0
                    self._tick_ewma = (
                        dt if self._tick_ewma is None
                        else 0.8 * self._tick_ewma + 0.2 * dt)
                self._record_tick(tick, t_end, admitted=admitted,
                                  expired=expired, prefilled=prefilled,
                                  decoded=decoded, busy=busy, util=util,
                                  queued_whole=queued_whole,
                                  counts={k: self.stats[k] - n
                                          for k, n in before.items()})
        return {"admitted": admitted, "prefill_slots": prefilled,
                "decode_slots": decoded, "busy": busy, "expired": expired}

    def _record_tick(self, tick: span, t_end: float, *, admitted: int,
                     expired: int, prefilled: int, decoded: int, busy: int,
                     util: float, queued_whole: bool,
                     counts: Dict[str, int]) -> None:
        """The tick-level accounting record: the phase decomposition,
        summed from the tick's child spans (the residual ``host`` phase is
        everything the six phase spans did not cover — queue sorts, table
        rewrites, retirement walks, the telemetry's record; the ``build``,
        ``absorb`` and ``record`` children time it piece by piece and are
        no phases), plus the per-tick gauges.  Appended to ``tick_records`` (bounded), emitted
        as an ``engine_tick`` event WHEN THE TICK DID WORK (idle polls
        stay off the timeline), and written to ``metrics_sink`` every
        tick under :data:`SERVING_METRICS_SCHEMA`."""
        st = self.stats
        t_start = tick.t0
        phases = dict.fromkeys(TICK_PHASES, 0.0)
        for _, _, name, c0, c1, _ in tick.children:
            phase = name.rpartition(".")[2]
            if phase in phases:
                phases[phase] += c1 - c0
        phases = {k: round(v, 9) for k, v in phases.items()}
        phases["host"] = round(
            max(0.0, (t_end - t_start) - sum(phases.values())), 9)
        rec = {
            "tick": self._tick,
            "t_start": t_start,
            "t_end": t_end,
            "tick_s": round(t_end - t_start, 9),
            "phases": phases,
            "queue_depth": len(self.queue),
            "busy": busy,
            "admitted": admitted,
            "expired": expired,
            "prefill_slots": prefilled,
            "decode_slots": decoded,
            "batch_util": round(decoded / self.num_slots, 4),
            "pool_util": round(util, 4),
            "emitted_tokens": self._tick_emitted,
            # the decode call was dispatched before the prefill calls were
            # fetched
            "queued_whole": queued_whole,
            # late_joins: slots whose prompt ended here and wait for the
            # next decode call; run_ahead: ahead_rows, the rows of this
            # tick's decode call that took their token from the device, and
            # flight_dropped, in-flight tokens dropped on arrival
            **counts,
            "prefix_hit_rate": round(
                st["prefix_cached_tokens"] / st["prefix_prompt_tokens"], 4)
            if st["prefix_prompt_tokens"] else 0.0,
            "spec_accept_rate": round(
                st["spec_accepted"] / st["spec_drafted"], 4)
            if st["spec_drafted"] else 0.0,
        }
        if self.state_model:
            rec.update(self._tick_moe)
        if self._idx_topk:
            rec.update(zip(("indexed_positions", "selected_positions"),
                           self._tick_dsa))
        if self.window:
            rec.update(zip(("window_positions", "blocks_handed_on"),
                           self._tick_window))
        self.tick_records.append(rec)
        if admitted or expired or prefilled or decoded or busy or self.queue:
            self._ev.emit(
                "engine_tick", spec=bool(self.spec_k),
                prefill_rids=list(self._tick_prefill_rids),
                decode_rids=list(self._tick_decode_rids),
                spans=[[c[2], c[3], c[4]] for c in tick.children], **rec)
        if self.metrics_sink is not None:
            try:
                self.metrics_sink.write(serving_metrics_record(rec))
            except OSError:
                pass  # full disk / read-only path: engine work matters more

    def run_until_idle(
        self,
        max_ticks: int = 100_000,
        stop: Optional[Any] = None,
        persist_path: Optional[str] = None,
    ) -> None:
        """Drain the queue and every in-flight slot.  ``stop`` is a
        :class:`~..utils.preemption.GracefulShutdown` (or anything with a
        ``requested`` flag): when it trips mid-loop the engine performs a
        preemption-safe :meth:`drain` (persisting to ``persist_path`` when
        given) instead of finishing the work — the SLURM SIGTERM
        contract."""
        while self.queue or self.n_busy:
            if stop is not None and getattr(stop, "requested", False):
                self.drain(persist_path=persist_path)
                return
            self.step()
            if self._tick > max_ticks:
                raise RuntimeError(
                    f"engine did not drain within {max_ticks} ticks "
                    f"(queued={len(self.queue)}, busy={self.n_busy})")

    # ----------------------------------------------------------- drain / resume

    def _descriptor(self, req: Request, *, emitted: Sequence[int],
                    key: Optional[np.ndarray],
                    orig_prompt_len: int, pre_gen: int) -> Dict[str, Any]:
        """One restartable request descriptor.  ``prompt`` is the ORIGINAL
        prompt; ``emitted`` every token produced so far (a resume prefix
        the admitted prompt carried, plus this engine's output);
        ``key`` the carried PRNG key that samples the NEXT token."""
        prompt = [int(t) for t in req.tokens]
        pre = prompt[orig_prompt_len:]
        # req.max_new_tokens is the budget REMAINING at this admission;
        # the descriptor records the original total so a chain of
        # drain/resume cycles never inflates or shrinks the request
        return {
            "prompt": prompt[:orig_prompt_len],
            "emitted": [int(t) for t in pre] + [int(t) for t in emitted],
            "max_new_tokens": int(req.max_new_tokens) + pre_gen,
            "temperature": float(req.temperature),
            "top_k": req.top_k,
            "top_p": req.top_p,
            "eos_id": req.eos_id,
            "seed": int(req.seed),
            "priority": int(req.priority),
            "deadline_s": req.deadline_s,
            "orig_rid": int(req.rid),
            "key": None if key is None else [int(v) for v in key],
        }

    def drain(self, persist_path: Optional[str] = None) -> Dict[str, Any]:
        """Preemption-safe shutdown: stop admitting (subsequent submits
        are shed with reason ``draining``) and unwind every in-flight slot
        and queued request into restartable descriptors — prompt, emitted
        tokens, sampling params, the carried PRNG key.  Blocks are freed
        and slots reset, so the engine is idle afterwards (``run_ahead``'s
        call in flight is forgotten: its tokens were not booked, and the
        replay samples them again from the carried key).

        ``persist_path`` writes the payload as JSON plus a
        ``<path>.manifest.json`` SHA-256 sidecar (the ``ckpt_guard``
        verify-before-restore idiom — :meth:`resume` refuses bytes that
        rotted on disk).  Returns the payload either way; a restarted
        engine replays it with :meth:`resume`."""
        self._needs_snapshots("drain")
        self._draining = True
        descs: List[Dict[str, Any]] = []
        n_inflight = 0
        for i, s in enumerate(self._slots):
            if s.state == FREE:
                continue
            n_inflight += 1
            # an in-flight DECODE slot's carried key samples its next
            # token; a PREFILL slot has emitted nothing, so the admission
            # key (from the seed / a prior injection) reproduces it
            key = (np.array(self._keys[i], copy=True)
                   if s.state == DECODE else None)
            inj = self._inject.get(s.rid)
            if key is None and inj is not None and inj.get("key") is not None:
                key = np.asarray(inj["key"], np.uint32)
            descs.append(self._descriptor(
                s.req, emitted=s.generated, key=key,
                orig_prompt_len=s.orig_prompt_len, pre_gen=s.pre_gen))
            alloc = self._allocs[i // self.slots_per_group]
            self._release_blocks(alloc, s.blocks)
            self._clear_slot_rows(i)
            self._inject.pop(s.rid, None)
            self._ttft_pred.pop(s.rid, None)
            s.reset()
        self._drop_flight()  # run_ahead: the descriptors hold what was booked
        n_queued = len(self.queue)
        for req, _t in self.queue:
            inj = self._inject.pop(req.rid, None)
            self._ttft_pred.pop(req.rid, None)
            descs.append(self._descriptor(
                req, emitted=[],
                key=(np.asarray(inj["key"], np.uint32)
                     if inj and inj.get("key") is not None else None),
                orig_prompt_len=(inj["orig_prompt_len"] if inj
                                 else len(req.tokens)),
                pre_gen=inj["pre_gen"] if inj else 0))
        self.queue = []
        payload = {"schema": DRAIN_SCHEMA, "n": len(descs),
                   "requests": descs}
        if persist_path is not None:
            self._persist_drain(persist_path, payload)
        self._ev.emit(
            "engine_drained", n_inflight=n_inflight, n_queued=n_queued,
            persisted=persist_path is not None, path=persist_path)
        return payload

    @staticmethod
    def _persist_drain(path: str, payload: Dict[str, Any]) -> None:
        import json
        import os

        from ..resilience.ckpt_guard import _sha256

        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        manifest = {
            "schema": DRAIN_SCHEMA + "-manifest",
            "size": os.path.getsize(path),
            "sha256": _sha256(path),
        }
        mtmp = path + ".manifest.json.tmp"
        with open(mtmp, "w") as f:
            json.dump(manifest, f)
        os.replace(mtmp, path + ".manifest.json")

    def resume(self, source: Any) -> List[int]:
        """Re-submit a drain payload (a dict from :meth:`drain`, or a path
        it persisted — verified against its SHA-256 manifest BEFORE
        parsing, the ``ckpt_guard`` contract).  Each in-flight descriptor
        is replayed as prompt + emitted-prefix through the ordinary
        chunked prefill with its carried key injected, so the token stream
        continues exactly where the drained engine stopped (temp-0:
        exact-trajectory; sampled: same key stream).  Returns the new
        rids, in descriptor order."""
        self._needs_snapshots("resume")
        if isinstance(source, str):
            source = self._load_drain(source)
        if not isinstance(source, dict) or source.get("schema") != DRAIN_SCHEMA:
            raise ValueError(
                f"not a {DRAIN_SCHEMA} payload: "
                f"{type(source).__name__}/{(source or {}).get('schema')!r}")
        self._draining = False
        rids: List[int] = []
        for d in source["requests"]:
            emitted = [int(t) for t in d.get("emitted") or []]
            remaining = int(d["max_new_tokens"]) - len(emitted)
            req = Request(
                tokens=[int(t) for t in d["prompt"]] + emitted,
                max_new_tokens=max(1, remaining),
                temperature=float(d.get("temperature", 0.0)),
                top_k=d.get("top_k"),
                top_p=d.get("top_p"),
                eos_id=d.get("eos_id"),
                seed=int(d.get("seed", 0)),
                priority=int(d.get("priority", 0)),
                deadline_s=d.get("deadline_s"),
            )
            rid = self.submit(req)
            # the flow link the request trace renders across an engine
            # restart: the new instance names the one it continues
            self._ev.emit(
                "request_resumed", rid=rid,
                orig_rid=int(d.get("orig_rid", -1)),
                emitted_tokens=len(emitted),
                shed=rid in self.rejected)
            if rid in self.rejected:
                rids.append(rid)
                continue
            if emitted or d.get("key") is not None:
                self._inject[rid] = {
                    "key": (np.asarray(d["key"], np.uint32)
                            if d.get("key") is not None else None),
                    "orig_prompt_len": len(d["prompt"]),
                    "pre_gen": len(emitted),
                }
            self.stats["resumed"] += 1
            rids.append(rid)
        return rids

    # ------------------------------------------------- cross-replica migration

    def prefix_lookup(self, tokens: Sequence[int]) -> int:
        """Prompt tokens of ``tokens`` already RESIDENT in this engine's
        prefix cache (the longest content-hash-chained full-block match,
        capped the way admission caps it: a whole-prompt hit still
        recomputes its last token).  0 with the cache off — the router's
        affinity signal, a pure host read with no side effects."""
        if not self.prefix_cache:
            return 0
        hashes = self._prefix_hashes(tokens)
        if not hashes:
            return 0
        n_hit = max(len(a.match(hashes)) for a in self._allocs)
        return min(n_hit * self.block_size, max(0, len(tokens) - 1))

    def decode_slots(self) -> List[Tuple[int, int]]:
        """``(rid, slot)`` for every slot in the DECODE phase — what a
        disaggregating router scans after a prefill tick to find requests
        whose prefill just completed (first token sampled, KV fully
        written) and are ready to hand off."""
        return [(s.rid, i) for i, s in enumerate(self._slots)
                if s.state == DECODE]

    def export_slot(self, rid: int) -> Tuple[Dict[str, Any], Any]:
        """Unwind one DECODE-state slot into a migration descriptor — the
        drain descriptor (prompt, emitted tokens, sampling state, carried
        PRNG key) EXTENDED with the device-side KV location: the slot's
        block list, its committed length, and ``n_live`` (blocks holding
        real KV — positions ``0..length-1``; trailing table blocks are
        only budget).  Returns ``(desc, cache)`` where ``cache`` is the
        engine's CURRENT pool, the very buffer and not a copy: it is valid
        as a ``migrate_blocks`` source UNTIL THIS ENGINE'S NEXT DEVICE CALL
        (a step, a verify step, a copy-on-write), which donates the pool
        and leaves this handle deleted; reading it then raises.  Until
        then the exported blocks hold the request's KV even though the
        allocator has them back, since only a device call writes the
        pool.  So the copy out of it (``Router._handoff``: begin, fetch
        and deliver of every transport, and the bounce back into this
        engine) has to be dispatched before this engine steps again, and
        a caller that wants the bytes for longer copies them out first
        (``ChunkedWireTransport.fetch`` does).  With ``run_ahead`` the pool
        is the one the decode call in flight hands back (a read of it waits
        for that call), and the slot's token in flight is dropped when it
        arrives: the descriptor holds what was booked, and the importer's
        first step computes that token again.  The slot is released
        immediately (blocks freed refcount-aware, rows cleared) — the
        request now lives only in the descriptor, which the router must
        either import somewhere or resume (never both: the
        block-conservation audit spans both allocators)."""
        self._needs_snapshots("KV migration (export_slot)")
        for i, s in enumerate(self._slots):
            if s.state == DECODE and s.rid == rid:
                break
        else:
            raise ValueError(
                f"rid {rid} is not a decoding slot (only DECODE-state "
                f"requests carry migratable KV — queued requests move "
                f"KV-free via drain descriptors)")
        length = int(self._lengths[i])
        desc = self._descriptor(
            s.req, emitted=s.generated,
            key=np.array(self._keys[i], copy=True),
            orig_prompt_len=s.orig_prompt_len, pre_gen=s.pre_gen)
        desc.update({
            "length": length,
            "blocks": [int(b) for b in s.blocks],
            "n_live": -(-length // self.block_size),
            "t_submit": s.t_submit,
            "ttft_s": s.ttft_s,
            "tpot_s": [float(t) for t in s.tpot_s],
        })
        cache = self.cache  # the copy source, until the next device call
        alloc = self._allocs[i // self.slots_per_group]
        self._release_blocks(alloc, s.blocks)
        self._clear_slot_rows(i)
        self._inject.pop(s.rid, None)
        self._ttft_pred.pop(s.rid, None)
        s.reset()
        self.stats["migrated_out"] += 1
        # the src half of the cross-replica trace link: this instance
        # ends here, and the importer's ``request_imported`` names the
        # instance that continues it
        self._ev.emit(
            "request_exported", rid=rid, length=length,
            n_live=desc["n_live"],
            emitted_tokens=len(desc.get("emitted") or []))
        return desc, cache

    def import_slot(self, desc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """Admit an :meth:`export_slot` descriptor directly into the
        DECODE phase — no prefill: the KV content arrives by
        ``migrate_blocks`` instead.  Finds a free slot, maps the longest
        RESIDENT prefix of the full context (prompt + emitted, content-
        hash chained — equal hash ⇒ equal KV, the prefix-cache argument)
        via ``share`` so warm migrations only ship the tail, allocates
        the remainder, and writes the slot rows (table, length, last
        token, sampling params, carried key).  Shared blocks are safe
        because every future write lands at positions ``>= length`` —
        always past the matched full blocks.  Migrated full blocks are
        registered so later same-prefix imports share instead of copying.

        Returns ``{rid, slot, blocks, n_shared, n_live}`` — the caller
        must copy src blocks ``[n_shared:n_live]`` onto dst blocks
        ``[n_shared:n_live]`` (``migrate_blocks``) and install the
        returned cache BEFORE this engine's next step.  ``None`` = no
        capacity (free slot or blocks), nothing partially admitted."""
        self._needs_snapshots("KV migration (import_slot)")
        emitted = [int(t) for t in desc.get("emitted") or []]
        if not emitted:
            raise ValueError(
                "import_slot needs an emitted prefix (a request with no "
                "sampled token has no decode state — resume() it instead)")
        if desc.get("key") is None:
            raise ValueError("import_slot descriptor lacks the carried key")
        prompt_full = [int(t) for t in desc["prompt"]] + emitted
        remaining = int(desc["max_new_tokens"]) - len(emitted)
        if remaining < 1:
            raise ValueError(
                f"descriptor has no budget left ({desc['max_new_tokens']} "
                f"total, {len(emitted)} emitted) — it should have retired")
        req = Request(
            tokens=prompt_full,
            max_new_tokens=remaining,
            temperature=float(desc.get("temperature", 0.0)),
            top_k=desc.get("top_k"),
            top_p=desc.get("top_p"),
            eos_id=desc.get("eos_id"),
            seed=int(desc.get("seed", 0)),
            priority=int(desc.get("priority", 0)),
            deadline_s=desc.get("deadline_s"),
        )
        if len(prompt_full) + remaining > self.max_ctx:
            raise ValueError(
                f"context {len(prompt_full)} + remaining {remaining} "
                f"exceeds max_ctx {self.max_ctx}")
        # the committed KV length: the LAST emitted token's KV has not
        # been written yet (the next decode step writes it at position
        # ``length`` before attending — the engine's own accounting:
        # lengths == admitted_prompt + generated - 1 while decoding)
        length = int(desc["length"])
        if length != len(prompt_full) - 1:
            raise ValueError(
                f"descriptor length {length} inconsistent with context "
                f"{len(prompt_full)} (expect length == context - 1: the "
                f"pending token's KV is not written yet)")
        need = self._blocks_needed(req)
        n_live = -(-length // self.block_size)
        # affinity match over the WRITTEN context only: the pending
        # token's position has no KV, so its (partial or full) block must
        # never be taken from the cache
        hashes = self._prefix_hashes(prompt_full[:length])
        now = time.perf_counter()
        for i, s in enumerate(self._slots):
            if s.state != FREE:
                continue
            alloc = self._allocs[i // self.slots_per_group]
            hit = alloc.match(hashes) if hashes else []
            for b in hit:
                alloc.share(b)
            fresh = alloc.alloc(need - len(hit))
            if fresh is None:
                for b in hit:
                    alloc.free([b])
                continue
            evicted = alloc.pop_evicted()
            blocks = hit + fresh
            rid = self._next_rid
            self._next_rid += 1
            self._seq[rid] = rid
            s.state, s.rid = DECODE, rid
            s.req = dataclasses.replace(req, rid=rid)
            s.blocks = blocks
            s.prompt = np.asarray(prompt_full, np.int32)
            s.off = length
            s.generated = []
            s.t_submit = float(desc.get("t_submit", now))
            s.t_admit = s.t_last = now
            s.ttft_s = desc.get("ttft_s")
            s.tpot_s = [float(t) for t in desc.get("tpot_s") or []]
            s.orig_prompt_len = len(desc["prompt"])
            s.pre_gen = len(emitted)
            self._tables[i] = 0
            self._tables[i, :need] = blocks
            self._lengths[i] = length
            self._last_tok[i] = emitted[-1]
            self._temps[i] = req.temperature
            self._top_k[i] = (
                req.top_k if req.top_k is not None else self.cfg.vocab_size)
            self._top_p[i] = req.top_p if req.top_p is not None else 1.0
            self._keys[i] = np.asarray(desc["key"], np.uint32)
            if self.prefix_cache:
                # migrated FULL blocks now hold KV for their chain hashes:
                # register so the next same-prefix import shares instead
                # of copying (first registration wins, as in prefill)
                for j, bh in enumerate(hashes):
                    if j >= len(hit):
                        alloc.register(blocks[j], bh)
                self.stats["prefix_prompt_tokens"] += length
                if hit:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_cached_tokens"] += (
                        len(hit) * self.block_size)
            if evicted:
                self.stats["cache_evictions"] += len(evicted)
                self._ev.emit(
                    "cache_evict", tick=self._tick, n_blocks=len(evicted),
                    group=i // self.slots_per_group)
            self.stats["migrated_in"] += 1
            # the dst half of the trace link: a fresh instance opening
            # straight in DECODE (no queue, no prefill — the KV arrives
            # by migrate_blocks), naming the src-engine rid it continues
            self._ev.emit(
                "request_imported", rid=rid,
                orig_rid=int(desc.get("orig_rid", -1)), length=length,
                n_shared=len(hit), n_live=n_live,
                emitted_tokens=len(emitted))
            return {"rid": rid, "slot": i, "blocks": list(blocks),
                    "n_shared": len(hit), "n_live": n_live}
        return None

    def abort_import(self, rid: int, n_valid: int = 0) -> None:
        """Unwind an :meth:`import_slot` admission whose KV never arrived
        (the migration transport died between import and ``deliver``).
        Blocks past ``n_valid`` hold garbage — their content hashes (the
        import optimistically registered migrated full blocks) are
        DROPPED before release, so a later same-prefix import can never
        ``share`` a block the wire never filled; valid (shared) blocks
        release refcount-aware as usual.  The slot returns to FREE with
        rows cleared — as if the import never happened.  The request
        itself lives on in the router's descriptor (re-prefill
        fallback)."""
        for i, s in enumerate(self._slots):
            if s.state != FREE and s.rid == rid:
                break
        else:
            raise ValueError(f"abort_import: rid {rid} holds no slot")
        alloc = self._allocs[i // self.slots_per_group]
        for b in s.blocks[n_valid:]:
            alloc._drop_hash(int(b))
        self._release_blocks(alloc, s.blocks)
        self._clear_slot_rows(i)
        self._seq.pop(s.rid, None)
        self._inject.pop(s.rid, None)
        self._ttft_pred.pop(s.rid, None)
        s.reset()
        self.stats["imports_aborted"] += 1
        self._ev.emit("import_aborted", rid=rid, n_valid=int(n_valid))

    def steal_queued(self, max_n: int) -> List[Dict[str, Any]]:
        """Pop up to ``max_n`` queued requests off the TAIL of the
        priority order (youngest of the lowest class — the requests that
        would wait longest here) into drain-style restartable descriptors
        for KV-free cross-replica migration: the router ``resume()``s
        them on a less-loaded replica with exact-parity replay (the PR-9
        drain/resume contract).  Injection state (a previously resumed
        request's carried key/prefix) travels in the descriptor."""
        out: List[Dict[str, Any]] = []
        while self.queue and len(out) < max_n:
            req, _t = self.queue.pop()
            inj = self._inject.pop(req.rid, None)
            self._ttft_pred.pop(req.rid, None)
            out.append(self._descriptor(
                req, emitted=[],
                key=(np.asarray(inj["key"], np.uint32)
                     if inj and inj.get("key") is not None else None),
                orig_prompt_len=(inj["orig_prompt_len"] if inj
                                 else len(req.tokens)),
                pre_gen=inj["pre_gen"] if inj else 0))
            self.stats["migrated_out"] += 1
        return out

    @staticmethod
    def _load_drain(path: str) -> Dict[str, Any]:
        import json
        import os

        from ..resilience.ckpt_guard import CheckpointCorruptError, _sha256

        mpath = path + ".manifest.json"
        if os.path.exists(mpath):
            with open(mpath) as f:
                manifest = json.load(f)
            size = os.path.getsize(path)
            if size != manifest.get("size"):
                raise CheckpointCorruptError(
                    f"drain payload {path}: size {size} != manifest "
                    f"{manifest.get('size')}")
            digest = _sha256(path)
            if digest != manifest.get("sha256"):
                raise CheckpointCorruptError(
                    f"drain payload {path}: sha256 mismatch")
        with open(path) as f:
            return json.load(f)

    # ------------------------------------------------------------------ metrics

    def reset_metrics(self) -> None:
        """Zero the serving metrics (the bench's warmup/measure split);
        compiled steps, pool, and queue state are untouched."""
        self.stats = {"decode_steps": 0, "prefill_chunks": 0,
                      "prefill_calls": 0, "decode_slot_steps": 0,
                      "generated_tokens": 0,
                      "shed": 0, "expired": 0, "cancelled": 0,
                      "preempted": 0, "resumed": 0, "faults_detected": 0,
                      "faults_healed": 0, "audits": 0,
                      "prefix_hits": 0, "prefix_cached_tokens": 0,
                      "prefix_prompt_tokens": 0, "cow_copies": 0,
                      "cache_evictions": 0,
                      "spec_drafted": 0, "spec_accepted": 0,
                      "migrated_in": 0, "migrated_out": 0,
                      "imports_aborted": 0,
                      "cp_ring_hops": 0, "cp_ring_bytes": 0,
                      "blocks_handed_on": 0,
                      "ticks_queued_whole": 0,
                      **dict.fromkeys(_TICK_COUNTS, 0),
                      **dict.fromkeys(_MOE_CALL_STATS, 0.0)}
        self._decode_sigs: set = set()
        self._prefill_sigs: set = set()
        self._cow_sigs: set = set()
        self._ttfts: List[float] = []
        self._tpots: List[float] = []
        self._ttfts_by_prio: Dict[int, List[float]] = {}
        self._tpots_by_prio: Dict[int, List[float]] = {}
        #: bounded per-tick accounting records (serving/tracing.py)
        self.tick_records: collections.deque = collections.deque(
            maxlen=self.tick_history)
        #: unresolved admission-time TTFT predictions, rid -> {est, raw}
        self._ttft_pred: Dict[int, Dict[str, float]] = {}
        self._calib_by_prio: Dict[int, List[float]] = {}
        self._calib_n = 0
        self._slo_by_prio: Dict[int, Dict[str, int]] = {}
        self._tick = 0
        self._occ_sum = self._util_sum = self._wutil_sum = 0.0
        self._occ_ticks = 0
        self._t_first = float("inf")
        self._t_last_done = 0.0
        self.finished = {}
        self.rejected = {}
        self._finished_order = []
        self._rejected_order = []
        # live MoE expert-load accumulators (MoE families only): summed
        # per-expert routed-token counts and the mean drop rate over the
        # measured steps — serving_summary()['moe'] / moe_imbalance()
        self._moe_expert_tokens: Optional[np.ndarray] = None
        self._moe_dropped_sum = 0.0
        self._moe_steps = 0
        for a in self._allocs + [self._walloc] * bool(self.window):
            a.peak_in_use = a.in_use

    def _absorb_moe_stats(self, et, dr, share=None,
                          decode: bool = False) -> None:
        """Fold one step's expert-load stats into the accumulators.
        ``et``: [groups, E] per-dp-group routed-token counts (groups = 1
        without a mesh), ``dr``: [groups] drop rates.  ``share`` (a held
        range of experts): ``[rows routed, rows on held experts, held
        experts touched, expert layers that ran batched]`` of the call,
        into ``stats`` and the tick."""
        if share is not None:
            routed, held, touched, batched = (
                float(v) for v in np.asarray(share))
            layers = float(self.cfg.pattern.count("E"))
            booked = {"moe_rows_routed": routed, "moe_rows_held": held,
                      "moe_layers_run": layers, "moe_layers_batched": batched}
            if decode:
                booked["experts_touched"] = touched
            else:
                booked["prefill_moe_layers_run"] = layers
                booked["prefill_moe_layers_batched"] = batched
            for k, v in booked.items():
                self.stats[k] += v
                self._tick_moe[k] += v
        et = np.asarray(et, np.float64).sum(axis=0)
        if self._moe_expert_tokens is None:
            self._moe_expert_tokens = et
        else:
            self._moe_expert_tokens += et
        self._moe_dropped_sum += float(np.mean(np.asarray(dr)))
        self._moe_steps += 1

    def moe_imbalance(self) -> float:
        """Live expert-load imbalance (``max/mean - 1`` over the summed
        per-expert counts; 0.0 when balanced, unknown, or not a MoE
        model) — the signal the Router weighs into a MoE replica's load
        index."""
        if self._moe_expert_tokens is None:
            return 0.0
        from ..obs.aggregate import moe_load_stats

        return float(moe_load_stats(self._moe_expert_tokens)["imbalance"])

    # ------------------------------------------------------------------ report

    def serving_summary(self) -> Dict[str, Any]:
        """The RUNREPORT ``serving`` section (``Telemetry.record_serving``
        attaches it; ``validate_runreport`` checks it).  On top of the
        PR-5 aggregates: per-priority TTFT/TPOT percentiles, the
        shed/preempt/expire/cancel counters, the fault-audit evidence,
        and the ``healthy | degraded | overloaded`` verdict — overloaded
        when demand was refused (shed/expired), degraded when the engine
        had to preempt or heal faults to keep serving, healthy otherwise.
        """
        span = self._t_last_done - self._t_first
        completed = sum(
            1 for f in self.finished.values()
            if f["reason"] in ("eos", "max_tokens"))
        peak_util = max(a.peak_in_use for a in self._allocs) / (
            self._allocs[0].n_usable)
        st = self.stats
        # the verdict cites its evidence: which metric tripped it, with
        # the counts (validate_runreport cross-checks the consistency)
        if st["shed"] + st["expired"] > 0:
            verdict = "overloaded"
            basis = (f"demand refused: shed={st['shed']}, "
                     f"expired={st['expired']}")
            evidence = {"shed": st["shed"], "expired": st["expired"]}
        elif st["preempted"] + st["faults_detected"] > 0:
            verdict = "degraded"
            basis = (f"served by degrading: preempted={st['preempted']}, "
                     f"faults_detected={st['faults_detected']}")
            evidence = {"preempted": st["preempted"],
                        "faults_detected": st["faults_detected"]}
        else:
            verdict = "healthy"
            basis = "no shed/expired demand, no preemptions, no faults"
            evidence = {}
        priorities = {
            str(p): {
                "completed": len(self._ttfts_by_prio.get(p, [])),
                "ttft_s": percentiles(self._ttfts_by_prio.get(p, [])),
                "tpot_s": percentiles(self._tpots_by_prio.get(p, [])),
            }
            for p in sorted(
                set(self._ttfts_by_prio) | set(self._tpots_by_prio))
        }
        # --- SLO: per-priority deadline attainment + goodput.  Demand =
        # completed + shed + expired (a refused request's deadline was
        # not met, however principled the refusal); goodput counts only
        # tokens of deadline-meeting requests.
        slo_prios: Dict[str, Any] = {}
        met_total = demand_total = goodput_tokens = 0
        for p in sorted(self._slo_by_prio):
            row = dict(self._slo_by_prio[p])
            demand = row["completed"] + row["shed"] + row["expired"]
            row["attainment"] = (
                round(row["met"] / demand, 4) if demand else None)
            slo_prios[str(p)] = row
            met_total += row["met"]
            demand_total += demand
            goodput_tokens += row["goodput_tokens"]
        calib_prios = {
            str(p): {
                "n": len(errs),
                **{f"rel_err_{k}": round(v, 4)
                   for k, v in percentiles(errs, ps=(50, 95)).items()},
            }
            for p, errs in sorted(self._calib_by_prio.items())
        }
        slo = {
            "goodput_tokens": goodput_tokens,
            "goodput_tok_s": (
                goodput_tokens / span if span > 0 and completed else 0.0),
            "attainment": (
                round(met_total / demand_total, 4) if demand_total else None),
            "priorities": slo_prios,
            # predicted-vs-actual TTFT calibration: per-priority relative
            # error of the estimate admission used, plus the EWMA bias
            # factor estimate_ttft feeds back into itself — the
            # per-replica feedback signal a router consumes
            "calibration": {
                "n": self._calib_n,
                "bias": (round(self._ttft_bias, 6)
                         if self._ttft_bias is not None else None),
                "pending": len(self._ttft_pred),
                "priorities": calib_prios,
            },
        }
        # --- tick-level accounting roll-up (full per-tick records live
        # on tick_records / the engine_tick timeline)
        ticks = list(self.tick_records)
        phases_mean = {}
        if ticks:
            for name in TICK_PHASES:
                phases_mean[name] = float(
                    np.mean([t["phases"].get(name, 0.0) for t in ticks]))
        tick_accounting = {
            "ticks": len(ticks),
            "mean_tick_s": (float(np.mean([t["tick_s"] for t in ticks]))
                            if ticks else 0.0),
            "phases_mean_s": {k: round(v, 9)
                              for k, v in phases_mean.items()},
            # the tick's order: of the ticks that made prefill calls AND a
            # decode call (by the records kept), those whose decode call
            # was dispatched before the prefill calls were fetched (every
            # one, by the engine's count), and the slots that took their
            # first decode step a tick after their prompt's last slice
            "ticks_prefill_and_decode": sum(
                1 for t in ticks if t["prefill_slots"] and t["decode_slots"]),
            "ticks_queued_whole": st["ticks_queued_whole"],
            # and with run_ahead the rows of the decode calls that took their
            # token from the device, the in-flight tokens dropped on arrival
            **{k: st[k] for k in _TICK_COUNTS},
        }
        # --- live expert-load (MoE families): moe_load_stats over the
        # accumulated per-expert routed-token counts.  The overflow
        # tripwire fires here, where the stats are concrete.
        moe = None
        if self.cfg.moe_experts:
            from ..obs.aggregate import moe_load_stats
            from ..parallel.moe import check_expert_overflow

            dropped = (self._moe_dropped_sum / self._moe_steps
                       if self._moe_steps else 0.0)
            moe = moe_load_stats(
                self._moe_expert_tokens
                if self._moe_expert_tokens is not None
                else [0.0] * (self.cfg.moe.held_range[1] if self.state_model
                              else self.cfg.moe_experts),
                dropped_rate=dropped,
            )
            check_expert_overflow(moe, where="serving_summary")
        return {
            "requests": {"completed": completed, "queued": len(self.queue),
                         "in_flight": self.n_busy,
                         "shed": st["shed"], "expired": st["expired"],
                         "cancelled": st["cancelled"],
                         "preempted": st["preempted"],
                         "resumed": st["resumed"],
                         # cross-replica migration traffic (router tier):
                         # requests that left with their KV (export_slot /
                         # steal_queued) and arrived with it (import_slot)
                         "migrated_in": st["migrated_in"],
                         "migrated_out": st["migrated_out"],
                         "imports_aborted": st["imports_aborted"]},
            "generated_tokens": st["generated_tokens"],
            "tokens_per_sec": (
                st["generated_tokens"] / span
                if span > 0 and completed else 0.0),
            "ttft_s": percentiles([t for t in self._ttfts if t is not None]),
            "tpot_s": percentiles(self._tpots),
            "priorities": priorities,
            "verdict": verdict,
            "verdict_basis": basis,
            "verdict_evidence": evidence,
            "slo": slo,
            "tick_accounting": tick_accounting,
            "faults": {"detected": st["faults_detected"],
                       "healed": st["faults_healed"],
                       "audits": st["audits"]},
            "drained": self._draining,
            "slot_occupancy": {
                "mean": (self._occ_sum / self._occ_ticks
                         if self._occ_ticks else 0.0),
                "num_slots": self.num_slots,
            },
            "kv_pool": {
                "num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "dp_groups": self.dp,
                "mean_utilization": (self._util_sum / self._occ_ticks
                                     if self._occ_ticks else 0.0),
                "peak_utilization": peak_util,
                # the obs memory section cross-checks these two: the
                # device buffer actually held vs what the shape math says
                # init_paged_kv should have allocated
                "pool_bytes": pool_bytes(self.cache),
                # of which the indexer's keys (indexed attention; else 0)
                "index_bytes": index_bytes(self.cache),
                "pool_bytes_expected": expected_pool_bytes(
                    self.cfg, self.dp * self.num_blocks, self.block_size,
                    quantized=self.kv_quant,
                    window_blocks=self.window_blocks),
                # a model with window layers: of which their pool, with its
                # own blocks and utilisation (the figures above count the
                # pool that keeps every position)
                **({"window": {
                    "window": self.window,
                    "num_blocks": self.window_blocks,
                    "blocks_per_slot": self.window_reach,
                    "pool_bytes": window_bytes(self.cache),
                    "mean_utilization": (self._wutil_sum / self._occ_ticks
                                         if self._occ_ticks else 0.0),
                    "peak_utilization": (self._walloc.peak_in_use
                                         / self._walloc.n_usable),
                    "blocks_handed_on": st["blocks_handed_on"],
                }} if self.window else {}),
            },
            # which attention implementation the compiled programs traced
            # (docs/serving.md "Paged attention kernel"): 'pallas' walks
            # the block table in-kernel, 'gather' is the parity oracle
            "attn_impl": self.attn_impl,
            # ring paged prefill (cp_axis engines only): CP width, the
            # chunks that rode the ring, and the modeled ring wire volume
            # — obs/report.py validates the block's schema
            **({"long_context": {
                "cp": self.cp,
                "cp_axis": self.cp_axis,
                "max_ctx": self.max_ctx,
                "chunk": self.chunk,
                "prefill_chunks": st["prefill_chunks"],
                "prefill_calls": st["prefill_calls"],
                "ring_hops": st["cp_ring_hops"],
                "ring_bytes": st["cp_ring_bytes"],
            }} if self.cp_axis is not None else {}),
            **({"moe": moe} if moe is not None else {}),
            "decode_steps": st["decode_steps"],
            "prefill_chunks": st["prefill_chunks"],
            "prefill_calls": st["prefill_calls"],
            "decode_batch_mean": (
                st["decode_slot_steps"] / st["decode_steps"]
                if st["decode_steps"] else 0.0),
            # serving fast path (prefix cache + speculative decode):
            # fraction of admitted prompt tokens served from resident
            # blocks, and fraction of proposed draft tokens the verify
            # step accepted — both 0.0 when the feature is off/unused
            "prefix_hit_rate": (
                st["prefix_cached_tokens"] / st["prefix_prompt_tokens"]
                if st["prefix_prompt_tokens"] else 0.0),
            "spec_accept_rate": (
                st["spec_accepted"] / st["spec_drafted"]
                if st["spec_drafted"] else 0.0),
            "prefix_cache": {
                "enabled": self.prefix_cache,
                "hits": st["prefix_hits"],
                "cached_tokens": st["prefix_cached_tokens"],
                "cow_copies": st["cow_copies"],
                "evictions": st["cache_evictions"],
                "cached_blocks": sum(a.n_cached for a in self._allocs),
                "cow_signatures": len(self._cow_sigs),
            },
            "spec": {"k": self.spec_k, "drafted": st["spec_drafted"],
                     "accepted": st["spec_accepted"]},
            # compile-once evidence: distinct device-call signatures the
            # engine issued (must be 1 per phase however many requests of
            # whatever shapes were served — priorities, preemptions,
            # faults, and drains included)
            "decode_signatures": len(self._decode_sigs),
            "prefill_signatures": len(self._prefill_sigs),
        }
