"""Append-only structured event log — the run's timeline.

Subsumes the print-based side channels (``utils/preemption.py`` signal
prints, ``tools/debug_nan.py`` NaN reports): instead of a line on stderr
that evaporates, a structured record lands in memory (always) and in a
JSONL file (when a path/sink is attached), with both wall-clock and
monotonic timestamps plus the emitting process index — enough to interleave
events from several hosts after the fact.

Every kind the package emits is declared in :data:`EVENT_KINDS` below —
the central registry ``tests/test_repo_lint.py`` checks call sites
against, so a typo'd kind fails CI instead of silently vanishing from the
timeline.  (User code may emit free-form kinds; the registry governs the
package only.)

==================  =====================================================
``run_start/end``   session boundaries (Telemetry emits these)
``compile``         first compilation of a wrapped step
``recompile``       a wrapped step saw a NEW input signature — the silent
                    throughput killer Telemetry exists to catch
``checkpoint_save`` / ``checkpoint_restore``
``preemption``      a termination signal arrived (GracefulShutdown); the
                    record carries the grace deadline when configured
``nan_watchdog``    a ``nan_guard``-ed function produced non-finite output
``loss_scale``      dynamic loss-scale change
``straggler``       a host's step time is an outlier (obs.aggregate)
``overlap_configure``  XLA latency-hiding flag outcome (dist.overlap)
``xla_trace_start/stop``  scoped jax.profiler capture window (obs.trace)
==================  =====================================================

Resilience kinds (``torchdistpackage_tpu.resilience``, PR 4):

==================  =====================================================
``fault_injected``  the chaos harness fired a declared fault
``ckpt_retry``      a checkpoint I/O attempt failed and is being retried
``ckpt_quarantine`` a corrupt checkpoint step was renamed aside; resume
                    walked back to the newest good step
``rollback``        the self-healing loop rewound to a good checkpoint
                    after divergence (non-finite / loss-spike)
``resilience_abort``  retry budget spent — the run aborted cleanly with
                    a RUNREPORT ``resilience`` verdict
``hang_suspected`` / ``hang_resolved`` / ``hang_abort``  watchdog
                    heartbeat-gap escalation
``desync_detected`` cross-host consistency check found disagreement
                    (step / config hash / code hash / RNG / param sum)
==================  =====================================================

Memory kinds (``obs.mem_ledger`` + Telemetry, PR 6):

==================  =====================================================
``mem_snapshot``    periodic live/peak HBM sample from the one
                    ``memory_stats`` reader (``mem_ledger.live_memory``)
``oom_risk``        a live sample or the end-of-run memory verdict
                    crossed the OOM-risk line (peak >= 95% of capacity)
==================  =====================================================

Numerics kinds (``obs.numerics`` + Telemetry, PR 7):

==================  =====================================================
``numerics_alert``  a step's training-dynamics stats crossed a health
                    threshold (grad explosion/vanishing, update ratio out
                    of band, non-finite loss/grads); emitted on entering
                    the bad state by ``Telemetry.end_step`` and by
                    ``ResilientLoop`` BEFORE it decides to roll back —
                    the alert precedes the ``rollback`` on the timeline
``nan_block_located``  ``tools.debug_nan.find_nan_block`` walked the
                    model and found the first block producing non-finite
                    values (record carries the block name + bad paths)
==================  =====================================================

Compression kinds (``dist/compressed.py`` + the parallel families, PR 8):

==================  =====================================================
``compress_policy`` ``grad_compress='auto'`` scored each grad leaf's
                    collective through ``CommModel.predict_compressed``
                    while building a train step; the record carries the
                    per-leaf compress/exact choices with both predictions
                    (the ``compression`` RUNREPORT section reads it —
                    ``obs.comm_model.compression_report``)
==================  =====================================================

Serving kinds (``torchdistpackage_tpu.serving``, PR 5):

==================  =====================================================
``request_admitted``  a queued request took a free slot (blocks
                    allocated; record carries the queue wait)
``prefill_chunk``   one chunked-prefill slice ran for the prefilling
                    slots (the admission path that never stalls decodes)
``request_retired`` EOS / max-token completion — slot and blocks freed;
                    the record carries the request's TTFT
``slots_snapshot``  periodic occupancy + KV-pool utilization sample
==================  =====================================================

Serving-under-stress kinds (``serving/engine.py``, PR 9 — the overload /
fault half of the lifecycle; docs/serving.md "Serving under stress"):

==========================  =============================================
``request_preempted``       a higher-priority request evicted this slot:
                            blocks freed, accumulated output discarded,
                            request requeued for prompt replay
``request_shed``            admission refused at the door — bounded queue
                            full, estimated TTFT past the deadline, or
                            the engine is draining (record = the
                            structured rejection verdict)
``request_expired``         a queued request's deadline passed before a
                            slot freed; removed without service
``request_cancelled``       ``cancel(rid)`` retired the request (queued
                            or in-flight; blocks freed same tick)
``engine_fault_detected``   the per-tick invariant audit (block
                            conservation, table/ownership agreement) or
                            the sampled-token validity check found a
                            poisoned slot / leaked block
``engine_recovered``        the fault was healed: poisoned slots retired
                            + requeued, orphaned blocks reclaimed, the
                            rest of the batch untouched
``engine_drained``          ``drain()`` unwound the queue + in-flight
                            slots into restartable descriptors
                            (preemption-safe shutdown)
==========================  =============================================

Serving fast-path kinds (``serving/engine.py``, PR 10 — prefix cache +
speculative decoding; docs/serving.md "Prefix cache" / "Speculative
decoding"):

==========================  =============================================
``prefix_hit``              admission mapped a resident shared prefix
                            into the new slot's table (record carries
                            the cached token count and whether the last
                            block was copy-on-written)
``block_cow``               a whole-prompt cache hit scheduled a
                            copy-on-write of its final block (src/dst
                            block ids; the copy is one fixed-signature
                            compiled program per admission wave)
``spec_draft``              the host drafter proposed ``spec_k`` tokens
                            for every decoding slot this tick
``spec_verify``             the compiled verify step judged the drafts:
                            record carries tokens emitted vs drafts
                            accepted (the accept-rate evidence)
``cache_evict``             allocator pressure evicted refcount-0 cached
                            blocks (LRU) to cover a fresh allocation
==========================  =============================================

Serving observability kinds (``serving/engine.py`` + ``serving/tracing.py``,
PR 11 — request-lifecycle tracing + tick accounting; docs/serving.md
"Serving observability"):

==========================  =============================================
``request_submitted``       a request entered ``submit()`` (rid assigned)
                            — the anchor of the lifecycle trace's
                            ``queued`` span, emitted before any
                            shed/admission decision
``request_resumed``         ``resume()`` re-submitted a drain descriptor;
                            the record carries ``orig_rid``, the flow
                            link a Perfetto request track follows across
                            an engine restart
``engine_tick``             one engine tick's host-side accounting:
                            per-phase durations (audit / sched / prefill
                            / draft / decode / fetch / host), ``spans``
                            (the measured ``[name, t0, t1]`` of each
                            ``tdp:engine.*`` phase span), queue
                            depth, slot occupancy, batch + pool
                            utilization, live hit/accept rates, and the
                            per-rid prefill/decode attribution the
                            request trace is assembled from (emitted
                            only for ticks that did work)
==========================  =============================================

Multi-replica router kinds (``serving/router.py``, PR 15 — prefix-affinity
routing, prefill/decode disaggregation, cross-replica KV migration;
docs/serving.md "Multi-replica routing and disaggregation"):

==========================  =============================================
``request_routed``          the router placed a submit on a replica:
                            record carries the replica index, its
                            resident-prefix affinity (tokens), the
                            replica's biased TTFT estimate, and the
                            fallback rank (0 = first choice; >0 = a
                            better-ranked replica shed it first)
``request_migrated``        a request moved between replicas — queued
                            (``rebalance`` / ``evacuation``: KV-free
                            drain-descriptor resume, exact-parity
                            replay) or in-flight (``prefill_handoff``:
                            the disaggregation path, KV travels by
                            ``blocks_migrated``)
``replica_degraded``        the router observed a replica degrading
                            (fault counter moved, or new shed/expired
                            demand = the overloaded verdict) and what it
                            did about it (observed / rebalance /
                            evacuate)
``blocks_migrated``         one cross-pool KV migration ran: src/dst
                            replica, blocks copied vs prefix-shared on
                            arrival, wire bytes, and the comm-model
                            pricing verdict (int8 wire iff the model
                            approved the DCN-crossing leg)
==========================  =============================================

Fleet-observability kinds (``serving/router.py``, PR 17 — the router
decision ledger; docs/serving.md "Fleet observability").  Every
placement the fleet makes is attributable to exactly one of these
records, which carry the INPUTS the decision was made from, not just
the outcome:

==========================  =============================================
``route_decision``          one ``Router.submit`` decision, shed or
                            placed: the full per-replica candidate table
                            (affinity tokens, biased TTFT estimate,
                            load, role) in the order it was ranked, the
                            chosen replica, the replicas that refused
                            first (fallthrough, with their rejection
                            reasons), and the outcome
``handoff_decision``        one disaggregation handoff decision: the
                            import-candidate table (arrival affinity,
                            load, slot/block capacity), the chosen
                            decode replica, and the outcome (``handoff``
                            / ``deferred`` when no target had capacity /
                            ``bounced`` when the import raced away and
                            the request went back to its source)
``rebalance_decision``      one KV-free rebalance decision: what
                            triggered it (``overloaded`` demand /
                            ``watermark`` spread / ``manual``), the
                            per-replica queue depths it saw, the spread,
                            and how many requests it stole and landed
``replica_up``              a replica entered rotation (``set_alive``;
                            record carries the reason — the autoscaler's
                            seam)
``replica_down``            a replica left rotation: ``set_alive`` or an
                            evacuation (reason ``manual`` /
                            ``faults_detected`` / policy-specific)
``request_exported``        an engine unwound a DECODE slot into a
                            migration descriptor (``export_slot``) — the
                            src half of the cross-replica trace link
``request_imported``        an engine admitted a migration descriptor
                            straight into DECODE (``import_slot``);
                            ``orig_rid`` names the src-engine instance
                            it continues — the dst half of the link
==========================  =============================================

Auto-sharding planner kinds (``dist/autoplan.py``, PR 13):

==========================  =============================================
``plan_selected``           the planner chose a plan: record carries the
                            plan key, its modeled step time, and the
                            candidate/pruned counts (the RUNREPORT
                            ``autoplan`` section is the full audit)
``plan_rejected_oom``       a candidate's modeled per-device resident
                            bytes (``MemoryModel.estimate``) crossed the
                            OOM-risk line — pruned BEFORE any compile
==========================  =============================================

Zero-bubble pipeline kinds (``parallel/pipeline_parallel/zero_bubble.py``,
PR 14 — emitted at schedule-build (trace) time, once per compile):

==========================  =============================================
``zb_wgrad_deferred``       the ZB schedule queued its per-microbatch
                            wgrad work items (x, g, dx) instead of fusing
                            them into the backward wavefront — record
                            carries the unit and queue-slot counts
``zb_cooldown_filled``      the schedule's tick accounting: main-scan vs
                            wgrad-drain tick counts plus the modeled zb
                            and 1f1b bubble fractions at this (P, M) —
                            the numbers the RUNREPORT pipeline counters
                            and the bench A/B rows are checked against
==========================  =============================================

A module-level default log lets deep call sites (signal handlers, debug
callbacks) emit without plumbing a handle through every layer:
``emit_event("preemption", signum=15)``.
"""

from __future__ import annotations

import collections
import datetime
import time
from typing import Any, Dict, FrozenSet, Optional

#: Every event kind the package itself emits.  tests/test_repo_lint.py
#: AST-scans the package for ``emit_event("...")`` / ``.emit("...")``
#: call sites and asserts each literal kind appears here — an unregistered
#: kind is either a typo (the bug this catches) or a new feature that must
#: document itself by adding a line.
EVENT_KINDS: FrozenSet[str] = frozenset({
    # telemetry session
    "run_start", "run_end", "compile", "recompile",
    # checkpoint / preemption
    "checkpoint_save", "checkpoint_restore", "preemption",
    # numerics + hosts
    "nan_watchdog", "loss_scale", "straggler",
    # tools / comm
    "overlap_configure", "xla_trace_start", "xla_trace_stop",
    # resilience (PR 4)
    "fault_injected", "ckpt_retry", "ckpt_quarantine", "rollback",
    "resilience_abort", "hang_suspected", "hang_resolved", "hang_abort",
    "desync_detected", "checkpoint_save_skipped",
    # serving (PR 5)
    "request_admitted", "prefill_chunk", "request_retired", "slots_snapshot",
    # serving under stress (PR 9)
    "request_preempted", "request_shed", "request_expired",
    "request_cancelled", "engine_fault_detected", "engine_recovered",
    "engine_drained",
    # serving fast path (PR 10)
    "prefix_hit", "block_cow", "spec_draft", "spec_verify", "cache_evict",
    # serving observability (PR 11)
    "request_submitted", "request_resumed", "engine_tick",
    # multi-replica router (PR 15)
    "request_routed", "request_migrated", "replica_degraded",
    "blocks_migrated",
    # fleet observability: the router decision ledger + the engine-side
    # halves of the cross-replica trace link (PR 17)
    "route_decision", "handoff_decision", "rebalance_decision",
    "replica_up", "replica_down", "request_exported", "request_imported",
    # memory observability (PR 6)
    "mem_snapshot", "oom_risk",
    # numerics observability (PR 7)
    "numerics_alert", "nan_block_located",
    # quantized collectives (PR 8)
    "compress_policy",
    # auto-sharding planner (PR 13)
    "plan_selected", "plan_rejected_oom",
    # zero-bubble pipeline schedule (PR 14)
    "zb_wgrad_deferred", "zb_cooldown_filled",
    # MoE dispatch + expert-load serving (PR 18): which dispatch path a
    # trace resolved ('auto' is backend-dependent), and the host-side
    # capacity-overflow alarm (dropped-token rate over threshold)
    "moe_dispatch_selected", "expert_overflow",
    # elastic fleet (PR 19): every autoscaler evaluation (hold included)
    # with its evidence; per-chunk wire re-requests healed by bounded
    # backoff; a transfer declared dead taking the re-prefill fallback;
    # and the engine-side unwind of an import whose KV never arrived
    "scale_decision", "migration_retry", "migration_fallback",
    "import_aborted",
    # ring paged prefill (PR 20): a prefill chunk that rode the cp ring
    # (width + per-rank sub-chunk), the modeled per-tick ring hop/byte
    # accounting, and a long-document prefill->decode KV handoff at the
    # router (length >= long_ctx_threshold)
    "cp_prefill_chunk", "cp_ring_hop", "kv_handoff_long",
})


def _process_index() -> int:
    """Best-effort process index: 0 before/without distributed init."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


class EventLog:
    """In-memory (bounded deque) + optional JSONL-file event log.

    - ``path``: append-mode JSONL file.  Written on the master process only
      unless ``all_processes=True`` (per-host event files on a pod should
      use distinct paths — e.g. suffix ``jax.process_index()``).
    - ``sink``: any object with a ``write(record: dict)`` method (an
      :class:`~.exporters.JsonlSink` or friends) — used instead of ``path``.
    """

    def __init__(
        self,
        path: Optional[str] = None,
        sink=None,
        history_max: int = 4096,
        all_processes: bool = False,
    ) -> None:
        if path is not None and sink is None:
            from .exporters import JsonlSink

            sink = JsonlSink(path)
        self._sink = sink
        self._all_processes = all_processes
        self.events: collections.deque = collections.deque(maxlen=history_max)

    def emit(self, kind: str, **fields: Any) -> Dict[str, Any]:
        """Record one event; returns the record (all processes)."""
        rec: Dict[str, Any] = {
            "type": "event",
            "kind": str(kind),
            # wall clock via datetime (time.time() is lint-banned in the
            # package: every interval in the repo is perf_counter-based)
            "t_wall": datetime.datetime.now().timestamp(),
            # perf_counter shares its epoch with the step records'
            # t_end_s stamps, so events and spans land on one trace axis
            "t_mono": time.perf_counter(),
            "process": _process_index(),
        }
        rec.update(fields)
        self.events.append(rec)
        if self._sink is not None and (self._all_processes or rec["process"] == 0):
            try:
                self._sink.write(rec)
            except OSError:
                pass  # read-only checkout / full disk: keep the in-memory log
        return rec

    def of_kind(self, kind: str):
        return [e for e in self.events if e["kind"] == kind]

    def as_list(self):
        return list(self.events)


class TaggedEventLog:
    """A view of an :class:`EventLog` that stamps fixed fields on every
    emit — how a fleet gives each replica's engine an identity on a
    SHARED timeline without threading a replica index through every
    engine emit site.  ``Router`` wraps each replica's ``_ev`` with
    ``tag_events(log, replica=i)``; downstream consumers
    (``serving.tracing.assemble_fleet_request_timelines``) split the
    one timeline back into per-replica streams on the ``replica`` field.
    Everything except ``emit`` forwards to the wrapped log (same
    history, same sink), and an explicit field on an emit call wins over
    the tag."""

    def __init__(self, inner: EventLog, tags: Dict[str, Any]) -> None:
        self.inner = inner
        self.tags = dict(tags)

    def emit(self, kind: str, **fields: Any) -> Dict[str, Any]:
        return self.inner.emit(kind, **{**self.tags, **fields})

    def __getattr__(self, name: str) -> Any:
        return getattr(self.inner, name)


def tag_events(log: Any, **tags: Any) -> TaggedEventLog:
    """Wrap ``log`` so every emit carries ``tags``.  Re-tagging a
    tagged log replaces its tags instead of stacking views (a Router
    rebuilt over the same engines must not accumulate stale indices)."""
    while isinstance(log, TaggedEventLog):
        log = log.inner
    return TaggedEventLog(log, tags)


_default_log: Optional[EventLog] = None


def default_event_log() -> EventLog:
    """The process-wide event log (created in-memory on first use)."""
    global _default_log
    if _default_log is None:
        _default_log = EventLog()
    return _default_log


def set_default_event_log(log: Optional[EventLog]) -> None:
    """Install (or with None: reset) the process-wide default log.
    ``Telemetry`` installs its own log here so signal handlers and debug
    callbacks land on the same timeline as the step records."""
    global _default_log
    _default_log = log


def emit_event(kind: str, **fields: Any) -> Dict[str, Any]:
    """Emit on the process-wide default log — the zero-plumbing entry point
    for deep call sites (signal handlers, ``jax.debug.callback``)."""
    return default_event_log().emit(kind, **fields)
