"""serving — paged KV cache + continuous batching for high-throughput decode.

``models/generate.py`` gives the framework *a* decode path; this package
gives it a SERVING path: a vLLM-style block-pool KV cache
(:mod:`.paged_cache`) and a slot-based continuous-batching engine
(:mod:`.engine`) whose hot loop is two statically-shaped compiled programs
— one decode step, one prefill-chunk step — however many requests of
whatever shapes flow through.  Host code between ticks only rewrites
small int32 block tables.

The transformer math is NOT reimplemented here: ``cached_block_forward``
(models/generate.py) takes ``cache_ops`` and both cache layouts run the
same block, so paged decode agrees with contiguous ``generate()`` to the
bit (tests/test_serving.py).  TP/DP sharding comes from the same mesh
axes as training; ``obs`` integration reports TTFT/TPOT percentiles,
aggregate tokens/s, slot occupancy and pool utilization in the RUNREPORT
``serving`` section.

Overload and faults are scheduler states, not exceptions (docs/serving.md
"Serving under stress"): priority classes with evict-and-requeue
preemption, deadline-aware admission that sheds with structured verdicts,
same-tick cancellation, a per-tick block-conservation audit with
self-healing recovery (chaos-matrix proven), and preemption-safe
SIGTERM drain/resume with exact-token replay — all host-side, so the
two-compiled-programs hot loop survives every path.

The fast path (docs/serving.md "Prefix cache" / "Speculative decoding"):
``prefix_cache=True`` turns the block pool content-addressed — per-block
refcounts, a chain-hash index over full token blocks, copy-on-write for
whole-prompt hits, LRU retention of released prefixes — so shared
system-prompt traffic prefills once per PREFIX; ``spec_k=K`` adds
self-speculative decoding at a static draft width (host n-gram drafter,
one compiled verify program over all k+1 positions, temp-0 bit-exact,
sampled rows via residual rejection sampling).  See docs/serving.md.

Observability (docs/serving.md "Serving observability"): every tick is
decomposed host-side into phase accounting (:mod:`.tracing` —
``engine_tick`` events, Perfetto phase lanes + counter tracks, the
``serving_metrics`` live-export schema), the event timeline reconstructs
each request's full lifecycle as a flow-linked Perfetto track (queued →
prefill → decode across preemptions and drain→resume), and
``serving_summary()['slo']`` reports per-priority deadline attainment,
goodput, and the predicted-vs-actual TTFT calibration whose bias feeds
back into ``estimate_ttft`` — all host arithmetic, zero extra compiled
programs.

Fleet observability (docs/serving.md "Fleet observability"): the Router
keeps a decision LEDGER — every route/handoff/rebalance/liveness
decision is a registered event carrying the candidate table it was made
from — and a request that crosses replicas stitches into one
flow-linked Perfetto track (:func:`assemble_fleet_request_timelines`).
The engine's five device touches sit behind a :class:`DeviceStep` seam
(:mod:`.sim`), so ``tools/trace_replay.py`` can push 10^5+ synthetic
requests through the real Router + :class:`StubDeviceStep` engines on
CPU and emit the validated FLEETREPORT as evidence.
"""

from .autoscale import AUTOSCALE_VERDICTS, Autoscaler
from .engine import Request, ServingEngine
from .router import (
    FLEET_BALANCE_VERDICTS,
    IMBALANCE_SKEWED_AT,
    ROLES,
    Router,
)
from .transport import (
    ChunkedWireTransport,
    LoopbackTransport,
    MigrationTransport,
    ReplicaDiedError,
    TransportDeadError,
    TransportError,
)
from .sim import (
    CompiledDeviceStep,
    DeviceStep,
    LatencyModel,
    StubDeviceStep,
    host_migrate_blocks,
)
from .tracing import (
    REQUEST_PHASES,
    REQUEST_TERMINALS,
    ROUTER_EVENT_KINDS,
    SERVING_METRICS_SCHEMA,
    TICK_PHASES,
    assemble_fleet_request_timelines,
    assemble_request_timelines,
    fleet_trace_events,
    lifecycle_phases,
    phase_table,
    stalls,
    request_trace_events,
    serving_metrics_record,
    serving_trace_events,
    tick_trace_events,
    validate_request_record,
)
from .paged_cache import (
    NULL_BLOCK,
    BlockAllocator,
    block_size_of,
    chain_block_hashes,
    copy_blocks,
    expected_pool_bytes,
    gather_kv,
    init_paged_kv,
    migrate_blocks,
    migration_wire_bytes,
    paged_attention,
    paged_forward,
    paged_forward_moe,
    paged_write,
    pool_bytes,
)

__all__ = [
    "AUTOSCALE_VERDICTS",
    "Autoscaler",
    "Request",
    "ServingEngine",
    "ChunkedWireTransport",
    "LoopbackTransport",
    "MigrationTransport",
    "ReplicaDiedError",
    "TransportDeadError",
    "TransportError",
    "FLEET_BALANCE_VERDICTS",
    "IMBALANCE_SKEWED_AT",
    "ROLES",
    "Router",
    "CompiledDeviceStep",
    "DeviceStep",
    "LatencyModel",
    "StubDeviceStep",
    "host_migrate_blocks",
    "REQUEST_PHASES",
    "REQUEST_TERMINALS",
    "ROUTER_EVENT_KINDS",
    "SERVING_METRICS_SCHEMA",
    "TICK_PHASES",
    "assemble_fleet_request_timelines",
    "fleet_trace_events",
    "assemble_request_timelines",
    "lifecycle_phases",
    "phase_table",
    "stalls",
    "request_trace_events",
    "serving_metrics_record",
    "serving_trace_events",
    "tick_trace_events",
    "validate_request_record",
    "NULL_BLOCK",
    "BlockAllocator",
    "block_size_of",
    "chain_block_hashes",
    "copy_blocks",
    "expected_pool_bytes",
    "gather_kv",
    "init_paged_kv",
    "migrate_blocks",
    "migration_wire_bytes",
    "paged_attention",
    "paged_forward",
    "paged_forward_moe",
    "paged_write",
    "pool_bytes",
]
