"""obs — unified run telemetry for training, serving, and benchmarks.

The framework could train and serve but not *report on itself*: throughput,
MFU, memory peaks, pipeline bubble fraction, and MoE load balance were
computed ad hoc (or not at all) with no shared schema, no cross-host view,
and no event timeline.  This subpackage is the one shared telemetry layer
every train loop and example emits through:

- :mod:`.telemetry` — :class:`Telemetry`, a run-session object that wraps a
  jitted train/decode step, records per-step spans (data / dispatch /
  device / fetch), detects recompiles, polls ``device.memory_stats()``, and
  computes MFU + bytes-moved from XLA ``cost_analysis`` of the *compiled*
  step (compiler ground truth, kept beside the caller's 6N+12LSD hand
  formula).
- :mod:`.events` — append-only structured event log (compile, checkpoint
  save/restore, preemption, NaN-watchdog trip, loss-scale change,
  straggler alert) with monotonic timestamps and process index.
- :mod:`.aggregate` — cross-host reduction of host-side step times
  (min/mean/max per host → straggler detection) plus the per-parallelism
  counters: pipeline bubble fraction, MoE expert-load imbalance.
- :mod:`.report` + :mod:`.exporters` — pluggable sinks (JSONL always;
  TensorBoard scalars and Prometheus textfile behind optional-import
  guards) and the end-of-run ``RUNREPORT.json`` + markdown summary.
- :mod:`.comm_ledger` — per-step collective ledger parsed from the
  AOT-compiled step's HLO: every all-reduce / all-gather / reduce-scatter
  / all-to-all / collective-permute with payload bytes, mapped onto mesh
  axes and classified per parallelism dimension (dp/tp/pp/moe).
- :mod:`.comm_model` — alpha–beta cost model over the ledger: per-TPU-
  generation ICI/DCN link tables, ``CommModel.calibrate(mesh)`` fitting
  measured ``dist.comm_bench`` timings, and the RUNREPORT ``comm``
  section (modeled vs measured comm time, comm-bound vs compute-bound
  verdict, overlap headroom).
- :mod:`.mem_ledger` — memory observability: the per-compiled-program
  static buffer ledger from ``memory_analysis()`` (argument / output /
  temp / donation-savings bytes, argument bytes attributed to pytree
  leaves through the compiled input shardings), the repo's ONE
  ``memory_stats()`` reader (``live_memory``), ``ok|tight|oom_risk``
  headroom verdicts, and the planner-facing ``MemoryModel.estimate``.
- :mod:`.numerics` — numerics observability: the jittable
  ``numerics_stats`` fused into the train step (per-layer-group grad/
  param/update norms, update ratio, non-finite counts, low-precision
  range fractions), the per-dtype HLO FLOP/byte ledger (what actually
  runs in bf16 vs f32 vs int8), threshold-driven ``numerics_alert``
  events, and the RUNREPORT ``numerics`` section.
- :mod:`.parity` — A/B run-parity: compare two runs' record streams /
  RUNREPORTs into an ``exact|bounded|diverged`` verdict with per-step
  drift curves and per-leaf param divergence (``tools/parity_diff.py``
  is the CLI).
- :mod:`.trace` — Perfetto-loadable Chrome-trace export of the run
  (spans, events, ledger + HBM + grad-norm counters) + ``XlaStepTrace``,
  a programmatic ``jax.profiler`` capture bracketing a chosen step
  window.

Design constraints: ``obs`` is a LEAF subsystem — it imports nothing from
the rest of the package at module scope (``utils.metrics`` shims over
``obs.exporters``, so a module-level import the other way would cycle), and
every device/backend touch is guarded so the CPU sim, a half-initialized
backend, or an old jax still produce a report instead of a crash.
"""

from .events import (
    EVENT_KINDS,
    EventLog,
    default_event_log,
    emit_event,
    set_default_event_log,
)
from .exporters import (
    JsonlSink,
    MultiSink,
    PrometheusTextfileSink,
    TensorBoardSink,
    tensorboard_available,
)
from .telemetry import Telemetry, compiled_cost, peak_flops_for
from .aggregate import (
    cross_host_step_stats,
    moe_load_stats,
    percentiles,
    pipeline_bubble_fraction,
    pipeline_time_inflation,
    step_time_stats,
)
from .report import (
    AUTOPLAN_SCHEMA,
    PLAN_VERDICTS,
    RESILIENCE_VERDICTS,
    RUNREPORT_SCHEMA,
    SERVING_VERDICTS,
    default_report_path,
    render_markdown,
    validate_runreport,
    write_runreport,
)
from .comm_ledger import (
    COMM_RECORD_SCHEMA,
    LEDGER_SCHEMA,
    comm_record,
    ledger_from_compiled,
    ledger_from_hlo,
    tp_pp_overlap,
)
from .comm_model import (
    COMPRESSION_SCHEMA,
    CommModel,
    comm_report,
    compressed_ledger_bytes,
    compressed_wire_bytes,
    compression_report,
    fit_alpha_beta,
)
from .mem_ledger import (
    MEM_LEDGER_SCHEMA,
    MEM_VERDICTS,
    MemoryModel,
    device_capacity,
    headroom_verdict,
    live_memory,
    mem_report,
    static_ledger,
)
from .numerics import (
    DEFAULT_THRESHOLDS,
    DTYPE_LEDGER_SCHEMA,
    NUMERICS_SCHEMA,
    check_alerts,
    dtype_ledger_from_compiled,
    dtype_ledger_from_hlo,
    global_grad_norm,
    numerics_report,
    numerics_stats,
)
from .parity import (
    PARITY_SCHEMA,
    PARITY_VERDICTS,
    compare_streams,
    param_divergence,
    parity_section,
    stream_of,
)
from .trace import (
    XlaStepTrace,
    build_trace,
    default_trace_path,
    export_trace,
    validate_trace,
)

__all__ = [
    "EVENT_KINDS",
    "EventLog",
    "default_event_log",
    "emit_event",
    "set_default_event_log",
    "JsonlSink",
    "MultiSink",
    "PrometheusTextfileSink",
    "TensorBoardSink",
    "tensorboard_available",
    "Telemetry",
    "compiled_cost",
    "peak_flops_for",
    "cross_host_step_stats",
    "moe_load_stats",
    "percentiles",
    "pipeline_bubble_fraction",
    "pipeline_time_inflation",
    "step_time_stats",
    "RESILIENCE_VERDICTS",
    "SERVING_VERDICTS",
    "RUNREPORT_SCHEMA",
    "default_report_path",
    "render_markdown",
    "validate_runreport",
    "write_runreport",
    "COMM_RECORD_SCHEMA",
    "LEDGER_SCHEMA",
    "comm_record",
    "ledger_from_compiled",
    "ledger_from_hlo",
    "tp_pp_overlap",
    "CommModel",
    "comm_report",
    "fit_alpha_beta",
    "MEM_LEDGER_SCHEMA",
    "MEM_VERDICTS",
    "MemoryModel",
    "device_capacity",
    "headroom_verdict",
    "live_memory",
    "mem_report",
    "static_ledger",
    "DEFAULT_THRESHOLDS",
    "DTYPE_LEDGER_SCHEMA",
    "NUMERICS_SCHEMA",
    "check_alerts",
    "dtype_ledger_from_compiled",
    "dtype_ledger_from_hlo",
    "global_grad_norm",
    "numerics_report",
    "numerics_stats",
    "PARITY_SCHEMA",
    "PARITY_VERDICTS",
    "compare_streams",
    "param_divergence",
    "parity_section",
    "stream_of",
    "XlaStepTrace",
    "build_trace",
    "default_trace_path",
    "export_trace",
    "validate_trace",
]
