"""Telemetry — the per-run session object every loop reports through.

Wrap the jitted train/decode step once and every call is accounted for:

    tel = Telemetry(run="train_llama", tokens_per_step=B * S,
                    sinks=[JsonlSink("metrics.jsonl")])
    step = tel.wrap_step(step)
    for it in range(n):
        batch = next(batches)                      # -> 'data' span
        params, state, loss = step(params, state, batch)   # -> 'dispatch'
        rec = tel.end_step(step=it, loss=loss)     # -> 'device' + 'fetch'
    report = tel.finalize()                        # RUNREPORT.json (+ .md)

Per-step spans (host clock, seconds):

- ``data``     — end of last step's fetch to this step's dispatch (host
  input pipeline: batch building, device_put).
- ``dispatch`` — the wrapped call itself.  XLA is async, so this is trace/
  cache-lookup + enqueue time; a big number here means host-bound.
- ``device``   — ``block_until_ready`` on the step outputs: actual
  accelerator execution (plus any queue ahead of it).
- ``fetch``    — ``float()`` of the scalars handed to :meth:`end_step`
  (device->host transfer of the loss etc.).

Recompile detection: the wrapper keys on the abstract signature (shape /
dtype / tree structure) of the call's arguments.  A NEW signature after
the first is a recompile — the silent throughput killer (a leaked varying
dimension, a dtype flip) — and emits a ``recompile`` event plus a
``recompiled: true`` mark on the step record.

MFU ground truth: the first compilation of each signature goes through
AOT ``lower().compile()``, so XLA's own ``cost_analysis`` of the compiled
step (FLOPs, bytes accessed) is captured as a side effect — no second
compile, no hand-counting.  The report shows this number beside the
caller's 6N+12LSD hand formula (``flops_per_token``): remat recompute and
non-matmul ops are IN the XLA count and NOT in the model-FLOPs count, so
the two bracket the truth from opposite sides.

Memory: ``mem_ledger.live_memory()`` (the repo's one ``memory_stats()``
reader) is polled each step (guarded — the CPU sim reports nothing) into
a live/peak TIMELINE (``mem_snapshot`` events + a Perfetto counter
track), and every AOT-compiled signature's ``memory_analysis()`` is
parsed into a static buffer ledger (:mod:`.mem_ledger`) — the report's
``memory`` section reconciles the two against device capacity into an
``ok|tight|oom_risk`` headroom verdict.

Numerics: pass the in-step :func:`~.numerics.numerics_stats` dict to
``end_step(..., numerics=stats)`` and Telemetry promotes it to a
per-step timeline (grad/param/update norms, update ratio, non-finite
counts, low-precision range fractions), runs the
:func:`~.numerics.check_alerts` thresholds (``numerics_alert`` events on
entering a bad state), exports ``grad_norm`` / ``update_ratio`` Perfetto
counter tracks, and parses every AOT-compiled signature's HLO into a
per-dtype FLOP/byte ledger — the report's validated ``numerics`` section.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import aggregate as _agg
from . import report as _report
from .events import EventLog, set_default_event_log

# Peak dense bf16 FLOP/s per chip by device_kind substring (public specs).
# The package's one lookup table (it may not import from benchmarks/, a
# higher layer; tests/test_repo_lint.py holds it to benchmarks/peaks.json).
# The CPU has no peak worth a utilization (None); a kind with no row is an
# error, not a silently dropped MFU.
PEAK_BF16_FLOPS = [
    ("v6", 918e12),  # Trillium
    ("v5p", 459e12),
    ("v5e", 197e12),  # aka v5 lite
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
    ("cpu", None),
]


def peak_flops_for(device_kind: str) -> Optional[float]:
    dk = device_kind.lower()
    for sub, peak in PEAK_BF16_FLOPS:
        if sub in dk:
            return peak
    raise ValueError(
        f"no peak-FLOP/s row for device_kind={device_kind!r}; add one to "
        "obs.telemetry.PEAK_BF16_FLOPS")


def compiled_cost(compiled) -> Dict[str, float]:
    """``{"flops", "bytes_accessed"}`` from XLA's cost analysis of a
    compiled executable (zeros-omitted; {} when the backend reports
    nothing).  Same extraction as ``tools/profiler.py`` — compiler ground
    truth, per participating device of the SPMD program."""
    out: Dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca.get("flops"):
            out["flops"] = float(ca["flops"])
        if ca.get("bytes accessed"):
            out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:
        pass
    return out


def _abstract_signature(args: Tuple[Any, ...]) -> Tuple:
    """Hashable (treedef, per-leaf shape/dtype) key — what jit's cache keys
    on, minus shardings (a sharding-only change recompiles without showing
    here; the AOT fallback path still catches it as a failed call)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sig.append((tuple(leaf.shape), str(leaf.dtype)))
        else:
            sig.append((type(leaf).__name__,))
    return (str(treedef), tuple(sig))


def _any_deleted(tree: Any) -> bool:
    """True when a jax array in ``tree`` was consumed by a call that
    donated it."""
    import jax

    return any(isinstance(leaf, jax.Array) and leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(tree))


def _host_numerics(stats: Dict[str, Any]) -> Dict[str, Any]:
    """Fetch a (possibly nested) dict of device scalars to host floats —
    one device_get for the whole tree, so the numerics stats cost a
    single transfer alongside the loss."""
    import jax

    host = jax.device_get(stats)

    def conv(node):
        if isinstance(node, dict):
            return {k: conv(v) for k, v in node.items()}
        try:
            return float(node)
        except (TypeError, ValueError):
            return node

    return conv(host)


def _local_memory_stats() -> Optional[Tuple[int, int]]:
    """(peak_bytes, live_bytes) summed over local devices; None when no
    device reports (CPU sim).  Thin shim over the repo's one
    ``memory_stats()`` reader, :func:`.mem_ledger.live_memory`."""
    from .mem_ledger import live_memory

    mem = live_memory()
    return (mem["peak_bytes"], mem["live_bytes"]) if mem["reported"] else None


class Telemetry:
    """One instance per run.  See the module docstring for the loop shape.

    Parameters
    ----------
    run: name stamped on every record and the report.
    sinks: list of exporter sinks fed every step record and the summary
        (JSONL/TensorBoard/Prometheus — :mod:`.exporters`).  Optional: the
        in-memory history + RUNREPORT always work.
    tokens_per_step: enables tokens/sec throughput accounting.
    flops_per_token: the HAND formula (e.g. 6N+12LSD) — kept
        separate from the XLA-measured FLOPs so the report can show both.
    peak_flops: per-chip peak FLOP/s; default looked up from the device
        kind (:func:`peak_flops_for`), None on CPU.
    report_path: where :meth:`finalize` writes ``RUNREPORT.json`` (+ a
        sibling ``.md``).  Default: the ``TDP_RUNREPORT`` env var; unset ->
        no file, the report dict is still returned.
    event_log: a shared :class:`EventLog`; by default a fresh one is
        created AND installed as the process default so ``GracefulShutdown``
        / ``nan_guard`` events land on this run's timeline.
    trace_path: where :meth:`finalize` writes the Perfetto-loadable Chrome
        trace of the run (:mod:`.trace`).  Default: the ``TDP_TRACE`` env
        var; unset -> no trace file.
    mesh: the mesh the step runs over — used to map the compiled step's
        collectives onto named axes (:mod:`.comm_ledger`).  Default: the
        ``dist.topology.tpc`` base mesh when initialized.
    comm_ledger_enabled: parse the compiled step's HLO into the collective
        ledger (RUNREPORT ``comm`` section).  On by default; the parse
        happens once per run, at first compile.
    mem_ledger_enabled: parse every compiled signature's
        ``memory_analysis()`` into a static buffer ledger
        (:mod:`.mem_ledger`; RUNREPORT ``memory`` section).  On by
        default; same no-second-compile hook as the comm ledger.
    mem_snapshot_every: emit a ``mem_snapshot`` event every N steps with
        the live/peak HBM sample (0 = never; the per-step samples land on
        the step records and the report timeline regardless).
    numerics_thresholds: overrides for the ``numerics_alert`` thresholds
        (:data:`~.numerics.DEFAULT_THRESHOLDS`) applied to every
        ``end_step(..., numerics=...)`` record — and to the loss scalar
        itself, so a non-finite loss alerts even without in-step stats.
    dtype_ledger_enabled: parse every compiled signature's HLO into the
        per-dtype FLOP/byte ledger (:func:`~.numerics.dtype_ledger_from_hlo`;
        RUNREPORT ``numerics`` section).  Same no-second-compile hook as
        the comm/mem ledgers.
    xla_trace: a :class:`~.trace.XlaStepTrace` — programmatic
        ``jax.profiler`` capture bracketing a window of wrapped steps.
    """

    def __init__(
        self,
        run: str = "run",
        sinks: Optional[List[Any]] = None,
        tokens_per_step: Optional[int] = None,
        flops_per_token: Optional[float] = None,
        peak_flops: Optional[float] = None,
        report_path: Optional[str] = None,
        event_log: Optional[EventLog] = None,
        poll_memory: bool = True,
        history_max: int = 100_000,
        trace_path: Optional[str] = None,
        mesh: Optional[Any] = None,
        comm_ledger_enabled: bool = True,
        xla_trace: Optional[Any] = None,
        mem_ledger_enabled: bool = True,
        mem_snapshot_every: int = 16,
        numerics_thresholds: Optional[Dict[str, float]] = None,
        dtype_ledger_enabled: bool = True,
    ) -> None:
        import jax

        self.run = run
        self.sinks = list(sinks) if sinks else []
        self.tokens_per_step = tokens_per_step
        self.flops_per_token = flops_per_token
        self.poll_memory = poll_memory
        self.report_path = (
            report_path if report_path is not None else _report.default_report_path()
        )
        from . import trace as _trace

        self.trace_path = (
            trace_path if trace_path is not None else _trace.default_trace_path()
        )
        self.mesh = mesh
        self.comm_ledger_enabled = comm_ledger_enabled
        self.comm_ledger: Optional[Dict[str, Any]] = None
        self.mem_ledger_enabled = mem_ledger_enabled
        self.mem_snapshot_every = mem_snapshot_every
        #: static ledgers, one per AOT-compiled signature (mem_ledger)
        self.mem_ledgers: List[Dict[str, Any]] = []
        #: per-step live/peak HBM samples (the mem_snapshot timeline)
        self.mem_timeline: List[Dict[str, Any]] = []
        self._peak_frac = 0.0
        self._oom_emitted = False
        self.numerics_thresholds = dict(numerics_thresholds or {})
        self.dtype_ledger_enabled = dtype_ledger_enabled
        #: per-dtype HLO ledgers, one per AOT-compiled signature (numerics)
        self.dtype_ledgers: List[Dict[str, Any]] = []
        #: per-step numerics samples (the training-dynamics timeline)
        self.numerics_timeline: List[Dict[str, Any]] = []
        self._alert_active: set = set()
        self.parity: Optional[Dict[str, Any]] = None
        self.compression: Optional[Dict[str, Any]] = None
        self.xla_trace = xla_trace
        if event_log is None:
            event_log = EventLog()
            set_default_event_log(event_log)
        self.events = event_log
        self.counters: Dict[str, Any] = {}
        self.resilience: Optional[Dict[str, Any]] = None
        self.serving: Optional[Dict[str, Any]] = None
        self.router: Optional[Dict[str, Any]] = None
        self.autoplan: Optional[Dict[str, Any]] = None
        self.history: List[Dict[str, Any]] = []
        self._history_max = history_max

        self._backend = jax.default_backend()
        self._chip = jax.devices()[0].device_kind
        self._n_devices = jax.device_count()
        self._n_processes = jax.process_count()
        self._is_master = jax.process_index() == 0
        self.peak_flops = (
            peak_flops if peak_flops is not None
            else peak_flops_for(self._chip))

        self._compiled: Dict[Tuple, Dict[str, Any]] = {}
        self._wrap_n = 0  # wrap_step counter: scopes the AOT cache per fn
        self._aot_ok = True
        self._pending_out: Any = None
        self._pending_spans: Dict[str, float] = {}
        self._dispatch_end = 0.0  # when the wrapped call returned
        self._recompiled = False
        self._last_fetch_end: Optional[float] = None
        self._step_n = 0
        self.n_compiles = 0
        self.n_recompiles = 0
        self.compile_time_s = 0.0
        self.xla_cost: Dict[str, float] = {}
        self._peak_bytes = 0
        self._t_start = time.monotonic()
        self.events.emit(
            "run_start", run=run, backend=self._backend, chip=self._chip,
            n_devices=self._n_devices, n_processes=self._n_processes,
        )

    # ------------------------------------------------------------- wrapping

    def wrap_step(self, fn: Callable, cost_analysis: bool = True) -> Callable:
        """Instrument a (jitted) step callable.

        The first call per abstract signature is AOT-lowered and compiled,
        capturing compile time + XLA cost analysis; subsequent calls go to
        the compiled executable (no double compile; it keeps the jitted
        step's donations).  If the AOT executable
        rejects a call (sharding/donation edge the signature key can't
        see), the wrapper permanently falls back to the original callable —
        telemetry must never change what the loop computes.  A call that
        failed AFTER it consumed a donated argument is not made again: the
        error is the caller's to see.

        The executable cache is scoped PER WRAPPED CALLABLE: two different
        step fns wrapped by the same Telemetry (e.g. the 1F1B and ZB arms
        of a schedule A/B) may share an abstract input signature, and a
        signature-only key would silently hand arm B arm A's executable —
        an A/B that measures one program twice.
        """
        import jax

        jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
        self._wrap_n += 1
        wrap_id = self._wrap_n

        def wrapped(*args, **kwargs):
            now = time.perf_counter()
            if self._last_fetch_end is not None:
                self._pending_spans["data"] = now - self._last_fetch_end
            if self.xla_trace is not None:
                self.xla_trace.on_step_start(self._step_n)
            entry = None
            sig = None
            if not kwargs:  # kwargs: skip AOT, plain call below
                sig = (wrap_id, _abstract_signature(args))
                entry = self._compiled.get(sig)
                if entry is None:
                    entry = self._compile_entry(jfn, sig, args, cost_analysis)
            t0 = time.perf_counter()
            target = entry["compiled"] if (entry and entry["compiled"]) else jfn
            try:
                out = target(*args, **kwargs)
            except Exception:
                if target is jfn or _any_deleted((args, kwargs)):
                    # nothing to fall back to, or with: the failed call
                    # already consumed an argument it donates (the serving
                    # step's KV pool, a train step's state)
                    raise
                # AOT path rejected the call: fall back for good
                self._aot_ok = False
                for e in self._compiled.values():
                    e["compiled"] = None
                out = jfn(*args, **kwargs)
            self._dispatch_end = time.perf_counter()
            self._pending_spans["dispatch"] = self._dispatch_end - t0
            self._pending_out = out
            return out

        return wrapped

    def _compile_entry(self, jfn, sig, args, cost_analysis) -> Dict[str, Any]:
        first = not self._compiled
        # a RE-compile is the same wrapped step seeing a new input
        # signature (the silent throughput killer); a different wrapped
        # step's first compile is a plain compile
        re_sig = any(k[0] == sig[0] for k in self._compiled)
        compiled = None
        cost: Dict[str, float] = {}
        t0 = time.perf_counter()
        if cost_analysis and self._aot_ok:
            try:
                compiled = jfn.lower(*args).compile()
                cost = compiled_cost(compiled)
            except Exception:
                self._aot_ok = False
                compiled = None
        dt = time.perf_counter() - t0
        entry = {"compiled": compiled, "cost": cost}
        self._compiled[sig] = entry
        self.n_compiles += 1
        self.compile_time_s += dt
        if compiled is not None and self.mem_ledger_enabled:
            # same no-second-compile hook: the compiled program's static
            # buffer ledger (args/outputs/temps/donation savings)
            try:
                from . import mem_ledger as _mem

                led = _mem.static_ledger(
                    compiled, label=f"sig{len(self._compiled) - 1}")
                if led is not None:
                    self.mem_ledgers.append(led)
            except Exception:
                pass
        # HLO text rendered ONCE per signature, shared by the comm ledger
        # (first signature) and the per-dtype ledger (every signature)
        hlo_text = None
        if compiled is not None and (
                self.comm_ledger_enabled or self.dtype_ledger_enabled):
            try:
                hlo_text = compiled.as_text()
            except Exception:
                hlo_text = None
            if not isinstance(hlo_text, str) or not hlo_text:
                hlo_text = None
        if hlo_text is not None and self.dtype_ledger_enabled:
            try:
                from . import numerics as _numerics

                self.dtype_ledgers.append(_numerics.dtype_ledger_from_hlo(
                    hlo_text, label=f"sig{len(self._compiled) - 1}"))
            except Exception:
                pass
        if first:
            self.xla_cost = dict(cost)
            if hlo_text is not None and self.comm_ledger_enabled:
                # same no-second-compile hook that captures cost_analysis:
                # parse the compiled step's collectives into the comm ledger
                try:
                    from . import comm_ledger as _ledger

                    self.comm_ledger = _ledger.ledger_from_hlo(
                        hlo_text, mesh=self.mesh)
                except Exception:
                    self.comm_ledger = None
        if re_sig:
            self._recompiled = True
            self.n_recompiles += 1
        self.events.emit(
            "compile" if not re_sig else "recompile",
            run=self.run,
            compile_time_s=round(dt, 4),
            flops=cost.get("flops"),
            bytes_accessed=cost.get("bytes_accessed"),
            n_signatures=len(self._compiled),
        )
        return entry

    def compiled_programs(self) -> List[Any]:
        """The executables :meth:`wrap_step` AOT-compiled, one per
        signature — what the ledgers parsed, for callers that read the
        program text themselves."""
        return [e["compiled"] for e in self._compiled.values()
                if e["compiled"] is not None]

    # ------------------------------------------------------------ recording

    def end_step(
        self,
        step: Optional[int] = None,
        *,
        numerics: Optional[Dict[str, Any]] = None,
        wait: bool = True,
        **scalars: Any,
    ) -> Dict[str, Any]:
        """Close the step opened by the wrapped call: block on its outputs
        (device span, from the moment the call returned: a caller that has
        already fetched them, as the serving engine has, waited for the
        device there), fetch the passed scalars (fetch span), build the
        record, feed the sinks.  Returns the record with host floats — use
        ``rec["loss"]`` instead of a second ``float(loss)``.

        ``wait=False``: the caller runs a call ahead (the serving engine's
        ``run_ahead``): it closes the step BEFORE the newest wrapped call's,
        whose outputs it has fetched itself, so nothing is blocked on, and
        the device span is the time since the newest call returned (since
        the ``end_step`` before, where no call was made since), which holds
        the caller's wait for the step it closes.  The spans still sum to
        the time from one ``end_step`` to the next.

        ``numerics``: the in-step :func:`~.numerics.numerics_stats` dict
        (device scalars).  It is fetched with the other scalars (same
        fetch span), lands on the record as ``rec["numerics"]`` (with
        ``grad_norm`` / ``update_ratio`` promoted to top-level floats for
        sinks and the trace counter tracks), extends the numerics
        timeline, and runs the alert thresholds."""
        import jax

        t0 = time.perf_counter()
        if self._pending_out is not None:
            t0 = self._dispatch_end
            if wait:
                try:
                    jax.block_until_ready(self._pending_out)
                except Exception:
                    pass
            self._pending_out = None
        elif not wait and self._last_fetch_end is not None:
            t0 = self._last_fetch_end  # no call since the step before's end
        t1 = time.perf_counter()
        rec: Dict[str, Any] = {
            "type": "step",
            "run": self.run,
            "step": int(step) if step is not None else self._step_n,
        }
        for k, v in scalars.items():
            try:
                rec[k] = float(v)
            except (TypeError, ValueError):
                rec[k] = v
        if numerics is not None:
            rec["numerics"] = _host_numerics(numerics)
            for k in ("grad_norm", "update_ratio", "nonfinite_grads"):
                if k in rec["numerics"]:
                    rec[k] = rec["numerics"][k]
        t2 = time.perf_counter()
        spans = dict(self._pending_spans)
        self._pending_spans = {}
        spans["device"] = t1 - t0
        spans["fetch"] = t2 - t1
        for name, dt in spans.items():
            rec[f"span_{name}_s"] = dt
        step_time = sum(spans.values())
        rec["step_time_s"] = step_time
        rec["t_end_s"] = t2  # perf_counter-domain stamp for the trace exporter
        if self.xla_trace is not None:
            self.xla_trace.on_step_end(
                int(step) if step is not None else self._step_n)
        if self._recompiled:
            rec["recompiled"] = True
            self._recompiled = False
        if self.tokens_per_step and step_time > 0:
            rec["tok_per_sec"] = self.tokens_per_step / step_time
        if self.poll_memory:
            from .mem_ledger import OOM_RISK_FRAC, live_memory

            mem = live_memory()
            if mem["reported"]:
                rec["peak_bytes_in_use"] = mem["peak_bytes"]
                rec["bytes_in_use"] = mem["live_bytes"]
                self._peak_bytes = max(self._peak_bytes, mem["peak_bytes"])
                if mem["peak_frac"] is not None:
                    self._peak_frac = max(self._peak_frac, mem["peak_frac"])
                self.mem_timeline.append({
                    "step": rec["step"],
                    "live_bytes": mem["live_bytes"],
                    "peak_bytes": mem["peak_bytes"],
                })
                if (self.mem_snapshot_every
                        and self._step_n % self.mem_snapshot_every == 0):
                    self.events.emit(
                        "mem_snapshot", step=rec["step"],
                        live_bytes=mem["live_bytes"],
                        peak_bytes=mem["peak_bytes"],
                        peak_frac=mem["peak_frac"])
                if (not self._oom_emitted and mem["peak_frac"] is not None
                        and mem["peak_frac"] >= OOM_RISK_FRAC):
                    # first crossing of the risk line lands on the
                    # timeline AS IT HAPPENS, not only at finalize
                    self._oom_emitted = True
                    self.events.emit(
                        "oom_risk", step=rec["step"],
                        peak_frac=round(mem["peak_frac"], 4),
                        basis="live memory_stats sample")
        if numerics is not None:
            self.numerics_timeline.append({
                "step": rec["step"],
                **{k: v for k, v in rec["numerics"].items() if k != "groups"},
                **({"loss": rec["loss"]}
                   if isinstance(rec.get("loss"), float) else {}),
            })
        # threshold checks over the host record (covers the plain-loss
        # path too: a non-finite loss alerts without in-step stats);
        # alerts fire on ENTERING a bad state, not every step inside it
        from . import numerics as _numerics

        alerts = _numerics.check_alerts(rec, self.numerics_thresholds)
        for a in alerts:
            if a["reason"] not in self._alert_active:
                self.events.emit(
                    "numerics_alert", step=rec["step"],
                    source="telemetry", **a)
        self._alert_active = {a["reason"] for a in alerts}
        self._last_fetch_end = t2
        self._step_n += 1
        if len(self.history) < self._history_max:
            self.history.append(rec)
        if self._is_master:
            for s in self.sinks:
                try:
                    s.write(rec)
                except Exception:
                    pass
        return rec

    def record_counters(self, **named: Any) -> None:
        """Attach per-parallelism counters to the report, e.g.
        ``tel.record_counters(pipeline={"bubble_fraction": f},
        moe=moe_load_stats(...))``."""
        self.counters.update(named)

    def record_resilience(self, summary: Dict[str, Any]) -> None:
        """Attach the self-healing loop's summary as the report's optional
        ``resilience`` section (``ResilientLoop.run`` calls this when a
        Telemetry is wired in; validated by ``validate_runreport``)."""
        self.resilience = dict(summary)

    def record_parity(self, section: Dict[str, Any]) -> None:
        """Attach an A/B :func:`~.parity.parity_section` to the report's
        ``numerics.parity`` sub-section (``exact|bounded|diverged``
        verdict; validated by ``validate_runreport``)."""
        self.parity = dict(section)

    def record_compression(self, section: Dict[str, Any]) -> None:
        """Attach an :func:`~.comm_model.compression_report` section as the
        report's optional ``compression`` section (the quantized-collective
        policy next to predicted-vs-ledger-measured wire bytes per axis;
        validated by ``validate_runreport``)."""
        self.compression = dict(section)

    def record_autoplan(self, section: Dict[str, Any]) -> None:
        """Attach a ``dist.autoplan.plan`` result as the report's optional
        ``autoplan`` section (candidates considered, pruned-OOM count,
        chosen plan with per-term score breakdowns, and — when the caller
        ran plans through measured steps — the ``modeled_vs_measured``
        audit record; validated by ``validate_runreport``)."""
        self.autoplan = dict(section)

    def record_serving(self, summary: Dict[str, Any]) -> None:
        """Attach a ``ServingEngine.serving_summary()`` as the report's
        optional ``serving`` section (TTFT/TPOT percentiles, aggregate
        tokens/s, slot occupancy, KV-pool utilization — validated by
        ``validate_runreport``)."""
        self.serving = dict(summary)

    def record_router(self, summary: Dict[str, Any]) -> None:
        """Attach a ``serving.Router.summary()`` as the report's optional
        ``router`` section: one full serving section per replica plus
        the fleet roll-up (fleet tokens/s + goodput, affinity hit rate,
        migration count/bytes, rebalance/evacuation counts, per-replica
        verdicts — validated by ``validate_runreport``)."""
        self.router = dict(summary)

    # ------------------------------------------------------------- finalize

    def _steady_steps(self) -> List[Dict[str, Any]]:
        """Records excluding compile-tainted steps (the first record and any
        recompiled one): those intervals time XLA, not the steady state."""
        if not self.history:
            return []
        first = self.history[0]["step"]
        return [
            r for r in self.history
            if not r.get("recompiled") and r["step"] != first
        ]

    def finalize(
        self,
        extra: Optional[Dict[str, Any]] = None,
        write: bool = True,
        print_summary: bool = True,
    ) -> Dict[str, Any]:
        """Build the end-of-run report; on the master process write
        ``RUNREPORT.json`` + markdown (when a report path is configured)
        and hand the summary to every sink.  Collective when
        ``process_count > 1`` (cross-host step-time aggregation) — call it
        on every process, as with any collective."""
        steady = self._steady_steps()
        times = [r["step_time_s"] for r in steady]
        stats = _agg.step_time_stats(times)
        hosts = _agg.cross_host_step_stats(times, event_log=self.events)

        span_means: Dict[str, float] = {}
        for name in ("data", "dispatch", "device", "fetch"):
            vals = [r[f"span_{name}_s"] for r in steady if f"span_{name}_s" in r]
            if vals:
                span_means[name] = float(np.mean(vals))

        throughput: Dict[str, Any] = {}
        tps = [r["tok_per_sec"] for r in steady if "tok_per_sec" in r]
        if tps:
            throughput["tokens_per_sec"] = float(np.mean(tps))
            throughput["tokens_per_sec_final"] = float(tps[-1])
            # trajectory downsampled to <= 64 points so the artifact stays
            # readable for long runs
            stride = max(1, len(tps) // 64)
            throughput["trajectory"] = [round(t, 2) for t in tps[::stride]]

        mfu: Dict[str, Any] = {}
        mean_t = stats.get("mean", 0.0)
        if mean_t > 0:
            if self.xla_cost.get("flops"):
                mfu["xla_flops_per_step"] = self.xla_cost["flops"]
                mfu["xla_flops_per_sec"] = self.xla_cost["flops"] / mean_t
                if self.peak_flops:
                    mfu["xla"] = round(
                        self.xla_cost["flops"] / mean_t / self.peak_flops, 4)
            if self.xla_cost.get("bytes_accessed"):
                mfu["xla_bytes_per_step"] = self.xla_cost["bytes_accessed"]
            if self.flops_per_token and self.tokens_per_step:
                formula = self.flops_per_token * self.tokens_per_step
                mfu["formula_flops_per_step"] = formula
                if self.peak_flops:
                    mfu["formula"] = round(formula / mean_t / self.peak_flops, 4)
                if self.xla_cost.get("flops"):
                    mfu["xla_vs_formula_rel"] = round(
                        (self.xla_cost["flops"] - formula) / formula, 4)

        comm: Dict[str, Any] = {}
        if self.comm_ledger is not None:
            try:
                from . import comm_model as _comm_model

                comm = _comm_model.comm_report(
                    self.comm_ledger,
                    stats.get("mean"),
                    xla_flops=self.xla_cost.get("flops"),
                    peak_flops=self.peak_flops,
                    mesh=self.mesh,
                ) or {}
            except Exception:
                comm = {}

        from . import mem_ledger as _mem

        try:
            capacity = _mem.device_capacity()
        except Exception:
            capacity = None
        kv_pool = None
        if self.serving is not None and "kv_pool" in self.serving:
            kv_pool = {
                k: self.serving["kv_pool"].get(k)
                for k in ("pool_bytes", "pool_bytes_expected", "num_blocks",
                          "block_size", "dp_groups")
                if k in self.serving["kv_pool"]
            } or None
        memory = _mem.mem_report(
            programs=self.mem_ledgers,
            measured_peak_bytes=self._peak_bytes or None,
            measured_peak_frac=self._peak_frac or None,
            capacity_bytes=capacity,
            timeline=self.mem_timeline,
            kv_pool=kv_pool,
            emit=not self._oom_emitted,
        )
        # the two keys every pre-existing consumer reads stay put
        memory["peak_bytes_in_use"] = self._peak_bytes
        memory["reported"] = self._peak_bytes > 0

        from . import numerics as _numerics

        numerics_sec = _numerics.numerics_report(
            timeline=self.numerics_timeline,
            dtype_ledgers=self.dtype_ledgers,
            events=self.events.as_list(),
            parity=self.parity,
            thresholds=self.numerics_thresholds,
        )

        if self.xla_trace is not None:
            self.xla_trace.close()
        self.events.emit("run_end", run=self.run, steps=self._step_n)
        report = {
            "schema": _report.RUNREPORT_SCHEMA,
            "run": self.run,
            "backend": self._backend,
            "chip": self._chip,
            "n_devices": self._n_devices,
            "n_processes": self._n_processes,
            "steps": self._step_n,
            "wall_time_s": round(time.monotonic() - self._t_start, 3),
            "step_time_s": stats,
            "spans_mean_s": span_means,
            "throughput": throughput,
            "mfu": mfu,
            "memory": memory,
            "numerics": numerics_sec,
            "compile": {
                "count": self.n_compiles,
                "time_s": round(self.compile_time_s, 3),
                # same-step re-signature compiles only: two DIFFERENT
                # wrapped steps (a schedule A/B) are two first compiles
                "recompiles": self.n_recompiles,
            },
            "hosts": hosts,
            "comm": comm,
            "counters": self.counters,
            "events": self.events.as_list(),
        }
        if self.resilience is not None:
            report["resilience"] = self.resilience
        if self.serving is not None:
            report["serving"] = self.serving
        if self.router is not None:
            report["router"] = self.router
        if self.compression is not None:
            report["compression"] = self.compression
        if self.autoplan is not None:
            report["autoplan"] = self.autoplan
        if extra:
            report.update(extra)
        if self._is_master:
            for s in self.sinks:
                try:
                    s.write_summary(report)
                except Exception:
                    pass
            if write and self.report_path:
                _report.write_runreport(report, self.report_path)
            if write and self.trace_path:
                from . import trace as _trace

                _trace.export_trace(self, self.trace_path)
            if print_summary:
                from ..utils.logging import master_print

                master_print(_report.render_summary_line(report))
        return report
