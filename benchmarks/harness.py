"""What every run of every cell shares: finding the cell's files by name,
refusing the wrong device, the compile cache, counting compiles, tracing,
reading the per-layer metrics, and the one result line.

A cell is ``benchmarks/workloads/<cell>.json``; it names its configuration
(``benchmarks/configs/<config>.json``) and its runner
(``benchmarks/runners/<runner>.py``, a module with ``run(ctx) -> dict``).
A per-layer metric is ``benchmarks/layer_metrics/<name>.json``: a reader's
name and its arguments; the reader is a function of
``benchmarks/layer_metrics/readers.py`` or, where a metric needs its own,
``read`` in ``benchmarks/layer_metrics/<name>.py``.  Nothing here lists
cells, configurations, families or metrics.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from benchmarks import arch as A

REPO = os.path.dirname(A.ROOT)


class Refused(SystemExit):
    """The run cannot give a number: exit non-zero, print no result."""

    def __init__(self, why: str):
        super().__init__(f"benchmark refused: {why}")


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's record."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - ticks / os.sysconf("SC_CLK_TCK"))


def load_manifest() -> Dict[str, Any]:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise Refused(f"BENCHMARK.json has no workload {name!r}")


def metrics_of(manifest: Dict[str, Any], section: str, cell: str) -> List[Dict[str, Any]]:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
    moved = {m["name"] for m in manifest["end_to_end"]
             if cell in m.get("workloads", [cell])}
    out = []
    for m in manifest[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in moved:
            out.append(m)
    return out


class CompileCounter:
    """Counts JAX's compile requests.  Each one ends in a backend compile or,
    with a warm persistent cache, in a load from it; either way a program was
    made ready, which is what "nothing compiled inside the window" counts."""

    _REQUEST = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax

        self.events: collections.Counter = collections.Counter()
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name == self._REQUEST:
            self.events[name] += 1
            self.seconds += secs

    def _event(self, name: str, **kw) -> None:
        self.events[name] += 1

    @property
    def programs(self) -> int:
        return self.events[self._REQUEST]

    def snapshot(self) -> Dict[str, Any]:
        return {"compile_requests": self.programs,
                "compile_or_load_s": self.seconds,
                "cache_hits": self.events[self._HIT]}


def device_report() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """The peak on the fullest chip, as the backend reports it (0 where it
    reports nothing: the CPU)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def require_chip(chips: int) -> Dict[str, float]:
    """Refuse anything but a TPU whose kind has a row in the peaks table and
    whose device count is the cell's ``chips``; return that row."""
    import jax

    dev = device_report()
    if jax.default_backend() != "tpu":
        raise Refused(f"the default backend is {jax.default_backend()!r}, "
                      f"not a TPU")
    if dev["count"] != chips:
        raise Refused(f"the cell asks for {chips} chip(s), JAX sees "
                      f"{dev['count']}")
    return peaks_for(dev["kind"])


def peaks_for(kind: str) -> Dict[str, float]:
    table = A.load_json("peaks.json")
    if kind not in table or kind == "source":
        raise Refused(f"device kind {kind!r} has no row in benchmarks/peaks.json")
    return table[kind]


def setup_compile_cache() -> str:
    """The persistent compile cache at the program's own fixed place (its
    ``compile_cache()`` honours the environment's directory if one is set,
    else ``<checkout>/.jax_cache``), caching every program however small:
    the sub-second ones would otherwise compile on every start."""
    import jax

    from torchdistpackage_tpu.dist.overlap import compile_cache

    path = compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


@dataclasses.dataclass
class Context:
    """One run of one cell."""

    manifest: Dict[str, Any]
    workload: Dict[str, Any]       # the BENCHMARK.json entry
    cell: Dict[str, Any]           # benchmarks/workloads/<cell>.json
    config: Dict[str, Any]         # benchmarks/configs/<config>.json
    seed: int
    seconds: float
    trace: bool
    peaks: Dict[str, float]
    compiles: CompileCounter
    trace_dir: str
    #: seconds the accelerator's own runtime took to come up (the first
    #: ``jax.devices()``): no work of the program's or the benchmark's
    backend_init_s: float = 0.0
    #: test seam: passed to the engine as its fault-injection hook
    chaos: Any = None
    #: benchmarks/control.py only: also read the control, the reference in
    #: this lower precision put in the program's place ('fp8'), or the
    #: program's own lower precision switched on ('kv_int8')
    control: Optional[str] = None
    lines: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def log(self, **record: Any) -> None:
        record["at_s"] = round(process_age_s(), 3)   # seconds since exec
        self.lines.append(record)
        print(json.dumps(record, default=_jsonable), flush=True)

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def setup_seconds(self) -> float:
        """``setup_s``, read as the window opens: exec of the process to now
        (imports, weights, compiling or loading every program, warming up,
        the first checked steps), less the accelerator runtime's own
        start-up, which varied 9.6-20 s between identical runs (PERF.md).
        The line it prints gives both, so that what was left out shows."""
        from_exec = process_age_s()
        self.log(phase="window_opens", setup_s=from_exec - self.backend_init_s,
                 setup_from_exec_s=from_exec,
                 backend_init_s=self.backend_init_s)
        return from_exec - self.backend_init_s


def _jsonable(x: Any):
    try:
        return float(x)
    except (TypeError, ValueError):
        return str(x)


@contextlib.contextmanager
def span(name: str, sink: Optional[List[float]] = None):
    """A host span of the benchmark's own: on the profiler's clock as
    ``bm:<name>`` when a trace is running, and its seconds into ``sink``."""
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"bm:{name}"):
        yield
    if sink is not None:
        sink.append(time.perf_counter() - t0)


class Tracer:
    """Traces the first ``LIMIT_S`` seconds of the window (``--trace 1``);
    the rest of the window runs untraced.  ``tick()`` is called between
    steps and stops the capture once the time is up."""

    LIMIT_S = 6.0   # a few train steps, some tens of ticks; ~70k events

    def __init__(self, ctx: Context):
        self.dir = ctx.trace_dir if ctx.trace else None
        self.running = False
        self.t_start = 0.0
        self._window = None
        self.stop_s = 0.0

    def start(self) -> None:
        if self.dir is None:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation("bm:window")
        self._window.__enter__()
        self.running = True
        self.t_start = time.perf_counter()

    def tick(self, force: bool = False) -> None:
        if self.running and (
                force or time.perf_counter() - self.t_start >= self.LIMIT_S):
            import jax

            t0 = time.perf_counter()
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.running = False
            self.stop_s = time.perf_counter() - t0

    def reduce(self) -> Optional[Dict[str, Any]]:
        """busy/window seconds, top operations, idle gaps; None untraced."""
        if self.dir is None:
            return None
        from benchmarks import trace_reduce as R

        self.tick(force=True)
        devices, spans, modules = R.read_xplane(R.find_xplane(self.dir))
        win = [s for s in spans if s[0] == "bm:window"]
        if not win:
            raise Refused("the trace lacks the benchmark's window span")
        t0, t1 = win[0][1], win[0][1] + win[0][2]
        out = R.reduce_trace(devices, [s for s in spans if s[0] != "bm:window"],
                             t0, t1, modules)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


# ------------------------------------------------------- per-layer metrics


def read_layer_metric(name: str, obs: Dict[str, Any]) -> Optional[float]:
    """The metric's own file names its reader; None = nothing to read."""
    spec = A.load_json("layer_metrics", f"{name}.json")
    try:
        own = A.find_file("layer_metrics", f"{name}.py")
    except FileNotFoundError:
        own = None
    if own is not None:
        mod_spec = importlib.util.spec_from_file_location(
            f"benchmarks.layer_metrics._own_{abs(hash(own))}", own)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        fn: Callable = mod.read
    else:
        readers = importlib.import_module("benchmarks.layer_metrics.readers")
        fn = getattr(readers, spec["reader"])
    value = fn(obs, **spec.get("args", {}))
    if value is None or not math.isfinite(value):
        return None
    return float(value)


def result_line(ctx: Context, out: Dict[str, Any]) -> Dict[str, Any]:
    """The contract's last line, from what the runner returned."""
    cell = ctx.workload["name"]
    device = {**device_report(), "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics: Dict[str, Dict[str, Any]] = {}
    line: Dict[str, Any] = {
        "correct": bool(out["correct"]), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]), "metrics": metrics, "device": device}
    if not ctx.trace:
        for m in metrics_of(ctx.manifest, "end_to_end", cell):
            if m["name"] not in out["end_to_end"]:
                raise Refused(f"the runner gave no {m['name']}")
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
        return line
    tr = out["obs"].get("trace")
    if tr is None or not tr["busy_s"] > 0:
        raise Refused("a traced run in which no operation ran on the device")
    device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
    line["breakdown"] = {"device_ops": tr["device_ops"],
                         "idle_gaps": tr["idle_gaps"]}
    for m in metrics_of(ctx.manifest, "per_layer", cell):
        value = read_layer_metric(m["name"], out["obs"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return line


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             manifest: Optional[Dict[str, Any]] = None, *,
             look_for_chip: bool = True, chaos: Any = None,
             control: Optional[str] = None) -> Dict[str, Any]:
    """One run of one cell; returns the result line (and prints it last)."""
    manifest = manifest or load_manifest()
    entry = find_cell(manifest, workload)
    cell = A.load_json("workloads", f"{workload}.json")
    config = A.load_config(entry["config"])
    for key in ("config", "traffic", "chips"):
        if cell[key if key != "traffic" else "traffic_name"] != entry[key]:
            raise Refused(f"workloads/{workload}.json and BENCHMARK.json "
                          f"disagree on {key}")
    import jax

    t0 = time.perf_counter()
    jax.devices()
    backend_init_s = time.perf_counter() - t0
    if look_for_chip:
        peaks = require_chip(int(entry["chips"]))
    else:  # the tests' seam: any backend, the v5e's peaks for arithmetic
        peaks = peaks_for("TPU v5 lite")
    cache_dir = setup_compile_cache() if look_for_chip else None
    ctx = Context(
        manifest=manifest, workload=entry, cell=cell, config=config,
        seed=int(seed), seconds=float(seconds), trace=bool(trace), peaks=peaks,
        compiles=CompileCounter(), chaos=chaos, control=control,
        backend_init_s=backend_init_s,
        trace_dir=os.path.join(REPO, ".bench_trace", workload))
    ctx.log(phase="start", workload=workload, seed=ctx.seed,
            seconds=ctx.seconds, trace=ctx.trace, device=device_report(),
            jax=jax.__version__, compile_cache=cache_dir,
            backend_init_s=backend_init_s)
    runner = importlib.import_module(f"benchmarks.runners.{cell['runner']}")
    out = runner.run(ctx)
    line = result_line(ctx, out)
    ctx.log(phase="compile_cache", **ctx.compiles.snapshot())
    print(json.dumps(line), flush=True)
    line["log"] = ctx.lines
    return line
