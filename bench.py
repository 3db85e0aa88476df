"""Benchmark: flagship GPT training throughput (tokens/sec/chip).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "config",
"chip", "mfu", "peak_flops_est"}.

On TPU: a GPT-125M-class model at seq 2048, bf16 matmuls, full train step
(fwd+bwd+adamw) on the available chip(s) (single-chip DP mesh when only
one), PLUS a ~1B-param config (``--big``: d2048/L16, remat + streamed CE —
the north-star direction) measured in its own child first so the 1b line
precedes the headline 125m line.  Set ``BENCH_BIG=0`` to skip.
A run needs a TPU: with none attached it exits non-zero.  Only a caller that
sets ``JAX_PLATFORMS=cpu`` itself gets the tiny CPU-sim config, and its lines
say ``tokens/sec/cpu-sim-device`` — a count for the harness tests, not a speed.

Baseline policy (BASELINE.md "first measurement wins" + VERDICT r2 item 2):
``BENCH_BASELINE.json`` stores one record per **(backend, config)** — a new
config NEVER overwrites another config's record — and ``vs_baseline`` is
computed against the BEST value recorded for the backend, so switching to a
slower config reports < 1.0 instead of silently re-basing.

MFU: model FLOPs/token = 6·N_params + 12·L·S·D (PaLM-style accounting:
6N for the dense matmuls fwd+bwd, 12·L·S·D for the attention score/value
matmuls; remat recompute is hardware overhead and deliberately NOT counted —
MFU is model FLOPs over peak). Peak bf16 FLOP/s looked up by device_kind
(table shared with the obs subsystem: ``obs.peak_flops_for``).  The line
ALSO carries the obs-derived cross-check from XLA ``cost_analysis`` of the
compiled step (``mfu_xla``, ``flops_per_token_xla``,
``mfu_xla_vs_formula_rel``): compiler-counted FLOPs include non-matmul ops
and remat recompute, so xla >= formula and a small positive rel diff is
expected; a LARGE one is printed to stderr, never hidden.

A/B mode: ``python bench.py --ab`` runs the candidate
(batch, remat, xent_chunk) configs ONE CHILD PROCESS EACH (fresh backend per
candidate — an OOM/hang in one config cannot abort the others, and there is
no allocator-fragmentation carry-over), printing one JSON line per config
plus a "winner" line, and recording each config's first measurement in the
baselines file. Use this to choose the default config honestly.

Overlap A/B: ``python bench.py --overlap on`` applies the latency-hiding
XLA preset (``dist/overlap.py``, validated against the local jaxlib)
inside the measurement child before backend init; ``--overlap off`` runs
the identical config untouched.  Both rows carry the same ``config_hash``
(the pairing key), an ``overlap`` field naming the arm, and the compiled
step's HLO async evidence (``overlap_async_ops``,
``overlap_async_bytes_fraction``, ``overlap_mean_sched_distance`` from the
comm ledger) so the A/B proves WHERE the win comes from, not just that it
exists.  See docs/overlap.md.

Process structure: the parent never touches JAX (a process that has holds
the chip, and a child that needs it then fails), and runs one measurement
child at a time (``BENCH_ACCEL_TIMEOUT``, default 900 s; ``BENCH_CPU_TIMEOUT``,
default 600 s, under an explicit ``JAX_PLATFORMS=cpu``).  A child that fails
or times out makes the run exit non-zero.
Run with ``--measure`` to execute the measurement directly in-process.
"""

import dataclasses
import functools
import json
import os
import subprocess
import sys
import time

# (batch_per_chip, remat, xent_chunk) A/B candidates on the accelerator;
# module scope so the parent's --ab timeout scales with the same list the
# child runs.  The default single-config run uses the first entry — keep it
# set to the A/B winner (docs/BENCH_AB.md).  xent_chunk streams the head+CE
# over sequence chunks (gpt_loss(xent_chunk=...)) instead of materializing
# the ~2 GB [B, S, V] logits.
TPU_CANDIDATES = [
    (16, "flash", None),
    (16, True, None),
    (8, False, None),
]

# ~1B-param candidates (--big): the north-star direction (BASELINE.json
# targets a 7B mixed-parallel model; a 125M single-chip record must not be
# the framework's ceiling).  d2048/L16/seq2048 ≈ 0.94B params; remat +
# streamed CE are mandatory at this size on a 16 GB chip.  Larger d
# amortizes the non-matmul fraction, so MFU should EXCEED the 125M
# config's (target >= 0.45).
BIG_CANDIDATES = [
    (4, "flash", 256),
    (8, "flash", 256),
    (4, True, 256),
    (8, True, 256),
    # residuals offloaded to pinned_host: HBM cost of the 'flash' policy
    # drops to ~one block in flight — candidate for batches that OOM in
    # plain 'flash' mode
    (16, "flash_offload", 256),
]

# Long-context candidates (--long): the 125M model at seq 8192 — the
# single-chip long-S story (CP spreads S across chips; this measures the
# per-chip leaf: flash tiles at long S + remat='flash' + streamed CE).
# (1024, 1024) tiles measured fastest at EVERY v5e shape including S=8192
# (docs/FLASH_TUNE_v5e.json, 4 reports).  Measured 2026-07-31: b2 flash
# 54,868 tok/s (MFU 0.437) beats b4 52,208 and b2 flash_offload 45,704
# (the offload is a memory lever; it costs host-DMA bandwidth when the
# shape fits in HBM — docs/BENCH_AB.md session 5).
LONG_CANDIDATES = [
    (2, "flash", 512),
    (4, "flash", 512),
    (2, "flash_offload", 512),
]
# MoE candidates (--moe): GPT-MoE on one chip (EP=1 — expert compute is
# local; this measures the ROUTING + DISPATCH + expert-FFN leaf the EP
# all_to_all wraps at scale).  4-tuples: (batch, remat, xent_chunk,
# dispatch).  Measured 2026-07-31 (docs/BENCH_AB.md): b8 sorted 66,636
# tok/s (MFU 0.358 activated) wins; sorted beats dense 10.2% at the
# identical b2 config.  Dense at b>=4 is untestable (the [T, E, C]
# one-hots alone exceed HBM).
MOE_CANDIDATES = [
    (8, "flash", None, "sorted"),
    (16, "flash", None, "sorted"),
    (2, "flash", None, "sorted"),
    (2, "flash", None, "dense"),
]

# Retired candidates (recorded in BENCH_BASELINE.json / docs/BENCH_AB.md):
# (32, True, None) 22,263 collapses (spills); (16, False, 256) OOMs —
# streamed CE removes the logits but b16 no-remat still saves every block
# activation (12 x [16, 2048, 768] bf16 + per-head tensors), which exhausts
# v5e HBM.  Session-4 (2026-07-31) on-chip results: post-tile-tune,
# b16+remat (85,299) beat b8 no-remat (82,765); remat='flash' (save the
# flash kernel's o/lse so the backward skips its fwd re-run) pushed b16 to
# 89,815 — the current record and headline default.  Larger flash-remat
# batches lost ground (b24 87,127; b32+ce256 85,618): past b16 the extra
# arithmetic intensity no longer covers the saved-activation traffic.
# ce256 variants cost ~2% at 125M and stay retired from the sweep (the
# streamed CE is a memory lever, not a throughput one).

def _peak_flops(device_kind: str):
    # the lookup table lives in obs.telemetry (one source for the repo);
    # only measurement children call this, so the import stays out of the
    # jax-free parent process
    from torchdistpackage_tpu.obs import peak_flops_for

    return peak_flops_for(device_kind)


def _only_index(argv):
    """--only N: restrict an --ab child to candidate N (one child per
    candidate keeps an OOM in one config from aborting the others)."""
    for i, a in enumerate(argv):
        if a == "--only" and i + 1 < len(argv):
            return int(argv[i + 1])
    return None


def _flag_value(argv, flag):
    """Value of ``--flag path`` style args (None when absent)."""
    for i, a in enumerate(argv):
        if a == flag and i + 1 < len(argv):
            return argv[i + 1]
    return None


def _measure() -> None:
    import jax

    from torchdistpackage_tpu.dist.overlap import compile_cache

    # the per-candidate children compile near-identical HLO: share one
    # persistent cache across them
    compile_cache()

    # --overlap on: apply the latency-hiding XLA preset BEFORE the first
    # device touch (flags are parsed at backend init; dist/overlap.py
    # validates them against this jaxlib and drops what it rejects).
    # --overlap off runs the identical config with no flag changes — the
    # paired A/B row.
    ov = _flag_value(sys.argv, "--overlap")
    if ov not in (None, "on", "off"):
        raise SystemExit(f"--overlap must be 'on' or 'off', got {ov!r}")
    if ov == "on":
        from torchdistpackage_tpu.dist import overlap as _overlap

        _overlap.configure(preset="auto")
    # --grad-compress {off,int8,auto}: run the step through a DataParallel
    # mesh so the grad reduction is an explicit, ledgered collective (the
    # A/B's comm_bytes_per_dim delta is the headline).  On an explicit
    # JAX_PLATFORMS=cpu run there is only one device and no collective to
    # measure — bootstrap the 8-device sim (must precede backend init).
    gc = _flag_value(sys.argv, "--grad-compress")
    if gc not in (None, "off", "int8", "auto"):
        raise SystemExit(
            f"--grad-compress must be 'off', 'int8' or 'auto', got {gc!r}")
    # --autoplan: plan the parallelism from the three cost models
    # (dist/autoplan.py) and run the chosen plan against the hand-picked
    # default at equal config_hash.  Like --grad-compress, an explicit
    # JAX_PLATFORMS=cpu run bootstraps the 8-device sim so there is a
    # mesh to plan over.
    autoplan = "--autoplan" in sys.argv
    if (gc or autoplan) and os.environ.get("JAX_PLATFORMS") == "cpu":
        from torchdistpackage_tpu.dist.overlap import cpu_sim

        cpu_sim(8)
    import jax.numpy as jnp

    main(jax, jnp, ab="--ab" in sys.argv, only=_only_index(sys.argv),
         big="--big" in sys.argv, long="--long" in sys.argv,
         moe="--moe" in sys.argv, trace=_flag_value(sys.argv, "--trace"),
         overlap=ov, grad_compress=gc, autoplan=autoplan)


def _load_baselines(path: str) -> dict:
    """{backend: {config_str: record}} with migration from the two legacy
    layouts (flat record; {backend: record})."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return {}
    if "backend" in raw and "value" in raw:  # oldest: one flat record
        raw = {raw["backend"]: raw}
    out = {}
    for backend, rec in raw.items():
        if isinstance(rec, dict) and "value" in rec:  # legacy: one per backend
            out[backend] = {rec.get("config", "?"): rec}
        else:
            out[backend] = dict(rec)
    return out


def _best_recorded(baselines: dict, backend: str, fallback: float,
                   metric: str = None) -> float:
    """The BEST value recorded for ``backend`` across configs OF THE SAME
    metric (size class) — the vs_baseline denominator.  A config switch can
    never re-base history, but different model sizes are different series:
    the 1b config must not report vs_baseline ~0.1 merely because a 125m
    record exists."""
    return max(
        (
            r["value"]
            for r in baselines.get(backend, {}).values()
            # records predating metric stamping match NO scoped query — a
            # legacy 125m record must not pollute the 1b denominator
            if metric is None or r.get("metric") == metric
        ),
        default=fallback,
    )


def _record_baseline(baselines: dict, path: str, backend: str, config: str,
                     value: float, chip: str = "?",
                     metric: str = "gpt-train-throughput",
                     unit: str = "tokens/sec/chip") -> None:
    """First measurement of (backend, config) wins; later runs never touch it."""
    per_cfg = baselines.setdefault(backend, {})
    if config not in per_cfg:
        per_cfg[config] = {
            "backend": backend, "value": value,
            "unit": unit, "config": config,
            "recorded": time.strftime("%Y-%m-%d"),
            "chip": chip, "metric": metric,
        }
        try:
            with open(path, "w") as f:
                json.dump(baselines, f, indent=1)
        except OSError:
            pass  # read-only checkout: keep reporting, skip recording


def _run_config(jax, jnp, cfg, batch_size, steps, warmup, remat, xent_chunk=None,
                trace=None, grad_compress=None):
    """One timed measurement; returns (tokens_per_sec_chip, global_batch,
    flops_per_token, xla_flops_per_token, comm_ledger, mem).

    ``mem`` carries the run's memory AND numerics evidence columns merged
    straight onto the JSON line: ``peak_hbm_bytes`` (max per-device
    measured peak) and ``mem_headroom_frac`` (1 - peak/capacity on the
    hottest device) when the backend reports memory stats, plus
    ``mem_modeled_peak_bytes`` from the compiled step's static buffer
    ledger ({} on the CPU sim); ``grad_norm_final`` — the global grad
    norm of the LAST timed step, computed inside the same compiled
    program (obs.numerics.global_grad_norm, shared with clip) so a bench
    round also certifies the math was alive, not just fast; and
    ``dtype_flop_frac`` — the compiled step's matmul-FLOP mix per dtype
    from the HLO dtype ledger (bf16 vs f32 vs int8 — the precision
    evidence, printed as a table on stderr).

    ``comm_ledger`` is the HLO collective ledger of the compiled step
    (``obs.comm_ledger``) — None when AOT compilation was unavailable.
    ``trace``: path — after the timed loop, re-run a few steps under
    ``obs.Telemetry`` and export the Perfetto host trace there (costs one
    extra AOT compile; opt-in).

    ``xla_flops_per_token`` comes from XLA ``cost_analysis`` of the
    *compiled* step (obs.compiled_cost — compiler ground truth, per
    device), vs the 6N+12LSD hand formula of ``flops_per_token``.  The two
    bracket the truth from opposite sides: XLA counts EVERYTHING it runs
    (non-matmul ops, optimizer, remat recompute), the hand formula counts
    model matmul FLOPs only — so XLA >= formula, with the gap widening
    under remat.  None when the backend reports no cost analysis."""
    import optax

    from torchdistpackage_tpu.models import gpt_loss, init_gpt_params

    if cfg.moe_experts:
        from torchdistpackage_tpu.models import gpt_moe_loss, init_gpt_moe_params

        params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)

        def loss_fn(p, batch):
            return gpt_moe_loss(p, batch, cfg, remat=remat)

    else:
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)

        def loss_fn(p, batch):
            return gpt_loss(p, batch, cfg, remat=remat, xent_chunk=xent_chunk)

    opt = optax.adamw(3e-4)
    state = opt.init(params)

    # DP mesh over all attached chips so per-chip throughput is honest on
    # multi-chip hosts: params replicated, batch sharded on its leading dim.
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_chips = max(1, jax.device_count())
    mesh = Mesh(jax.devices(), axis_names=("data",))
    replicated = NamedSharding(mesh, P())
    batch_sharded = NamedSharding(mesh, P("data"))
    params = jax.device_put(params, replicated)
    state = jax.device_put(state, replicated)

    # 6N counts only matmul params: tok_emb/pos_emb forwards are gather/add
    # (backward scatter-add), never executed as matmuls — counting them would
    # inflate MFU ~15% at this vocab size (the head matmul params DO count).
    # MoE: experts count at top_k/E — each token's FLOPs touch only its
    # routed experts (the standard sparse-MFU accounting); router counts in
    # full.
    n_matmul_params = 0
    for k, sub in params.items():
        if k in ("tok_emb", "pos_emb"):
            continue
        if k == "blocks" and isinstance(sub, list):  # MoE heterogeneous list
            for bp in sub:
                for name, leafset in bp.items():
                    if name == "moe":
                        ex = sum(l.size for l in jax.tree.leaves(leafset["experts"]))
                        n_matmul_params += leafset["router"]["w"].size
                        n_matmul_params += ex * cfg.moe_top_k // cfg.moe_experts
                    else:
                        n_matmul_params += sum(
                            l.size for l in jax.tree.leaves(leafset))
        else:
            n_matmul_params += sum(l.size for l in jax.tree.leaves(sub))
    flops_per_token = (
        6 * n_matmul_params + 12 * cfg.nlayers * cfg.max_seq * cfg.dim
    )

    # donate params/opt-state: relaxes buffer lifetimes so XLA updates in
    # place instead of holding input AND output copies of ~1.6 GB of
    # params+moments — a pure lifetime annotation, no semantic change
    from torchdistpackage_tpu.obs.numerics import global_grad_norm

    if grad_compress is not None:
        # --grad-compress arm: the step runs through DataParallel so the
        # grad reduction is an EXPLICIT shard_map collective the ledger
        # can attribute (the plain-jit replicated step has no dp
        # collective to compress).  'off' takes the identical DP path
        # with the exact pmean — the paired baseline.  compress_min_size
        # is lowered so the tiny CPU-sim config's leaves qualify.
        from torchdistpackage_tpu.parallel.data_parallel import DataParallel

        dp = DataParallel(
            mesh=mesh,
            grad_compress=None if grad_compress == "off" else grad_compress,
            compress_min_size=4096,
        )
        step = dp.make_train_step(loss_fn, opt, numerics=True)
    else:
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            # numerics evidence rides in the same program: one extra scalar
            gnorm = global_grad_norm(grads)
            updates, state = opt.update(grads, state, params)
            return jax.tree.map(jnp.add, params, updates), state, loss, gnorm

    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    global_batch = batch_size * n_chips
    batch = {
        "tokens": jax.random.randint(k1, (global_batch, cfg.max_seq), 0, cfg.vocab_size),
        "targets": jax.random.randint(k2, (global_batch, cfg.max_seq), 0, cfg.vocab_size),
    }
    batch = jax.device_put(batch, batch_sharded)

    # AOT-compile so XLA's own cost analysis of the EXACT program being
    # timed is captured (no second trace/compile: the compiled executable
    # is what the loop runs).  Per-device FLOPs -> per-token via the
    # per-chip token count.
    from torchdistpackage_tpu.obs import compiled_cost, ledger_from_compiled
    from torchdistpackage_tpu.obs import mem_ledger as _mem
    from torchdistpackage_tpu.obs import numerics as _numerics

    xla_flops_per_token = None
    run_step = compiled = step.lower(params, state, batch).compile()
    cost = compiled_cost(compiled)
    if cost.get("flops"):
        xla_flops_per_token = cost["flops"] / (
            global_batch * cfg.max_seq / n_chips)
    # the same no-second-compile hook feeds the comm ledger: which
    # collectives the step runs, over which axes, moving which bytes
    ledger = ledger_from_compiled(compiled, mesh=mesh)
    # ... the static memory ledger (args/temps/donation savings) ...
    mem_led = _mem.static_ledger(compiled, label="train_step")
    # ... and the per-dtype HLO ledger (bf16 vs f32 vs int8 mix)
    dtype_led = _numerics.dtype_ledger_from_compiled(
        compiled, label="train_step")

    # The steps form a data dependency chain (params feed the next step), so
    # fetching the final loss waits for the whole run.
    for _ in range(warmup):
        params, state, loss, gnorm = run_step(params, state, batch)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, state, loss, gnorm = run_step(params, state, batch)
    float(loss)
    dt = time.perf_counter() - t0
    # the DP (--grad-compress) step returns the fused numerics-stats dict
    # in the gnorm slot; the plain step returns the bare scalar
    grad_norm_final = float(
        gnorm["grad_norm"] if isinstance(gnorm, dict) else gnorm)

    if trace:
        # opt-in Perfetto host trace of the SAME step: a short
        # Telemetry-wrapped run after the timed loop (separate so the
        # wrapper's bookkeeping can't pollute the measurement)
        from torchdistpackage_tpu.obs import Telemetry, export_trace

        tel = Telemetry(run="bench", tokens_per_step=global_batch * cfg.max_seq,
                        report_path="", trace_path="", mesh=mesh,
                        poll_memory=False)
        tstep = tel.wrap_step(step)
        for i in range(3):
            params, state, loss, gnorm = tstep(params, state, batch)
            tel.end_step(step=i, loss=loss, grad_norm=gnorm)
        tel.finalize(write=False, print_summary=False)
        export_trace(tel, trace)
        print(f"bench: wrote Perfetto trace to {trace}", file=sys.stderr)

    # memory evidence for the JSON line: measured per-device peak +
    # headroom against capacity (the number that decides whether a bigger
    # batch even runs), modeled static peak alongside
    mem = {}
    live = _mem.live_memory()
    if live["reported"]:
        mem["peak_hbm_bytes"] = max(
            r["peak_bytes_in_use"] for r in live["per_device"])
        if live["peak_frac"]:
            mem["mem_headroom_frac"] = round(1.0 - live["peak_frac"], 4)
    if mem_led is not None:
        mem["mem_modeled_peak_bytes"] = mem_led["peak_estimate_bytes"]
        print(_mem.render_table(mem_led), file=sys.stderr)
    # numerics evidence: the final step's global grad norm (a NaN/0 here
    # means the measured throughput trained garbage) + the dtype FLOP mix
    mem["grad_norm_final"] = round(grad_norm_final, 6)
    if dtype_led is not None:
        if dtype_led.get("flop_frac"):
            mem["dtype_flop_frac"] = dtype_led["flop_frac"]
        print(_numerics.render_dtype_table(dtype_led), file=sys.stderr)

    return (global_batch * cfg.max_seq * steps / dt / n_chips, global_batch,
            flops_per_token, xla_flops_per_token, ledger, mem)


def _run_pp_plan_config(jax, jnp, cfg, chosen, batch_size, steps, warmup,
                        remat, microbatches=8, schedule="1f1b"):
    """Time a pp>1 plan (tokens/sec/chip, mean step seconds) through the
    PIPELINE runner: the plan's mesh + PartitionSpecs drive
    ``gpt_pipeline_1f1b`` (or ``gpt_pipeline_zb`` for ``schedule='zb'``)
    inside a ``DataParallel`` train step — the schedule the planner's pp
    compute term models is the schedule that runs, so pp plans are now
    *measured*, not just scored (the ROADMAP item-1 follow-up).  The
    batch rides ``[M, global_batch/M, S]`` with dim 1 sharded over
    ``data``; ``xent_chunk`` does not apply (the pipelined last stage
    streams per-microbatch already)."""
    import optax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchdistpackage_tpu.dist import autoplan as _autoplan
    from torchdistpackage_tpu.models import (
        gpt_pipeline_1f1b, gpt_pipeline_zb, init_gpt_params)
    from torchdistpackage_tpu.parallel.data_parallel import DataParallel

    M = microbatches
    n_chips = max(1, jax.device_count())
    global_batch = batch_size * n_chips
    if global_batch % M or (global_batch // M) % chosen["dp"]:
        raise ValueError(
            f"pp runner needs microbatches ({M}) | global batch "
            f"({global_batch}) and dp ({chosen['dp']}) | per-microbatch "
            f"rows ({global_batch // M})")
    mesh = _autoplan.build_mesh(chosen)
    specs = _autoplan.plan_param_specs(chosen, cfg)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tp_axis = "tensor" if chosen["tp"] > 1 else None
    sched_fn = gpt_pipeline_zb if schedule == "zb" else gpt_pipeline_1f1b

    def vg_fn(p, b):
        return sched_fn(p, b, cfg, num_microbatches=M, tp_axis=tp_axis,
                        sp=tp_axis is not None, remat=remat)

    opt = optax.adamw(3e-4)
    dp = DataParallel(mesh=mesh)
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        value_and_grad_fn=vg_fn, optimizer=opt, param_specs=specs,
        batch_spec={"tokens": P(None, "data"), "targets": P(None, "data")})

    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    shape = (M, global_batch // M, cfg.max_seq)
    batch = jax.device_put({
        "tokens": jax.random.randint(k1, shape, 0, cfg.vocab_size),
        "targets": jax.random.randint(k2, shape, 0, cfg.vocab_size),
    }, NamedSharding(mesh, P(None, "data")))

    for _ in range(warmup):
        sharded, state, loss = step(sharded, state, batch)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        sharded, state, loss = step(sharded, state, batch)
    float(loss)
    dt = time.perf_counter() - t0
    return global_batch * cfg.max_seq * steps / dt / n_chips, dt / steps


def _run_moe_plan_config(jax, jnp, cfg, chosen, batch_size, steps, warmup,
                         remat):
    """Time a MoE plan (tokens/sec/chip, mean step seconds) through a
    GSPMD jit step: the plan's mesh (``data x ep x tensor``) with the
    REAL ``gpt_moe_param_specs`` tree from ``plan_param_specs`` (expert
    stacks sharded over ``ep``, router replicated) and the batch over
    ``("data", "ep")`` — XLA derives the dispatch all_to_all the ep
    sharding implies, which is exactly the collective the planner's
    ``moe-all-to-all`` term prices."""
    import optax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchdistpackage_tpu.dist import autoplan as _autoplan
    from torchdistpackage_tpu.models import gpt_moe_loss, init_gpt_moe_params

    params = init_gpt_moe_params(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, batch):
        return gpt_moe_loss(p, batch, cfg, remat=remat)

    opt = optax.adamw(3e-4)
    state = opt.init(params)
    mesh = _autoplan.build_mesh(chosen)
    n_chips = max(1, jax.device_count())
    specs = _autoplan.plan_param_specs(chosen, cfg)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: x is None)
    state = jax.device_put(state, NamedSharding(mesh, P()))

    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    global_batch = batch_size * n_chips
    batch = jax.device_put({
        "tokens": jax.random.randint(
            k1, (global_batch, cfg.max_seq), 0, cfg.vocab_size),
        "targets": jax.random.randint(
            k2, (global_batch, cfg.max_seq), 0, cfg.vocab_size),
    }, NamedSharding(mesh, _autoplan.batch_partition_spec(chosen)))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, state = opt.update(grads, state, params)
        return jax.tree.map(jnp.add, params, updates), state, loss

    for _ in range(warmup):
        params, state, loss = step(params, state, batch)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, state, loss = step(params, state, batch)
    float(loss)
    dt = time.perf_counter() - t0
    return global_batch * cfg.max_seq * steps / dt / n_chips, dt / steps


def _run_plan_config(jax, jnp, cfg, chosen, batch_size, steps, warmup, remat,
                     xent_chunk=None, microbatches=8):
    """Time the planner-chosen plan (tokens/sec/chip) through the same
    model/batch/steps as :func:`_run_config`.  Four runners cover every
    executable plan (``dist.autoplan.enumerate_candidates(
    executable_only=True)``):

    - MoE configs -> :func:`_run_moe_plan_config` (GSPMD over the plan's
      ``data x ep x tensor`` mesh; MoE plans are always pp == 1, dp
      layout, uncompressed);
    - pure dp with grad compression -> ``DataParallel(grad_compress=
      'int8')`` (the int8 ring only exists on the shard_map path);
    - ``pp > 1`` -> the pipeline runner (:func:`_run_pp_plan_config`)
      driving the schedule the plan's ``pp_schedule`` names;
    - everything else (dp / fsdp / tp mixes) -> a GSPMD jit step over the
      plan's mesh with the plan's param PartitionSpecs — XLA derives the
      collectives the specs imply, which is exactly the layout the
      planner scored."""
    import optax

    if getattr(cfg, "moe_experts", 0):
        return _run_moe_plan_config(
            jax, jnp, cfg, chosen, batch_size, steps, warmup, remat)
    if chosen["pp"] > 1:
        return _run_pp_plan_config(
            jax, jnp, cfg, chosen, batch_size, steps, warmup, remat,
            microbatches=microbatches,
            schedule=chosen.get("pp_schedule") or "1f1b")

    from torchdistpackage_tpu.dist import autoplan as _autoplan
    from torchdistpackage_tpu.models import gpt_loss, init_gpt_params

    params = init_gpt_params(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, batch):
        return gpt_loss(p, batch, cfg, remat=remat, xent_chunk=xent_chunk)

    opt = optax.adamw(3e-4)
    state = opt.init(params)

    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _autoplan.build_mesh(chosen)
    n_chips = max(1, jax.device_count())
    specs = _autoplan.plan_param_specs(chosen, cfg)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: x is None)
    state = jax.device_put(state, NamedSharding(mesh, P()))

    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    global_batch = batch_size * n_chips
    batch = jax.device_put({
        "tokens": jax.random.randint(
            k1, (global_batch, cfg.max_seq), 0, cfg.vocab_size),
        "targets": jax.random.randint(
            k2, (global_batch, cfg.max_seq), 0, cfg.vocab_size),
    }, NamedSharding(mesh, _autoplan.batch_partition_spec(chosen)))

    if (chosen["compress"]["grads"] and chosen["layout"] == "dp"
            and chosen["tp"] == 1):
        from torchdistpackage_tpu.parallel.data_parallel import DataParallel

        dp = DataParallel(mesh=mesh, grad_compress="int8",
                          compress_min_size=4096)
        step = dp.make_train_step(loss_fn, opt)
    else:
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            updates, state = opt.update(grads, state, params)
            return jax.tree.map(jnp.add, params, updates), state, loss

    for _ in range(warmup):
        params, state, loss = step(params, state, batch)[:3]
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, state, loss = step(params, state, batch)[:3]
    float(loss)
    dt = time.perf_counter() - t0
    return global_batch * cfg.max_seq * steps / dt / n_chips, dt / steps


def _run_autoplan(jax, jnp, cfg, batch_size, steps, warmup, remat,
                  xent_chunk, baselines, baseline_path, backend, chip, peak,
                  size_tag, unit) -> None:
    """The ``--autoplan`` A/B: measure the hand-picked default, close the
    loop (the measured step calibrates the compute term; a comm_bench
    calibration grounds the comm terms incl. the int8 arms), plan, run
    the chosen plan, and emit the paired ``ap-{default,planned}`` rows at
    equal ``config_hash``.

    Pipeline plans are executable now (the ``_run_pp_plan_config``
    runner): when the chosen plan has pp>1 it is timed under the schedule
    the planner picked, and in EITHER case the best-ranked pp>1 plan is
    additionally timed under BOTH schedules (classic 1F1B and zero-
    bubble) so ``attach_measured`` carries the bubble audit — modeled
    slot-accounting bubble fractions next to a measured one
    (``measured_bubble_fraction`` for the zb arm = ``1 - t_ideal/t_zb``
    with ``t_ideal`` the no-bubble extrapolation ``t_1f1b * (1 -
    bf_1f1b)`` from the measured 1F1B arm's own slot model)."""
    import hashlib

    from torchdistpackage_tpu.dist import autoplan as _autoplan
    from torchdistpackage_tpu.obs.aggregate import (
        pipeline_bubble_fraction, pipeline_time_inflation)
    from torchdistpackage_tpu.obs.comm_model import CommModel

    n_chips = max(1, jax.device_count())
    tps_def, global_batch, fpt, fpt_xla, _ledger, _mem = _run_config(
        jax, jnp, cfg, batch_size, steps, warmup, remat,
        xent_chunk=xent_chunk)
    step_def = global_batch * cfg.max_seq / (tps_def * n_chips)
    fpt_basis = fpt_xla or fpt
    # sustained per-device FLOP/s the DEFAULT config actually achieved —
    # the measurement-grounded compute basis (HLO FLOPs / measured step)
    eff = fpt_basis * global_batch * cfg.max_seq / n_chips / step_def

    # calibrate the comm model on a dp x tp view of the attached chips so
    # the planner's per-axis alpha/beta (incl. the int8-ring arms) come
    # from THIS fabric, not the generation tables
    comm_model = None
    try:
        from jax.sharding import Mesh

        import numpy as _np

        tp_cal = 2 if n_chips % 2 == 0 and n_chips > 1 else 1
        cal_mesh = Mesh(
            _np.asarray(jax.devices()).reshape(n_chips // tp_cal, tp_cal),
            axis_names=("data", "tensor"))
        comm_model = CommModel.calibrate(
            mesh=cal_mesh, sizes=(1 << 14, 1 << 18), iters=3,
            ops=("all_reduce", "all_gather"),
            compressed_ops=("int8_all_reduce", "int8_reduce_scatter",
                            "int8_all_gather"))
    except Exception as e:
        print(f"bench: comm calibration failed ({e!r}); using the table "
              f"model", file=sys.stderr)

    # microbatch count for pp candidates: the largest power of two <= 8
    # dividing the global batch (the pp runner reshapes [M, B/M, S])
    M_plan = 8
    while M_plan > 1 and global_batch % M_plan:
        M_plan //= 2

    result = _autoplan.plan(
        cfg, n_chips, global_batch=global_batch,
        comm_model=comm_model, effective_flops=eff, fpt=fpt_basis,
        executable_only=True, device_kind=chip, microbatches=M_plan)
    chosen = result["chosen"]
    if chosen is None:
        # every executable candidate over the HBM budget: report the
        # default arm plus the verdict instead of crashing the child
        print("bench: autoplan found NO executable plan within the memory "
              f"budget ({result['n_pruned_oom']}/{result['n_candidates']} "
              "pruned)", file=sys.stderr)
        print(json.dumps({
            "metric": f"gpt-{size_tag}-train-throughput",
            "value": round(tps_def, 2), "unit": unit,
            "config": f"gpt d{cfg.dim} L{cfg.nlayers} seq{cfg.max_seq} "
                      f"b{global_batch} ap-default",
            "chip": chip, "backend": backend, "autoplan": "default",
            "autoplan_verdict": "all_oom",
            "plan_pruned_oom": result["n_pruned_oom"],
        }))
        return
    print(f"bench: autoplan chose {chosen['key']} "
          f"(modeled step {chosen['step_s'] * 1e3:.3f} ms vs default "
          f"measured {step_def * 1e3:.3f} ms; "
          f"{result['n_pruned_oom']}/{result['n_candidates']} pruned OOM)",
          file=sys.stderr)

    tps_plan, step_plan = _run_plan_config(
        jax, jnp, cfg, chosen, batch_size, steps, warmup, remat,
        xent_chunk=xent_chunk, microbatches=M_plan)
    rows = [{
        "key": chosen["key"], "modeled_step_s": chosen["step_s"],
        "measured_step_s": step_plan,
    }]
    if chosen["pp"] > 1:
        rows[0]["pp_schedule"] = chosen["pp_schedule"]
        rows[0]["modeled_bubble_fraction"] = chosen["bubble_fraction"]
        rows[0]["microbatches"] = M_plan

    # the bubble audit: time the best-ranked pp>1 plan under BOTH
    # schedules (one measurement is reused when the chosen plan IS that
    # pp plan) so the modeled 1F1B-vs-ZB tick accounting meets wall clock
    pp_row = chosen if chosen["pp"] > 1 else next(
        (r for r in result["ranked"] if r["pp"] > 1), None)
    pp_audit = None
    if pp_row is not None:
        try:
            infl = {s: pipeline_time_inflation(M_plan, pp_row["pp"], s)
                    for s in ("1f1b", "zb")}
            bf = {s: pipeline_bubble_fraction(
                M_plan, pp_row["pp"], schedule=s) for s in ("1f1b", "zb")}
            meas = {}
            for sched in ("1f1b", "zb"):
                if pp_row is chosen and sched == chosen["pp_schedule"]:
                    meas[sched] = step_plan
                else:
                    _, meas[sched] = _run_pp_plan_config(
                        jax, jnp, cfg, pp_row, batch_size, steps, warmup,
                        remat, microbatches=M_plan, schedule=sched)
            t_ideal = meas["1f1b"] * (1.0 - bf["1f1b"])
            for sched in ("1f1b", "zb"):
                rows.append({
                    "key": f"{pp_row['key']}·{sched}",
                    "modeled_step_s": (
                        pp_row["compute_s"] / infl[pp_row["pp_schedule"]]
                        * infl[sched] + pp_row["comm_s"]),
                    "measured_step_s": meas[sched],
                    "pp_schedule": sched,
                    "modeled_bubble_fraction": round(bf[sched], 4),
                    "measured_bubble_fraction": round(
                        max(0.0, 1.0 - t_ideal / meas[sched]), 4),
                    "microbatches": M_plan,
                })
            pp_audit = {
                "key": pp_row["key"], "microbatches": M_plan,
                "zb_vs_1f1b_measured": round(meas["zb"] / meas["1f1b"], 4),
                "zb_vs_1f1b_modeled": round(infl["zb"] / infl["1f1b"], 4),
                "bubble_fraction_zb": round(bf["zb"], 4),
                "bubble_fraction_1f1b": round(bf["1f1b"], 4),
            }
        except ValueError as e:
            print(f"bench: pp bubble audit skipped ({e})", file=sys.stderr)
    _autoplan.attach_measured(result, rows)

    metric = f"gpt-{size_tag}-train-throughput"
    base_config_str = (
        f"gpt d{cfg.dim} L{cfg.nlayers} seq{cfg.max_seq} b{global_batch}")
    config_hash = hashlib.sha1(
        f"{metric}|{base_config_str}".encode()).hexdigest()[:12]
    for arm, tps in (("default", tps_def), ("planned", tps_plan)):
        config_str = f"{base_config_str} ap-{arm}"
        _record_baseline(baselines, baseline_path, backend, config_str, tps,
                         chip=chip, metric=metric, unit=unit)
        line = {
            "metric": metric,
            "value": round(tps, 2),
            "unit": unit,
            "vs_baseline": round(
                tps / _best_recorded(baselines, backend, tps, metric=metric),
                4),
            "config": config_str,
            "chip": chip,
            "backend": backend,
            "config_hash": config_hash,
            "autoplan": arm,
        }
        if peak:
            line["peak_flops_est"] = peak
            line["mfu"] = round(tps * fpt / peak, 4)
        if arm == "planned":
            mvm = result["modeled_vs_measured"]["rows"][0]
            line["plan"] = chosen["key"]
            if chosen.get("ep"):
                line["plan_ep"] = chosen["ep"]
            line["autoplan_tok_s"] = round(tps, 2)
            line["plan_modeled_step_s"] = round(chosen["step_s"], 6)
            line["plan_measured_step_s"] = round(step_plan, 6)
            line["plan_modeled_vs_measured_rel"] = mvm["rel_err"]
            line["plan_candidates"] = result["n_candidates"]
            line["plan_pruned_oom"] = result["n_pruned_oom"]
            line["plan_comm_basis"] = result["basis"]["comm"]
            line["vs_default"] = round(tps / tps_def, 4)
            if chosen["pp"] > 1:
                line["plan_pp_schedule"] = chosen["pp_schedule"]
                line["bubble_fraction"] = chosen["bubble_fraction"]
                line["plan_microbatches"] = M_plan
            if pp_audit is not None:
                # the 1F1B-vs-ZB pair timed through the pipeline runner:
                # modeled vs measured schedule ratio + both tick-model
                # bubble fractions (bench_trend trends bubble_fraction)
                line["pp_audit"] = pp_audit
                line.setdefault(
                    "bubble_fraction", pp_audit["bubble_fraction_zb"])
        print(json.dumps(line))


def main(jax, jnp, ab: bool = False, only=None, big: bool = False,
         long: bool = False, moe: bool = False, trace=None,
         overlap=None, grad_compress=None, autoplan: bool = False) -> None:
    from torchdistpackage_tpu.models import GPTConfig

    backend = jax.default_backend()
    on_accel = backend == "tpu"
    if not on_accel and os.environ.get("JAX_PLATFORMS") != "cpu":
        # jax itself falls back to the CPU when libtpu finds no chip
        raise SystemExit(
            f"bench: no TPU (backend {backend!r}); only an explicit "
            f"JAX_PLATFORMS=cpu runs the CPU-sim config")

    chip = jax.devices()[0].device_kind
    peak = _peak_flops(chip)
    unit = "tokens/sec/chip" if on_accel else "tokens/sec/cpu-sim-device"

    if on_accel and moe:
        # MoE leaf: the 125M dense trunk with 8 experts every 2nd block
        # (Switch placement) — 0.57B total params, ~0.18B activated/token
        cfg = GPTConfig(
            vocab_size=32768, dim=768, nheads=12, nlayers=12, max_seq=2048,
            ffn_mult=4, dtype=jnp.bfloat16, attn_impl="flash",
            moe_experts=8, moe_top_k=2, moe_every=2,
        )
        candidates = MOE_CANDIDATES
        steps, warmup = 10, 2
        size_tag = "moe8x125m"
    elif on_accel and long:
        # long-context leaf: 125M at S=8192 (the CP ring's per-chip config)
        cfg = GPTConfig(
            vocab_size=32768, dim=768, nheads=12, nlayers=12, max_seq=8192,
            ffn_mult=4, dtype=jnp.bfloat16, attn_impl="flash",
        )
        candidates = LONG_CANDIDATES
        steps, warmup = 8, 2
        size_tag = "125m-s8k"
    elif on_accel and big:
        cfg = GPTConfig(
            vocab_size=32768, dim=2048, nheads=16, nlayers=16, max_seq=2048,
            ffn_mult=4, dtype=jnp.bfloat16, attn_impl="flash",
        )
        candidates = BIG_CANDIDATES
        steps, warmup = 10, 2
        size_tag = "1b"
    elif on_accel:
        cfg = GPTConfig(
            vocab_size=32768, dim=768, nheads=12, nlayers=12, max_seq=2048,
            ffn_mult=4, dtype=jnp.bfloat16, attn_impl="flash",
        )
        candidates = TPU_CANDIDATES
        steps, warmup = 12, 3
        size_tag = "125m"
    else:
        cfg = GPTConfig(
            vocab_size=512, dim=128, nheads=4, nlayers=4, max_seq=256,
            ffn_mult=2, dtype=jnp.float32,
        )
        candidates = [(4, False, None)]
        steps, warmup = 5, 2
        size_tag = "tiny"
        if moe:
            # tiny-MoE CPU leaf: keeps --moe --autoplan runnable on the
            # 8-device sim (the planner's ep arms need experts to shard)
            cfg = dataclasses.replace(
                cfg, moe_experts=4, moe_top_k=2, moe_every=2)
            candidates = [(4, False, None, "sorted")]
            size_tag = "tiny-moe"

    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_BASELINE.json")
    baselines = _load_baselines(baseline_path)

    if autoplan:
        # --autoplan measures the default config, plans from the three
        # cost models, and emits the paired ap-{default,planned} rows
        batch_size, remat, xent_chunk = candidates[0][:3]
        _run_autoplan(jax, jnp, cfg, batch_size, steps, warmup, remat,
                      xent_chunk, baselines, baseline_path, backend, chip,
                      peak, size_tag, unit)
        return

    if only is not None:
        if only >= len(candidates):
            # the parent sweeps TPU_CANDIDATES indices; the explicit CPU-sim
            # config has a 1-entry list — emit a marker (instead of silently
            # printing nothing with rc 0) so the parent can stop
            print(json.dumps({"skipped_candidate": only, "backend": backend}))
            return
        candidates = candidates[only:only + 1]
    elif not ab:
        candidates = candidates[:1]

    results = []
    for cand in candidates:
        batch_size, remat, xent_chunk = cand[:3]
        dispatch = cand[3] if len(cand) > 3 else None
        run_cfg = (
            dataclasses.replace(cfg, moe_dispatch=dispatch) if dispatch else cfg
        )
        tps, global_batch, fpt, fpt_xla, ledger, mem = _run_config(
            jax, jnp, run_cfg, batch_size, steps, warmup, remat,
            xent_chunk=xent_chunk, trace=trace, grad_compress=grad_compress)
        # remat: False | True | 'flash' | 'flash_offload' (save the flash
        # kernel's residuals — in HBM or pinned_host — so the backward skips
        # the Pallas fwd re-run; scan_blocks docstring)
        remat_tag = {False: "", True: " remat"}.get(remat, f" remat-{remat}")
        moe_tag = f"-moe{cfg.moe_experts}" if cfg.moe_experts else ""
        base_config_str = (
            f"gpt{moe_tag} d{cfg.dim} L{cfg.nlayers} seq{cfg.max_seq} b{global_batch}"
            f"{remat_tag}"
            f"{f' ce{xent_chunk}' if xent_chunk else ''}"
            f"{f' {dispatch}' if dispatch else ''}"
        )
        metric = f"gpt-{size_tag}-train-throughput"
        # --overlap / --grad-compress A/B pairing: each arm is a DIFFERENT
        # config for baseline recording (a flag change must not overwrite
        # the other's first-measurement record) but the arms share
        # config_hash — the join key that pairs the JSON rows of one A/B.
        config_str = base_config_str
        if overlap:
            config_str = f"{config_str} ov-{overlap}"
        if grad_compress:
            config_str = f"{config_str} gc-{grad_compress}"
        _record_baseline(baselines, baseline_path, backend, config_str, tps,
                         chip=chip, metric=metric, unit=unit)
        best = _best_recorded(baselines, backend, tps, metric=metric)
        line = {
            "metric": metric,
            "value": round(tps, 2),
            "unit": unit,
            "vs_baseline": round(tps / best, 4),
            "config": config_str,
            "chip": chip,
            "backend": backend,
        }
        if overlap or grad_compress:
            import hashlib

            line["config_hash"] = hashlib.sha1(
                f"{metric}|{base_config_str}".encode()).hexdigest()[:12]
        if grad_compress:
            line["grad_compress"] = grad_compress
        if overlap:
            line["overlap"] = overlap
            try:
                from torchdistpackage_tpu.dist.overlap import active

                rec = active() or {}
                line["overlap_preset"] = rec.get("preset")
                line["overlap_flags_applied"] = len(rec.get("applied", []))
                line["overlap_flags_dropped"] = len(rec.get("dropped", []))
            except Exception:
                pass
        if ledger is not None and ledger.get("async"):
            # the HLO-level overlap evidence for THIS compiled step: how
            # many collectives went async and how far the scheduler
            # spread their -start/-done pairs (obs.comm_ledger)
            a = ledger["async"]
            tot = ledger.get("total_bytes") or 0
            line["overlap_async_ops"] = a["ops"]
            line["overlap_async_bytes_fraction"] = (
                round(a["bytes"] / tot, 4) if tot else 0.0)
            if a.get("mean_sched_distance") is not None:
                line["overlap_mean_sched_distance"] = a["mean_sched_distance"]
        # memory columns: measured peak HBM + headroom fraction (absent on
        # the CPU sim, which reports no memory stats), modeled static peak
        line.update(mem)
        if peak:
            line["peak_flops_est"] = peak
            line["mfu"] = round(tps * fpt / peak, 4)
            if fpt_xla:
                line["mfu_xla"] = round(tps * fpt_xla / peak, 4)
        if ledger is not None:
            # comm-ledger summary next to MFU: the per-dimension collective
            # bytes of the exact compiled step the numbers above timed
            # (stderr — stdout stays one JSON line per config)
            from torchdistpackage_tpu.obs.comm_ledger import render_table

            print(render_table(ledger), file=sys.stderr)
            if ledger.get("per_dim"):
                line["comm_bytes_per_dim"] = {
                    d: v["bytes"] for d, v in ledger["per_dim"].items()}
        if fpt_xla:
            # the peak cancels in the ratio, so the cross-check works on
            # CPU too; |rel| > 15% is printed loudly, never hidden (remat
            # recompute and non-matmul ops are IN the XLA count only)
            line["flops_per_token_formula"] = round(fpt)
            line["flops_per_token_xla"] = round(fpt_xla)
            rel = (fpt_xla - fpt) / fpt
            line["mfu_xla_vs_formula_rel"] = round(rel, 4)
            if abs(rel) > 0.15:
                print(
                    f"bench: XLA cost-analysis FLOPs/token ({fpt_xla:.3e}) "
                    f"vs 6N+12LSD formula ({fpt:.3e}) disagree by "
                    f"{rel:+.1%} (remat={remat}) — see line field "
                    f"mfu_xla_vs_formula_rel", file=sys.stderr)
        results.append(line)
        if ab or only is not None:
            print(json.dumps(line))

    if ab and only is None:
        winner = max(results, key=lambda r: r["value"])
        print(json.dumps({"ab_winner": winner["config"], "value": winner["value"]}))
    elif only is None:
        print(json.dumps(results[0]))


def _run_child(timeout: float, extra_args=(), capture=False):
    """Run one bench.py ``--measure`` child.  Returns True/False, or (when
    ``capture``) the child's stdout str on success / None on failure.
    ``capture`` captures stdout ONLY — stderr stays inherited so OOM / XLA
    tracebacks from a failing candidate remain visible."""
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--measure",
             *extra_args],
            timeout=timeout,
            stdout=subprocess.PIPE if capture else None,
            text=capture,
        )
        if capture:
            sys.stdout.write(res.stdout)
            sys.stdout.flush()
            return res.stdout if res.returncode == 0 else None
        return res.returncode == 0
    except subprocess.TimeoutExpired:
        print(f"bench: child timed out after {timeout:.0f}s", file=sys.stderr)
        return None if capture else False


def _ab_main(timeout: float, big: bool = False, long: bool = False,
             moe: bool = False, overlap=None) -> bool:
    """One child per candidate: an OOM/hang in one config cannot abort the
    sweep (observed: b16 no-remat exhausts v5e HBM and killed the round-3
    sweep's remaining configs), and each child gets a fresh backend — no
    allocator fragmentation carry-over between configs.  The
    ``skipped_candidate`` marker (an index past the explicit CPU-sim
    config's 1-entry list) ends the sweep.  Returns whether any candidate
    produced a value."""
    cands = (MOE_CANDIDATES if moe else LONG_CANDIDATES if long
             else BIG_CANDIDATES if big else TPU_CANDIDATES)
    extra = (("--moe",) if moe else ("--long",) if long
             else ("--big",) if big else ())
    if overlap:
        extra = (*extra, "--overlap", overlap)
    best = None
    for i in range(len(cands)):
        out = _run_child(
            timeout, ("--ab", "--only", str(i), *extra), capture=True)
        if out is None:
            print(
                f"bench: candidate {i} {cands[i]} failed/timed out",
                file=sys.stderr,
            )
            continue
        stop = False
        for ln in out.splitlines():
            try:
                rec = json.loads(ln)
            except ValueError:
                continue
            if "skipped_candidate" in rec:
                stop = True
                continue
            if "value" in rec and (best is None or rec["value"] > best["value"]):
                best = rec
        if stop:
            break
    if best is not None:
        print(json.dumps({"ab_winner": best["config"], "value": best["value"]}))
    else:
        print(json.dumps({"ab_winner": None, "error": "no candidate succeeded"}))
    return best is not None


if __name__ == "__main__":
    if "--measure" in sys.argv:
        _measure()  # prints the JSON line(s) itself
        sys.exit(0)

    on_cpu = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    timeout = float(
        os.environ.get("BENCH_CPU_TIMEOUT", "600") if on_cpu
        else os.environ.get("BENCH_ACCEL_TIMEOUT", "900"))

    if "--ab" in sys.argv:
        ok = _ab_main(timeout, big="--big" in sys.argv,
                      long="--long" in sys.argv, moe="--moe" in sys.argv,
                      overlap=_flag_value(sys.argv, "--overlap"))
        sys.exit(0 if ok else 1)

    # `python bench.py --long` / `--moe` measure their own series
    # (gpt-125m-s8k / gpt-moe8x125m) instead of the S=2048 headline — the
    # flag must reach the measurement children or results would land in the
    # wrong baseline series while appearing to succeed.  moe-first order
    # matches _ab_main and main() so every entry point resolves a
    # conflicting `--long --moe` to the same sweep.
    child_args = (("--moe",) if "--moe" in sys.argv
                  else ("--long",) if "--long" in sys.argv else ())
    for flag in ("--trace", "--overlap", "--grad-compress"):
        # forwarded to the measurement child, which validates the value
        value = _flag_value(sys.argv, flag)
        if value:
            child_args = (*child_args, flag, value)
    if "--autoplan" in sys.argv:
        child_args = (*child_args, "--autoplan")
    ok = True
    # the ~1B north-star config measures in its OWN child first, so its line
    # precedes the headline (the parsed last line stays the 125m record
    # series); skipped under the other series and on the CPU sim
    if not on_cpu and not child_args and os.environ.get("BENCH_BIG", "1") != "0":
        ok = _run_child(timeout, ("--big",))
    ok = _run_child(timeout, child_args) and ok
    sys.exit(0 if ok else 1)
