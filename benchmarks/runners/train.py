"""The training runner: ``DataParallel.make_train_step`` around ``gpt_loss``
on the cell's mesh, started as a copy of chip_smoke.py's ``train_phase``.
Set-up builds ONE compiled step with its state, drives it from the seed
through its first ``check_steps`` steps (the readings that decide
``correct``), and hands the same object to the timed window.  A fresh seeded
batch every step, made on the host and put on the device one step ahead."""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import arch as A
from benchmarks import checks, costs, harness
from benchmarks.traffic import generator


def build_step(dp, pcfg, opt, specs, tp_axis, mix):
    """The program's train step (the tests break it here)."""
    from torchdistpackage_tpu.models import gpt_loss

    return dp.make_train_step(
        lambda p, b: gpt_loss(p, b, pcfg, axis=tp_axis, sp=tp_axis is not None,
                              remat=mix["remat"], xent_chunk=mix["xent_chunk"]),
        opt, param_specs=specs)


def first_moment(state):
    """Adam's first moment inside an optax state."""
    for part in state:
        if hasattr(part, "mu"):
            return part.mu
    raise harness.Refused("the optimizer state holds no first moment")


def run(ctx: harness.Context) -> Dict[str, Any]:
    import jax
    import optax
    from jax.sharding import NamedSharding

    from torchdistpackage_tpu.dist import tpc
    from torchdistpackage_tpu.models import gpt_param_specs
    from torchdistpackage_tpu.parallel import DataParallel

    from benchmarks.reference import train as T
    from benchmarks.weights import make_weights

    cell, mix = ctx.cell, ctx.cell["traffic"]
    fam = A.family_of(ctx.config)
    a = fam.arch(ctx.config, mix["seq"])
    pcfg = fam.program_config(ctx.config, mix["seq"])
    opt_args = cell["optimizer"]

    tpc.reset()
    tpc.setup_process_groups([tuple(ax) for ax in cell["mesh"]],
                             devices=jax.devices()[:ctx.chips])
    mesh = tpc.get_view()
    tp_axis = "tensor" if dict(
        tuple(ax) for ax in cell["mesh"]).get("tensor", 1) > 1 else None
    specs = gpt_param_specs(pcfg, tp_axis=tp_axis)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    params = make_weights(a, ctx.seed, sharding=shardings)
    dp = DataParallel(mesh=mesh)
    opt = optax.adamw(**opt_args)
    state = opt.init(params)
    step = build_step(dp, pcfg, opt, specs, tp_axis, mix)

    mesh_sizes = dict(tuple(ax) for ax in cell["mesh"])
    tp = mesh_sizes.get("tensor", 1)
    global_batch = mix["batch_per_data_group"] * mesh_sizes.get("data", 1)
    tokens_per_step = global_batch * mix["seq"]

    def host_batch(i: int):
        return generator.train_batch(mix, a.vocab, ctx.seed, i, global_batch)

    spans: Dict[str, List[float]] = {"feed": [], "dispatch": [], "fetch": [],
                                     "step": []}

    def feed(i: int):
        with harness.span("feed", spans["feed"]):
            return dp.shard_batch(host_batch(i))

    batch = feed(0)
    t0 = time.perf_counter()
    compiled = step.lower(params, state, batch).compile()
    compile_s = time.perf_counter() - t0
    mosaic = compiled.as_text().count("tpu_custom_call")
    if jax.default_backend() == "tpu" and not mosaic:
        raise harness.Refused("no Mosaic kernel in the compiled train step")

    # ---- the first steps, through the window's own call and feed
    n_check = int(cell["check_steps"])
    program: Dict[str, Any] = {"losses": []}
    for i in range(n_check):
        params, state, loss = compiled(params, state, batch)
        batch = feed(i + 1)
        program["losses"].append(float(loss))
        if i == 0:
            program["grad_norms"] = {
                k: float(v) / (1.0 - opt_args["b1"]) for k, v in
                jax.device_get(T.leaf_norms(first_moment(state))).items()}
    p0 = make_weights(a, ctx.seed, sharding=shardings)
    program["update_norms"] = {k: float(v) for k, v in jax.device_get(
        T.delta_norms(params, p0)).items()}
    del p0
    programs_before = ctx.compiles.programs
    ctx.log(phase="setup", compile_s=compile_s, mosaic_calls=mosaic,
            global_batch=global_batch, tokens_per_step=tokens_per_step,
            params=a.num_params(), check_losses=program["losses"])

    # ---- the window
    band = float(cell["loss_band_nats"])
    tracer = harness.Tracer(ctx)
    setup_s = ctx.setup_seconds()
    tracer.start()
    losses: List[float] = []
    t_start = time.perf_counter()
    i = n_check
    while True:
        t_step = time.perf_counter()
        with harness.span("dispatch", spans["dispatch"]):
            params, state, loss = compiled(params, state, batch)
        batch = feed(i + 1)
        with harness.span("fetch", spans["fetch"]):
            losses.append(float(loss))
        now = time.perf_counter()
        spans["step"].append(now - t_step)
        tracer.tick()
        i += 1
        if now - t_start >= ctx.seconds:
            break
    # stopping the profiler is the benchmark's own time, not the program's
    window_s = now - t_start - tracer.stop_s
    trace = tracer.reduce()
    if ctx.compiles.programs != programs_before:
        raise harness.Refused(
            f"{ctx.compiles.programs - programs_before} programs compiled "
            f"inside the window")
    peak = harness.memory_peak_bytes()
    steps = len(losses)
    bad = [x for x in losses
           if not (math.isfinite(x) and abs(x - math.log(a.vocab)) <= band)]
    tok_s_chip = steps * tokens_per_step / window_s / ctx.chips
    ctx.log(phase="window", steps=steps, window_s=window_s,
            step_s_median=float(np.median(spans["step"])),
            loss_first=losses[0], loss_last=losses[-1],
            memory_peak_bytes=peak, tracer_stop_s=tracer.stop_s)

    # ---- the reference, once the program's state is freed
    del params, state, batch, compiled
    t_ref = time.perf_counter()
    ref = checks.follow_training(
        a, ctx.seed, opt_args, [host_batch(k) for k in range(n_check)])
    numbers = checks.train_numbers(program, ref)
    leaves = numbers.pop("_leaves")
    v = checks.verdict(numbers, cell["limits"])
    ctx.log(phase="check", reference_s=time.perf_counter() - t_ref,
            program_losses=program["losses"], reference_losses=ref.losses,
            worst_leaves=leaves, **v)

    if ctx.control:
        low = checks.follow_training(
            a, ctx.seed, opt_args, [host_batch(k) for k in range(n_check)],
            quant=ctx.control)
        cn = checks.train_numbers(
            {"losses": low.losses, "grad_norms": low.grad_norms[0],
             "update_norms": low.update_norms}, ref)
        cn.pop("_leaves")
        ctx.log(phase="control", precision=ctx.control,
                **checks.verdict(cn, cell["limits"]))

    flops_tok = costs.train_flops_per_token(a, mix["seq"])
    obs = {
        "spans": spans,
        "values": {
            "tokens_per_s_per_chip": tok_s_chip,
            "flops_per_token": flops_tok,
            "peak_flops": ctx.peaks["bf16_flops"],
            "memory_peak_bytes": peak,
        },
        # one chip's share of one layer's flash forward and backward
        "costs": {"flash": {
            **{k: v / tp for k, v in costs.flash_fwd_bwd(
                a, mix["batch_per_data_group"], mix["seq"]).items()},
            "calls_per_execution": a.layers}},
        "peaks": ctx.peaks,
        "trace": trace,
    }
    return {
        "correct": v["correct"] and not bad,
        "attempted": steps, "failed": len(bad),
        "memory_peak_bytes": peak,
        "end_to_end": {"setup_s": setup_s, "train_tok_s_chip": tok_s_chip},
        "obs": obs,
    }
