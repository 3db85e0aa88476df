"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the main path once, through the entry points a user calls, at the full
width of the repo's ~1B GPT (d2048, 16 heads x 128, vocab 32768, bf16, flash
attention; all 16 layers): the Pallas kernels against their oracles, a
``ServingEngine`` that answers six requests, and a ``DataParallel`` trainer that
takes six steps under ``Telemetry``.  With four devices the same phases run on
``[("data", 2), ("tensor", 2)]`` (TP+SP training, tp_dp serving) and
``dryrun_multichip`` adds its three tiny compositions, so every collective
family compiles over ICI once.

It refuses to run unless JAX's default backend is a TPU whose ``device_kind``
has a row in the peaks table, and exits non-zero when any check fails: there is
no path that downgrades a failure to a warning.  Everything happens in this one
process, because a chip belongs to one process at a time.  The last line of
standard output is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it; every line before it is one JSON object per phase.

The phases are plain functions of a :class:`SmokeConfig`, so
tests/test_chip_smoke.py runs them at toy size on the CPU simulator.  Only
``main`` knows the real sizes, and it has no size or platform switch.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import sys
import time
from typing import Any, Dict, List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    model: Any                          # models.GPTConfig
    mesh: Tuple[Tuple[str, int], ...]   # ordered (axis, size) over ALL devices
    # train: one fixed batch, `train_steps` steps
    batch_per_chip: int = 4
    train_steps: int = 6
    xent_chunk: int = 256
    # serve: engine geometry and one (prompt, new tokens) pair per request
    num_slots: int = 8
    block_size: int = 128
    chunk: int = 256
    requests: Tuple[Tuple[int, int], ...] = (
        (100, 32), (317, 48), (520, 64), (777, 40), (1100, 56), (1500, 33))
    seed: int = 0

    @property
    def tp(self) -> int:
        return dict(self.mesh).get("tensor", 1)

    @property
    def dp(self) -> int:
        return dict(self.mesh).get("data", 1)


class CompileCounter:
    """Counts JAX's compile requests.  Each one ends in a backend compile or,
    with a warm persistent cache, in a load from it; either way a program was
    made ready, which is what "nothing compiled after warm-up" must count."""

    _REQUEST = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

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
        """Programs made ready so far."""
        return self.events[self._REQUEST]

    def snapshot(self) -> Dict[str, Any]:
        # a miss is counted when a program is WRITTEN: jax caches only those
        # that took over a second to compile
        return {"compile_requests": self.programs,
                "compile_or_load_s": round(self.seconds, 2),
                "cache_hits": self.events[self._HIT],
                "cache_misses": self.events[self._MISS]}


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _mosaic_calls(programs) -> int:
    """Mosaic-compiled Pallas kernels in the given compiled programs.  The
    Pallas interpreter leaves none: it lowers a kernel to plain HLO."""
    return sum(p.as_text().count("tpu_custom_call") for p in programs)


def _setup_mesh(cfg: SmokeConfig):
    import jax

    from torchdistpackage_tpu.dist import tpc

    tpc.reset()
    tpc.setup_process_groups(list(cfg.mesh), devices=jax.devices())
    return tpc.get_view()


# --------------------------------------------------------------- kernels


def kernels_phase(cfg: SmokeConfig) -> Dict[str, Any]:
    """Flash fwd+bwd against ``mha_reference``, paged decode and paged chunk
    against the gather path, at the per-chip shapes the other phases run;
    the latent kernel's decode and chunk calls (``H`` absorbed queries a
    slot over one shared row of ``4.5 x head_dim`` a position, the first
    ``4 x`` its value: 576 / 512 at a head of 128) against theirs.
    The reference side computes in float32 at ``highest`` matmul precision.
    Tolerance: max abs error <= 2^-6 of the reference's largest magnitude —
    four bf16 ulps, what bf16 inputs and probabilities cost; a wrong mask or
    a dropped block is off by the magnitude itself."""
    import jax
    import jax.numpy as jnp

    from torchdistpackage_tpu.ops import flash_attention, mha_reference
    from torchdistpackage_tpu.ops.mla_attention import (
        mla_gather_attention, mla_paged_attention)
    from torchdistpackage_tpu.serving.paged_cache import paged_attention

    m = cfg.model
    dt = m.dtype
    H = m.nheads // cfg.tp
    Hkv = m.block.kv_head_count // cfg.tp
    hd = m.block.head_dim
    f32 = jnp.float32
    out: Dict[str, Any] = {}

    def rel_err(got, ref) -> float:
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        _check(bool(np.isfinite(got).all()), "kernel output not finite")
        return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))

    # flash: the train step's per-chip call
    B = cfg.batch_per_chip * cfg.tp
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(cfg.seed), 4)
    q = jax.random.normal(kq, (B, H, m.max_seq, hd), f32).astype(dt)
    k = jax.random.normal(kk, (B, Hkv, m.max_seq, hd), f32).astype(dt)
    v = jax.random.normal(kv, (B, Hkv, m.max_seq, hd), f32).astype(dt)
    do = jax.random.normal(kd, (B, H, m.max_seq, hd), f32)

    def fwd_and_grads(attn):
        def loss(q, k, v):
            o = attn(q, k, v).astype(f32)
            return jnp.sum(o * do), o
        (_, o), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o,) + grads

    got = fwd_and_grads(flash_attention)
    with jax.default_matmul_precision("highest"):
        ref = fwd_and_grads(lambda q, k, v: mha_reference(
            q.astype(f32), k.astype(f32), v.astype(f32)))
    for name, g, r in zip(("o", "dq", "dk", "dv"), got, ref):
        out[f"flash_{name}"] = rel_err(g, r)

    # paged: the engine's decode (S_in=1) and prefill-chunk calls
    Bs = cfg.num_slots // cfg.dp
    mb = m.max_seq // cfg.block_size
    nb = 1 + Bs * mb
    rng = np.random.RandomState(cfg.seed)
    kp, kvp = jax.random.split(jax.random.PRNGKey(cfg.seed + 1))
    k_pool = jax.random.normal(kp, (nb, Hkv, cfg.block_size, hd), f32).astype(dt)
    v_pool = jax.random.normal(kvp, (nb, Hkv, cfg.block_size, hd), f32).astype(dt)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, nb)).reshape(Bs, mb).astype(np.int32))
    for name, s_in in (("decode", 1), ("chunk", cfg.chunk)):
        offs = rng.randint(0, m.max_seq - s_in + 1, size=Bs).astype(np.int32)
        offs[0], offs[-1] = 0, m.max_seq - s_in  # empty and full context
        offs = jnp.asarray(offs)
        qp = jax.random.normal(
            jax.random.PRNGKey(cfg.seed + 2), (Bs, H, s_in, hd), f32).astype(dt)
        got = jax.jit(lambda q, kc, vc, o, t: paged_attention(
            q, kc, vc, o, tables=t, impl="pallas"))(
                qp, k_pool, v_pool, offs, tables)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, kc, vc, o, t: paged_attention(
                q.astype(f32), kc.astype(f32), vc.astype(f32), o, tables=t,
                impl="gather"))(qp, k_pool, v_pool, offs, tables)
        out[f"paged_{name}"] = rel_err(got, ref)

        # latent: the same tables and offsets over a pool of shared rows
        dc, W = 4 * hd, 4 * hd + hd // 2
        lat = jax.random.normal(
            jax.random.PRNGKey(cfg.seed + 3), (nb, 1, W, cfg.block_size),
            f32).astype(dt)
        ql = jax.random.normal(
            jax.random.PRNGKey(cfg.seed + 4), (Bs, H, s_in, W), f32).astype(dt)
        kw = dict(latent=dc, sm_scale=W ** -0.5)
        got = jax.jit(lambda q, p, o, t: mla_paged_attention(
            q, p, t, o, **kw))(ql, lat, offs, tables)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, p, o, t: mla_gather_attention(
                q.astype(f32), p.astype(f32), t, o, **kw))(
                    ql, lat, offs, tables)
        out[f"mla_{name}"] = rel_err(got, ref)

    tol = 2.0 ** -6
    bad = {n: e for n, e in out.items() if not e <= tol}
    _check(not bad, f"kernels disagree with their oracles (tol {tol}): {bad}")
    out = {n: round(e, 6) for n, e in out.items()}
    out["tolerance"] = tol
    return out


# ----------------------------------------------------------------- serve


def serve_phase(cfg: SmokeConfig, compiles: CompileCounter) -> Dict[str, Any]:
    """The model through ``ServingEngine(attn_impl="auto")``: every request
    retires with the token count it asked for, the decode step has one
    signature, and nothing compiles after the first prefill and the first
    decode tick."""
    import jax
    from jax.sharding import NamedSharding

    from torchdistpackage_tpu.dist import check_placement
    from torchdistpackage_tpu.models import gpt_param_specs, init_gpt_params
    from torchdistpackage_tpu.obs import Telemetry
    from torchdistpackage_tpu.serving import Request, ServingEngine

    m = cfg.model
    params = init_gpt_params(jax.random.PRNGKey(cfg.seed), m)
    mesh_kw: Dict[str, Any] = {}
    mesh = None
    if math.prod(size for _, size in cfg.mesh) > 1:  # else: the default device
        mesh = _setup_mesh(cfg)
        tp_axis = "tensor" if cfg.tp > 1 else None
        params = jax.tree.map(
            lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec)),
            params, gpt_param_specs(m, tp_axis=tp_axis))
        mesh_kw = dict(mesh=mesh, axis=tp_axis,
                       dp_axis="data" if cfg.dp > 1 else None)
    tel = Telemetry(run="chip_smoke_serve", mesh=mesh, report_path="",
                    trace_path="")
    eng = ServingEngine(
        params, m, num_slots=cfg.num_slots, block_size=cfg.block_size,
        chunk=cfg.chunk, max_ctx=m.max_seq, attn_impl="auto", telemetry=tel,
        **mesh_kw)
    if mesh is not None:
        check_placement((params, eng.cache), mesh)

    rng = np.random.RandomState(cfg.seed)
    rids = [
        eng.submit(Request(
            tokens=rng.randint(0, m.vocab_size, size=n_prompt).tolist(),
            max_new_tokens=n_new, temperature=0.0))
        for n_prompt, n_new in cfg.requests
    ]
    # the engine's own record of each tick (a tick fetches the sampled tokens,
    # so its wall time ends after the device is done), plus the compile count
    ticks: List[Dict[str, Any]] = []
    while eng.queue or eng.n_busy:
        _check(len(ticks) < 10_000, "engine did not drain")
        eng.step()
        ticks.append({**eng.tick_records[-1], "programs": compiles.programs})

    for rid, (n_prompt, n_new) in zip(rids, cfg.requests):
        fin = eng.finished.get(rid)
        _check(fin is not None, f"request {rid} never retired: {eng.rejected}")
        _check(fin["new_tokens"] == n_new and fin["prompt_len"] == n_prompt,
               f"request {rid} retired with {fin['new_tokens']} new tokens "
               f"after a {fin['prompt_len']}-token prompt, asked for "
               f"{n_new} after {n_prompt}")
        toks = np.asarray(fin["tokens"][n_prompt:])
        _check(bool(((0 <= toks) & (toks < m.vocab_size)).all()),
               f"request {rid} produced tokens outside the vocabulary")

    summary = eng.serving_summary()
    tel.record_serving(summary)
    _check(summary["decode_signatures"] == 1,
           f"decode retraced: {summary['decode_signatures']} signatures")
    warm = max(next(i for i, t in enumerate(ticks) if t[kind])
               for kind in ("prefill_slots", "decode_slots"))
    _check(ticks[-1]["programs"] == ticks[warm]["programs"],
           f"{ticks[-1]['programs'] - ticks[warm]['programs']} programs "
           f"compiled after the first prefill and decode ticks")

    steady = ticks[warm + 1:]
    decode_only = [t for t in steady
                   if t["decode_slots"] and not t["prefill_slots"]]
    with_prefill = [t for t in steady if t["prefill_slots"]]
    return {
        "attn_impl": summary["attn_impl"],
        "mosaic_calls": _mosaic_calls(tel.compiled_programs()),
        "geometry": {"num_slots": cfg.num_slots, "block_size": cfg.block_size,
                     "chunk": cfg.chunk, "max_ctx": m.max_seq,
                     "num_blocks_per_dp_group": eng.num_blocks,
                     "mesh": dict(cfg.mesh) if mesh is not None else None},
        "requests": [list(r) for r in cfg.requests],
        "generated_tokens": summary["generated_tokens"],
        "ticks": len(ticks),
        "decode_signatures": summary["decode_signatures"],
        "compile_s": round(tel.compile_time_s, 2),
        "decode_tick_ms_median": _median_ms(
            [t["tick_s"] for t in decode_only]),
        # the engine's split of those ticks: the decode dispatch returns
        # before the device is done, the fetch waits for it
        "decode_tick_phases_ms_median": {
            name: _median_ms([t["phases"][name] for t in decode_only])
            for name in (decode_only[0]["phases"] if decode_only else ())},
        "prefill_tick_ms_median": _median_ms(
            [t["tick_s"] for t in with_prefill]),
        "memory": _memory(),
    }


def _median_ms(seconds: List[float]):
    return round(float(np.median(seconds)) * 1e3, 3) if seconds else None


def _memory() -> Dict[str, Any]:
    """Measured device memory.  ``peak`` is the process's high-water mark so
    far, not this phase's alone."""
    from torchdistpackage_tpu.obs.mem_ledger import live_memory

    mem = live_memory()
    return {
        "reported": mem["reported"],
        "peak_bytes_per_device": max(
            (r["peak_bytes_in_use"] for r in mem["per_device"]), default=None),
        "live_bytes_per_device": max(
            (r["bytes_in_use"] for r in mem["per_device"]), default=None),
        "limit_bytes_per_device": max(
            (r["bytes_limit"] for r in mem["per_device"]), default=None),
    }


# ----------------------------------------------------------------- train


def train_phase(cfg: SmokeConfig, compiles: CompileCounter) -> Dict[str, Any]:
    """``DataParallel.make_train_step`` under ``Telemetry.wrap_step`` on the
    configured mesh (TP+SP when it has a tensor axis), ``remat="flash"``,
    streamed cross-entropy, adamw, one fixed batch."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from torchdistpackage_tpu.dist import check_placement
    from torchdistpackage_tpu.models import (
        gpt_loss, gpt_param_specs, init_gpt_params)
    from torchdistpackage_tpu.obs import Telemetry
    from torchdistpackage_tpu.parallel import DataParallel

    m = cfg.model
    mesh = _setup_mesh(cfg)
    tp_axis = "tensor" if cfg.tp > 1 else None
    specs = gpt_param_specs(m, tp_axis=tp_axis)
    dp = DataParallel(mesh=mesh)
    params = dp.broadcast_params(
        init_gpt_params(jax.random.PRNGKey(cfg.seed), m), param_specs=specs)
    opt = optax.adamw(1e-4)  # no warm-up: 3e-4 spiked the bf16 loss on the chip
    state = opt.init(params)
    step = dp.make_train_step(
        lambda p, b: gpt_loss(p, b, m, axis=tp_axis, sp=tp_axis is not None,
                              remat="flash", xent_chunk=cfg.xent_chunk),
        opt, param_specs=specs, numerics=True)

    global_batch = cfg.batch_per_chip * mesh.size
    k1, k2 = jax.random.split(jax.random.PRNGKey(cfg.seed + 1))
    batch = dp.shard_batch({
        "tokens": jax.random.randint(
            k1, (global_batch, m.max_seq), 0, m.vocab_size),
        "targets": jax.random.randint(
            k2, (global_batch, m.max_seq), 0, m.vocab_size),
    })
    # (optax's step counter starts as an uncommitted scalar on the default
    # device; the step's outputs are checked after the loop)
    check_placement((params, batch), mesh)

    tel = Telemetry(run="chip_smoke_train", mesh=mesh, report_path="",
                    trace_path="", tokens_per_step=global_batch * m.max_seq)
    tstep = tel.wrap_step(step)
    losses, grad_norms, step_s = [], [], []
    for i in range(cfg.train_steps):
        t0 = time.perf_counter()
        params, state, loss, stats = tstep(params, state, batch)
        jax.block_until_ready((params, state, loss))
        step_s.append(time.perf_counter() - t0)
        rec = tel.end_step(step=i, loss=loss, numerics=stats)
        losses.append(rec["loss"])
        grad_norms.append(rec["grad_norm"])
        if i == 0:
            programs_after_first = compiles.programs
    programs_after_last = compiles.programs
    check_placement((params, state), mesh)
    report = tel.finalize(write=False, print_summary=False)

    _check(bool(np.isfinite(losses).all()), f"loss not finite: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall on a repeated batch: {losses}")
    _check(bool(np.isfinite(grad_norms).all()) and min(grad_norms) > 0,
           f"grad norm not finite and positive: {grad_norms}")
    _check(report["compile"]["count"] == 1
           and report["compile"]["recompiles"] == 0,
           f"expected one compile of the step, got {report['compile']}")
    _check(programs_after_last == programs_after_first,
           f"{programs_after_last - programs_after_first} programs compiled "
           f"after step 0")
    return {
        "mesh": dict(cfg.mesh),
        "global_batch": global_batch,
        "seq": m.max_seq,
        "losses": [round(x, 4) for x in losses],
        "grad_norms": [round(x, 4) for x in grad_norms],
        "mosaic_calls": _mosaic_calls(tel.compiled_programs()),
        "compile_s": report["compile"]["time_s"],
        "first_step_s": round(step_s[0], 2),
        "step_s_after_warmup": [round(s, 4) for s in step_s[1:]],
        "memory": _memory(),
    }


# ------------------------------------------------------------------ main


def main() -> None:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"phase": "device", **device,
                      "jax": jax.__version__}), flush=True)
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: the default backend is "
                 f"{jax.default_backend()!r}, not a TPU; nothing was run")

    import jax.numpy as jnp

    from torchdistpackage_tpu.dist.overlap import compile_cache
    from torchdistpackage_tpu.models import GPTConfig
    from torchdistpackage_tpu.obs import peak_flops_for

    peak_flops_for(dev.device_kind)  # a kind with no row raises
    meshes = {1: (("data", 1),), 4: (("data", 2), ("tensor", 2))}
    if device["count"] not in meshes:
        sys.exit(f"chip_smoke: no mesh for {device['count']} devices "
                 f"(have {sorted(meshes)})")
    cache_dir = compile_cache()
    compiles = CompileCounter()
    # the repo's ~1B GPT, every layer
    cfg = SmokeConfig(
        model=GPTConfig(
            vocab_size=32768, dim=2048, nheads=16, nlayers=16, max_seq=2048,
            ffn_mult=4, dtype=jnp.bfloat16, attn_impl="flash"),
        mesh=meshes[device["count"]])

    t_start = time.perf_counter()

    def run(phase: str, fn, *args) -> Dict[str, Any]:
        t0 = time.perf_counter()
        result = fn(*args)
        print(json.dumps({"phase": phase, **result,
                          "phase_s": round(time.perf_counter() - t0, 1)}),
              flush=True)
        return result

    run("kernels", kernels_phase, cfg)
    for phase, fn in (("serve", serve_phase), ("train", train_phase)):
        result = run(phase, fn, cfg, compiles)
        # what only a chip can show: main() never runs anywhere else
        _check(result["mosaic_calls"] > 0,
               f"{phase}: no Mosaic kernel in the compiled programs — the "
               f"Pallas kernels were interpreted or swapped for a reference")
        _check(result["memory"]["reported"],
               f"{phase}: the chip reported no memory stats")
        _check(result.get("attn_impl", "pallas") == "pallas",
               f"{phase}: attn_impl='auto' resolved to {result.get('attn_impl')}")
    if device["count"] > 1:
        import __graft_entry__ as graft

        # the impl asserts its arrays' placement itself and cannot respawn
        # on the simulator: that is dryrun_multichip's job
        run("compositions",
            lambda: graft._dryrun_multichip_impl(device["count"]) or {})

    print(json.dumps({"phase": "compile_cache", "dir": cache_dir,
                      **compiles.snapshot(),
                      "total_s": round(time.perf_counter() - t_start, 1)}),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
