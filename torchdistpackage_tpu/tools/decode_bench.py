"""Decode throughput benchmark: bf16 vs int8 weight-only serving.

Measures incremental decode tokens/sec for a ~1B GPT on the local chip(s),
A/B-ing the dense tree against ``quantize_decode_params`` — the
measured-decode half of the int8 serving story (docs/ROADMAP.md analysis:
decode reads every weight once per token, so weight-only int8 has up to
~2x of HBM bandwidth to win back; training-side numbers live in bench.py).

    python -m torchdistpackage_tpu.tools.decode_bench            # on-chip
    TDP_CPU_SIM=1 python -m torchdistpackage_tpu.tools.decode_bench  # smoke

Emits through the obs schema: one ``decode-latency`` JSON line per
(batch, context, variant) cell with **p50/p95/p99 latency percentiles per
phase** — ``prefill`` (time to first token) and ``decode_step`` (per-token
incremental latency) — plus the legacy per-cell throughput/speedup lines,
and an end-of-run ``RUNREPORT.json`` when ``TDP_RUNREPORT`` is set (the
same env contract as the train examples).  Mean-only reporting hid tail
behavior; serving SLOs are percentile SLOs.

Phase separation without a profiler: a generation of n tokens costs
``prefill + n * decode_step``; timing a short and a long generation per
rep gives one sample of each phase per rep by differencing.  Results are
recorded in docs/BENCH_AB.md.

``--serve`` benches the continuous-batching engine
(``serving.ServingEngine``) against the sequential batch-of-1
``generate()`` baseline at the same params, over a fixed-seed Poisson-ish
arrival schedule with mixed output lengths — the workload continuous
batching exists for.  Emits ``serve-latency`` JSON lines (TTFT/TPOT
percentiles, same schema as the per-phase cells), an aggregate
serve-vs-sequential speedup line, and the RUNREPORT ``serving`` section.

``--serve --overload`` adds the stress arm: the same compiled engine
replayed at ~2x its just-measured capacity with mixed priorities and
low-priority deadlines.  One ``serve-overload`` JSON line carries the
gating ``value`` (overloaded aggregate tokens/s) plus ``shed_rate``,
``preempt_count`` and per-priority p99 TTFT (``tools/bench_trend``
trends all three), and the RUNREPORT ``serving`` section records the
overload-vs-uncontended A/B (docs/serving.md "Serving under stress").

``--serve --attn-impl {gather,pallas}`` adds the paged-attention-kernel
A/B (docs/serving.md "Paged attention kernel"): the same fp requests
through both attention implementations — paired
``serve-paged-{gather,pallas}`` lines at equal ``config_hash``, token
bit-parity ASSERTED between the arms, and the ``serve-paged-ab`` line
carrying ``paged_pallas_tok_s`` (a ``bench_trend`` aux column).

``--serve --shared-prefix`` and ``--serve --spec K`` add the fast-path
A/Bs (docs/serving.md "Prefix cache" / "Speculative decoding"): the
prefix arm replays shared-system-prompt traffic with the prefix cache
off vs on (paired ``serve-prefix-{cold,warm}`` lines at equal
``config_hash`` — prefill ticks saved ∝ hit rate), and the spec arm
replays single-stream greedy requests at ``spec_k`` 0 vs K with token
BIT-parity asserted between the arms (paired ``serve-spec-{off,on}``
lines; ``prefix_hit_rate`` / ``spec_accept_rate`` ride the trend's aux
columns).  CPU-sim rows in docs/BENCH_AB.md.

``--serve --router R`` adds the multi-replica router A/B (docs/serving.md
"Multi-replica routing and disaggregation"): the same fixed-seed
shared-prefix trace, replayed as a concurrency-capped closed loop,
through ONE big engine vs a disaggregated fleet (1 prefill tier + R-1
decode replicas, prefix-affinity routing + KV-block handoffs) at equal
total slots — paired ``serve-router-{mono,fleet}`` lines at equal
``config_hash`` (aggregate tok/s, per-priority p99 TTFT, migration
count/bytes; ``fleet_goodput_tok_s`` / ``affinity_hit_rate`` /
``migration_bytes`` ride the trend's aux columns), the
``serve-router-ab`` roll-up, and the validated RUNREPORT ``router``
section.

``--trace out.json`` additionally prints the comm-ledger summary of the
compiled decode step (one extra AOT compile) and writes the run's
Perfetto-loadable Chrome trace — cells appear as instant events on the
timeline (the cell loops are not Telemetry-wrapped, so there are no
per-step spans; the event timeline and ledger still render).  With
``--serve``, the trace additionally carries the serving-observability
layer (docs/serving.md "Serving observability"): one async flow track
per request (queued → prefill → decode across preemptions and
drain/resume), engine-tick phase lanes, and queue/occupancy/utilization
counter tracks — every serve arm (``--overload`` / ``--shared-prefix`` /
``--spec`` included) lands on the one timeline, and a per-tick
phase-breakdown table is printed next to the latency tables.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def bench_decode(jax, jnp, cfg, params, B, ctx, steps=64, reps=5,
                 kv_quant=False):
    """Decode phase latencies through the REAL serving path — ``generate()``'s
    single-jit scan (static cache, no host round trips).

    Returns ``(tok_s_best, prefill_s_samples, decode_step_s_samples)``:
    best-of-reps decode throughput (tokens/sec, 0.0 when every rep fell
    inside timing noise) plus per-rep latency samples for the two phases —
    ``decode_step`` from differencing two generation lengths (prefill
    cancels), ``prefill`` by subtracting the short run's decode share from
    its total.  Negative/degenerate samples are dropped rather than
    reported (tiny smoke shapes time below clock noise)."""
    from ..models import generate

    prompt = jnp.ones((B, ctx), jnp.int32)
    short, long_ = max(steps // 8, 1), steps

    def sync(out):
        # fetching the last token waits for the whole generation
        return int(out[0, -1])

    fns = {}
    for n in (short, long_):
        f = jax.jit(lambda p, t, n=n: generate(
            p, t, cfg, max_new_tokens=n, kv_quant=kv_quant))
        sync(f(params, prompt))  # compile
        fns[n] = f

    best = 0.0
    prefill_samples, decode_samples = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        sync(fns[short](params, prompt))
        t1 = time.perf_counter()
        sync(fns[long_](params, prompt))
        t2 = time.perf_counter()
        t_short, t_long = t1 - t0, t2 - t1
        dt = t_long - t_short  # decode-only: prefill cancels
        if dt > 0:
            best = max(best, B * (long_ - short) / dt)
            per_tok = dt / (long_ - short)
            decode_samples.append(per_tok)
            pre = t_short - short * per_tok
            if pre > 0:
                prefill_samples.append(pre)
    return best, prefill_samples, decode_samples


def _mem_cols():
    """``{peak_hbm_bytes, mem_headroom_frac}`` for the JSON lines — the
    max per-device measured peak and its headroom against capacity, via
    the one memory_stats reader (obs.mem_ledger).  {} on the CPU sim."""
    from ..obs.mem_ledger import live_memory

    live = live_memory()
    if not live["reported"]:
        return {}
    cols = {"peak_hbm_bytes": max(
        r["peak_bytes_in_use"] for r in live["per_device"])}
    if live["peak_frac"]:
        cols["mem_headroom_frac"] = round(1.0 - live["peak_frac"], 4)
    return cols


def _phase_lines(B, ctx, variant, prefill_s, decode_s):
    """obs-schema ``decode-latency`` records (ms percentiles per phase)."""
    from ..obs import percentiles

    out = []
    for phase, samples in (("prefill", prefill_s), ("decode_step", decode_s)):
        if not samples:
            continue
        pct = {k: round(v * 1e3, 4)
               for k, v in percentiles(samples).items()}
        out.append({
            "metric": "decode-latency",
            "phase": phase,
            "unit": "ms",
            "B": B,
            "ctx": ctx,
            "variant": variant,
            "n_samples": len(samples),
            **{f"{k}_ms": v for k, v in pct.items()},
        })
    return out


def _overload_arm(jax, jnp, cfg, params, tel, eng, base_summary, *,
                  n_requests, num_slots, seed, smoke):
    """The stress A/B: replay arrivals at ~2x the engine's MEASURED
    capacity with mixed priorities and low-priority deadlines, against
    the uncontended numbers ``bench_serve`` just produced on the SAME
    compiled engine.  The claim under test (docs/serving.md "Serving
    under stress"): high-priority p99 TTFT holds near its uncontended
    value while low-priority requests shed/expire/preempt with structured
    events — bounded, observable degradation instead of collapse.

    Emits one ``serve-overload`` JSON line whose ``value`` is the
    overloaded aggregate tokens/s (the gate ``bench_trend`` trends) with
    ``shed_rate`` / ``preempt_count`` aux columns and per-priority p99
    TTFT; returns the overload ``serving_summary()`` with the
    ``overload_ab`` comparison attached (the RUNREPORT evidence)."""
    import numpy as np

    from ..serving import Request
    from ..utils.logging import master_print

    rng = np.random.RandomState(seed + 1)
    p_lens = [4, 8] if smoke else [16, 32, 64]
    n_lens = [8, 12] if smoke else [8, 16, 32]
    mean_new = float(np.mean(n_lens))
    cap_tok_s = max(base_summary["tokens_per_sec"], 1e-6)
    # request service rate the uncontended arm measured -> 2x arrivals
    interval = mean_new / cap_tok_s / 2.0
    # low-priority deadline: a handful of uncontended mean-TTFT budgets —
    # generous when the engine keeps up, unmeetable once 2x demand queues
    base_ttft = (base_summary.get("ttft_s") or {}).get("p50") or interval
    deadline = 8.0 * max(base_ttft, interval)

    eng.reset_metrics()
    eng.max_queue = 2 * num_slots
    sched, t = [], 0.0
    for i in range(n_requests):
        P, N = int(rng.choice(p_lens)), int(rng.choice(n_lens))
        prompt = rng.randint(0, cfg.vocab_size, size=P).tolist()
        t += float(rng.exponential(scale=interval))
        prio = int(rng.choice([0, 0, 2]))  # 1/3 high-priority traffic
        sched.append((t, Request(
            prompt, N, priority=prio,
            deadline_s=None if prio else deadline)))

    pending = list(sched)
    t0 = time.perf_counter()
    while pending or eng.n_busy or eng.queue:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            eng.submit(pending.pop(0)[1])
        if not (eng.n_busy or eng.queue):
            time.sleep(min(1e-3, max(0.0, pending[0][0] - now)))
            continue
        eng.step()
    eng.max_queue = None
    summary = eng.serving_summary()

    reqs = summary["requests"]
    refused = reqs["shed"] + reqs["expired"]
    shed_rate = refused / n_requests
    base_prio = base_summary.get("priorities") or {}
    over_prio = summary.get("priorities") or {}

    def p99(prios, p):
        return ((prios.get(str(p)) or {}).get("ttft_s") or {}).get("p99")

    slo = summary.get("slo") or {}
    line = {
        "metric": "serve-overload",
        # the trend gate: aggregate goodput under 2x arrivals (a scheduler
        # regression shows up here before anything else)
        "value": round(summary["tokens_per_sec"], 1),
        "n_requests": n_requests, "num_slots": num_slots,
        "arrival_x_capacity": 2.0,
        "shed_rate": round(shed_rate, 4),
        "preempt_count": reqs["preempted"],
        "expired": reqs["expired"],
        "verdict": summary["verdict"],
        # PR-11 SLO columns (bench_trend AUX): true goodput (tokens/s of
        # deadline-meeting requests only) and deadline attainment — a
        # tokens/s hold bought by missing deadlines is visible here
        "goodput_tok_s": round(slo.get("goodput_tok_s", 0.0), 1),
        "decode_signatures": summary["decode_signatures"],
    }
    if slo.get("attainment") is not None:
        line["slo_attainment"] = round(slo["attainment"], 4)
    ab = {"arrival_x_capacity": 2.0, "shed_rate": round(shed_rate, 4),
          "priorities": {}}
    agg_u = (base_summary.get("ttft_s") or {}).get("p99")
    for p in sorted({int(k) for k in over_prio} | {int(k) for k in base_prio}):
        # the uncontended arm serves every request at full attention, so
        # its aggregate p99 stands in for classes it didn't label
        u, o = p99(base_prio, p) or agg_u, p99(over_prio, p)
        row = {"uncontended_p99_ttft_s": u, "overloaded_p99_ttft_s": o}
        if o:
            line[f"ttft_p99_ms_prio{p}"] = round(o * 1e3, 4)
        if u and o:
            row["ratio"] = round(o / u, 3)
        ab["priorities"][str(p)] = row
    summary["overload_ab"] = ab
    master_print(json.dumps(line), flush=True)
    return summary


def bench_serve(jax, jnp, cfg, params, tel, *, n_requests, num_slots,
                block_size, chunk, seed, smoke, overload=False):
    """Continuous batching vs sequential batch-of-1 ``generate()`` at
    EQUAL params, over a fixed-seed Poisson-ish arrival schedule with
    mixed prompt/output lengths — the traffic shape the engine exists
    for.  Both arms replay the identical schedule (a request cannot start
    before its arrival time) with compiles warmed up-front, so the
    speedup line measures scheduling, not tracing.  Returns the engine's
    ``serving_summary()`` plus the baseline numbers.  ``overload=True``
    adds the stress arm (:func:`_overload_arm`): the same engine replayed
    at ~2x its just-measured capacity with mixed priorities/deadlines."""
    import numpy as np

    from ..models import generate
    from ..serving import Request, ServingEngine
    from ..utils.logging import master_print

    rng = np.random.RandomState(seed)
    # shapes drawn from small sets so the baseline's per-(P, N) jit
    # signatures stay bounded (the engine needs no such mercy: its two
    # programs are shape-blind)
    # arrivals must outpace single-request service for continuous batching
    # to have anything to win: mean gap ~ a fraction of one request's
    # decode time, so the sequential arm queues while the engine overlaps
    p_lens = [4, 8] if smoke else [16, 32, 64]
    n_lens = [8, 12] if smoke else [8, 16, 32]
    arrival_scale = 0.002 if smoke else 0.05
    sched, t = [], 0.0
    for _ in range(n_requests):
        P, N = int(rng.choice(p_lens)), int(rng.choice(n_lens))
        prompt = rng.randint(0, cfg.vocab_size, size=P).tolist()
        t += float(rng.exponential(scale=arrival_scale))
        sched.append((t, prompt, N))

    # --- engine arm (throwaway request warms both compiled steps)
    eng = ServingEngine(params, cfg, num_slots=num_slots,
                        block_size=block_size, chunk=chunk, telemetry=tel,
                        max_ctx=max(p_lens) + max(n_lens))
    eng.submit(Request(sched[0][1], sched[0][2]))
    eng.run_until_idle()
    eng.reset_metrics()
    pending = list(sched)
    t0 = time.perf_counter()
    while pending or eng.n_busy or eng.queue:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            _, prompt, N = pending.pop(0)
            eng.submit(Request(prompt, N))
        if not (eng.n_busy or eng.queue):
            time.sleep(min(1e-3, max(0.0, pending[0][0] - now)))
            continue
        eng.step()
    summary = eng.serving_summary()

    # --- sequential baseline: batch-of-1 generate(), FIFO, arrival-gated
    fns = {}
    for _, prompt, N in sched:
        key = (len(prompt), N)
        if key not in fns:
            f = jax.jit(lambda p, tk, n=N: generate(
                p, tk, cfg, max_new_tokens=n))
            int(f(params, jnp.ones((1, key[0]), jnp.int32))[0, -1])  # warm
            fns[key] = f
    t0 = time.perf_counter()
    t_first = None
    tokens = 0
    for arr, prompt, N in sched:
        wait = arr - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        if t_first is None:
            t_first = time.perf_counter()
        int(fns[(len(prompt), N)](
            params, jnp.asarray(prompt, jnp.int32)[None])[0, -1])  # sync
        tokens += N
    seq_tok_s = tokens / (time.perf_counter() - t_first)

    for phase, key in (("ttft", "ttft_s"), ("tpot", "tpot_s")):
        pct = summary.get(key) or {}
        if not pct:
            continue
        master_print(json.dumps({
            "metric": "serve-latency", "phase": phase, "unit": "ms",
            "n_requests": summary["requests"]["completed"],
            "num_slots": num_slots,
            **{f"{k}_ms": round(v * 1e3, 4) for k, v in pct.items()},
        }), flush=True)
    master_print(json.dumps({
        "metric": "serve-throughput",
        "n_requests": n_requests, "num_slots": num_slots,
        "block_size": block_size, "chunk": chunk,
        "serve_tok_s": round(summary["tokens_per_sec"], 1),
        "sequential_tok_s": round(seq_tok_s, 1),
        "speedup": round(summary["tokens_per_sec"] / seq_tok_s, 3)
        if seq_tok_s > 0 else None,
        "slot_occupancy_mean": round(
            summary["slot_occupancy"]["mean"], 4),
        "kv_pool_mean_utilization": round(
            summary["kv_pool"]["mean_utilization"], 4),
        # compile-once evidence: however many request shapes flowed
        # through, the engine issued exactly one signature per phase
        "decode_signatures": summary["decode_signatures"],
        "prefill_signatures": summary["prefill_signatures"],
        **_mem_cols(),
    }), flush=True)
    summary["sequential_tok_s"] = seq_tok_s
    if overload:
        # the RUNREPORT carries the STRESS arm (with the uncontended
        # comparison attached as overload_ab) — that is the arm whose
        # verdict/shedding evidence this mode exists to produce
        summary = _overload_arm(
            jax, jnp, cfg, params, tel, eng, summary,
            n_requests=n_requests, num_slots=num_slots, seed=seed,
            smoke=smoke)
        summary["sequential_tok_s"] = seq_tok_s
    tel.record_serving(summary)
    return summary


def _closed_loop(eng, requests):
    """Submit-all-then-drain through ``eng``; returns (wall_s, summary).
    Closed-loop on purpose: the fast-path A/Bs measure work ELIMINATED
    (prefill ticks, decode steps), so arrival gaps would only add noise."""
    for r in requests:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run_until_idle()
    return time.perf_counter() - t0, eng.serving_summary()


def bench_serve_prefix(jax, jnp, cfg, params, tel, *, n_requests, num_slots,
                       block_size, chunk, seed, smoke):
    """The prefix-cache A/B: every request = one shared system prompt +
    a short unique tail (the few-shot/system-prompt traffic shape), once
    through an engine with the prefix cache OFF and once ON — same
    params, same requests, paired ``serve-prefix-{cold,warm}`` JSON lines
    at equal ``config_hash``.  The claim under test: prefill ticks saved
    ∝ hit rate (the warm arm's chunked prefill starts after the cached
    boundary), with the compile-once signature evidence green in both
    arms."""
    import hashlib

    import numpy as np

    from ..serving import Request, ServingEngine
    from ..utils.logging import master_print

    rng = np.random.RandomState(seed + 2)
    sys_len = 4 * block_size                 # full blocks: all reusable
    tail_lens = [2, 3, 4]
    n_new = 6 if smoke else 12
    sys_prompt = rng.randint(0, cfg.vocab_size, size=sys_len).tolist()
    reqs = [Request(sys_prompt
                    + rng.randint(0, cfg.vocab_size,
                                  size=int(rng.choice(tail_lens))).tolist(),
                    n_new)
            for _ in range(n_requests)]
    cfg_hash = hashlib.sha1(
        f"serve-prefix|d{cfg.dim}|L{cfg.nlayers}|n{n_requests}|s{num_slots}"
        f"|bs{block_size}|c{chunk}|sys{sys_len}|seed{seed}".encode()
    ).hexdigest()[:12]

    results = {}
    for arm, warm in (("cold", False), ("warm", True)):
        eng = ServingEngine(
            params, cfg, num_slots=num_slots, block_size=block_size,
            chunk=chunk, max_ctx=sys_len + max(tail_lens) + n_new,
            prefix_cache=warm)
        eng.submit(Request(sys_prompt, 2))  # warm the compiled steps
        eng.run_until_idle()
        eng.reset_metrics()
        wall, summary = _closed_loop(eng, [Request(r.tokens, r.max_new_tokens)
                                           for r in reqs])
        tok_s = summary["generated_tokens"] / wall if wall > 0 else 0.0
        line = {
            "metric": f"serve-prefix-{arm}",
            "value": round(tok_s, 1),
            "n_requests": n_requests, "num_slots": num_slots,
            "shared_prefix_tokens": sys_len,
            "prefill_chunks": summary["prefill_chunks"],
            "prefix_hit_rate": round(summary["prefix_hit_rate"], 4),
            "decode_signatures": summary["decode_signatures"],
            "prefill_signatures": summary["prefill_signatures"],
            "config_hash": cfg_hash,
        }
        master_print(json.dumps(line), flush=True)
        results[arm] = (summary, tok_s)
    cold, warm = results["cold"][0], results["warm"][0]
    saved = cold["prefill_chunks"] - warm["prefill_chunks"]
    master_print(json.dumps({
        "metric": "serve-prefix-ab",
        "prefill_chunks_saved": saved,
        "prefill_chunks_saved_frac": round(
            saved / cold["prefill_chunks"], 4) if cold["prefill_chunks"] else 0,
        "prefix_hit_rate": round(warm["prefix_hit_rate"], 4),
        "speedup": round(results["warm"][1] / results["cold"][1], 3)
        if results["cold"][1] > 0 else None,
        "config_hash": cfg_hash,
    }), flush=True)
    tel.record_serving(warm)
    return warm


def bench_serve_spec(jax, jnp, cfg, params, tel, *, spec_k, n_requests,
                     num_slots, block_size, chunk, seed, smoke):
    """The speculative-decoding A/B: the same greedy requests (prompts
    with self-similar structure, where the n-gram drafter has something
    to look up) through a ``spec_k=0`` engine and a ``spec_k=K`` engine —
    paired ``serve-spec-{off,on}`` lines at equal ``config_hash``, with
    the bit-parity of every emitted token ASSERTED between the arms
    (greedy verification is exact, so the speedup is free of semantic
    drift).

    Runs SINGLE-STREAM (``num_slots=1``), the latency regime speculative
    decoding exists for: at one token per step per sequence, the decode
    latency floor is the whole story, and each accepted draft removes an
    entire tick.  ``decode_steps`` off-vs-on is the portable evidence —
    wall-clock ratios also fold in per-call shape effects of the backend
    (see docs/BENCH_AB.md for the CPU-sim caveat)."""
    import hashlib

    import numpy as np

    from ..serving import Request, ServingEngine
    from ..utils.logging import master_print

    num_slots = 1  # latency regime: the workload spec decoding is FOR
    n_requests = min(n_requests, 4 if smoke else 6)
    rng = np.random.RandomState(seed + 3)
    n_new = 24 if smoke else 48
    pat_lens = [2, 3, 4]
    reqs = []
    for _ in range(n_requests):
        pat = rng.randint(0, cfg.vocab_size,
                          size=int(rng.choice(pat_lens))).tolist()
        prompt = (pat * 8)[:12]  # repetitive: prompt-lookup has targets
        reqs.append(Request(prompt, n_new))
    cfg_hash = hashlib.sha1(
        f"serve-spec|d{cfg.dim}|L{cfg.nlayers}|n{n_requests}|s{num_slots}"
        f"|bs{block_size}|c{chunk}|new{n_new}|seed{seed}".encode()
    ).hexdigest()[:12]

    results = {}
    for arm, k in (("off", 0), ("on", spec_k)):
        eng = ServingEngine(
            params, cfg, num_slots=num_slots, block_size=block_size,
            chunk=chunk, max_ctx=12 + n_new, spec_k=k)
        eng.submit(Request(reqs[0].tokens, 2))  # warm the compiled steps
        eng.run_until_idle()
        eng.reset_metrics()
        wall, summary = _closed_loop(eng, [Request(r.tokens, r.max_new_tokens)
                                           for r in reqs])
        tok_s = summary["generated_tokens"] / wall if wall > 0 else 0.0
        line = {
            "metric": f"serve-spec-{arm}",
            "value": round(tok_s, 1),
            "spec_k": k, "n_requests": n_requests, "num_slots": num_slots,
            "decode_steps": summary["decode_steps"],
            "spec_accept_rate": round(summary["spec_accept_rate"], 4),
            "decode_signatures": summary["decode_signatures"],
            "config_hash": cfg_hash,
        }
        master_print(json.dumps(line), flush=True)
        results[arm] = (eng, summary, tok_s)
    # bit-parity between the arms: greedy verification is exact
    off_eng, on_eng = results["off"][0], results["on"][0]
    off_out = sorted((f["rid"], tuple(int(t) for t in f["tokens"]))
                     for f in off_eng.finished.values())
    on_out = sorted((f["rid"], tuple(int(t) for t in f["tokens"]))
                    for f in on_eng.finished.values())
    assert [t for _, t in off_out] == [t for _, t in on_out], (
        "speculative arm diverged from non-speculative tokens")
    off_s, on_s = results["off"][1], results["on"][1]
    master_print(json.dumps({
        "metric": "serve-spec-ab",
        "spec_k": spec_k,
        "spec_accept_rate": round(on_s["spec_accept_rate"], 4),
        "decode_steps_saved": off_s["decode_steps"] - on_s["decode_steps"],
        "speedup": round(results["on"][2] / results["off"][2], 3)
        if results["off"][2] > 0 else None,
        "bit_parity": True,
        "config_hash": cfg_hash,
    }), flush=True)
    tel.record_serving(on_s)
    return on_s


def bench_serve_router(jax, jnp, cfg, params, tel, *, n_replicas,
                       n_requests, num_slots, block_size, chunk, seed,
                       smoke):
    """The multi-replica router A/B (docs/serving.md "Multi-replica
    routing and disaggregation"): the same fixed-seed shared-prefix
    trace through ONE big engine (``num_slots * n_replicas`` slots, the
    mono arm) and through a disaggregated fleet at EQUAL TOTAL SLOTS —
    one prefill-tier replica feeding ``n_replicas - 1`` decode replicas,
    prefix-affinity routing + KV-block handoffs doing the work.  Paired
    ``serve-router-{mono,fleet}`` JSON lines at equal ``config_hash``
    (aggregate tok/s, per-priority p99 TTFT, migration count/bytes) and
    the ``serve-router-ab`` speedup line; the fleet's validated
    ``router`` section lands in the RUNREPORT.

    The trace is a CONCURRENCY-CAPPED closed loop: ``cap`` sessions
    round-trip continuously (a finished request immediately admits the
    next), the latency-bound serving regime where capacity is
    provisioned for peak but live load sits below it.  That is the
    regime the router exists for: an engine tick costs O(its own width
    + pool) HOWEVER FEW slots are live (static shapes — masked rows
    still compute), so the mono arm pays full-width ticks for a
    fraction-full batch, while affinity routing CONSOLIDATES each warm
    prefix group onto one small replica — the fleet runs a couple of
    hot, cheap replicas and never steps the idle ones.  At full
    saturation the bigger batch amortizes better and mono wins — that
    is disclosed, not hidden: push ``--serve-requests`` up against the
    cap and watch the ratio cross 1.  Warm handoffs ship only unshared
    TAIL blocks (``migration_shared_blocks`` vs ``migration_bytes``).
    """
    import hashlib

    import numpy as np

    from ..serving import Request, Router, ServingEngine
    from ..utils.logging import master_print

    total_slots = num_slots * n_replicas
    prefill_slots = max(1, total_slots // 4)
    n_decode = n_replicas - 1
    decode_slots = [(total_slots - prefill_slots) // n_decode] * n_decode
    decode_slots[-1] += (total_slots - prefill_slots) - sum(decode_slots)
    cap = max(2, total_slots // 3)  # live sessions: moderate load

    rng = np.random.RandomState(seed + 7)
    sys_len = 4 * block_size
    tail_lens = [2, 3, 4]
    n_lens = [12, 18, 24] if smoke else [16, 24, 32]
    sys_prompts = [rng.randint(0, cfg.vocab_size, size=sys_len).tolist()
                   for _ in range(2)]
    trace = []
    for i in range(n_requests):
        sysp = sys_prompts[i % 2]
        tail = rng.randint(0, cfg.vocab_size,
                           size=int(rng.choice(tail_lens))).tolist()
        trace.append(dict(
            tokens=sysp + tail,
            max_new_tokens=int(rng.choice(n_lens)),
            priority=int(rng.choice([0, 0, 2])),
        ))
    max_ctx = sys_len + max(tail_lens) + max(n_lens)
    cfg_hash = hashlib.sha1(
        f"serve-router|d{cfg.dim}|L{cfg.nlayers}|n{n_requests}"
        f"|R{n_replicas}|s{total_slots}|bs{block_size}|c{chunk}"
        f"|sys{sys_len}|cap{cap}|seed{seed}".encode()).hexdigest()[:12]

    def prio_cols(summary):
        out = {}
        for p, row in (summary.get("priorities") or {}).items():
            p99 = (row.get("ttft_s") or {}).get("p99")
            if p99 is not None:
                out[f"ttft_p99_ms_prio{p}"] = round(p99 * 1e3, 4)
        return out

    def paced(submit, pump, n_done):
        """Replay the trace at ``cap`` concurrent sessions: both arms
        admit request i the moment fewer than ``cap`` of the first i are
        unfinished — identical admission ORDER, load set by completion."""
        i = 0
        t0 = time.perf_counter()
        while n_done() < len(trace):
            while i < len(trace) and i - n_done() < cap:
                submit(Request(**trace[i]))
                i += 1
            pump()
        return time.perf_counter() - t0

    # --- mono arm: one big engine at the fleet's total width
    mono = ServingEngine(params, cfg, num_slots=total_slots,
                         block_size=block_size, chunk=chunk,
                         max_ctx=max_ctx, prefix_cache=True)
    for sysp in sys_prompts:  # warm compiles AND the prefix cache
        mono.submit(Request(sysp, 2))
    mono.run_until_idle()
    mono.reset_metrics()
    wall = paced(mono.submit, mono.step, lambda: len(mono.finished))
    mono_s = mono.serving_summary()
    mono_tok_s = mono_s["generated_tokens"] / wall if wall > 0 else 0.0
    assert mono_s["decode_signatures"] == 1, mono_s["decode_signatures"]
    master_print(json.dumps({
        "metric": "serve-router-mono",
        "value": round(mono_tok_s, 1),
        "num_slots": total_slots, "n_requests": n_requests,
        "prefill_chunks": mono_s["prefill_chunks"],
        "prefix_hit_rate": round(mono_s["prefix_hit_rate"], 4),
        "decode_signatures": mono_s["decode_signatures"],
        **prio_cols(mono_s),
        "config_hash": cfg_hash,
    }), flush=True)

    # --- fleet arm: 1 prefill replica + (R-1) decode replicas
    replicas = [ServingEngine(params, cfg, num_slots=prefill_slots,
                              block_size=block_size, chunk=chunk,
                              max_ctx=max_ctx, prefix_cache=True)]
    for ds in decode_slots:
        replicas.append(ServingEngine(
            params, cfg, num_slots=ds, block_size=block_size, chunk=chunk,
            max_ctx=max_ctx, prefix_cache=True))
    # warm EVERY replica's compiled programs AND prefix cache standalone
    # (affinity would concentrate router-driven warm traffic on one
    # replica and leave the rest to compile mid-measurement)
    for eng in replicas:
        for sysp in sys_prompts:
            eng.submit(Request(sysp, 2))
        eng.run_until_idle()
    router = Router(replicas,
                    roles=["prefill"] + ["decode"] * n_decode)
    # ... and every (prefill, decode) pair's migrate program explicitly
    # with a NULL->NULL no-op copy — a pair compiling mid-measurement
    # would time XLA, not the fleet
    lanes = np.zeros(replicas[0].max_blocks, np.int32)
    for j in range(1, n_replicas):
        replicas[j].cache = router._mig_fn(0, j, False)(
            replicas[0].cache, replicas[j].cache, lanes, lanes)
    router.reset_metrics()

    def fleet_done():
        return len(router.finished) + len(router.rejected)

    wall_f = paced(router.submit, router.step, fleet_done)
    fleet = router.summary()
    gen = fleet["fleet"]["generated_tokens"]
    fleet_tok_s = gen / wall_f if wall_f > 0 else 0.0
    for row in fleet["replicas"]:
        want = {"prefill": (0, 1), "decode": (1, 0)}[row["role"]]
        got = (row["decode_signatures"], row["prefill_signatures"])
        assert got == want, (row["role"], got)
    # fleet-level percentiles across replicas, priority-merged
    fleet_prio: dict = {}
    for row in fleet["replicas"]:
        for p, pr in (row.get("priorities") or {}).items():
            fleet_prio.setdefault(p, []).extend(
                [] if not pr.get("ttft_s") else [pr["ttft_s"].get("p99")])
    fleet_prio_cols = {
        f"ttft_p99_ms_prio{p}": round(max(v for v in vals if v) * 1e3, 4)
        for p, vals in fleet_prio.items() if any(vals)}
    mig = fleet["fleet"]["migrations"]
    aff = fleet["fleet"]["affinity"]
    master_print(json.dumps({
        "metric": "serve-router-fleet",
        "value": round(fleet_tok_s, 1),
        "n_replicas": n_replicas, "num_slots": total_slots,
        "prefill_slots": prefill_slots, "n_requests": n_requests,
        "affinity_hit_rate": round(aff["hit_rate"], 4),
        "fleet_goodput_tok_s": round(
            fleet["fleet"]["goodput_tok_s"], 1),
        "fleet_slo_attainment": (
            round(fleet["fleet"]["attainment"], 4)
            if fleet["fleet"]["attainment"] is not None else None),
        "migration_count": mig["handoffs"],
        "migration_bytes": mig["bytes"],
        "migration_shared_blocks": mig["shared_blocks"],
        "migration_retry_count": mig.get("retries", 0),
        "transport_fallback_count": mig.get("fallbacks", 0),
        "autoscale_actions": (fleet["fleet"].get("autoscale") or {}
                              ).get("actions", 0),
        "rebalances": fleet["fleet"]["rebalances"],
        "decode_signatures": 1,
        **fleet_prio_cols,
        "config_hash": cfg_hash,
    }), flush=True)
    master_print(json.dumps({
        "metric": "serve-router-ab",
        "value": round(fleet_tok_s / mono_tok_s, 3)
        if mono_tok_s > 0 else None,
        "mono_tok_s": round(mono_tok_s, 1),
        "fleet_tok_s": round(fleet_tok_s, 1),
        "affinity_hit_rate": round(aff["hit_rate"], 4),
        "migration_bytes": mig["bytes"],
        "config_hash": cfg_hash,
    }), flush=True)
    tel.record_serving(mono_s)
    tel.record_router(fleet)
    return fleet


def bench_serve_paged(jax, jnp, cfg, params, tel, *, attn_impl, n_requests,
                      num_slots, block_size, chunk, seed, smoke):
    """The paged-attention-kernel A/B (docs/serving.md "Paged attention
    kernel"): the same fp requests through an ``attn_impl='gather'``
    engine (table-gather then dense attention — the parity oracle) and an
    ``attn_impl='pallas'`` engine (in-kernel block-table walk) — paired
    ``serve-paged-{gather,pallas}`` JSON lines at equal ``config_hash``,
    with token BIT-parity asserted between the arms.  Both arms run the
    model in f32 (bf16 params upcast): the kernel keeps f32 scores while
    the gather path's bf16 einsum rounds them through bf16, so at bf16 a
    rare argmax boundary can legitimately flip — f32 is the dtype the
    parity claim is exact at (the engine goldens in
    tests/test_paged_attention.py assert the same), and the arms stay
    apples-to-apples against each other.  ``attn_impl`` picks which
    arm's ``serving_summary()`` lands in the RUNREPORT.

    On the CPU sim the pallas arm runs the INTERPRETER (docs/serving.md:
    correctness story, not a speed story) — wall-clock there only proves
    the path runs; the kernel's win is a real-chip number."""
    import hashlib

    import numpy as np

    from ..serving import Request, ServingEngine
    from ..utils.logging import master_print

    rng = np.random.RandomState(seed + 5)
    p_lens = [4, 8] if smoke else [16, 32, 64]
    n_lens = [6, 10] if smoke else [8, 16, 32]
    reqs = [Request(rng.randint(0, cfg.vocab_size,
                                size=int(rng.choice(p_lens))).tolist(),
                    int(rng.choice(n_lens)))
            for _ in range(n_requests)]
    # f32 arms: the dtype the bit-parity claim is exact at (see docstring)
    params = jax.device_put(jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if x.dtype == jnp.bfloat16 else x, params))
    import dataclasses

    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    cfg_hash = hashlib.sha1(
        f"serve-paged|d{cfg.dim}|L{cfg.nlayers}|n{n_requests}|s{num_slots}"
        f"|bs{block_size}|c{chunk}|seed{seed}".encode()
    ).hexdigest()[:12]

    results = {}
    for arm in ("gather", "pallas"):
        eng = ServingEngine(
            params, cfg, num_slots=num_slots, block_size=block_size,
            chunk=chunk, max_ctx=max(p_lens) + max(n_lens),
            attn_impl=arm)
        eng.submit(Request(reqs[0].tokens, 2))  # warm the compiled steps
        eng.run_until_idle()
        eng.reset_metrics()
        wall, summary = _closed_loop(eng, [Request(r.tokens, r.max_new_tokens)
                                           for r in reqs])
        tok_s = summary["generated_tokens"] / wall if wall > 0 else 0.0
        line = {
            "metric": f"serve-paged-{arm}",
            "value": round(tok_s, 1),
            "attn_impl": arm,
            "dtype": "float32",
            "n_requests": n_requests, "num_slots": num_slots,
            "block_size": block_size,
            "decode_steps": summary["decode_steps"],
            "decode_signatures": summary["decode_signatures"],
            "prefill_signatures": summary["prefill_signatures"],
            "config_hash": cfg_hash,
            **_mem_cols(),
        }
        master_print(json.dumps(line), flush=True)
        results[arm] = (eng, summary, tok_s)
    # token bit-parity between the arms (fp pool): the kernels differ
    # only in float accumulation order, and greedy argmax absorbs it
    g_eng, p_eng = results["gather"][0], results["pallas"][0]
    g_out = [t for _, t in sorted(
        (f["rid"], tuple(int(x) for x in f["tokens"]))
        for f in g_eng.finished.values())]
    p_out = [t for _, t in sorted(
        (f["rid"], tuple(int(x) for x in f["tokens"]))
        for f in p_eng.finished.values())]
    assert g_out == p_out, (
        "pallas paged-attention arm diverged from the gather oracle")
    master_print(json.dumps({
        "metric": "serve-paged-ab",
        # value = pallas/gather speedup (the trended series); the pallas
        # arm's absolute tokens/s rides the aux trail AND its own line
        "value": round(results["pallas"][2] / results["gather"][2], 3)
        if results["gather"][2] > 0 else 0.0,
        "paged_pallas_tok_s": round(results["pallas"][2], 1),
        "paged_gather_tok_s": round(results["gather"][2], 1),
        "bit_parity": True,
        "interpret_mode": jax.default_backend() == "cpu",
        "config_hash": cfg_hash,
    }), flush=True)
    chosen = results[attn_impl][1]
    tel.record_serving(chosen)
    return chosen


def bench_serve_long_context(jax, jnp, cfg, params, tel, *, cp, contexts,
                             block_size, chunk, seed, smoke):
    """The context-parallel prefill A/B (docs/long_context.md "CP prefill
    serving"): one long document per context point, prefilled to first
    token by a single-replica chunked-prefill engine (the oracle) and by
    a cp-way ring-paged engine on a ``context`` mesh — paired
    ``serve-longctx-cp{1,N}`` JSON lines at equal ``config_hash``, value
    = TTFT seconds, with token BIT-parity asserted per context point.
    The ``serve-longctx-ab`` rollup carries the trended TTFT speedup at
    the longest context plus the ``cp_prefill_ttft_s`` /
    ``long_ctx_tok_s`` aux columns (bench_trend AUX_KEYS).

    Both arms run f32 (the dtype the parity claim is exact at — see
    bench_serve_paged).  On the CPU sim both arms pay interpreter and
    host-ring overheads, so the TTFT ratio only proves the path runs and
    the ledger prices the hops; the crossover where ring compute-split
    beats one replica's serial chunk walk is a real-chip number
    (ROADMAP 5c)."""
    import dataclasses
    import hashlib

    import numpy as np

    from ..dist import tpc
    from ..serving import Request, ServingEngine
    from ..utils.logging import master_print

    if cp > 1 and len(jax.devices()) < cp:
        master_print(
            f"decode_bench: --long-context needs {cp} devices for the CP "
            f"arm, have {len(jax.devices())}", file=sys.stderr)
        return None
    params = jax.device_put(jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if x.dtype == jnp.bfloat16 else x, params))
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    new_tokens = 4 if smoke else 16
    cfg_hash = hashlib.sha1(
        f"serve-longctx|d{cfg.dim}|L{cfg.nlayers}|cp{cp}"
        f"|ctx{','.join(str(c) for c in contexts)}"
        f"|bs{block_size}|c{chunk}|seed{seed}".encode()
    ).hexdigest()[:12]
    rng = np.random.RandomState(seed + 7)

    def run_arm(width, ctx, prompt):
        if width > 1:
            tpc.setup_process_groups(
                [("context", width)], devices=jax.devices()[:width])
            eng = ServingEngine(
                params, cfg, num_slots=1, block_size=block_size,
                chunk=chunk, max_ctx=ctx, mesh=tpc.get_view(),
                cp_axis="context")
        else:
            eng = ServingEngine(params, cfg, num_slots=1,
                                block_size=block_size, chunk=chunk,
                                max_ctx=ctx)
        # warm both compiled phases on a chunk-sized request so the
        # measured TTFT is serving time, not XLA time
        eng.submit(Request(prompt[:chunk].tolist(), 2))
        eng.run_until_idle()
        eng.reset_metrics()
        t0 = time.perf_counter()
        rid = eng.submit(Request(prompt.tolist(), new_tokens))
        eng.run_until_idle(max_ticks=ctx)
        wall = time.perf_counter() - t0
        f = eng.finished[rid]
        if width > 1:
            tpc.reset()
        return eng, f, wall

    rows = {1: [], cp: []}
    summary_n = None
    for ctx in contexts:
        prompt = rng.randint(
            0, cfg.vocab_size, size=ctx - new_tokens).astype(np.int32)
        toks = {}
        for width in sorted({1, cp}):
            eng, f, wall = run_arm(width, ctx, prompt)
            s = eng.serving_summary()
            toks[width] = tuple(int(x) for x in f["tokens"])
            rows[width].append(
                (ctx, float(f["ttft_s"]), f["new_tokens"] / wall))
            if width == cp:
                summary_n = s
            master_print(json.dumps({
                "metric": f"serve-longctx-cp{width}",
                "value": round(float(f["ttft_s"]), 4),
                "context": ctx, "cp": width,
                "prefill_chunks": s["prefill_chunks"],
                "ring_hops": s.get("long_context", {}).get("ring_hops", 0),
                "ring_bytes": s.get("long_context", {}).get("ring_bytes", 0),
                "decode_signatures": s["decode_signatures"],
                "prefill_signatures": s["prefill_signatures"],
                "config_hash": cfg_hash,
                **_mem_cols(),
            }), flush=True)
        # token bit-parity: the ring splits the same fp math by rank
        assert toks[1] == toks[cp], (
            f"CP prefill arm diverged from the single-replica oracle "
            f"at context {ctx}")
    longest = max(contexts)
    ttft1 = dict((c, t) for c, t, _ in rows[1])[longest]
    ttftn = dict((c, t) for c, t, _ in rows[cp])[longest]
    master_print(json.dumps({
        "metric": "serve-longctx-ab",
        # value = cp1/cpN TTFT speedup at the longest context (the
        # trended series); the CP arm's absolute TTFT and decode
        # throughput ride the aux trail
        "value": round(ttft1 / ttftn, 3) if ttftn > 0 else 0.0,
        "cp": cp, "context": longest,
        "cp_prefill_ttft_s": round(ttftn, 4),
        "long_ctx_tok_s": round(
            sum(r[2] for r in rows[cp]) / len(rows[cp]), 2),
        "bit_parity": True,
        "interpret_mode": jax.default_backend() == "cpu",
        "config_hash": cfg_hash,
    }), flush=True)
    tel.record_serving(summary_n)
    return summary_n


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m torchdistpackage_tpu.tools.decode_bench",
        description="Decode/serving throughput benchmark "
                    "(bf16 vs int8 cells; --serve for the "
                    "continuous-batching engine A/B).")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config (implied by TDP_CPU_SIM)")
    ap.add_argument("--trace", metavar="OUT_JSON", default=None,
                    help="write a Perfetto-loadable Chrome trace and print "
                         "the compiled decode step's comm ledger")
    ap.add_argument("--serve", action="store_true",
                    help="bench the continuous-batching engine against the "
                         "sequential batch-of-1 generate() baseline "
                         "(replaces the weight-quant cells)")
    ap.add_argument("--overload", action="store_true",
                    help="with --serve: add the stress arm — arrivals at "
                         "~2x the measured capacity with mixed priorities "
                         "and deadlines; emits the serve-overload line "
                         "(shed_rate, preempt_count, per-priority p99 "
                         "TTFT) and records the overload A/B in the "
                         "RUNREPORT serving section")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="with --serve: add the prefix-cache A/B — every "
                         "request shares one system prompt; paired "
                         "serve-prefix-{cold,warm} lines at equal "
                         "config_hash (prefill ticks saved vs hit rate)")
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="with --serve: add the speculative-decoding A/B "
                         "at static draft width K — paired "
                         "serve-spec-{off,on} lines at equal config_hash, "
                         "token bit-parity asserted between the arms")
    ap.add_argument("--router", type=int, default=0, metavar="R",
                    help="with --serve: add the multi-replica router A/B "
                         "— the same shared-prefix trace through one big "
                         "engine vs a disaggregated fleet of R replicas "
                         "(1 prefill tier + R-1 decode) at equal total "
                         "slots; paired serve-router-{mono,fleet} lines "
                         "at equal config_hash with migration "
                         "count/bytes, and the RUNREPORT router section")
    ap.add_argument("--attn-impl", choices=("gather", "pallas"), default=None,
                    help="with --serve: add the paged-attention-kernel A/B "
                         "— BOTH arms always run paired at equal "
                         "config_hash (serve-paged-{gather,pallas} lines, "
                         "token bit-parity asserted on the fp path); the "
                         "chosen value picks which arm's summary lands in "
                         "the RUNREPORT serving section")
    ap.add_argument("--long-context", action="store_true",
                    help="with --serve: add the context-parallel prefill "
                         "A/B — one long document per context point "
                         "(8k/32k/128k full, toy lengths on smoke) "
                         "through a single-replica chunked-prefill "
                         "engine vs a --cp-way ring-paged engine; "
                         "paired serve-longctx-cp{1,N} TTFT lines at "
                         "equal config_hash, token bit-parity asserted, "
                         "and the serve-longctx-ab rollup")
    ap.add_argument("--cp", type=int, default=2, metavar="N",
                    help="--long-context ring width (default 2)")
    ap.add_argument("--serve-requests", type=int, default=None,
                    metavar="N", help="requests in the --serve schedule "
                    "(default: 8 smoke / 24 full)")
    ap.add_argument("--slots", type=int, default=4,
                    help="--serve decode-batch width (default 4)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="--serve KV pool block size (default 16)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="--serve prefill chunk tokens (default 16)")
    ap.add_argument("--seed", type=int, default=0,
                    help="--serve arrival-schedule seed (default 0)")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv)
    from ..dist.overlap import compile_cache, cpu_sim

    if os.environ.get("TDP_CPU_SIM"):
        # full sim bootstrap, not just the platform pin: --long-context's
        # CP arm needs the virtual device count too
        cpu_sim(os.environ["TDP_CPU_SIM"])
    compile_cache()
    import jax
    import jax.numpy as jnp

    from ..models import GPTConfig, init_gpt_params
    from ..obs import Telemetry
    from ..utils.logging import master_print
    from .surgery import quantize_decode_params

    smoke = bool(os.environ.get("TDP_CPU_SIM")) or args.smoke
    dt = jnp.bfloat16
    if smoke:
        cfg = GPTConfig(vocab_size=256, dim=128, nheads=4, nlayers=2,
                        max_seq=512, ffn_mult=4, dtype=dt)
        cells = [(1, 32)]
        steps, reps = 4, 3
    else:
        # the bench.py --big config (d2048/L16 ≈ 0.94B params)
        cfg = GPTConfig(vocab_size=32000, dim=2048, nheads=16, nlayers=16,
                        max_seq=4096, ffn_mult=4, dtype=dt)
        cells = [(1, 128), (1, 1024), (8, 128), (8, 1024)]
        steps, reps = 64, 5

    trace_path = args.trace

    # the bench is its own telemetry session: latency cells land in the
    # counters of an end-of-run RUNREPORT (TDP_RUNREPORT env) like any
    # integrated example
    tel = Telemetry(run="decode_bench", poll_memory=False)

    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(jax.tree.map(lambda x: x.astype(dt), params))
    qp = jax.device_put(quantize_decode_params(params))
    nb = sum(x.nbytes for x in jax.tree.leaves(params))
    nq = sum(x.nbytes for x in jax.tree.leaves(qp))
    master_print(
        f"param bytes: bf16={nb / 1e9:.2f} GB, int8 tree={nq / 1e9:.2f} GB",
        file=sys.stderr)

    if trace_path:
        # comm ledger of the compiled decode step, printed next to the
        # latency numbers (single-chip runs legitimately show none)
        try:
            from ..models import generate
            from ..obs import ledger_from_compiled
            from ..obs.comm_ledger import render_table

            B0, ctx0 = cells[0]
            prompt0 = jnp.ones((B0, ctx0), jnp.int32)
            dec = jax.jit(lambda p, t: generate(p, t, cfg, max_new_tokens=4))
            led = ledger_from_compiled(dec.lower(params, prompt0).compile())
            master_print(render_table(led), file=sys.stderr)
            if led:
                tel.record_counters(decode_comm_ledger={
                    "per_dim": led["per_dim"],
                    "total_bytes": led["total_bytes"],
                    "n_collectives": led["n_collectives"],
                })
        except Exception as e:
            master_print(f"decode_bench: ledger unavailable ({e!r})",
                         file=sys.stderr)

    latency_cells = []
    if args.serve:
        cells = []  # the engine A/B is its own arm
        bench_serve(
            jax, jnp, cfg, params, tel,
            n_requests=args.serve_requests or (12 if smoke else 24),
            num_slots=args.slots, block_size=args.block_size,
            chunk=args.chunk, seed=args.seed, smoke=smoke,
            overload=args.overload)
        if args.shared_prefix:
            bench_serve_prefix(
                jax, jnp, cfg, params, tel,
                n_requests=args.serve_requests or (12 if smoke else 24),
                num_slots=args.slots, block_size=args.block_size,
                chunk=args.chunk, seed=args.seed, smoke=smoke)
        if args.spec:
            bench_serve_spec(
                jax, jnp, cfg, params, tel, spec_k=args.spec,
                n_requests=args.serve_requests or (12 if smoke else 24),
                num_slots=args.slots, block_size=args.block_size,
                chunk=args.chunk, seed=args.seed, smoke=smoke)
        if args.attn_impl:
            bench_serve_paged(
                jax, jnp, cfg, params, tel, attn_impl=args.attn_impl,
                n_requests=args.serve_requests or (8 if smoke else 24),
                num_slots=args.slots, block_size=args.block_size,
                chunk=args.chunk, seed=args.seed, smoke=smoke)
        if args.long_context:
            bench_serve_long_context(
                jax, jnp, cfg, params, tel, cp=args.cp,
                contexts=[96, 160] if smoke else [8192, 32768, 131072],
                block_size=args.block_size, chunk=args.chunk,
                seed=args.seed, smoke=smoke)
        if args.router:
            if args.router < 2:
                master_print("decode_bench: --router needs R >= 2",
                             file=sys.stderr)
                return 2
            bench_serve_router(
                jax, jnp, cfg, params, tel, n_replicas=args.router,
                n_requests=args.serve_requests or (12 if smoke else 24),
                num_slots=args.slots, block_size=args.block_size,
                chunk=args.chunk, seed=args.seed, smoke=smoke)
        if trace_path:
            # the tick-level accounting next to the latency tables: where
            # each engine tick's time went, aggregated over every serve
            # arm above (all arms share this session's event timeline —
            # the same records the Perfetto trace renders as phase lanes)
            from ..serving.tracing import phase_table

            master_print(phase_table(tel.events.as_list()),
                         file=sys.stderr)
    elif (args.overload or args.shared_prefix or args.spec
          or args.attn_impl or args.router or args.long_context):
        master_print(
            "decode_bench: --overload/--shared-prefix/--spec/--attn-impl/"
            "--router/--long-context need --serve",
            file=sys.stderr)
        return 2
    for B, ctx in cells:
        r_bf, pre_bf, dec_bf = bench_decode(jax, jnp, cfg, params, B, ctx,
                                            steps, reps)
        r_q, pre_q, dec_q = bench_decode(jax, jnp, cfg, qp, B, ctx,
                                         steps, reps)
        r_qkv, pre_qkv, dec_qkv = bench_decode(jax, jnp, cfg, qp, B, ctx,
                                               steps, reps, kv_quant=True)
        for variant, pre, dec in (
            ("bf16", pre_bf, dec_bf),
            ("int8w", pre_q, dec_q),
            ("int8w+int8kv", pre_qkv, dec_qkv),
        ):
            for line in _phase_lines(B, ctx, variant, pre, dec):
                latency_cells.append(line)
                # cells land on the trace timeline as instant events
                tel.events.emit(
                    "decode_cell", phase=line["phase"], variant=variant,
                    B=B, ctx=ctx, p50_ms=line.get("p50_ms"))
                master_print(json.dumps(line), flush=True)
        if r_bf > 0 and r_qkv > 0:
            master_print(json.dumps({
                "B": B, "ctx": ctx, "int8w+int8kv_tok_s": round(r_qkv, 1),
                "speedup_vs_bf16": round(r_qkv / r_bf, 3),
            }), flush=True)
        else:
            master_print(json.dumps({"B": B, "ctx": ctx, "kv_quant": True,
                                     "degenerate": True,
                                     "int8w+int8kv_tok_s": round(r_qkv, 1)}),
                         flush=True)
        if r_bf <= 0 or r_q <= 0:
            # every rep's length-difference fell inside timing noise (tiny
            # smoke shapes): report the degenerate cell instead of a
            # fictitious rate / ZeroDivisionError
            master_print(json.dumps({"B": B, "ctx": ctx, "degenerate": True,
                                     "bf16_tok_s": round(r_bf, 1),
                                     "int8_tok_s": round(r_q, 1)}),
                         flush=True)
            continue
        master_print(json.dumps({
            "B": B, "ctx": ctx,
            "bf16_tok_s": round(r_bf, 1),
            "int8_tok_s": round(r_q, 1),
            "speedup": round(r_q / r_bf, 3),
            **_mem_cols(),
        }), flush=True)

    tel.record_counters(decode_latency=latency_cells)
    tel.finalize(print_summary=False)
    if trace_path:
        from ..obs import export_trace

        export_trace(tel, trace_path)
        master_print(f"decode_bench: wrote Perfetto trace to {trace_path}",
                     file=sys.stderr)


if __name__ == "__main__":
    main()
