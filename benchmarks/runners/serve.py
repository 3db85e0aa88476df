"""The serving runner: the model through ``ServingEngine(attn_impl="auto")``,
started as a copy of chip_smoke.py's ``serve_phase``, driven by the cell's
traffic mix in a closed loop (a client sends its next request when its last
returns) or an open loop (requests are due on a schedule fixed by the seed,
and time runs from when each was DUE, not from when it was submitted).

Set-up submits the first requests and runs the engine's first ticks, which
compile the prefill-chunk and the decode signature; the window then drives
that same engine.  Once the window has closed, every request that it
finished goes through the reference."""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import arch as A
from benchmarks import checks, costs, harness, stats
from benchmarks.traffic import generator


def run(ctx: harness.Context) -> Dict[str, Any]:
    import jax

    from torchdistpackage_tpu.serving import Request, ServingEngine

    from benchmarks.weights import make_weights

    cell, mix, geo = ctx.cell, ctx.cell["traffic"], ctx.cell["engine"]
    fam = A.family_of(ctx.config)
    a = fam.arch(ctx.config, geo["max_ctx"])
    pcfg = fam.program_config(ctx.config, geo["max_ctx"])
    params = make_weights(a, ctx.seed)
    # benchmarks/control.py only: the program's own lower precision, its int8
    # KV pool, switched on; this run's numbers are then the control's
    if ctx.control not in (None, "fp8", "kv_int8"):
        raise harness.Refused(f"no control {ctx.control!r} for a served model")
    eng = ServingEngine(
        params, pcfg, num_slots=geo["num_slots"], block_size=geo["block_size"],
        chunk=geo["chunk"], max_ctx=geo["max_ctx"],
        num_blocks=geo.get("num_blocks"), attn_impl="auto",
        kv_quant=ctx.control == "kv_int8",
        tick_history=1 << 20, chaos=ctx.chaos)
    if jax.default_backend() == "tpu" and eng.attn_impl != "pallas":
        raise harness.Refused(
            f"attn_impl='auto' resolved to {eng.attn_impl!r} on a TPU")

    reqs = generator.requests(mix, a.vocab, ctx.seed)
    closed = mix["kind"] == "closed_loop"
    sent: Dict[int, Dict[str, Any]] = {}   # rid -> request record
    next_req = 0
    spans: Dict[str, List[float]] = {"submit": [], "engine_step": []}

    def submit(due_abs=None) -> None:
        nonlocal next_req
        r = reqs[next_req % len(reqs)]
        next_req += 1
        with harness.span("submit", spans["submit"]):
            rid = eng.submit(Request(tokens=r["tokens"],
                                     max_new_tokens=r["max_new_tokens"],
                                     temperature=0.0))
        sent[rid] = {"asked": r["max_new_tokens"], "prompt_len": len(r["tokens"]),
                     "due": due_abs, "t_sent": time.perf_counter()}

    # ---- set-up: the first requests, and the ticks that compile
    if closed:
        for _ in range(mix["clients"]):
            submit()
        warm = {"prefill_slots": False, "decode_slots": False}
        for _ in range(64):   # until both signatures have run (and compiled)
            eng.step()
            for k in warm:
                warm[k] |= bool(eng.tick_records[-1][k])
            if all(warm.values()):
                break
        else:
            raise harness.Refused("the engine's first ticks never decoded")
    else:  # an open loop starts from an empty engine: one short request
        eng.submit(Request(tokens=[1] * geo["chunk"], max_new_tokens=2))
        eng.run_until_idle()
    seen = set(eng.finished) | set(eng.rejected)
    ticks_before = len(eng.tick_records)
    programs_before = ctx.compiles.programs
    ctx.log(phase="setup", attn_impl=eng.attn_impl, params=a.num_params(),
            num_blocks=eng.num_blocks, warmup_finished=len(seen))

    # ---- the window
    tracer = harness.Tracer(ctx)
    setup_s = ctx.setup_seconds()
    tracer.start()
    t_start = time.perf_counter()
    arrive_until = ctx.seconds * float(mix.get("arrive_share", 1.0))
    finished: List[Dict[str, Any]] = []
    live_ctx: Dict[int, int] = {}
    kv_token_reads = 0.0     # live KV positions summed over decode ticks
    decode_slot_ticks = 0
    queue_at_arrivals_end = None
    while time.perf_counter() - t_start < ctx.seconds:
        now = time.perf_counter() - t_start
        if queue_at_arrivals_end is None and now >= arrive_until:
            queue_at_arrivals_end = len(eng.queue)
        while (not closed and next_req < len(reqs)
               and reqs[next_req]["due_s"] <= min(now, arrive_until)):
            submit(t_start + reqs[next_req]["due_s"])
        if not (eng.queue or eng.n_busy):
            time.sleep(0.001)   # an open loop between arrivals
            continue
        with harness.span("engine_step", spans["engine_step"]):
            eng.step()
        for rid, _slot in eng.decode_slots():
            live_ctx[rid] = live_ctx.get(rid, sent[rid]["prompt_len"]) + 1
            kv_token_reads += live_ctx[rid]
            decode_slot_ticks += 1
        for rid in list(eng.finished.keys() - seen):
            seen.add(rid)
            live_ctx.pop(rid, None)
            finished.append({**eng.finished[rid], **sent[rid]})
            if closed:
                submit()
        tracer.tick()
    # stopping the profiler is the benchmark's own time, not the program's
    window_s = time.perf_counter() - t_start - tracer.stop_s
    trace = tracer.reduce()
    if ctx.compiles.programs != programs_before:
        raise harness.Refused(
            f"{ctx.compiles.programs - programs_before} programs compiled "
            f"inside the window")
    peak = harness.memory_peak_bytes()

    ticks = list(eng.tick_records)[ticks_before:]
    emitted = sum(t["emitted_tokens"] for t in ticks)
    decode_only = [t for t in ticks if t["decode_slots"] and not t["prefill_slots"]]
    with_prefill = [t for t in ticks if t["prefill_slots"]]
    rejected = sum(1 for rid in sent if rid in eng.rejected)
    # an open loop's arrivals stop early so that the window drains: what has
    # no last token at its end failed (a closed loop always has work in flight)
    unfinished = 0 if closed else sum(
        1 for rid in sent if rid not in eng.finished and rid not in eng.rejected)

    # every retired request: the count it asked for, tokens in the vocabulary
    bad = 0
    for f in finished:
        gen = np.asarray(f["tokens"][f["prompt_len"]:])
        if (f["new_tokens"] != f["asked"] or len(gen) != f["asked"]
                or not ((0 <= gen) & (gen < a.vocab)).all()):
            bad += 1
    failed = bad + rejected + unfinished

    e2e: Dict[str, float] = {"setup_s": setup_s,
                             "serve_tok_s": emitted / window_s}
    ttft = [(f["t_submit"] + f["ttft_s"] - (f["due"] or f["t_sent"])) * 1e3
            for f in finished if f["ttft_s"] is not None]
    gaps = [g * 1e3 for f in finished for g in f["tpot_s"]]
    waits = [(f["t_submit"] - f["due"]) * 1e3 for f in finished if f["due"]]
    if ttft:
        e2e["ttft_p90_ms"] = stats.percentile(ttft, 90)
    if gaps:
        e2e["gap_p95_ms"] = stats.percentile(gaps, 95)
    late = stats.lateness([f["due"] for f in finished if f["due"]],
                          [f["t_sent"] for f in finished if f["due"]])
    ctx.log(phase="window", window_s=window_s, ticks=len(ticks),
            ticks_with_prefill=len(with_prefill), emitted_tokens=emitted,
            sent=len(sent),
            queue_at_arrivals_end=queue_at_arrivals_end,
            finished=len(finished), rejected=rejected, unfinished=unfinished,
            wrong_count_or_vocab=bad, memory_peak_bytes=peak,
            ttft_ms={"n": len(ttft), "median": stats.median(ttft) if ttft else None},
            gap_ms={"n": len(gaps), "median": stats.median(gaps) if gaps else None},
            generator_lateness=late, tracer_stop_s=tracer.stop_s)

    # ---- the reference over every request that the window finished, once
    # the engine and its pool are freed
    del eng
    t_ref = time.perf_counter()
    got = checks.served_gap(params, a, finished, pad_to=geo["max_ctx"])
    v = checks.verdict({"served_logit_gap": got["served_logit_gap"]},
                       cell["limits"])
    if not finished:
        v["correct"] = False
    ctx.log(phase="check", reference_s=time.perf_counter() - t_ref,
            checked_requests=len(finished), served_tokens=got["tokens"],
            tokens_not_top=got["not_top"], mean_gap=got["mean_gap"], **v)

    if ctx.control == "fp8":
        low = checks.served_gap(params, a, finished, quant=ctx.control,
                                pad_to=geo["max_ctx"])
        ctx.log(phase="control", precision=ctx.control,
                tokens_not_top=low["not_top"], mean_gap=low["mean_gap"],
                **checks.verdict(
                    {"served_logit_gap": low["served_logit_gap"]},
                    cell["limits"]))

    def phase_ms(rows, names):
        return [sum(t["phases"].get(n, 0.0) for n in names) for t in rows]

    n_decode_ticks = sum(1 for t in ticks if t["decode_slots"])
    obs = {
        "spans": {
            **spans,
            "decode_tick": [t["tick_s"] for t in decode_only],
            "prefill_tick": [t["tick_s"] for t in with_prefill],
            "sched_host": phase_ms(ticks, ("audit", "sched", "host")),
            "queue_wait": [w / 1e3 for w in waits],
        },
        "values": {"memory_peak_bytes": peak,
                   "tokens_per_s": emitted / window_s},
        # one layer's paged decode call at the window's mean live context
        "costs": {"paged_decode": {
            **costs.paged_decode(a, kv_token_reads / max(1, n_decode_ticks),
                                 decode_slot_ticks / max(1, n_decode_ticks)),
            "calls_per_execution": a.layers}},
        "peaks": ctx.peaks,
        "trace": trace,
    }
    return {"correct": v["correct"] and failed == 0,
            "attempted": len(finished) + rejected + unfinished,
            "failed": failed, "memory_peak_bytes": peak,
            "end_to_end": e2e, "obs": obs}
