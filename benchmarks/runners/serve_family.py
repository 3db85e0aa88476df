"""The serving runner for a family that is not a dense transformer:
``runners/serve.py``'s closed or open loop around ``ServingEngine(
attn_impl="auto")``, with everything that file takes from ``Arch``
(weights, reference logits, costs) asked of the FAMILY MODULE instead
(``benchmarks/families/<family>.py``: ``shape``, ``program_config``,
``make_weights``, ``reference_following``, ``paged_decode``,
``decode_step``).
Folding the two runners into one is a later benchmark PR's: this one may not
edit a file that is there.

Three more differences.  The check takes a SEEDED SAMPLE of the window's
finished requests (``check.sample`` in the cell's file; all if fewer), so
that the reference stays near a minute.  With ``check.follow_routing`` the
engine records the experts that every position chose, and the reference
FOLLOWS them (benchmarks/reference/nemotron_h.py, "Following a choice"): a
top-k choice flips on rounding, and after a flip two precisions compute
different functions, so the logits are compared along the program's own
choices, and the choices themselves are held to the reference's scores
(``routing_deficit``).  And the run reads the counters a state model's
engine keeps: the resident recurrent state, and the expert layers' rows
(routed, on held experts, experts touched).

Two things keep the host out of the measured rate, which on a one-chip
machine's shared cores is what varies from run to run.  ``engine.run_ahead``
in the cell's file is the engine's option of that name: the decode call of
a tick is dispatched before the one before it is fetched, so this loop and
the engine's own walk run while the device computes.  And the objects that
set-up left behind (the traced programs, the weights' tree) are frozen out
of the collector before the window, so that no full collection walks them
inside it."""

from __future__ import annotations

import gc
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmarks import arch as A
from benchmarks import checks, harness, stats
from benchmarks.traffic import generator


def served_gap(forward: Callable, sample: List[Dict[str, Any]],
               quant: Optional[str] = None, pad_to: int = 0) -> Dict[str, Any]:
    """``checks.served_gap`` with the reference as an argument:
    ``forward(tokens [S], quant, follow) -> {logits [S, V], routing,
    deficit}``.  The widest gap by which a served token's logit lies below
    the reference's best, over every served token of ``sample``, the
    reference following the experts that the request's positions chose
    (``req['routing']``; its own where a request carries none); and the
    widest deficit of a followed expert.  With ``quant`` the lower
    precision stands in for the program: its first token at each position
    AND its choice of experts."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps_of(ref, served):
        return jnp.max(ref, axis=-1) - jnp.take_along_axis(
            ref, served[:, None], axis=-1)[:, 0]

    every: List[np.ndarray] = []
    deficit = 0.0
    for req in sample:
        toks = np.asarray(req["tokens"], np.int32)
        p, n = int(req["prompt_len"]), len(toks)
        size = max(pad_to, n - 1)
        inp = np.zeros(size, np.int32)
        inp[:n - 1] = toks[:-1]
        if quant is None:
            served = np.zeros(size, np.int32)
            served[:n - 1] = toks[1:]
            served, chose = jnp.asarray(served), req.get("routing")
        else:
            low = forward(inp, quant, None)
            served = jnp.argmax(low["logits"], axis=-1).astype(jnp.int32)
            chose = (None if low["routing"] is None
                     else np.asarray(low["routing"])[:n - 1])
        ref = forward(inp, None, chose)            # row t predicts token t+1
        every.append(np.asarray(gaps_of(ref["logits"], served))[p - 1:n - 1])
        if ref["deficit"] is not None:
            deficit = max(deficit, float(np.asarray(ref["deficit"])[:n - 1].max()))
    gaps = np.concatenate(every) if every else np.zeros(0, np.float32)
    if not gaps.size:
        return {"served_logit_gap": float("inf"), "routing_deficit": deficit,
                "tokens": 0, "not_top": 0, "mean_gap": 0.0, "quantiles": {}}
    return {"served_logit_gap": float(gaps.max()), "routing_deficit": deficit,
            "tokens": int(gaps.size),
            "not_top": int((gaps > 0).sum()), "mean_gap": float(gaps.mean()),
            "quantiles": {f"p{q}": float(np.percentile(gaps, q))
                          for q in (50, 90, 99, 99.9)}}


def run(ctx: harness.Context) -> Dict[str, Any]:
    import jax

    fam = A.family_of(ctx.config)
    cell, mix, geo = ctx.cell, ctx.cell["traffic"], ctx.cell["engine"]
    s = fam.shape(ctx.config, geo["max_ctx"])
    # a parent commit without this family's model fails here, at once
    pcfg = fam.program_config(ctx.config, geo["max_ctx"])

    from torchdistpackage_tpu.serving import Request, ServingEngine

    if ctx.control not in (None, "fp8"):
        raise harness.Refused(f"no control {ctx.control!r} for this family")
    params = fam.make_weights(s, ctx.seed)
    eng = ServingEngine(
        params, pcfg, num_slots=geo["num_slots"], block_size=geo["block_size"],
        chunk=geo["chunk"], max_ctx=geo["max_ctx"],
        num_blocks=geo.get("num_blocks"), attn_impl="auto",
        tick_history=1 << 20, chaos=ctx.chaos,
        record_routing=bool(cell["check"].get("follow_routing")),
        run_ahead=bool(geo.get("run_ahead")))
    if jax.default_backend() == "tpu" and eng.attn_impl != "pallas":
        raise harness.Refused(
            f"attn_impl='auto' resolved to {eng.attn_impl!r} on a TPU")

    reqs = generator.requests(mix, s.vocab, ctx.seed)
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
        # until both signatures have run (and compiled), the decode call
        # twice: a run-ahead engine's second takes the first's outputs
        warm = {"prefill_slots": 0, "decode_slots": 0}
        for _ in range(64):
            eng.step()
            for k in warm:
                warm[k] += bool(eng.tick_records[-1][k])
            if warm["prefill_slots"] and warm["decode_slots"] >= 2:
                break
        else:
            raise harness.Refused("the engine's first ticks never decoded")
    else:  # an open loop starts from an empty engine: one short request
        eng.submit(Request(tokens=[1] * geo["chunk"], max_new_tokens=2))
        eng.run_until_idle()
    seen = set(eng.finished) | set(eng.rejected)
    ticks_before = len(eng.tick_records)
    programs_before = ctx.compiles.programs
    ctx.log(phase="setup", attn_impl=eng.attn_impl, params=fam.num_params(s),
            num_blocks=eng.num_blocks, state_bytes=eng.state_bytes,
            warmup_finished=len(seen))

    # ---- the window
    gc.collect()
    gc.freeze()
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
    gc.unfreeze()
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
    unfinished = 0 if closed else sum(
        1 for rid in sent if rid not in eng.finished and rid not in eng.rejected)

    # every retired request: the count it asked for, tokens in the vocabulary
    bad = 0
    for f in finished:
        gen = np.asarray(f["tokens"][f["prompt_len"]:])
        if (f["new_tokens"] != f["asked"] or len(gen) != f["asked"]
                or not ((0 <= gen) & (gen < s.vocab)).all()):
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
    summary = eng.serving_summary()
    # the engine's counters over the window's ticks (a state model's tick
    # records carry them; an engine without them leaves the metrics out)
    moe = {k: sum(t[k] for t in ticks) if ticks and k in ticks[0] else None
           for k in ("moe_rows_routed", "moe_rows_held", "experts_touched")}
    n_decode_ticks = sum(1 for t in ticks if t["decode_slots"])
    state_bytes = getattr(eng, "state_bytes", None)
    imbalance = eng.moe_imbalance() + 1.0 if moe["moe_rows_held"] else None
    ctx.log(phase="window", window_s=window_s, ticks=len(ticks),
            ticks_with_prefill=len(with_prefill), emitted_tokens=emitted,
            sent=len(sent),
            queue_at_arrivals_end=queue_at_arrivals_end,
            finished=len(finished), rejected=rejected, unfinished=unfinished,
            wrong_count_or_vocab=bad, memory_peak_bytes=peak,
            prefill_signatures=summary["prefill_signatures"],
            decode_signatures=summary["decode_signatures"],
            state_bytes=state_bytes, **moe,
            ttft_ms={"n": len(ttft), "median": stats.median(ttft) if ttft else None},
            gap_ms={"n": len(gaps), "median": stats.median(gaps) if gaps else None},
            generator_lateness=late, tracer_stop_s=tracer.stop_s)

    # ---- the reference over a seeded sample of the requests that the
    # window finished, once the engine, its pool and its state are freed
    del eng
    want = int(cell["check"]["sample"])
    order = np.random.RandomState(ctx.seed & 0x7FFFFFFF).permutation(
        len(finished))
    sample = [finished[i] for i in sorted(order[:want])]

    def forward(tokens, quant, follow):
        return fam.reference_following(params, tokens, s, quant, follow)

    def numbers(got):
        return {k: got[k] for k in ("served_logit_gap", "routing_deficit")}

    t_ref = time.perf_counter()
    got = served_gap(forward, sample, pad_to=geo["max_ctx"])
    v = checks.verdict(numbers(got), cell["limits"])
    if not sample:
        v["correct"] = False
    ctx.log(phase="check", reference_s=time.perf_counter() - t_ref,
            checked_requests=len(sample), finished_requests=len(finished),
            served_tokens=got["tokens"], tokens_not_top=got["not_top"],
            mean_gap=got["mean_gap"], gap_quantiles=got["quantiles"], **v)

    if ctx.control == "fp8":
        low = served_gap(forward, sample, quant=ctx.control,
                         pad_to=geo["max_ctx"])
        ctx.log(phase="control", precision=ctx.control,
                tokens_not_top=low["not_top"], mean_gap=low["mean_gap"],
                gap_quantiles=low["quantiles"],
                **checks.verdict(numbers(low), cell["limits"]))

    def phase_ms(rows, names):
        return [sum(t["phases"].get(n, 0.0) for n in names) for t in rows]

    live = kv_token_reads / max(1, n_decode_ticks)
    slots = decode_slot_ticks / max(1, n_decode_ticks)
    costs = {"paged_decode": {**fam.paged_decode(s, live, slots),
                              "calls_per_execution": s.pattern.count("*")}}
    if moe["experts_touched"] is not None:
        costs["decode_step"] = {
            **fam.decode_step(s, live, slots,
                              moe["experts_touched"] / max(1, n_decode_ticks)),
            "calls_per_execution": 1}
    obs = {
        "spans": {
            **spans,
            "decode_tick": [t["tick_s"] for t in decode_only],
            "prefill_tick": [t["tick_s"] for t in with_prefill],
            "sched_host": phase_ms(ticks, ("audit", "sched", "host")),
            "queue_wait": [w / 1e3 for w in waits],
        },
        "values": {"memory_peak_bytes": peak,
                   "tokens_per_s": emitted / window_s,
                   "state_bytes": state_bytes or None,
                   "moe_rows_routed": moe["moe_rows_routed"],
                   "moe_rows_held": moe["moe_rows_held"],
                   "moe_max_over_mean": imbalance},
        "costs": costs,
        "peaks": ctx.peaks,
        "trace": trace,
    }
    return {"correct": v["correct"] and failed == 0,
            "attempted": len(finished) + rejected + unfinished,
            "failed": failed, "memory_peak_bytes": peak,
            "end_to_end": e2e, "obs": obs}
