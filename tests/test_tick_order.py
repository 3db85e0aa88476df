"""The order of a tick (docs/serving.md "The tick"): every device call of a
tick is dispatched before any call of that tick is fetched, so the decode
call runs on the device while the prefill calls' results travel and are
walked.  What makes that possible: a slot whose prompt ends in a tick's
prefill calls takes its first decode step in the NEXT tick's decode call.

The order, the counters and the dropped slices on the host-only
``StubDeviceStep`` (no compilation, and the SERIAL order: nothing can run
beside a step that runs in the caller's thread); the tokens of mixed queues
on compiled toys, one engine a kind, against references that know nothing of
ticks; and the decode discipline (f): a single-device engine runs a decode
call ahead by its own choice, and serves what the serial engine serves."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.models import (
    GPTConfig, generate, init_gpt_moe_params, init_gpt_params, llama_config)
from torchdistpackage_tpu.resilience import ChaosMonkey, Fault
from torchdistpackage_tpu.serving import (
    Request, ServingEngine, StubDeviceStep, migrate_blocks)
from torchdistpackage_tpu.serving.engine import DECODE, FREE, PREFILL
from torchdistpackage_tpu.utils.profiling import spans

CFG = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=48)
CHUNK = 4


def _stub(**kw):
    kw.setdefault("num_slots", 3)
    return ServingEngine(None, CFG, block_size=4, chunk=CHUNK,
                         device_step=StubDeviceStep(), **kw)


def _req(n, new, **kw):
    return Request(tokens=list(range(1, n + 1)), max_new_tokens=new, **kw)


def _decoding_then_one_prefilling(**kw):
    """Request 0 decodes; request 1 (two slices) is queued for the next
    tick, whose calls are then a prefill call AND a decode call."""
    eng = _stub(**kw)
    a = eng.submit(_req(3, 12))
    for _ in range(2):
        eng.step()
    assert eng._slots[0].state == DECODE and len(eng._slots[0].generated) == 2
    b = eng.submit(_req(CHUNK + 2, 6))
    return eng, a, b


def _kids(ring, name):
    return [r for r in ring if r[2] == name]


# ------------------------------------------------------------------ (a) spans


def test_the_decode_call_is_dispatched_before_the_prefill_call_is_fetched():
    eng, a, b = _decoding_then_one_prefilling()
    spans.clear()
    eng.step()
    ring = spans.snapshot()
    (prefill,), (decode,) = (_kids(ring, "tdp:engine.prefill"),
                             _kids(ring, "tdp:engine.decode"))
    fetches = sorted(_kids(ring, "tdp:engine.fetch"), key=lambda r: r[3])
    assert len(fetches) == 2
    # dispatched in the device's order, P then D, and both before any fetch
    assert prefill[4] <= decode[3] and decode[4] <= fetches[0][3]
    # the first fetch is the PREFILL call's, by the engine's running count:
    # the decode call's build had moved the count on before it opened
    assert decode[5]["call"] == prefill[5]["call"] + 1
    assert [f[5]["call"] for f in fetches] == [prefill[5]["call"],
                                               decode[5]["call"]]
    assert prefill[5]["rids"] == [b] and decode[5]["rids"] == [a]


def test_a_held_decode_fetches_the_prefill_call_at_once():
    """``hold_decode`` (a disaggregated prefill tier) has no decode call:
    the prefill call is fetched in its own tick, nothing queued whole."""
    eng = _stub()
    eng.hold_decode = True
    rid = eng.submit(_req(3, 4))
    spans.clear()
    eng.step()
    ring = spans.snapshot()
    assert not _kids(ring, "tdp:engine.decode")
    (prefill,), (fetch,) = (_kids(ring, "tdp:engine.prefill"),
                            _kids(ring, "tdp:engine.fetch"))
    assert fetch[5]["call"] == prefill[5]["call"]
    assert eng.decode_slots() == [(rid, 0)]   # parked with its first token
    assert eng.stats["ticks_queued_whole"] == eng.stats["late_joins"] == 0


# --------------------------------------------------------------- (b) the join


@pytest.mark.parametrize("kw", [{}, {"spec_k": 2}], ids=["decode", "verify"])
def test_a_prompt_that_ends_in_tick_t_joins_the_decode_call_of_tick_t_plus_1(
        kw):
    eng, a, b = _decoding_then_one_prefilling(**kw)
    eng.step()                      # b's first slice beside a's decode step
    assert eng._slots[1].state == PREFILL
    assert eng.stats["ticks_queued_whole"] == 1
    assert eng.stats["late_joins"] == 1            # a's own, ticks ago
    eng.step()                      # tick t: b's prompt ends
    rec = eng.tick_records[-1]
    assert rec["queued_whole"] and rec["late_joins"] == 1
    assert (rec["prefill_slots"], rec["decode_slots"]) == (1, 1)
    assert eng._tick_decode_rids == [a]            # no decode call of tick t
    s = eng._slots[1]
    assert s.state == DECODE and len(s.generated) == 1   # its FIRST token
    assert s.ttft_s is not None
    eng.step()                      # tick t + 1
    rec = eng.tick_records[-1]
    assert eng._tick_decode_rids == [a, b] and rec["decode_slots"] == 2
    assert not rec["queued_whole"] and rec["late_joins"] == 0
    assert len(s.generated) >= 2
    eng.run_until_idle()
    assert eng.stats["late_joins"] == 2            # a's own and b's
    assert eng.stats["ticks_queued_whole"] == 2
    acc = eng.serving_summary()["tick_accounting"]
    assert acc["ticks_queued_whole"] == acc["ticks_prefill_and_decode"] == 2
    assert acc["late_joins"] == 2
    assert eng.finished[b]["new_tokens"] == 6


def test_the_tokens_do_not_depend_on_who_is_in_a_call():
    """The stub's tokens are a function of a sequence's own last token,
    position and key, as a compiled call's rows are of their own slot: a
    request that arrives mid-flight ends as it does alone."""
    eng, a, b = _decoding_then_one_prefilling()
    eng.run_until_idle()
    for rid in (a, b):
        alone = _stub()
        f = eng.finished[rid]
        r = alone.submit(Request(tokens=f["tokens"][:f["prompt_len"]].tolist(),
                                 max_new_tokens=f["new_tokens"]))
        alone.run_until_idle()
        np.testing.assert_array_equal(alone.finished[r]["tokens"], f["tokens"])


# ------------------------------------------- (d) a first token that is the last


def _first_token(n):
    probe = _stub()
    rid = probe.submit(_req(n, 2))
    probe.run_until_idle()
    return int(probe.finished[rid]["tokens"][n])


@pytest.mark.parametrize("how", ["eos", "max_new_tokens"])
def test_a_first_token_that_ends_the_request_retires_at_the_walk(how):
    eng, a, _ = _decoding_then_one_prefilling()
    n = 3
    kw = ({"eos_id": _first_token(n)} if how == "eos" else {})
    c = eng.submit(_req(n, 5 if how == "eos" else 1, **kw))
    eng.step()                      # c's whole prompt, beside the others
    assert eng.finished[c]["reason"] == how.replace("max_new_", "max_")
    assert eng.finished[c]["new_tokens"] == 1
    assert eng._slots[2].state == FREE
    assert eng.tick_records[-1]["late_joins"] == 0
    joined = []
    while eng.queue or eng.n_busy:
        eng.step()
        joined += eng._tick_decode_rids
    assert c not in joined and a in joined
    assert eng.audit(heal=False)["ok"]


# ------------------------------ (e) a slot lost between a dispatch and its fetch


@pytest.mark.parametrize("how", ["cancelled", "preempted"])
def test_a_slot_lost_between_the_prefill_dispatch_and_its_fetch_drops_its_slice(
        how, monkeypatch):
    """Between a tick's prefill dispatch and its fetch the engine builds
    and dispatches the decode call and absorbs a flight; whatever retires,
    cancels or requeues a prefilling slot there (here: a hook on the decode
    call) must find its slice dropped when it arrives, as ``run_ahead``'s
    flight drops a token: the slot's new owner, or nobody, is not handed
    the old one's token."""
    want = _stub()
    for r in (_req(3, 12), _req(3, 5)):
        want.submit(r)
    want.run_until_idle()

    eng = _stub()
    a = eng.submit(_req(3, 12))
    for _ in range(2):
        eng.step()
    b = eng.submit(_req(3, 5))      # its whole prompt is one slice
    real = eng._dispatch_decode

    def lose_b():
        call = real()
        (i,) = [i for i, s in enumerate(eng._slots) if s.rid == b]
        assert eng._slots[i].state == PREFILL
        if how == "cancelled":
            assert eng.cancel(b)
        else:
            eng._preempt(i, by=eng._slots[0].req)
        return call

    monkeypatch.setattr(eng, "_dispatch_decode", lose_b)
    eng.step()
    monkeypatch.undo()
    assert eng._slots[1].state == FREE and not eng._slots[1].generated
    assert eng.tick_records[-1]["late_joins"] == 0
    assert eng.audit(heal=False)["ok"]
    if how == "cancelled":
        f = eng.finished[b]
        assert (f["reason"], f["new_tokens"]) == ("cancelled", 0)
        assert f["ttft_s"] is None
    else:
        assert [r.rid for r, _ in eng.queue] == [b]
    eng.run_until_idle()
    np.testing.assert_array_equal(eng.finished[a]["tokens"],
                                  want.finished[0]["tokens"])
    if how == "preempted":   # replayed from its prompt: the same tokens
        np.testing.assert_array_equal(eng.finished[b]["tokens"],
                                      want.finished[1]["tokens"])


# ------------------------------------------- (c) mixed queues on compiled toys


def _mixed_queue(eng, prompts, news):
    """The prompts submitted two by two, two ticks apart, on three slots:
    prefill calls and decode calls share ticks, slots are re-admitted while
    others decode."""
    rids = []
    for k in range(0, len(prompts), 2):
        for p, n in zip(prompts[k:k + 2], news[k:k + 2]):
            rids.append(eng.submit(Request(tokens=list(p), max_new_tokens=n)))
        eng.step()
        eng.step()
    eng.run_until_idle()
    assert eng.stats["ticks_queued_whole"] >= 3
    assert eng.stats["late_joins"] == len(rids)
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    assert eng.audit(heal=False)["ok"]
    return [eng.finished[r] for r in rids]


@pytest.fixture(scope="module")
def dense():
    params = init_gpt_params(jax.random.PRNGKey(0), CFG)
    gold = {}

    def want(prompt, new):
        if new not in gold:
            gold[new] = jax.jit(lambda p, t: generate(p, t, CFG,
                                                      max_new_tokens=new))
        return np.asarray(gold[new](params, jnp.asarray(prompt)[None]))[0]

    return params, want


@pytest.mark.parametrize("kw", [{}, {"prefix_cache": True}, {"spec_k": 2},
                                {"prefix_cache": True, "spec_k": 2}],
                         ids=["dense", "prefix_cache", "spec_k",
                              "prefix_cache+spec_k"])
def test_a_dense_mixed_queue_ends_on_generates_tokens(dense, kw):
    """Bit-equal to ``generate()``, whose batch knows no ticks; with
    ``prefix_cache`` the six prompts share a two-block prefix, so later
    ones map the blocks that an earlier one's LANDED slice registered."""
    params, want = dense
    rng = np.random.RandomState(3)
    shared = rng.randint(0, CFG.vocab_size, 8).tolist()
    prompts = [shared + rng.randint(0, CFG.vocab_size, n).tolist()
               for n in (1, 5, 3, 9, 2, 6)]
    eng = ServingEngine(params, CFG, num_slots=3, block_size=4, chunk=CHUNK,
                        max_ctx=40, **kw)
    done = _mixed_queue(eng, prompts, [6, 8, 6, 8, 6, 8])
    for f, p in zip(done, prompts):
        np.testing.assert_array_equal(f["tokens"], want(p, f["new_tokens"]))
    if kw.get("prefix_cache"):
        assert eng.stats["prefix_hits"] >= 3


def _family_toy(name):
    """(shape, float32 program config, float32 weights, the reference's
    teacher-forced logits) of a benchmark family at the toy widths its own
    test file uses."""
    import importlib

    test = importlib.import_module(f"test_{name}")
    fam, ref, toy = test.family, test.ref, test.TOY
    ctx = getattr(test, "MAX_CTX", 64)
    s = fam.shape(toy, ctx)
    cfg = dataclasses.replace(fam.program_config(toy, ctx), dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          test.make_weights(s, 7))
    return s, cfg, params, ref, ctx


@pytest.mark.parametrize("name", ["hybrid", "afmoe"],
                         ids=["state_model", "window_pool"])
def test_a_run_ahead_mixed_queue_stays_on_the_references_logits(name):
    """A state model and a model with a window pool, both with
    ``run_ahead``: every served token's logit lies within 1e-4 of the best
    of the plain float32 reference's full forward (no cache, no chunks, no
    ticks), and ends as the request served ALONE ends."""
    s, cfg, params, ref, ctx = _family_toy(name)
    rng = np.random.RandomState(1)
    lens = (5, 13, 22, 9, 30, 16)
    prompts = [rng.randint(0, s.vocab, n).tolist() for n in lens]
    news = [7, 5, 9, 6, 8, 4]

    def engine():
        return ServingEngine(params, cfg, num_slots=3, block_size=8, chunk=8,
                             max_ctx=ctx, attn_impl="gather", run_ahead=True)

    with jax.default_matmul_precision("highest"):
        done = _mixed_queue(engine(), prompts, news)
        alone = engine()
        for f, p in zip(done, prompts):
            r = alone.submit(Request(tokens=p, max_new_tokens=f["new_tokens"]))
            alone.run_until_idle()
            np.testing.assert_array_equal(alone.finished[r]["tokens"],
                                          f["tokens"])
    for f, p, n in zip(done, prompts, news):
        toks = np.asarray(f["tokens"])
        assert f["new_tokens"] == n and len(toks) == len(p) + n
        logits = np.asarray(ref.forward_logits(params, toks[:-1], s))[
            len(p) - 1:]
        served = logits[np.arange(n), toks[len(p):]]
        assert float((logits.max(-1) - served).max()) <= 1e-4


# ------------------------------------------------- (f) the decode discipline
#
# run_ahead is the engine's own choice: a tick's decode call is dispatched
# before the call of the tick before is fetched.  Same tokens; what the host
# sees lags one decode call.

KINDS = {
    "dense": (CFG, {}),
    "prefix_cache": (CFG, {"prefix_cache": True}),
    "kv_quant": (CFG, {"kv_quant": True}),
    "window": (llama_config(vocab_size=64, dim=32, nheads=4, nlayers=2,
                            max_seq=48, kv_heads=2, ffn_hidden=48,
                            dtype=jnp.float32, sliding_window=6), {}),
    "moe": (GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=48,
                      moe_experts=4, moe_top_k=2, moe_every=2,
                      moe_capacity_factor=2.0), {}),   # = E / top_k: no drops
}
SAMPLING = dict(temperature=1.0, top_k=16, top_p=0.9, seed=7)


@pytest.fixture(scope="module")
def pairs():
    """kind -> (the engine as it is constructed, the serial one), built
    once a module and reused idle."""
    built = {}

    def get(kind):
        if kind not in built:
            cfg, kw = KINDS[kind]
            init = init_gpt_moe_params if cfg.moe_experts else init_gpt_params
            params = init(jax.random.PRNGKey(0), cfg)
            built[kind] = tuple(
                ServingEngine(params, cfg, num_slots=3, block_size=4,
                              chunk=CHUNK, max_ctx=40, **kw, **how)
                for how in ({}, {"run_ahead": False}))
        ahead, serial = built[kind]
        assert ahead.run_ahead and not serial.run_ahead
        for eng in built[kind]:
            assert not (eng.queue or eng.n_busy) and eng._flight is None
            eng.reset_metrics()
        return built[kind]

    return get


def _prompts(vocab=CFG.vocab_size, lens=(4, 5, 3, 9, 2, 6)):
    """Six prompts behind a shared two-block prefix (what ``prefix_cache``
    maps), the last a copy of the first, which is whole blocks (a
    whole-prompt hit: copy-on-write while a decode call is in flight)."""
    rng = np.random.RandomState(3)
    shared = rng.randint(0, vocab, 8).tolist()
    prompts = [shared + rng.randint(0, vocab, n).tolist() for n in lens]
    return prompts[:-1] + [list(prompts[0])]


def _serve(eng, reqs, after_tick=None):
    """``reqs`` two by two, two ticks apart, on three slots, then to idle;
    ``after_tick(eng, rids)`` runs behind every tick."""
    rids = []

    def tick():
        eng.step()
        if after_tick is not None:
            after_tick(eng, rids)

    for k in range(0, len(reqs), 2):
        rids += [eng.submit(r) for r in reqs[k:k + 2]]
        tick()
        tick()
    for _ in range(500):
        if not (eng.queue or eng.n_busy):
            break
        tick()
    assert not (eng.queue or eng.n_busy)
    assert eng.audit(heal=False)["ok"]
    return rids


def _requests(news=(6, 8, 6, 8, 6, 8), sampler=None, **kw):
    return [Request(tokens=list(p), max_new_tokens=n,
                    **(SAMPLING if i == sampler else {}), **kw)
            for i, (p, n) in enumerate(zip(_prompts(), news))]


@pytest.mark.parametrize("sampler", [None, 1],
                         ids=["greedy", "one_sampling_row"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_the_engine_as_constructed_serves_the_serial_engines_tokens(
        pairs, kind, sampler):
    """Dense, prefix cache (shared blocks, a copy-on-write), int8 pool,
    window and Mixtral-shaped experts: the default engine runs ahead and
    ends every request, greedy or sampling, on the serial engine's tokens,
    with one program a signature."""
    ahead, serial = pairs(kind)
    got, want = ({r: eng.finished[r] for r in _serve(eng, _requests(
        sampler=sampler))} for eng in (ahead, serial))
    assert list(got) == list(want)
    for rid in want:
        np.testing.assert_array_equal(got[rid]["tokens"], want[rid]["tokens"])
        assert got[rid]["reason"] == want[rid]["reason"] == "max_tokens"
    for eng in (ahead, serial):
        s = eng.serving_summary()
        assert s["decode_signatures"] == s["prefill_signatures"] == 1
        assert eng.stats["late_joins"] == len(want)
    assert serial.stats["ahead_rows"] == serial.stats["flight_dropped"] == 0
    # every decode step of a request but its first took its token from the
    # device; retirement by count drops nothing (the slot sits a call out)
    assert ahead.stats["ahead_rows"] == sum(
        f["new_tokens"] - 2 for f in want.values())
    assert ahead.stats["flight_dropped"] == 0
    assert ahead.stats["decode_slot_steps"] == serial.stats[
        "decode_slot_steps"]
    if kind == "prefix_cache":
        assert ahead.stats["prefix_hits"] >= 3 and ahead.stats["cow_copies"]
    if sampler is not None:   # the draw was a draw
        greedy = _serve(serial, _requests())[sampler]
        assert not np.array_equal(serial.finished[greedy]["tokens"],
                                  list(want.values())[sampler]["tokens"])


def _first_seen_late(gen, lo=3):
    """The first index from ``lo`` on, two short of the end at most, whose
    token the answer had not held before (an ``eos_id`` that ends it THERE),
    or None: a toy's greedy answers repeat themselves."""
    return next((k for k in range(lo, len(gen) - 2) if gen[k] not in gen[:k]),
                None)


def _in_flight(eng, rid):
    """Is a token of ``rid`` on the device, not yet booked?"""
    return eng._flight is not None and any(
        who[0] == rid for _, who in eng._flight["slots"])


@pytest.fixture(scope="module")
def oracle(pairs):
    """The serial engine's uneventful run of :func:`_requests` with long
    answers: what every eventful run below must end on."""
    _, serial = pairs("dense")
    news = (12, 14, 12, 14, 12, 14)
    rids = _serve(serial, _requests(news))
    return news, [serial.finished[r]["tokens"] for r in rids]


@pytest.mark.parametrize("how", ["eos", "cancelled", "preempted", "poisoned",
                                 "exported", "drained"])
def test_a_slot_that_leaves_decode_drops_its_token_in_flight(pairs, oracle,
                                                             how):
    """Every way out of DECODE with a token on the device: the token is
    dropped when it arrives (``flight_dropped``), and the sequence goes on,
    here or elsewhere, to the serial engine's tokens."""
    ahead, serial = pairs("dense")
    news, want = oracle
    reqs = _requests(news)
    state = {"hit": 0}
    # the request that leaves: among the first three (admitted at once), one
    # whose answer holds a token it had not held before, for ``eos_id``
    victim, k = next((i, k) for i in range(3) for k in [_first_seen_late(
        want[i][len(reqs[i].tokens):].tolist())] if k is not None)

    if how == "eos":
        gen = want[victim][len(reqs[victim].tokens):].tolist()
        reqs[victim] = dataclasses.replace(reqs[victim], eos_id=gen[k])
        want = list(want)
        want[victim] = want[victim][:len(reqs[victim].tokens) + k + 1]

    def after_tick(eng, rids):
        if state["hit"] or len(rids) <= victim:
            return
        rid = rids[victim]
        s = next((s for s in eng._slots if s.rid == rid), None)
        if s is None or s.state != DECODE or len(s.generated) < 4:
            return
        if eng.run_ahead:
            assert _in_flight(eng, rid)
        state["hit"] = 1
        if how == "cancelled":
            assert eng.cancel(rid)
        elif how == "preempted":   # all three slots busy: it evicts one
            assert eng.n_busy == eng.num_slots
            state["by"] = eng.submit(Request(tokens=[5, 6, 7],
                                             max_new_tokens=4, priority=1))
        elif how == "poisoned":
            (i,) = [i for i, t in enumerate(eng._slots) if t.rid == rid]
            # in every call fetched in the next tick (a prefill call's
            # walk passes a decoding slot's entry by)
            eng.chaos = ChaosMonkey(faults=[Fault(
                "nan_logits", step=eng._tick + 1, slot=i, repeat=True)],
                seed=0)
        elif how == "exported":   # into the other engine, then its blocks
            other = state["other"]
            desc, pool = eng.export_slot(rid)
            res = other.import_slot(desc)
            lanes = lambda ids: jnp.asarray(ids[:res["n_live"]], jnp.int32)
            other.cache = migrate_blocks(pool, other.cache,
                                         lanes(desc["blocks"]),
                                         lanes(res["blocks"]))
            state["moved"] = res["rid"]
        elif how == "drained":
            state["payload"] = eng.drain()
            assert eng._flight is None
            state["resumed"] = eng.resume(state["payload"])

    def run(eng):
        state.clear()
        state["hit"] = 0
        # exported: the pair's other engine takes the slot, and decodes it
        # once this one is idle
        state["other"] = serial if eng is ahead else ahead
        try:
            rids = _serve(eng, reqs, None if how == "eos" else after_tick)
            state["other"].run_until_idle()
        finally:
            eng.chaos = None
        return rids

    for eng in (ahead, serial):
        rids = run(eng)
        done = dict(eng.finished)
        if how == "exported":
            done[rids[victim]] = state["other"].finished[state["moved"]]
            state["other"].reset_metrics()
        if how == "drained":   # replayed under new rids, in slot order first
            by_prompt = {}
            for r in state["resumed"]:
                f = eng.finished[r]
                by_prompt[(len(f["tokens"]), tuple(f["tokens"][:20]))] = f
            for i, rid in enumerate(rids):
                if rid not in done:
                    done[rid] = by_prompt[(len(want[i]),
                                           tuple(want[i][:20]))]
        for i, rid in enumerate(rids):
            got = done[rid]["tokens"]
            if how == "cancelled" and i == victim:
                assert done[rid]["reason"] == "cancelled"
                np.testing.assert_array_equal(got, want[i][:len(got)])
            else:
                np.testing.assert_array_equal(got, want[i])
        if how == "eos":
            assert done[rids[victim]]["reason"] == "eos"
        else:
            assert state["hit"]
        if how == "preempted":
            assert eng.stats["preempted"] == 1
        if how == "poisoned":
            assert eng.stats["faults_healed"] == 1
        dropped = eng.stats["flight_dropped"]
        if eng is serial:
            assert dropped == 0
        elif how == "drained":   # the whole call in flight: every busy slot
            assert dropped >= 1
        else:
            assert dropped == 1
        # (a drain drops the call in flight outside any tick)
        assert sum(t["flight_dropped"] for t in eng.tick_records) == (
            0 if how == "drained" else dropped)
        assert eng.serving_summary()["tick_accounting"][
            "flight_dropped"] == dropped
        assert eng.serving_summary()["decode_signatures"] == 1


def test_the_counters_count_what_they_say(pairs):
    """ONE request: its first decode step takes the host's token (a late
    join), every later one the device's (``ahead_rows``); one that ends on
    EOS had its next step in flight (``flight_dropped``), one that ends by
    count sat that call out.  The tick records carry each tick's own."""
    ahead, serial = pairs("dense")
    N = 12
    for prompt in _prompts():
        r = serial.submit(Request(tokens=prompt, max_new_tokens=N))
        serial.run_until_idle()
        gen = serial.finished[r]["tokens"][len(prompt):].tolist()
        k = _first_seen_late(gen)
        if k is not None:
            break
    for eos, n_ahead, n_dropped in ((None, N - 2, 0), (gen[k], k, 1)):
        ahead.reset_metrics()
        r = ahead.submit(Request(tokens=prompt, max_new_tokens=N, eos_id=eos))
        ahead.run_until_idle()
        np.testing.assert_array_equal(
            ahead.finished[r]["tokens"][len(prompt):],
            gen if eos is None else gen[:k + 1])
        assert ahead._flight is None
        st = ahead.stats
        assert (st["ahead_rows"], st["flight_dropped"]) == (n_ahead, n_dropped)
        assert st["decode_slot_steps"] == n_ahead + 1
        recs = ahead.tick_records
        assert sum(t["ahead_rows"] for t in recs) == n_ahead
        assert sum(t["flight_dropped"] for t in recs) == n_dropped
        assert {t["ahead_rows"] for t in recs} == {0, 1}
        acc = ahead.serving_summary()["tick_accounting"]
        assert (acc["ahead_rows"], acc["flight_dropped"]) == (n_ahead,
                                                              n_dropped)
        # what the host sees lags one decode call: a token a tick from the
        # tick after the first decode call on
        assert sum(t["emitted_tokens"] for t in recs) == (
            N if eos is None else k + 1)


def test_a_run_ahead_fetch_names_the_dispatch_of_the_tick_before(pairs):
    """A tick's decode fetch opens BEHIND that tick's own decode dispatch
    span and waits for the call of the tick before (``call``)."""
    ahead, _ = pairs("dense")
    ahead.submit(Request(tokens=_prompts()[0], max_new_tokens=8))
    spans.clear()
    ahead.run_until_idle()
    ring = spans.snapshot()
    tick_of = {t[0]: t[5]["tick"] for t in _kids(ring, "tdp:engine.tick")}
    decodes = _kids(ring, "tdp:engine.decode")
    # the prefill calls' fetches wait in their own tick; the others are the
    # decode calls', all of them, in order
    waits = [f for f in _kids(ring, "tdp:engine.fetch")
             if f[5]["call"] in {d[5]["call"] for d in decodes}]
    assert [f[5]["call"] for f in waits] == [d[5]["call"] for d in decodes]
    assert len(decodes) == 7
    for d, f, nxt in zip(decodes, waits, decodes[1:] + [None]):
        assert tick_of[f[1]] == tick_of[d[1]] + 1
        if nxt is not None:   # behind the next tick's own dispatch
            assert tick_of[nxt[1]] == tick_of[f[1]] and nxt[4] <= f[3]


def _mesh(devices8, *axes):
    tpc.setup_process_groups(list(axes), devices=devices8[:int(np.prod(
        [n for _, n in axes]))])
    return tpc.get_view()


@pytest.mark.parametrize("what", ["spec_k", "mesh", "cp_axis", "host_only"])
def test_the_engine_keeps_the_serial_order_where_the_step_has_no_prev(
        what, devices8):
    """The engine's own choice, from its constructor's arguments: a
    speculative engine (the next draft needs this tick's tokens), a mesh
    and a CP ring (the ``shard_map``'d steps carry no ``prev``) and a
    host-only device step (nothing runs beside the caller's thread) keep
    the serial order; an explicit ``True`` there raises, ``False`` is
    always allowed."""
    kw = {"spec_k": lambda: dict(spec_k=2),
          "mesh": lambda: dict(mesh=_mesh(devices8, ("data", 2)),
                               dp_axis="data"),
          "cp_axis": lambda: dict(mesh=_mesh(devices8, ("context", 2)),
                                  cp_axis="context"),
          "host_only": lambda: dict(device_step=StubDeviceStep())}[what]
    params = init_gpt_params(jax.random.PRNGKey(0), CFG)

    def engine(**more):
        return ServingEngine(params, CFG, num_slots=2, block_size=4,
                             chunk=CHUNK, max_ctx=40, **kw(), **more)

    for said in (None, False):
        eng = engine(**({} if said is None else {"run_ahead": said}))
        assert eng.run_ahead is False and eng._no_flight is None
    with pytest.raises(NotImplementedError, match="run_ahead with " + (
            "a host-only" if what == "host_only" else
            "a mesh" if what == "mesh" else what)):
        engine(run_ahead=True)
    # and it serves: two requests to the end, nothing taken from the device
    rids = [eng.submit(_req(5, 4)), eng.submit(_req(3, 5))]
    eng.run_until_idle()
    assert [eng.finished[r]["new_tokens"] for r in rids] == [4, 5]
    assert eng.stats["ahead_rows"] == eng.stats["flight_dropped"] == 0
