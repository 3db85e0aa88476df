"""The order of a tick (docs/serving.md "The tick"): every device call of a
tick is dispatched before any call of that tick is fetched, so the decode
call runs on the device while the prefill calls' results travel and are
walked.  What makes that possible: a slot whose prompt ends in a tick's
prefill calls takes its first decode step in the NEXT tick's decode call.

The order, the counters and the dropped slices on the host-only
``StubDeviceStep`` (no compilation); the tokens of mixed queues on compiled
toys, one engine a kind, against references that know nothing of ticks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistpackage_tpu.models import GPTConfig, generate, init_gpt_params
from torchdistpackage_tpu.serving import (
    Request, ServingEngine, StubDeviceStep)
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
