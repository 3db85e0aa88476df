"""Context-parallel chunked prefill over paged KV (PR 20).

The tentpole contract (docs/long_context.md "CP prefill serving"): a
prompt's prefill chunks run across a ``context`` mesh axis — each CP
rank owns a contiguous slice of the prompt and fills its OWN slice of
the block-sharded paged pool, ring-passing (k, v) payloads via
python-unrolled ppermutes so every hop is priced in the HLO comm
ledger.  The bar is BIT parity: temperature-0 tokens from a CP engine
must equal the single-replica chunked-prefill engine's, fp pool,
dense/GQA/sliding, gather oracle and pallas carry kernel, including
the prefill-tier -> decode-replica handoff — while ``decode_signatures``
stays 1 (the S_in=1 signature compiles the local-slice + psum-combine
decode, not a second ring program).

Reference engines are banked per session (``bundle_bank`` in conftest):
every test here shares one golden run per model family.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.models import (
    GPTConfig, gpt_param_specs, init_gpt_params, llama_config)
from torchdistpackage_tpu.obs import (
    EventLog, ledger_from_compiled, set_default_event_log)
from torchdistpackage_tpu.obs.comm_ledger import cp_ring_overlap
from torchdistpackage_tpu.obs.mem_ledger import headroom_verdict
from torchdistpackage_tpu.obs.report import _validate_serving
from torchdistpackage_tpu.ops.paged_attention import modeled_attend_temp_bytes
from torchdistpackage_tpu.ops.ring_paged import (
    modeled_cp_working_set_bytes, ring_chunk_bytes, ring_hops_per_chunk)
from torchdistpackage_tpu.serving import Request, Router, ServingEngine

PROMPT, NEW, BS, CHUNK = 9, 6, 4, 4

CFGS = {
    "dense": lambda: GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2,
                               max_seq=64),
    "gqa": lambda: llama_config(vocab_size=64, dim=32, nheads=4, nlayers=2,
                                max_seq=64, kv_heads=2, ffn_hidden=48,
                                dtype=jnp.float32),
    "sliding": lambda: llama_config(vocab_size=64, dim=32, nheads=4,
                                    nlayers=2, max_seq=64, kv_heads=2,
                                    ffn_hidden=48, dtype=jnp.float32,
                                    sliding_window=6),
}


def _prompts(cfg, n=2):
    return np.stack([
        np.asarray(jax.random.randint(
            jax.random.PRNGKey(10 + i), (PROMPT,), 0, cfg.vocab_size))
        for i in range(n)
    ]).astype(np.int32)


@pytest.fixture(scope="module")
def refs(bundle_bank):
    """Per-family golden bundle: unsharded single-replica chunked-prefill
    run (the parity oracle), banked for the session.  num_blocks=16 so
    CP engines at cp in {1, 2, 4} can share the same pool geometry (the
    router's handoff check requires equal geometry across replicas)."""

    def get(fam):
        def build():
            cfg = CFGS[fam]()
            params = init_gpt_params(jax.random.PRNGKey(0), cfg)
            prompts = _prompts(cfg)
            eng = ServingEngine(params, cfg, num_slots=2, block_size=BS,
                                chunk=CHUNK, num_blocks=16)
            rids = [eng.submit(Request(p.tolist(), NEW)) for p in prompts]
            eng.run_until_idle(max_ticks=500)
            want = [np.asarray(eng.finished[r]["tokens"]) for r in rids]
            assert eng.serving_summary()["decode_signatures"] == 1
            return {"cfg": cfg, "params": params, "prompts": prompts,
                    "want": want}
        return bundle_bank.get(("cp-ref", fam), build)

    return get


def _cp_engine(ref, cp, *, impl="gather", **kw):
    devices = jax.devices()
    tpc.setup_process_groups([("context", cp)], devices=devices[:cp])
    mesh = tpc.get_view()
    return ServingEngine(ref["params"], ref["cfg"], num_slots=2,
                         block_size=BS, chunk=CHUNK, num_blocks=16,
                         mesh=mesh, cp_axis="context", attn_impl=impl, **kw)


def _assert_parity(ref, eng, tag):
    rids = [eng.submit(Request(p.tolist(), NEW)) for p in ref["prompts"]]
    eng.run_until_idle(max_ticks=500)
    for w, r in zip(ref["want"], rids):
        np.testing.assert_array_equal(w, eng.finished[r]["tokens"],
                                      err_msg=tag)
    return eng.serving_summary()


# ------------------------------------------------------------ bit parity


@pytest.mark.parametrize("fam,cp", [
    ("dense", 2),
    ("sliding", 2),
    # wider rings and the dense family's 4-way split exercise no new
    # signature shapes (sub-chunk routing covered by the cp=4 dense arm)
    # — slow tier keeps them without charging tier-1 two more compiles
    pytest.param("dense", 4, marks=pytest.mark.slow),
    pytest.param("gqa", 4, marks=pytest.mark.slow),
])
def test_cp_prefill_token_parity(refs, fam, cp):
    """CP chunked prefill is bit-identical to the single-replica oracle,
    and the host-side ring ledger agrees with the hop/byte model."""
    ref = refs(fam)
    eng = _cp_engine(ref, cp)
    eng.prefill_width = 1   # two prompts prefilling in one tick: two calls
    s = _assert_parity(ref, eng, f"{fam} cp={cp}")
    cfg = ref["cfg"]
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    lc = s["long_context"]
    assert lc["cp"] == cp and lc["cp_axis"] == "context"
    # the ring turns once a compiled CALL and carries that call's rows
    assert lc["prefill_calls"] > lc["prefill_chunks"] > 0
    assert lc["ring_hops"] == \
        lc["prefill_calls"] * ring_hops_per_chunk(cfg.nlayers, cp)
    assert lc["ring_bytes"] == lc["prefill_calls"] * ring_chunk_bytes(
        nlayers=cfg.nlayers, cp=cp, batch=eng.prefill_width,
        kv_heads=cfg.block.kv_head_count, head_dim=cfg.block.head_dim,
        chunk=CHUNK, nb_local=16 // cp, block_size=BS, itemsize=4)


def test_cp1_degenerate_is_ring_free(refs):
    """cp=1 on a context mesh is the identity: same tokens, zero hops —
    the validated long_context block still renders (cp=1, ring_bytes=0)."""
    ref = refs("dense")
    s = _assert_parity(ref, _cp_engine(ref, 1), "dense cp=1")
    lc = s["long_context"]
    assert lc["cp"] == 1 and lc["ring_hops"] == 0 and lc["ring_bytes"] == 0
    assert lc["prefill_chunks"] > 0


def test_cp_pallas_carry_matches_gather(refs):
    """The pallas carry entry point (un-normalized online-softmax carry
    accumulated across ranks, finalized once) reproduces the gather
    oracle's tokens bit-for-bit on the GQA family under cp=2."""
    ref = refs("gqa")
    s = _assert_parity(ref, _cp_engine(ref, 2, impl="pallas"),
                       "gqa cp=2 pallas")
    assert s["decode_signatures"] == 1
    assert s["long_context"]["ring_hops"] > 0


@pytest.mark.slow
def test_cp_composes_with_tensor_parallel(refs, devices8):
    """cp=2 x tp=2: the ring runs over ``context`` while attention heads
    shard over ``tensor`` — tokens still bit-match the serial oracle."""
    from jax.sharding import NamedSharding

    ref = refs("gqa")
    cfg = ref["cfg"]
    tpc.setup_process_groups([("context", 2), ("tensor", 2)],
                             devices=devices8[:4])
    mesh = tpc.get_view()
    specs = gpt_param_specs(cfg, tp_axis="tensor")
    sharded = jax.tree.map(
        lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)),
        ref["params"], specs)
    eng = ServingEngine(sharded, cfg, num_slots=2, block_size=BS,
                        chunk=CHUNK, num_blocks=16, mesh=mesh,
                        axis="tensor", cp_axis="context")
    _assert_parity(ref, eng, "gqa tp=2 x cp=2")


# ------------------------------------------- prefill tier -> decode tier


def test_cp_prefill_tier_handoff(refs):
    """PR-15 disaggregation composes with CP prefill: the prefill
    replica rings a long prompt to first token, the router migrates its
    paged blocks to a plain decode replica, and the finished tokens
    still bit-match the single-replica oracle.  The handoff of a
    >=long_ctx_threshold prompt emits ``kv_handoff_long``."""
    ref = refs("gqa")
    log = EventLog()
    set_default_event_log(log)
    try:
        pre = _cp_engine(ref, 2)
        dec = ServingEngine(ref["params"], ref["cfg"], num_slots=2,
                            block_size=BS, chunk=CHUNK, num_blocks=16)
        pre._ev = log
        dec._ev = log
        router = Router([pre, dec], roles=["prefill", "decode"],
                        long_ctx_threshold=8)
        rids = [router.submit(Request(p.tolist(), NEW))
                for p in ref["prompts"]]
        router.run_until_idle()
    finally:
        set_default_event_log(None)
    for w, r in zip(ref["want"], rids):
        np.testing.assert_array_equal(w, router.finished[r]["tokens"],
                                      err_msg="cp prefill-tier handoff")
        assert router.finished[r]["replica"] == 1

    # tier separation: the CP replica only prefills, the decode replica
    # only decodes — one signature each
    assert pre.stats["decode_steps"] == 0 and pre.stats["prefill_chunks"] > 0
    assert dec.stats["prefill_chunks"] == 0 and dec.stats["decode_steps"] > 0
    sp, sd = pre.serving_summary(), dec.serving_summary()
    assert sp["prefill_signatures"] == 1 and sp["long_context"]["cp"] == 2
    assert sd["decode_signatures"] == 1 and sd["prefill_signatures"] == 0
    assert pre.stats["migrated_out"] == 2 and dec.stats["migrated_in"] == 2
    assert _validate_serving(sp) == []

    kinds = {e["kind"] for e in log.as_list()}
    assert {"cp_prefill_chunk", "cp_ring_hop", "kv_handoff_long"} <= kinds
    evs = [e for e in log.as_list() if e["kind"] == "kv_handoff_long"]
    assert len(evs) == 2
    for e in evs:
        assert e["cp"] == 2 and e["length"] >= 8 and e["bytes"] > 0
        assert e["src_replica"] == 0 and e["dst_replica"] == 1
        assert e["n_blocks"] == -(-(PROMPT + 1) // BS)


# --------------------------------------------------- HLO comm evidence


def test_cp_ring_hops_priced_per_hop(refs, devices8):
    """The comm-ledger acceptance bar: the compiled prefill chunk shows
    exactly ``4*(cp-1)*nlayers`` collective-permutes on the cp dim — the
    layer loop is python-unrolled, so there is no while-body undercount
    — and their HLO byte total equals the host model's
    ``ring_chunk_bytes``.  ``cp_ring_overlap`` summarizes the window."""
    ref = refs("dense")
    cfg = ref["cfg"]
    eng = _cp_engine(ref, 2)
    B, C, mb = eng.prefill_width, eng.chunk, eng.max_blocks  # the prefill call
    samp = {"temperature": jnp.zeros((B,), jnp.float32),
            "top_k": jnp.full((B,), cfg.vocab_size, jnp.int32),
            "top_p": jnp.ones((B,), jnp.float32)}
    lowered = eng._step_fn.lower(
        eng.params, eng.cache, jnp.zeros((B, C), jnp.int32),
        jnp.zeros((B, mb), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32), samp, jnp.zeros((B, 2), jnp.uint32))
    led = ledger_from_compiled(lowered.compile(), mesh=tpc.get_view())

    cps = [c for c in led["collectives"] if c["dim"] == "cp"]
    perms = [c for c in cps if "permute" in c["op"]]
    assert len(perms) == ring_hops_per_chunk(cfg.nlayers, 2) == 8
    assert all(c["bytes"] > 0 for c in perms)
    assert sum(c["bytes"] for c in perms) == ring_chunk_bytes(
        nlayers=cfg.nlayers, cp=2, batch=B,
        kv_heads=cfg.block.kv_head_count, head_dim=cfg.block.head_dim,
        chunk=C, nb_local=eng.num_blocks // 2, block_size=BS, itemsize=4)
    # plus the two combine all-reduces (logits psum, token pmax) and
    # nothing else on the cp dim
    assert len(cps) - len(perms) == 2

    ov = cp_ring_overlap(led)
    assert ov["cp_hops"] == 8
    assert ov["cp_hop_bytes"] == sum(c["bytes"] for c in perms)
    assert ov["cp_async_hops"] >= 0  # CPU HLO: sync; async only on the chip


# ------------------------------------------------------------ validation


def test_cp_engine_validation():
    """Construction-time guard rails (no compiles): mesh required,
    unsupported feature combos rejected, chunk and explicit num_blocks
    must split evenly across ranks, default num_blocks rounds UP."""
    cfg = CFGS["dense"]()
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="mesh"):
        ServingEngine(params, cfg, num_slots=2, block_size=BS, chunk=CHUNK,
                      cp_axis="context")
    tpc.setup_process_groups([("context", 2)], devices=jax.devices()[:2])
    mesh = tpc.get_view()
    kw = dict(num_slots=2, block_size=BS, mesh=mesh, cp_axis="context")
    with pytest.raises(ValueError, match="chunk"):
        ServingEngine(params, cfg, chunk=3, **kw)
    with pytest.raises(ValueError, match="num_blocks"):
        ServingEngine(params, cfg, chunk=CHUNK, num_blocks=15, **kw)
    for bad in (dict(spec_k=2), dict(kv_quant="int8"),
                dict(prefix_cache=True)):
        with pytest.raises((ValueError, NotImplementedError)):
            ServingEngine(params, cfg, chunk=CHUNK, **bad, **kw)
    # default pool geometry rounds up to a cp multiple
    eng = ServingEngine(params, cfg, chunk=CHUNK, **kw)
    assert eng.num_blocks % 2 == 0


# ----------------------------------------------- 128k/256k headroom math


def _cp_verdicts(*, max_ctx, cp, kv_heads=2, head_dim=8, nlayers=1,
                 block_size=512, chunk=512):
    """The acceptance-bar shape math at a long context: per-device bytes
    for (a) a single replica holding the whole pool and attending via
    the gather view, vs (b) one CP rank holding pool/cp plus the ring
    working set on the block-bounded pallas path."""
    nb = max_ctx // block_size
    pool = 2 * nlayers * nb * kv_heads * block_size * head_dim * 4
    mb = nb
    gather_ws = modeled_attend_temp_bytes(
        "gather", batch=1, kv_heads=kv_heads, max_blocks=mb,
        block_size=block_size, head_dim=head_dim, itemsize=4)
    pallas_ws = modeled_attend_temp_bytes(
        "pallas", batch=1, kv_heads=kv_heads, max_blocks=mb,
        block_size=block_size, head_dim=head_dim, itemsize=4, groups=2)
    cp_ws = modeled_cp_working_set_bytes(
        kv_heads=kv_heads, head_dim=head_dim, block_size=block_size,
        nb_local=nb // cp, chunk=chunk, cp=cp,
        attend_temp_bytes=pallas_ws)
    single = pool + gather_ws
    ranked = pool // cp + cp_ws
    return single, ranked


@pytest.mark.parametrize("max_ctx,cp", [(131072, 2), (262144, 4)])
def test_cp_headroom_verdicts(max_ctx, cp):
    """128k and 256k MemoryModel verdicts, pure shape math: at a budget
    sized between the two footprints, pool + gather view reads
    ``oom_risk`` while the CP rank's pool slice + ring working set reads
    ``ok`` — the quantitative case for the prefill tier."""
    single, ranked = _cp_verdicts(max_ctx=max_ctx, cp=cp)
    # the ring's rotating double-buffers cost ~1.5x the resident pool
    # slice, so CP's win at cp=2 is real but not free — the honest
    # budget is the one the single replica exactly exhausts
    assert ranked < 0.8 * single
    capacity = single
    assert headroom_verdict(single, capacity)["verdict"] == "oom_risk"
    assert headroom_verdict(ranked, capacity)["verdict"] == "ok"


# -------------------------------------------------- 128k CP serving (slow)


@pytest.mark.slow
def test_128k_cp_long_context_serving():
    """The PR-12 32k acceptance row, grown to 128k on a CP mesh: a
    128k-capacity engine split cp=2 serves a long prompt through ring
    paged prefill on the pallas carry path and decodes at one signature
    per phase; the rendered RUNREPORT memory section carries the
    ok-vs-oom_risk verdict pair from :func:`_cp_verdicts`."""
    from torchdistpackage_tpu.obs.mem_ledger import mem_report
    from torchdistpackage_tpu.serving import pool_bytes

    cfg = llama_config(vocab_size=64, dim=32, nheads=4, nlayers=1,
                       max_seq=131072, kv_heads=2, ffn_hidden=48,
                       dtype=jnp.float32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tpc.setup_process_groups([("context", 2)], devices=jax.devices()[:2])
    mesh = tpc.get_view()
    eng = ServingEngine(params, cfg, num_slots=1, block_size=512,
                        chunk=512, max_ctx=131072, mesh=mesh,
                        cp_axis="context", attn_impl="pallas")
    assert eng.max_blocks == 256
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2048,), 0, cfg.vocab_size), np.int32)
    rid = eng.submit(Request(prompt.tolist(), 4))
    eng.run_until_idle(max_ticks=100)
    f = eng.finished[rid]
    assert f["reason"] == "max_tokens" and f["new_tokens"] == 4
    s = eng.serving_summary()
    assert s["decode_signatures"] == 1 and s["prefill_signatures"] == 1
    assert s["long_context"]["ring_hops"] > 0

    # parity against the unsharded single-replica engine on the same
    # prompt — 128k pool geometry, not just the toy 64-token configs
    ref = ServingEngine(params, cfg, num_slots=1, block_size=512,
                        chunk=512, max_ctx=131072, attn_impl="pallas")
    rr = ref.submit(Request(prompt.tolist(), 4))
    ref.run_until_idle(max_ticks=100)
    np.testing.assert_array_equal(ref.finished[rr]["tokens"], f["tokens"])

    single, ranked = _cp_verdicts(max_ctx=131072, cp=2)
    capacity = single
    assert headroom_verdict(single, capacity)["verdict"] == "oom_risk"
    assert headroom_verdict(ranked, capacity)["verdict"] == "ok"
    # the real pool agrees with the shape math it halves: pool_bytes
    # sums the sharded leaves' GLOBAL shape, so /cp gives the per-rank
    # slice the verdict charges
    pool = pool_bytes(eng.cache)
    assert pool == 2 * cfg.nlayers * eng.num_blocks * 2 * 512 * 8 * 4
    section = mem_report(
        measured_peak_bytes=ranked, capacity_bytes=capacity,
        kv_pool={"pool_bytes": pool, "pool_bytes_expected": pool},
        emit=False)
    assert section["verdict"] == "ok"
    assert section["kv_pool"]["accounting_match"] is True
