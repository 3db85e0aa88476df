"""End-to-end example: SERVE a long document with context-parallel prefill.

``serve_gpt.py`` shows the continuous-batching engine; this one shows the
pod-scale long-context path (docs/long_context.md "CP prefill serving").
A ``context`` mesh axis shards the paged KV pool over its BLOCKS
dimension — each CP rank holds ``num_blocks / cp`` blocks — and every
prefill chunk runs on all ranks at once: rank r computes queries for its
slice of the chunk, fills its OWN pool slice, and a python-unrolled
``ppermute`` ring rotates (K, V) so every rank attends over the full
prefix.  Decode stays the single compiled one-token step (local-slice
attend + a tree combine), so ``decode_signatures == 1`` exactly as in the
plain engine, and the tokens are BIT-identical to an unsharded replica —
asserted here against a reference engine on the same prompts.

The RUNREPORT's serving section gains a ``long_context`` block (cp width,
chunk, prefill chunk / ring-hop / ring-byte totals that reconcile against
the per-hop priced HLO ledger) and the event timeline carries every
``cp_prefill_chunk`` / ``cp_ring_hop``.  A planner coda prices the same
ring at 128k context (``plan_prefill_tier``): the single-replica pool is
OOM-pruned and a CP width is chosen on modeled TTFT — the shape math the
slow-tier 128k serving test (tests/test_cp_prefill.py) checks for real.
CI (tests/test_examples.py) validates all of it.

- real TPU chips:      python examples/serve_long_context.py
- 8-device CPU sim:    TDP_CPU_SIM=8 python examples/serve_long_context.py
"""

import os

if os.environ.get("TDP_CPU_SIM"):
    from torchdistpackage_tpu.dist.overlap import cpu_sim

    cpu_sim(os.environ["TDP_CPU_SIM"])

import jax
import jax.numpy as jnp
import numpy as np

from torchdistpackage_tpu import setup_distributed, tpc
from torchdistpackage_tpu.dist.autoplan import plan_prefill_tier
from torchdistpackage_tpu.models import init_gpt_params, llama_config
from torchdistpackage_tpu.obs import Telemetry
from torchdistpackage_tpu.ops.ring_paged import ring_hops_per_chunk
from torchdistpackage_tpu.serving import Request, ServingEngine


def main():
    setup_distributed()
    ndev = len(jax.devices())
    if ndev < 2:
        raise SystemExit(
            "serve_long_context needs >= 2 devices for the context axis "
            "(try TDP_CPU_SIM=8)")
    cp = 4 if ndev >= 4 else 2

    on_cpu = jax.default_backend() == "cpu"
    smoke = bool(os.environ.get("TDP_SMOKE"))
    cfg = llama_config(
        vocab_size=256 if on_cpu else 32768,
        dim=64 if on_cpu else 512,
        nheads=4 if on_cpu else 8,
        kv_heads=2 if on_cpu else 4,  # GQA rides the ring too
        nlayers=2 if on_cpu else 8,
        max_seq=256 if on_cpu else 4096,
        dtype=jnp.float32 if on_cpu else jnp.bfloat16,
        attn_impl="naive" if on_cpu else "flash",
    )
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)

    # the traffic mix the CP tier exists for: one long document plus a
    # tail of short interactive prompts sharing the same engine.  The
    # long prompt spans many chunks (so the ring actually turns); the
    # shorts prove chunked CP prefill doesn't retrace or starve them.
    block_size, chunk = 16, 16
    max_ctx = 192 if smoke else 256
    rng = np.random.RandomState(0)
    long_doc = rng.randint(0, cfg.vocab_size, size=max_ctx - 32).tolist()
    shorts = [rng.randint(0, cfg.vocab_size,
                          size=int(rng.choice([5, 9, 14]))).tolist()
              for _ in range(3 if smoke else 6)]
    reqs = [Request(long_doc, 8, temperature=0.0, seed=0)] + [
        Request(p, 6, temperature=0.7, seed=1 + i)
        for i, p in enumerate(shorts)]

    # ---- reference arm: an unsharded single replica (the bit oracle) --
    ref = ServingEngine(params, cfg, num_slots=2, block_size=block_size,
                        chunk=chunk, max_ctx=max_ctx)
    want = []
    for r in reqs:
        rid = ref.submit(Request(r.tokens, r.max_new_tokens,
                                 temperature=r.temperature, seed=r.seed))
        ref.run_until_idle()
        want.append(np.asarray(ref.finished[rid]["tokens"]))

    # ---- CP arm: pool block-sharded over the context axis ------------
    tpc.setup_process_groups([("context", cp)], devices=jax.devices()[:cp])
    mesh = tpc.get_view()
    print(f"serving mesh: {dict(mesh.shape)} (cp={cp})")
    tel = Telemetry(run="serve_long_context", mesh=mesh,
                    poll_memory=not on_cpu)
    eng = ServingEngine(
        params, cfg, num_slots=2, block_size=block_size, chunk=chunk,
        max_ctx=max_ctx, mesh=mesh, cp_axis="context",
        attn_impl="gather" if on_cpu else "pallas",
        telemetry=tel, snapshot_every=4)
    rids = [eng.submit(r) for r in reqs]
    eng.run_until_idle(max_ticks=2000)

    summary = eng.serving_summary()
    tel.record_serving(summary)
    for w, rid in zip(want, rids):
        np.testing.assert_array_equal(
            w, eng.finished[rid]["tokens"],
            err_msg="CP tokens diverged from the single-replica oracle")
    assert summary["requests"]["completed"] == len(reqs)
    assert summary["decode_signatures"] == 1, "decode step retraced!"
    assert summary["prefill_signatures"] == 1, "prefill chunk retraced!"
    lc = summary["long_context"]
    assert lc["cp"] == cp and lc["cp_axis"] == "context"
    # the ring turns once a compiled call; a tick in which the document and
    # a short prompt both prefill makes two
    assert lc["ring_hops"] == lc["prefill_calls"] * ring_hops_per_chunk(
        cfg.nlayers, cp), lc
    assert lc["ring_bytes"] > 0, lc
    print(f"served {summary['requests']['completed']} requests "
          f"({len(long_doc)}-token doc + {len(shorts)} shorts) at "
          f"{summary['tokens_per_sec']:.1f} tok/s; {lc['prefill_calls']} "
          f"prefill calls rang {lc['ring_hops']} hops / "
          f"{lc['ring_bytes']} B; tokens bit-equal to the unsharded "
          f"oracle; decode signatures {summary['decode_signatures']}")

    # ---- planner coda: the same ring priced at 128k ------------------
    # At real long context the single replica's pool alone blows the HBM
    # budget; the planner prunes it on the mem-ledger verdict and ranks
    # the CP widths on modeled TTFT (compute/cp + priced ring hops).
    plan = plan_prefill_tier(
        {"dim": 512, "nheads": 8, "nlayers": 8, "max_seq": 131072,
         "vocab_size": 32768, "kv_heads": 4, "dtype": "bfloat16"},
        context_len=131072, chunk=512, block_size=512,
        cp_widths=(1, 2, 4, 8), capacity_bytes=1024**3,
        device_kind="cpu-sim" if on_cpu else None, emit=True)
    assert plan["verdict"] == "ok", plan
    pruned_keys = {p["key"] for p in plan["pruned"]}
    assert "cp1" in pruned_keys, plan  # whole pool on one rank: OOM
    chosen = plan["chosen"]
    print(f"128k plan: chose {chosen['key']} "
          f"(modeled ttft {chosen['ttft_s'] * 1e3:.1f} ms, "
          f"mem {chosen['memory']['verdict']}); pruned "
          f"{plan['n_pruned_oom']} width(s) as oom_risk")
    tel.finalize()


if __name__ == "__main__":
    main()
