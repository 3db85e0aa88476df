"""Auto-sharding planner (dist/autoplan.py, PR 13).

Host-side units pin the planner's three cost-model couplings:

- the analytic shape table against ``jax.eval_shape`` of the real init
  (leaf count + total bytes, per family);
- the analytic memory mirror against ``MemoryModel.estimate`` over the
  REAL (config, mesh, specs) triple — byte-identical, every candidate;
- the analytic spec assignment against :func:`plan_param_specs`'s real
  PartitionSpec tree (shard counts, incl. the ZeRO
  first-free-divisible-dim fsdp insertion);
- compression arms chosen iff the (calibrated) CommModel approves,
  awkward chip counts, the clean all-OOM verdict, ranking determinism,
  section validation, the event kinds, and the jax-free CLI.

The measured-validation arm shares ONE module-scope compiled bundle
(tier-1 budget rule): the planner's top-3 structurally distinct plans
each compile one tiny value_and_grad+sgd step and are timed once; every
measured assertion reads that bundle.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchdistpackage_tpu.dist import autoplan as ap
from torchdistpackage_tpu.models import GPTConfig, gpt_loss, init_gpt_params
from torchdistpackage_tpu.obs.comm_model import AxisCost, CommModel
from torchdistpackage_tpu.obs.events import default_event_log
from torchdistpackage_tpu.obs.report import _validate_autoplan

TINY = GPTConfig(vocab_size=512, dim=128, nheads=4, nlayers=4, max_seq=128,
                 ffn_mult=2, dtype=jnp.float32)

#: dict-config twin of TINY — what the jax-free CLI consumes
TINY_DICT = {"vocab_size": 512, "dim": 128, "nheads": 4, "nlayers": 4,
             "max_seq": 128, "ffn_mult": 2, "dtype": "float32"}


def _cpu_model(alpha_s=50e-6, beta=1e9):
    """A deterministic 'calibrated' model with CPU-sim-shaped link
    parameters: dispatch-dominated alpha, modest bandwidth."""
    c = AxisCost(alpha_s, beta, "calibrated")
    return CommModel({"data": c, "tensor": c, "pipe": c}, default=c,
                     chip="cpu-sim", source="calibrated")


# --------------------------------------------------------------- shape table


def test_shape_table_matches_eval_shape():
    """The analytic table IS the real param tree: leaf count and total
    bytes equal jax.eval_shape of the family init — for the dense GPT,
    a Llama-shaped GQA/SwiGLU/RMS/rope config, and the headless
    transformer family."""
    from torchdistpackage_tpu.obs.mem_ledger import _shapes_for_config
    from torchdistpackage_tpu.parallel.tensor_parallel import (
        TransformerConfig,
    )

    llama = GPTConfig(vocab_size=256, dim=64, nheads=8, nlayers=2,
                      max_seq=64, kv_heads=2, pos="rope", norm="rms",
                      act="swiglu", ffn_hidden=96, dtype=jnp.float32)
    tfm = TransformerConfig(dim=64, nheads=4, nlayers=3, ffn_mult=4)
    for cfg in (TINY, llama, tfm):
        d = ap.model_dims(cfg)
        leaves = jax.tree.leaves(_shapes_for_config(cfg))
        real_bytes = sum(
            int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
            for l in leaves)
        table = ap.param_table(d)
        table_bytes = sum(
            r.count * int(np.prod(r.shape)) * d.dtype_size for r in table)
        assert table_bytes == real_bytes, type(cfg).__name__
        assert sum(r.count for r in table) == len(leaves), type(cfg).__name__


def test_analytic_memory_matches_memory_model():
    """The jax-free memory mirror (the CLI's pruning judge) is
    byte-identical to ``MemoryModel.estimate`` over the real spec tree
    for EVERY candidate — so a plan the CLI prunes is exactly a plan the
    acceptance-path model prunes."""
    d = ap.model_dims(TINY)
    for c in ap.enumerate_candidates(d, 8, 8):
        a = ap.estimate_memory_analytic(d, c, 8, capacity_bytes=10**9)
        m = ap.estimate_memory_model(TINY, c, 8, capacity_bytes=10**9)
        for k in ("params_bytes", "grads_bytes", "opt_bytes", "act_bytes",
                  "total_bytes"):
            assert a[k] == m[k], (c["key"], k, a[k], m[k])
        assert a["verdict"] == m["verdict"], c["key"]


def test_spec_table_matches_real_partition_specs():
    """The rendered per-leaf spec table (the emitted plan's audit
    payload) agrees with the REAL PartitionSpec tree: identical per-leaf
    shard counts under the plan's mesh sizes — tp dims, the pipe stack
    dim, and the ZeRO data-axis insertion all land on the same dims."""
    from torchdistpackage_tpu.obs.mem_ledger import (
        _shapes_for_config, _shard_count,
    )

    d = ap.model_dims(TINY)
    cands = {c["key"]: c for c in ap.enumerate_candidates(d, 8, 8)}
    for key in ("fsdp4·tp2", "dp2·tp4", "fsdp8"):
        c = cands[key]
        table = {r["path"]: r for r in ap.spec_table(d, c)}
        shapes = _shapes_for_config(TINY)
        flat, treedef = jax.tree_util.tree_flatten(shapes)
        specs = treedef.flatten_up_to(ap.plan_param_specs(c, TINY))
        real_total = 0
        for leaf, spec in zip(flat, specs):
            real_total += -(-int(np.prod(leaf.shape))
                            // _shard_count(spec, c["mesh_axes"]))
        tab_total = sum(
            r.count * -(-int(np.prod(r.shape)) // ap._leaf_shards(r, c))
            for r in ap.param_table(d))
        assert real_total == tab_total, key
        # and the stacked-attention leaf's assignment is the expected one
        if c["tp"] > 1:
            assert "tensor" in table["blocks.attn.wqkv"]["spec"], table


# --------------------------------------------------------------- enumeration


def test_awkward_chip_counts_factor():
    """6 and 24 chips: every candidate's mesh multiplies back to the chip
    count, tp always divides nheads, dp always divides the batch — and a
    plan still exists (pure dp covers any count)."""
    # every shardable dim divisible by both 2 and 3, so the awkward
    # factor is reachable: tp|nheads AND tp|dim AND tp|ffn AND tp|vocab
    wide = dict(TINY_DICT, nheads=12, dim=96, vocab_size=768)
    for n_chips, batch in ((6, 12), (24, 24)):
        res = ap.plan(wide, n_chips, global_batch=batch,
                      memory="analytic", emit=False)
        assert res["verdict"] == "ok" and res["chosen"] is not None
        d = ap.model_dims(wide)
        cands = ap.enumerate_candidates(d, n_chips, batch)
        assert cands
        tps = set()
        for c in cands:
            assert c["dp"] * c["tp"] * c["pp"] == n_chips, c
            assert d.nheads % c["tp"] == 0
            assert batch % c["dp"] == 0
            tps.add(c["tp"])
        assert 3 in tps, f"awkward factor 3 never enumerated at {n_chips}"


def test_pp_candidates_modeled_and_executable():
    """Pipeline splits are in the search space (schedule-aware bubble on
    the compute term, ppermute comm term over the pipe axis) AND — PR 14
    — in the executable set: ``executable_only`` keeps pp>1 arms
    (restricted to the dp layout, no compression).  Every pp row records which schedule the
    planner priced it under and that schedule's tick-model bubble."""
    res = ap.plan(TINY_DICT, 8, global_batch=8, memory="analytic",
                  emit=False, top=64)
    pp_rows = [r for r in res["ranked"] if r["pp"] > 1]
    assert pp_rows, "no pipeline candidates enumerated"
    assert all(r["bubble_fraction"] > 0 for r in pp_rows)
    assert all(r["pp_schedule"] in ("1f1b", "zb") for r in pp_rows)
    assert all(r["pp_schedule"] is None and r["bubble_fraction"] == 0
               for r in res["ranked"] if r["pp"] == 1)
    # re-score one pp candidate directly: the ppermute term is priced
    d = ap.model_dims(TINY_DICT)
    c = next(c for c in ap.enumerate_candidates(d, 8, 8) if c["pp"] > 1)
    terms = ap.comm_terms(d, c, 8, _cpu_model())
    assert any(t["op"] == "ppermute" for t in terms), terms
    # at the default microbatches=8, pp=2 sits in the zb-wins regime
    # (M < 2(P-1) is false at P=2... the cheaper arm is schedule-derived,
    # not hardcoded) — pin against the aggregate model directly
    from torchdistpackage_tpu.obs.aggregate import pipeline_time_inflation

    for r in pp_rows:
        want = min(
            ("1f1b", "zb"),
            key=lambda s: pipeline_time_inflation(8, r["pp"], schedule=s))
        assert r["pp_schedule"] == want, r

    res_x = ap.plan(TINY_DICT, 8, global_batch=8, memory="analytic",
                    emit=False, executable_only=True, top=64)
    pp_x = [r for r in res_x["ranked"] if r["pp"] > 1]
    assert pp_x, "executable set lost its pp candidates"
    # executable pp arms: dp layout only, no compression arms
    assert all(r["layout"] == "dp" for r in pp_x)
    assert all(not r["compress"]["grads"] and not r["compress"]["acts"]
               for r in pp_x)


def test_all_oom_is_a_clean_verdict():
    """A model too big for any plan: verdict ``all_oom``, chosen None,
    every candidate pruned WITH a ``plan_rejected_oom`` event, and the
    section still validates — no crash anywhere on the path."""
    log = default_event_log()
    before = len(log.of_kind("plan_rejected_oom"))
    res = ap.plan(TINY_DICT, 8, global_batch=8, memory="analytic",
                  capacity_bytes=4096, emit=True)
    assert res["verdict"] == "all_oom"
    assert res["chosen"] is None
    assert res["n_pruned_oom"] == res["n_candidates"] > 0
    events = log.of_kind("plan_rejected_oom")
    assert len(events) - before == res["n_candidates"]
    assert all(e["total_bytes"] > e["capacity_bytes"] for e in events[-3:])
    assert _validate_autoplan(res) == []


def test_compression_only_when_calibrated_model_approves():
    """The int8 arm is chosen iff the calibrated model approves it: with
    compressed-axis parameters that make the ring fast, the winner
    carries ``+gc8`` and its term records ``model_approves=True``; with
    parameters that make the ring a loss, the winner is the exact arm."""
    exact = AxisCost(1e-6, 50e9, "calibrated")
    fast8 = CommModel({"data": exact}, default=exact, source="calibrated",
                      compressed_axis_costs={
                          "data": AxisCost(1e-6, 200e9, "calibrated-int8")})
    slow8 = CommModel({"data": exact}, default=exact, source="calibrated",
                      compressed_axis_costs={
                          "data": AxisCost(5e-4, 1e8, "calibrated-int8")})
    kw = dict(global_batch=8, memory="analytic", emit=False,
              executable_only=True)
    win = ap.plan(TINY_DICT, 8, comm_model=fast8, **kw)["chosen"]
    assert win["compress"]["grads"] is True, win["key"]
    term = next(t for t in win["terms"] if t["compressed"])
    assert term["model_approves"] is True
    assert term["basis"] == "calibrated-int8"
    lose = ap.plan(TINY_DICT, 8, comm_model=slow8, **kw)["chosen"]
    assert lose["compress"]["grads"] is False, lose["key"]
    # the model's own verdict matches: the ring it rejected predicts
    # slower than the exact collective it kept
    rec = slow8.predict_compressed(
        "all_reduce", 1 << 20, 8, axes=("data",))
    assert rec["compress"] is False


def test_plan_ranking_deterministic():
    """Same inputs -> bit-identical result (ranking ties broken by key),
    twice."""
    kw = dict(global_batch=8, memory="analytic", emit=False,
              comm_model=_cpu_model())
    a = ap.plan(TINY_DICT, 8, **kw)
    b = ap.plan(TINY_DICT, 8, **kw)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_section_validation_catches_corruption():
    res = ap.plan(TINY_DICT, 8, global_batch=8, memory="analytic",
                  emit=False)
    assert _validate_autoplan(res) == []
    assert _validate_autoplan(None) == []
    bad = dict(res, verdict="maybe")
    assert any("verdict" in e for e in _validate_autoplan(bad))
    bad = dict(res, n_pruned_oom=res["n_candidates"] + 1)
    assert any("n_pruned_oom" in e for e in _validate_autoplan(bad))
    bad = dict(res, chosen=None)
    assert any("chosen" in e for e in _validate_autoplan(bad))
    bad = json.loads(json.dumps(res))
    bad["chosen"].pop("terms")
    assert any("terms" in e for e in _validate_autoplan(bad))


def test_plan_selected_event_emitted():
    log = default_event_log()
    before = len(log.of_kind("plan_selected"))
    res = ap.plan(TINY_DICT, 8, global_batch=8, memory="analytic",
                  emit=True)
    evs = log.of_kind("plan_selected")
    assert len(evs) == before + 1
    assert evs[-1]["key"] == res["chosen"]["key"]
    assert evs[-1]["n_candidates"] == res["n_candidates"]


def test_plan_prefill_tier_prices_ring_and_prunes_oom():
    """The PR-20 CP prefill planner: each ring width's modeled TTFT =
    compute split cp ways + every ppermute hop priced through the
    CommModel at the SAME per-hop payloads the engine's HLO ledger
    shows; per-rank memory (pool/cp + ring working set) gates through
    ``headroom_verdict``.  At a capacity only the split arms fit, cp1 is
    pruned with the OOM evidence, the widest arm wins on modeled TTFT,
    and the planner events land on the timeline."""
    cfg = {"dim": 32, "nheads": 4, "nlayers": 1, "max_seq": 131072,
           "vocab_size": 64, "kv_heads": 2, "dtype": "float32"}
    log = default_event_log()
    sel0 = len(log.of_kind("plan_selected"))
    oom0 = len(log.of_kind("plan_rejected_oom"))
    plan = ap.plan_prefill_tier(
        cfg, context_len=131072, chunk=512, block_size=512,
        cp_widths=(1, 2, 3, 4, 8), capacity_bytes=40_000_000)
    assert plan["verdict"] == "ok"
    assert plan["skipped_widths"] == [3]  # 512 % 3 != 0: not executable
    assert [p["key"] for p in plan["pruned"]] == ["cp1"]
    assert plan["chosen"]["key"] == "cp8"
    by_cp = {r["cp"]: r for r in plan["ranked"]}
    # compute splits down, ring volume grows, with cp — and the hop
    # count matches the per-chunk HLO model times the chunk walk
    assert by_cp[8]["compute_s"] < by_cp[2]["compute_s"]
    assert by_cp[8]["ring_hops"] > by_cp[2]["ring_hops"] > 0
    n_chunks = 131072 // 512
    assert by_cp[2]["ring_hops"] == n_chunks * 4 * (2 - 1) * 1
    ops = {t["name"]: t for t in plan["chosen"]["terms"]}
    assert ops["cp-ring-fresh"]["op"] == "ppermute"
    assert ops["cp-ring-pool"]["per_op_s"] > 0
    assert log.of_kind("plan_selected")[-1]["key"] == "cp8"
    assert len(log.of_kind("plan_selected")) == sel0 + 1
    assert len(log.of_kind("plan_rejected_oom")) == oom0 + 1

    # no width fits -> the clean all_oom verdict, no winner event
    bad = ap.plan_prefill_tier(
        cfg, context_len=131072, chunk=512, block_size=512,
        cp_widths=(2, 4), capacity_bytes=1_000_000, emit=False)
    assert bad["verdict"] == "all_oom" and bad["chosen"] is None
    assert bad["n_pruned_oom"] == 2


# ------------------------------------------------------------- MoE / EP (PR 18)

MOE_TINY = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=4, max_seq=32,
                     moe_experts=4, moe_top_k=2, moe_every=2,
                     moe_capacity_factor=2.0, dtype=jnp.float32)


def test_moe_plan_enumerates_ep_candidates():
    """MoE configs plan instead of raising (PR 18): every dp x tp point
    crosses in ep | gcd(dp, E) arms with a dedicated ``ep`` mesh axis
    (``data = dp/ep``); pp, fsdp, and compression stay out of the MoE
    set; ep>1 rows price the dispatch all_to_all over the ep axis."""
    res = ap.plan(MOE_TINY, 8, global_batch=8, memory="model",
                  comm_model=_cpu_model(), emit=False, top=64)
    assert res["verdict"] == "ok" and res["chosen"] is not None
    assert _validate_autoplan(res) == []
    rows = res["ranked"]
    assert {1, 2, 4} <= {r.get("ep", 1) for r in rows}
    for r in rows:
        assert r["pp"] == 1 and r["layout"] == "dp"
        assert not r["compress"]["grads"] and not r["compress"]["acts"]
        assert r["mesh_axes"]["data"] * r["mesh_axes"]["ep"] == r["dp"]
        if r["ep"] > 1:
            assert f"ep{r['ep']}" in r["key"]
        else:
            assert "ep" not in r["key"]
    d = ap.model_dims(MOE_TINY)
    ep_row = next(r for r in rows if r["ep"] > 1)
    a2a = [t for t in ap.comm_terms(d, ep_row, 8, _cpu_model())
           if t["name"] == "moe-all-to-all"]
    assert a2a and a2a[0]["op"] == "all_to_all" and a2a[0]["axes"] == ["ep"]
    assert a2a[0]["count"] == 4 * d.n_moe_layers
    assert all(t["name"] != "moe-all-to-all" for t in ap.comm_terms(
        d, next(r for r in rows if r["ep"] == 1), 8, _cpu_model()))
    # activated-FLOP accounting: the capacity factor inflates the expert
    # FLOP term (flop_weight = top_k * cf / E on expert leaves)
    import dataclasses as _dc

    d2 = _dc.replace(d, moe_capacity_factor=2 * d.moe_capacity_factor)
    assert ap.flops_per_token(d2) > ap.flops_per_token(d)


def test_moe_memory_pin_and_shape_table():
    """The PR-13 byte-identical pin extends to MoE: the analytic mirror
    equals ``MemoryModel.estimate`` over the REAL gpt_moe spec tree
    (expert stacks EP-sharded via ``gpt_moe_param_specs``) for EVERY
    candidate, and the analytic table matches ``jax.eval_shape`` of
    ``init_gpt_moe_params`` leaf-for-leaf in count and bytes."""
    from torchdistpackage_tpu.obs.mem_ledger import _shapes_for_config

    d = ap.model_dims(MOE_TINY)
    leaves = jax.tree.leaves(_shapes_for_config(MOE_TINY))
    real_bytes = sum(
        int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize for l in leaves)
    table = ap.param_table(d)
    table_bytes = sum(
        r.count * int(np.prod(r.shape)) * d.dtype_size for r in table)
    assert table_bytes == real_bytes
    assert sum(r.count for r in table) == len(leaves)
    for c in ap.enumerate_candidates(d, 8, 8):
        a = ap.estimate_memory_analytic(d, c, 8, capacity_bytes=10**9)
        m = ap.estimate_memory_model(MOE_TINY, c, 8, capacity_bytes=10**9)
        for k in ("params_bytes", "grads_bytes", "opt_bytes", "act_bytes",
                  "total_bytes"):
            assert a[k] == m[k], (c["key"], k, a[k], m[k])
        assert a["verdict"] == m["verdict"], c["key"]
        # ep>1 shrinks per-device expert bytes vs its ep=1 sibling
        if c["ep"] > 1:
            sib = dict(c, ep=1, mesh_axes=dict(c["mesh_axes"],
                                               data=c["dp"], ep=1))
            assert a["params_bytes"] < ap.estimate_memory_analytic(
                d, sib, 8, capacity_bytes=10**9)["params_bytes"]


def test_moe_transformer_family_rejected():
    """The transformer family has no expert blocks — a dict config with
    experts but no vocab still fails loudly instead of mispricing."""
    with pytest.raises(ValueError, match="gpt"):
        ap.model_dims({"dim": 64, "nheads": 4, "nlayers": 2,
                       "moe_experts": 4})


# ------------------------------------------------- measured validation arm


@pytest.fixture(scope="module")
def measured_bundle():
    """ONE module-scope compiled bundle (tier-1 budget rule): plan TINY
    on the 8-dev sim with a CPU-shaped calibrated model restricted to the
    three structurally distinct dp layouts, then time each of the top-3
    plans through one tiny value_and_grad+sgd GSPMD step (3 compiles
    total in this file)."""
    # allow_pp=False: this bundle exercises the dp/tp GSPMD runner
    # layouts (the pipelined runner has its own goldens in
    # tests/test_pipeline.py)
    result = ap.plan(
        TINY, 8, global_batch=8, comm_model=_cpu_model(),
        memory="model", executable_only=True, compression=False,
        layouts=("dp",), allow_pp=False, emit=True)
    top3 = result["ranked"][:3]
    assert len(top3) == 3
    opt = optax.sgd(1e-3)

    def measure(c):
        params = init_gpt_params(jax.random.PRNGKey(0), TINY)
        mesh = ap.build_mesh(c)
        specs = ap.plan_param_specs(c, TINY)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs)
        state = jax.device_put(opt.init(params), NamedSharding(mesh, P()))
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        batch = jax.device_put({
            "tokens": jax.random.randint(
                k1, (8, TINY.max_seq), 0, TINY.vocab_size),
            "targets": jax.random.randint(
                k2, (8, TINY.max_seq), 0, TINY.vocab_size),
        }, NamedSharding(mesh, ap.batch_partition_spec(c)))

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(p, s, b):
            loss, g = jax.value_and_grad(
                lambda p_: gpt_loss(p_, b, TINY))(p)
            u, s = opt.update(g, s, p)
            return jax.tree.map(jnp.add, p, u), s, loss

        for _ in range(2):  # compile + warm
            params, state, loss = step(params, state, batch)
        assert np.isfinite(float(loss))
        t0 = time.perf_counter()
        for _ in range(6):
            params, state, loss = step(params, state, batch)
        float(loss)
        return (time.perf_counter() - t0) / 6

    rows = [{"key": r["key"], "modeled_step_s": r["step_s"],
             "measured_step_s": measure(r)} for r in top3]
    ap.attach_measured(result, rows)
    return result


def test_top3_are_structurally_distinct(measured_bundle):
    keys = [r["key"] for r in measured_bundle["ranked"][:3]]
    assert len(set(keys)) == 3
    tps = {measured_bundle["ranked"][i]["tp"] for i in range(3)}
    assert len(tps) == 3, f"top-3 collapsed onto one tp split: {keys}"


def test_modeled_vs_measured_ordering(measured_bundle):
    """The section records the modeled and the measured ordering of the
    planner's top-3 side by side.  What is deterministic is asserted: the
    modeled order is the ranking's, every plan that ran has a positive
    measured step, and the record is complete enough for the RUNREPORT to
    render a disagreement.  Whether the two orders AGREE is recorded, not
    asserted: a wall time on the CPU sim is a count, never a speed, and
    which of two simulated meshes steps faster follows the host's load."""
    mvm = measured_bundle["modeled_vs_measured"]
    assert _validate_autoplan(measured_bundle) == []
    rows = {r["key"]: r for r in mvm["rows"]}
    ranked = [r["key"] for r in measured_bundle["ranked"][:3]]
    assert mvm["modeled_order"] == ranked
    assert sorted(mvm["measured_order"]) == sorted(ranked)
    modeled = [rows[k]["modeled_step_s"] for k in mvm["modeled_order"]]
    assert modeled == sorted(modeled)
    for r in mvm["rows"]:
        assert r["measured_step_s"] > 0 and np.isfinite(r["measured_step_s"])
        assert r["rel_err"] == round(
            (r["modeled_step_s"] - r["measured_step_s"])
            / r["measured_step_s"], 4)
    assert mvm["ordering_agrees"] == (
        mvm["modeled_order"] == mvm["measured_order"])


def test_chosen_plan_trains(measured_bundle):
    """The emitted winner is executable end to end (the bundle already
    compiled and stepped it — finite loss asserted inside) and carries
    the audit payload: per-term breakdown + rendered per-leaf specs."""
    chosen = measured_bundle["chosen"]
    assert chosen["terms"], chosen
    assert chosen["param_specs"], chosen
    paths = {r["path"] for r in chosen["param_specs"]}
    assert {"tok_emb", "head"} <= paths


# ----------------------------------------------------------------- CLI


def test_cli_plan_table_and_json(tmp_path, capsys):
    from torchdistpackage_tpu.tools.autoplan import main

    cfg_path = tmp_path / "model.json"
    cfg_path.write_text(json.dumps(TINY_DICT))
    rc = main(["--config", str(cfg_path), "--chips", "8", "--batch", "16",
               "--hbm-gb", "1", "--chip", "TPU v5e"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "chosen:" in out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["metric"] == "autoplan" and line["verdict"] == "ok"
    assert line["chosen"]["key"]


def test_cli_all_oom_exits_nonzero(tmp_path, capsys):
    from torchdistpackage_tpu.tools.autoplan import main

    cfg_path = tmp_path / "model.json"
    cfg_path.write_text(json.dumps(TINY_DICT))
    rc = main(["--config", str(cfg_path), "--chips", "8",
               "--hbm-gb", "0.00001"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "NO PLAN FITS" in out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["verdict"] == "all_oom" and line["chosen"] is None


def test_cli_unreadable_config_exits_2(tmp_path, capsys):
    from torchdistpackage_tpu.tools.autoplan import main

    assert main(["--config", str(tmp_path / "missing.json"),
                 "--chips", "8"]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("[1, 2]")
    assert main(["--config", str(bad), "--chips", "8"]) == 2
    capsys.readouterr()
