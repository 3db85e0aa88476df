"""Quantized collectives end-to-end (dist/compressed.py + every parallel
family): ring-kernel units and the custom-VJP transpose pairing, error
feedback, the compression parity matrix (ZeRO / FSDP overlap / TP
activation boundaries incl. GQA), the auto-policy decision loop, and the
checked-in A/B acceptance demo — exact vs int8 ZeRO and TP on the 8-dev
CPU sim, RUNREPORTs through ``tools/parity_diff.py`` landing a
``bounded`` verdict with s8 bytes ONLY in the compressed arm and the
compressed axis's comm-ledger wire bytes down >= 3x.

Budget discipline (PR-6 convention): module-scope A/B fixtures run ONE
training pair per arm family; the parity-matrix arms fold fwd+grad into
single ``value_and_grad(has_aux=True)`` programs; everything else is a
sub-second toy.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.dist.compressed import (
    GROUP,
    auto_compress_policy,
    ef_compress,
    int8_psum_all_gather,
    int8_ring_all_gather,
    int8_ring_pmean,
    int8_ring_reduce_scatter,
)
from torchdistpackage_tpu.obs import (
    CommModel,
    JsonlSink,
    Telemetry,
    compression_report,
    validate_runreport,
)
from torchdistpackage_tpu.obs.comm_model import (
    COMPRESS_GROUP,
    compressed_ledger_bytes,
    compressed_wire_bytes,
)
from torchdistpackage_tpu.obs.events import EventLog, set_default_event_log
from torchdistpackage_tpu.parallel.data_parallel import DataParallel
from torchdistpackage_tpu.parallel.fsdp import FSDP
from torchdistpackage_tpu.parallel.zero import ZeroOptimizer
from torchdistpackage_tpu.parallel.tensor_parallel import (
    TransformerConfig,
    init_transformer_params,
    transformer_forward,
    transformer_param_specs,
)
from tests.test_data_parallel import _data, make_mlp_params, mlp_loss


def _axis_bytes(report, axis):
    """Ledger bytes of the collectives spanning ``axis`` in a RUNREPORT."""
    colls = report["comm"]["ledger"]["collectives"]
    return sum(c["bytes"] for c in colls if axis in c["axes"])


# ------------------------------------------------------------ ring units


def test_compress_group_constants_match():
    # obs is a leaf subsystem, so it mirrors the ring group size instead of
    # importing it — the two must never drift (predictions would silently
    # mis-cost the scale sideband)
    assert GROUP == COMPRESS_GROUP


def test_int8_ring_all_gather_matches_exact(devices8):
    mesh = Mesh(np.array(devices8), axis_names=("data",))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (16, 8, 4))) * 3.0

    for dim in (0, 1):
        def body(v):
            return (
                int8_ring_all_gather(v, "data", dim),
                jax.lax.all_gather(v, "data", axis=dim, tiled=True),
                int8_psum_all_gather(v, "data", dim),
            )

        in_spec = P("data") if dim == 0 else P(None, "data")
        out = P(None, "data") if dim == 1 else P("data")
        # gathered outputs are full-size per shard; reassembling with the
        # sharded spec keeps global shape = n * local — value check only
        ag, ex, pg = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(in_spec,), out_specs=(out, out, out),
        ))(jnp.asarray(x))
        bound = np.abs(x).max() / 127.0 * 1.01  # one quantization, no hops
        np.testing.assert_allclose(np.asarray(ag), np.asarray(ex), atol=bound)
        # the invariance-typed masked-psum gather assembles the identical
        # quantized tensor (int8 addition over one-hot contributors is
        # exact)
        np.testing.assert_array_equal(np.asarray(pg), np.asarray(ag))


def test_int8_ring_all_gather_vjp_is_quantized_reduce_scatter(devices8):
    """The custom-VJP pairing: grads through the int8 gather match the
    exact all_gather's transpose (psum_scatter) within quantization
    noise, and the BACKWARD jaxpr moves s8 ppermutes — the compressed
    backward FSDP/TP buy for free."""
    mesh = Mesh(np.array(devices8), axis_names=("data",))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (8, 6)))

    def loss_q(v):
        full = int8_ring_all_gather(v, "data", 0)
        return jnp.sum(full * full)

    def loss_e(v):
        full = jax.lax.all_gather(v, "data", axis=0, tiled=True)
        return jnp.sum(full * full)

    gq = jax.jit(shard_map(jax.grad(loss_q), mesh=mesh,
                           in_specs=(P("data"),), out_specs=P("data")))(
        jnp.asarray(x))
    ge = jax.jit(shard_map(jax.grad(loss_e), mesh=mesh,
                           in_specs=(P("data"),), out_specs=P("data")))(
        jnp.asarray(x))
    np.testing.assert_allclose(
        np.asarray(gq), np.asarray(ge), rtol=0.1,
        atol=0.2 * float(np.abs(np.asarray(ge)).max()))

    import re

    jaxpr = str(jax.make_jaxpr(shard_map(
        jax.grad(loss_q), mesh=mesh, in_specs=(P("data"),),
        out_specs=P("data")))(jnp.asarray(x)))
    s8_permutes = [ln for ln in jaxpr.splitlines()
                   if "ppermute" in ln and re.search(r"\b[si]8\[", ln)]
    assert s8_permutes, "backward of the int8 gather is not int8 on the wire"


def test_rings_are_unrolled_for_the_ledger(devices8):
    """The hardening bar: the rings are python-unrolled ppermute chains
    (the PR-3 ring_ag_matmul idiom) — NO scan/while wraps them, so the
    HLO comm ledger counts every hop's payload instead of undercounting
    a loop body by the trip count."""
    mesh = Mesh(np.array(devices8[:4]), axis_names=("d",))
    n = 4

    cases = {
        "pmean": (lambda v: int8_ring_pmean(v, "d"), P(), (16,)),
        "rs": (lambda v: int8_ring_reduce_scatter(v, "d", 0), P("d"), (16,)),
        "ag": (lambda v: int8_ring_all_gather(v, "d", 0), P("d"), (4,)),
    }
    for name, (fn, out_spec, shape) in cases.items():
        jaxpr = str(jax.make_jaxpr(shard_map(
            fn, mesh=mesh, in_specs=(P(),) if name != "ag" else (P("d"),),
            out_specs=out_spec))(jnp.ones(shape)))
        assert "scan" not in jaxpr and "while" not in jaxpr, name
        hops = jaxpr.count("ppermute")
        # n-1 data hops, each with a paired scale permute
        assert hops == 2 * (n - 1), (name, hops)


def test_ef_compress_roundtrip():
    x = jnp.asarray(np.random.RandomState(0).randn(17, 33) * 2.0,
                    jnp.float32)
    xq, e = ef_compress(x)
    # exact decomposition: quantized value + residual reconstructs x
    np.testing.assert_allclose(np.asarray(xq + e), np.asarray(x), rtol=0,
                               atol=1e-6)
    # residual is bounded by the per-group quantization step
    assert float(jnp.abs(e).max()) <= float(jnp.abs(x).max()) / 127.0 * 1.01
    assert e.dtype == jnp.float32


# --------------------------------------------------- knob validation fix


def test_dp_unknown_grad_compress_rejected():
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="grad_compress"):
        DataParallel(mesh=mesh, grad_compress="int4")
    # 'int8_ef' names the class that CAN do it
    with pytest.raises(ValueError, match="ZeroOptimizer"):
        DataParallel(mesh=mesh, grad_compress="int8_ef")


def test_dp_int8_with_microbatch_accum_supported(devices8):
    """The supported branch of the grad_compress x accum_reduce
    validation: the quantized ring rides INSIDE the accumulation scan and
    the trajectory tracks the exact microbatch run within quantization
    noise."""
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)

    def run(compress):
        dp = DataParallel(mesh=mesh, grad_compress=compress,
                          compress_min_size=0)
        p = dp.broadcast_params(jax.tree.map(np.array, params))
        s = opt.init(p)
        step = dp.make_train_step(
            mlp_loss, opt, grad_accum_iters=2, accum_reduce="microbatch")
        losses = []
        batch = dp.shard_batch(_data(jax.random.PRNGKey(100)))
        for _ in range(4):
            p, s, loss = step(p, s, batch)
            losses.append(float(loss))
        return losses

    exact = run(None)
    q = run("int8")
    assert q[-1] < q[0]  # it trains
    np.testing.assert_allclose(q, exact, rtol=0.05)


def test_zero_ef_with_microbatch_accum_rejected():
    """The loud-rejection branch: the error-feedback residual is per-step
    state and cannot ride the stateless in-scan reduce — refused naming
    BOTH knobs."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    zero = ZeroOptimizer(optax.sgd(1e-2), mesh=mesh, grad_compress="int8_ef")
    with pytest.raises(ValueError, match="int8_ef.*microbatch"):
        zero.make_train_step(
            mlp_loss, grad_accum_iters=2, accum_reduce="microbatch")


# ------------------------------------------------- ZeRO: EF + microbatch


def _zero_run(mesh, params, opt, compress, nsteps=5, **kw):
    zero = ZeroOptimizer(opt, mesh=mesh, grad_compress=compress,
                         compress_min_size=0, **kw)
    zp = zero.place_params(jax.tree.map(np.array, params))
    zs = zero.init(zp)
    step = zero.make_train_step(mlp_loss)
    batch = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))),
        _data(jax.random.PRNGKey(100)))
    losses = []
    for _ in range(nsteps):
        zp, zs, loss = step(zp, zs, batch)
        losses.append(float(loss))
    return zp, zs, losses


def test_zero_int8_ef_residual_carried_and_tracks_exact(devices8):
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)
    _, _, l_exact = _zero_run(mesh, params, opt, None)
    p_ef, s_ef, l_ef = _zero_run(mesh, params, opt, "int8_ef")
    np.testing.assert_allclose(l_ef, l_exact, rtol=0.05)
    # the residual exists, is per-data-member ([8, *leaf]), and is ALIVE
    # (a zero residual after 5 lossy steps means feedback isn't wired)
    ef = s_ef["ef"]
    assert set(ef) == set(params)
    assert ef["w1"].shape == (8,) + params["w1"].shape
    assert ef["w1"].sharding.spec[0] in ("data", ("data",))
    assert float(jnp.abs(ef["w1"]).max()) > 0.0


def test_zero_int8_microbatch_accum_runs_ring_in_scan(devices8):
    """Tentpole (a): ZeroOptimizer(grad_compress='int8') composes with
    accum_reduce='microbatch' — the quantized reduce-to-owner rides
    inside the accumulation scan; trajectory tracks the exact microbatch
    ZeRO run."""
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)

    def run(compress):
        zero = ZeroOptimizer(opt, mesh=mesh, grad_compress=compress,
                             compress_min_size=0)
        zp = zero.place_params(jax.tree.map(np.array, params))
        zs = zero.init(zp)
        step = zero.make_train_step(
            mlp_loss, grad_accum_iters=2, accum_reduce="microbatch")
        batch = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))),
            _data(jax.random.PRNGKey(100)))
        losses = []
        for _ in range(4):
            zp, zs, loss = step(zp, zs, batch)
            losses.append(float(loss))
        return losses

    np.testing.assert_allclose(run("int8"), run(None), rtol=0.05)


# ------------------------------------------------- FSDP overlap step arm


def test_fsdp_overlap_int8_parity_and_wire(devices8):
    """FSDP explicit-comm step with grad_compress='int8': int8 param
    all-gathers in the forward, int8 per-leaf reduce-scatters in the
    backward (the ring's custom VJP) — trajectory tracks the exact
    overlap step, and the compiled step moves s8 ppermutes."""
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    opt = optax.sgd(1e-2)
    batch_sh = jax.device_put(
        _data(jax.random.PRNGKey(100)),
        NamedSharding(mesh, P("data")))

    def run(gc):
        f = FSDP(mesh=mesh)
        fp = f.shard_params(jax.tree.map(
            np.array, make_mlp_params(jax.random.PRNGKey(0))))
        fs = opt.init(fp)
        step = f.make_overlap_train_step(
            mlp_loss, opt, grad_compress=gc, compress_min_size=0)
        losses = []
        for _ in range(4):
            fp, fs, loss = step(fp, fs, batch_sh)
            losses.append(float(loss))
        return losses

    exact = run(None)
    q = run("int8")
    assert q[-1] < q[0]
    np.testing.assert_allclose(q, exact, rtol=0.05)
    with pytest.raises(ValueError, match="grad_compress"):
        FSDP(mesh=mesh).make_overlap_train_step(
            mlp_loss, opt, grad_compress="int4")


# ------------------------------------ TP parity matrix (dense + GQA)


@pytest.mark.parametrize("family", ["dense", "gqa"])
def test_tp_activation_compression_golden(devices8, family):
    """Per-family exact-vs-int8 golden for the TP/SP activation
    boundaries: ONE value_and_grad(has_aux=True) program per arm (loss,
    output AND grads from one compile); the compressed arm must stay at
    quantization-noise distance on all three."""
    import functools

    cfg = TransformerConfig(
        dim=32, nheads=4, nlayers=1, ffn_mult=2,
        kv_heads=2 if family == "gqa" else None)
    cfg_q = dataclasses.replace(cfg, ag_compress="int8", compress_min_bytes=0)
    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devices8)
    mesh = tpc.get_view()
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    specs = transformer_param_specs(cfg, axis="tensor")
    sharded = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs)
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.dim)),
        NamedSharding(mesh, P()))

    def arm(c):
        def loss_with_out(p, xx):
            out = shard_map(
                functools.partial(transformer_forward, cfg=c, axis="tensor",
                                  sp=True, gather_output=False),
                mesh=mesh,
                in_specs=(specs, P()),
                out_specs=P(None, "tensor", None),
            )(p, xx)
            return jnp.mean(out ** 2), out

        (loss, out), grads = jax.jit(
            jax.value_and_grad(loss_with_out, has_aux=True))(sharded, x)
        return float(loss), np.asarray(out), jax.device_get(grads)

    l_e, out_e, g_e = arm(cfg)
    l_q, out_q, g_q = arm(cfg_q)
    scale = float(np.abs(out_e).max())
    np.testing.assert_allclose(out_q, out_e, atol=0.05 * scale)
    np.testing.assert_allclose(l_q, l_e, rtol=0.05)
    for (path, ge), (_, gq) in zip(
            jax.tree_util.tree_flatten_with_path(g_e)[0],
            jax.tree_util.tree_flatten_with_path(g_q)[0]):
        ref = float(np.abs(np.asarray(ge)).max())
        np.testing.assert_allclose(
            np.asarray(gq), np.asarray(ge), atol=max(ref, 1e-3) * 0.15,
            err_msg=f"grad drift at {jax.tree_util.keystr(path)}")


# ----------------------------------------- the A/B acceptance fixtures


@pytest.fixture(scope="module")
def ab_zero(tmp_path_factory):
    """Checked-in acceptance A/B, ZeRO arm: exact vs
    ZeroOptimizer(grad_compress='int8') training on the 8-dev sim, each
    arm leaving a validated RUNREPORT (comm + dtype ledgers captured via
    the step's ``.lower`` AOT hook)."""
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), axis_names=("data",))
    tmp = tmp_path_factory.mktemp("ab_zero")
    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)
    batch = jax.device_put(
        _data(jax.random.PRNGKey(100)), NamedSharding(mesh, P("data")))
    out = {}
    for name, compress in (("exact", None), ("int8", "int8")):
        log = EventLog()
        set_default_event_log(log)
        zero = ZeroOptimizer(opt, mesh=mesh, grad_compress=compress,
                             compress_min_size=0)
        zp = zero.place_params(jax.tree.map(np.array, params))
        zs = zero.init(zp)
        report_path = str(tmp / f"RUNREPORT_{name}.json")
        tel = Telemetry(run=f"zero-{name}", report_path=report_path,
                        mesh=mesh, event_log=log,
                        sinks=[JsonlSink(str(tmp / f"records_{name}.jsonl"))])
        step = tel.wrap_step(zero.make_train_step(mlp_loss))
        for i in range(6):
            zp, zs, loss = step(zp, zs, batch)
            # numerics={} keeps the per-step loss on the report's numerics
            # timeline (what parity_diff streams) without in-step stats
            tel.end_step(step=i, loss=loss, numerics={})
        out[name] = {
            "report": tel.finalize(print_summary=False),
            "report_path": report_path,
            "params": jax.device_get(zp),
        }
    set_default_event_log(None)
    return out


@pytest.fixture(scope="module")
def ab_tp(tmp_path_factory):
    """Checked-in acceptance A/B, TP arm: exact vs
    TransformerConfig(ag_compress='int8') activation boundaries, trained
    through DataParallel on the (data=4, tensor=2) sim mesh."""
    devs = jax.devices()[:8]
    tmp = tmp_path_factory.mktemp("ab_tp")
    tpc.setup_process_groups([("data", 4), ("tensor", 2)], devices=devs)
    mesh = tpc.get_view()
    cfg = TransformerConfig(dim=32, nheads=4, nlayers=1, ffn_mult=2)
    params = jax.device_get(init_transformer_params(jax.random.PRNGKey(0), cfg))
    specs = transformer_param_specs(cfg, axis="tensor")
    opt = optax.sgd(1e-2)
    batch = {
        "x": np.asarray(jax.random.normal(jax.random.PRNGKey(5), (8, 16, cfg.dim))),
        "y": np.asarray(jax.random.normal(jax.random.PRNGKey(6), (8, 16, cfg.dim))),
    }
    out = {}
    for name, c in (
        ("exact", cfg),
        ("int8", dataclasses.replace(cfg, ag_compress="int8",
                                     compress_min_bytes=0)),
    ):
        def loss_fn(p, b, _c=c):
            o = transformer_forward(p, b["x"], _c, axis="tensor", sp=True)
            return jnp.mean((o - b["y"]) ** 2)

        log = EventLog()
        set_default_event_log(log)
        dp = DataParallel(mesh=mesh)
        p = dp.broadcast_params(jax.tree.map(np.array, params),
                                param_specs=specs)
        s = opt.init(p)
        report_path = str(tmp / f"RUNREPORT_{name}.json")
        tel = Telemetry(run=f"tp-{name}", report_path=report_path, mesh=mesh,
                        event_log=log)
        step = tel.wrap_step(
            dp.make_train_step(loss_fn, opt, param_specs=specs,
                               numerics=True))
        sb = dp.shard_batch(batch)
        for i in range(5):
            p, s, loss, nstats = step(p, s, sb)
            tel.end_step(step=i, loss=loss, numerics=nstats)
        out[name] = {
            "report": tel.finalize(print_summary=False),
            "report_path": report_path,
        }
    set_default_event_log(None)
    tpc.reset()
    return out


@pytest.mark.parametrize("arm", ["zero", "tp"])
def test_ab_parity_diff_bounded_with_both_shifts(ab_zero, ab_tp, arm, capsys):
    """Acceptance bar: tools/parity_diff.py on each exact-vs-int8 pair ->
    'bounded' (exit 0), with the dtype-shift AND the per-axis compressed-
    bytes shift rendered by the one command."""
    from torchdistpackage_tpu.tools.parity_diff import main

    runs = ab_zero if arm == "zero" else ab_tp
    rc = main([runs["exact"]["report_path"], runs["int8"]["report_path"],
               "--label-a", "exact", "--label-b", "int8"])
    out = capsys.readouterr().out
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["verdict"] == "bounded"
    assert 0 < line["max_rel_delta"] < 0.05
    assert line["dtype_bytes_delta"]["s8"] > 0
    assert "comm ledger shift per axis" in out
    axis = "data" if arm == "zero" else "tensor"
    assert line["comm_axis_bytes"][axis]["ratio"] >= 3.0, line["comm_axis_bytes"]


@pytest.mark.parametrize("arm", ["zero", "tp"])
def test_ab_s8_only_in_compressed_arm(ab_zero, ab_tp, arm):
    """The dtype-ledger evidence channel: the s8 shift appears exactly
    and ONLY in the compressed arm's compiled step."""
    runs = ab_zero if arm == "zero" else ab_tp
    for name, want_s8 in (("exact", False), ("int8", True)):
        report = runs[name]["report"]
        assert validate_runreport(report) == [], (arm, name)
        per = report["numerics"]["dtype_ledgers"][0]["per_dtype"]
        assert ("s8" in per) == want_s8, (arm, name, sorted(per))
        if want_s8:
            assert per["s8"]["bytes"] > 0


@pytest.mark.parametrize("arm,axis", [("zero", "data"), ("tp", "tensor")])
def test_ab_compressed_axis_wire_bytes_3x(ab_zero, ab_tp, arm, axis):
    """Acceptance bar: the compressed axis's comm-ledger bytes (s8
    payloads + f32 scale sideband included) drop >= 3x vs the exact arm."""
    runs = ab_zero if arm == "zero" else ab_tp
    exact = _axis_bytes(runs["exact"]["report"], axis)
    q = _axis_bytes(runs["int8"]["report"], axis)
    assert exact > 0 and q > 0
    assert exact / q >= 3.0, (arm, exact, q, exact / q)


def test_ab_zero_param_divergence_bounded(ab_zero):
    from torchdistpackage_tpu.obs import param_divergence

    div = param_divergence(ab_zero["exact"]["params"],
                           ab_zero["int8"]["params"])
    assert div["global"]["rel"] < 0.05, div["global"]


# ------------------------------------------------ the auto decision loop


def test_auto_policy_calibrated_choices_match_predictions(devices8):
    """Acceptance bar, measurement side: 'auto' under a CALIBRATED model
    records choices that are EXACTLY predict_compressed's verdicts gated
    by the size floor — whatever the sim fabric measured (on CPU the
    quant arithmetic can honestly lose to the exact copy; the policy must
    follow the measurement either way, not a hardcoded preference)."""
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    model = CommModel.calibrate(
        mesh=mesh, axes=("data",), sizes=(1 << 14,),
        ops=("all_reduce", "ppermute"), iters=2, warmup=1,
        compressed_ops=("int8_all_reduce",))
    assert "data" in model.compressed_axis_costs
    assert model.predict_compressed(
        "all_reduce", 1 << 16, 8, axes=("data",))["basis"] == "calibrated-int8"

    params = make_mlp_params(jax.random.PRNGKey(0))
    log = EventLog()
    set_default_event_log(log)
    dp = DataParallel(mesh=mesh, grad_compress="auto", comm_model=model,
                      compress_min_size=100)
    p = dp.broadcast_params(jax.tree.map(np.array, params))
    s = optax.sgd(1e-2).init(p)
    step = dp.make_train_step(mlp_loss, optax.sgd(1e-2))
    p, s, _ = step(p, s, dp.shard_batch(_data(jax.random.PRNGKey(100))))
    ev = log.of_kind("compress_policy")[0]
    for rec in ev["leaves"]:
        want = model.predict_compressed(
            "all_reduce", rec["bytes"], 8, axes=("data",),
            elem_bytes=rec["bytes"] // rec["elems"])
        assert rec["compress"] == (
            bool(want["compress"]) and rec["elems"] >= 100), rec
    set_default_event_log(None)


def test_auto_policy_consults_comm_model_and_reports(devices8, tmp_path):
    """Acceptance bar, decision side: 'auto' records a compress_policy
    event whose per-leaf choices match predict_compressed, and the
    RUNREPORT compression section validates with predicted-vs-measured
    bytes for the data axis.  A DETERMINISTIC model (known link
    parameters where compression provably wins) drives this flow so the
    expected choices are stable — the calibrated-measurement variant is
    the test above."""
    from torchdistpackage_tpu.obs.comm_model import AxisCost

    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    link = AxisCost(alpha_s=1e-6, beta_Bps=1e9, kind="table")
    model = CommModel({"data": link}, default=link,
                      compressed_axis_costs={"data": link})
    pred = model.predict_compressed("all_reduce", 1 << 16, 8, axes=("data",))
    assert pred["wire_bytes_compressed"] < pred["wire_bytes_exact"]
    assert pred["compress"] is True

    params = make_mlp_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)
    log = EventLog()
    set_default_event_log(log)
    dp = DataParallel(mesh=mesh, grad_compress="auto", comm_model=model,
                      compress_min_size=100)
    p = dp.broadcast_params(jax.tree.map(np.array, params))
    s = opt.init(p)
    report_path = str(tmp_path / "RUNREPORT_auto.json")
    tel = Telemetry(run="auto", report_path=report_path, mesh=mesh,
                    event_log=log)
    step = tel.wrap_step(dp.make_train_step(mlp_loss, opt))
    batch = dp.shard_batch(_data(jax.random.PRNGKey(100)))
    for i in range(3):
        p, s, loss = step(p, s, batch)
        tel.end_step(step=i, loss=loss)

    events = log.of_kind("compress_policy")
    assert len(events) == 1  # once per compiled signature
    ev = events[0]
    assert ev["family"] == "data_parallel" and ev["mode"] == "auto"
    assert ev["n_leaves"] == len(jax.tree.leaves(params))
    # every recorded choice is EXACTLY the model's prediction gated by the
    # size floor — the policy demonstrably consults CommModel
    assert any(r["compress"] for r in ev["leaves"])
    assert any(not r["compress"] for r in ev["leaves"])
    for rec in ev["leaves"]:
        want = model.predict_compressed(
            "all_reduce", rec["bytes"], 8, axes=("data",),
            elem_bytes=rec["bytes"] // rec["elems"])
        assert rec["compress"] == (
            bool(want["compress"]) and rec["elems"] >= 100), rec

    # the RUNREPORT compression section: policy + predicted vs measured
    section = compression_report("auto", policy_events=events,
                                 ledger=tel.comm_ledger)
    tel.record_compression(section)
    report = tel.finalize(print_summary=False)
    assert validate_runreport(report) == []
    comp = report["compression"]
    assert comp["mode"] == "auto"
    assert comp["policy"]["n_compressed"] >= 1
    row = next(r for r in comp["per_axis"] if r["axes"] == "data")
    assert row["predicted_bytes"] > 0 and row["measured_bytes"] > 0
    # measured covers the whole step's data-axis traffic (loss pmean etc.
    # ride along) — reconciliation, not a tight bound
    assert abs(row["rel_err"]) < 0.5, row
    set_default_event_log(None)


def test_auto_policy_zero_family_event(devices8):
    """ZeRO's 'auto' emits the policy event too (family='zero', op=
    reduce_scatter), and the choices key on the reduce-to-owner path."""
    tpc.setup_process_groups([("data", 8)], devices=devices8)
    mesh = tpc.get_view()
    params = make_mlp_params(jax.random.PRNGKey(0))
    log = EventLog()
    set_default_event_log(log)
    _zero_run(mesh, params, optax.sgd(1e-2), "auto", nsteps=1)
    ev = log.of_kind("compress_policy")
    assert len(ev) == 1
    assert ev[0]["family"] == "zero" and ev[0]["op"] == "reduce_scatter"
    # b2 (4,) has no divisible dim -> replicated -> never compressed
    by_leaf = {r["leaf"]: r["compress"] for r in ev[0]["leaves"]}
    assert by_leaf["w1"] is True
    set_default_event_log(None)


def test_predict_compressed_byte_math():
    model = CommModel.from_defaults(device_kind="cpu")
    n, payload = 8, 4096 * 4  # 4096 f32 elems
    q = 4096 * (1 + 4.0 / COMPRESS_GROUP)
    assert compressed_wire_bytes("reduce_scatter", payload, n) == pytest.approx(
        q * 7 / 8)
    assert compressed_wire_bytes("all_reduce", payload, n) == pytest.approx(
        3 * q * 7 / 8)
    assert compressed_ledger_bytes("all_gather", payload, n) == pytest.approx(
        q * 7 / 8)
    assert compressed_ledger_bytes("all_reduce", payload, n) == pytest.approx(
        q * 7 / 8 + q)
    pred = model.predict_compressed("all_reduce", payload, n, axes=("data",))
    assert pred["ledger_bytes_exact"] == payload
    assert pred["wire_bytes_compressed"] < pred["wire_bytes_exact"]
    # single-member axis: nothing to move, never compress
    assert model.predict_compressed("all_reduce", payload, 1)["compress"] is False
    with pytest.raises(ValueError, match="no int8 ring"):
        model.predict_compressed("all_to_all", payload, n)


def test_zero_moe_override_leaves_never_compress():
    """The MoE cell of the matrix: expert leaves under a
    grad_reduce_overrides match (the moe_dp reduction with its EP
    overcount semantics) keep the EXACT path under every compress mode —
    the override's full-group normalization is not expressible through
    the ring's mean, so compressing it would silently change semantics."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    params = {"experts": {"w1": jnp.zeros((8, 64, 64))},
              "dense": {"w": jnp.zeros((64, 64))}}
    for mode in ("int8", "int8_ef", "auto"):
        zero = ZeroOptimizer(
            optax.sgd(1e-2), mesh=mesh, grad_compress=mode,
            compress_min_size=0,
            grad_reduce_overrides={"experts": ("data",)})
        _, _, sdims = zero._specs_for(params)
        policy, _ = zero._compress_decisions(params, sdims)
        assert policy["experts/w1"] is False, mode
        assert policy["dense/w"] is True, mode


def test_auto_compress_policy_records():
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    policy, records = auto_compress_policy(
        [("big", (256, 64), 4), ("small", (8,), 4)],
        "all_reduce", ("data",), mesh, min_size=1024)
    assert policy["big"] is True and policy["small"] is False
    by = {r["leaf"]: r for r in records}
    assert by["big"]["ledger_bytes_compressed"] < by["big"]["ledger_bytes_exact"]
    assert by["small"]["compress"] is False
