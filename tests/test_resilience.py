"""Fault matrix for the resilience subsystem: every injected fault must be
detected, land on the event timeline, and either recover with
exact-trajectory parity (where parity is defined) or abort cleanly.

Kept cheap per the PR-3 budget note: ONE tiny jitted train step (fwd+grad
folded into a single ``value_and_grad`` program) is compiled once at
module scope and reused by every trajectory test; everything else
(manifests, retries, watchdog, monitor, consistency) is pure host-side
python.
"""

import math
import os
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchdistpackage_tpu.obs.events import (
    EventLog,
    set_default_event_log,
)
from torchdistpackage_tpu.resilience import (
    ChaosMonkey,
    CheckpointCorruptError,
    DivergenceMonitor,
    Fault,
    GuardedCheckpointManager,
    ResilientLoop,
    Watchdog,
    check_consistency,
    config_fingerprint,
    consistency_fingerprint,
    corrupt_checkpoint,
    param_checksum,
    verify_checkpoint,
    verify_template,
    with_retries,
    write_manifest,
)
from torchdistpackage_tpu.utils import CheckpointManager, GracefulShutdown, auto_resume

# ------------------------------------------------------------ tiny model
# One compiled program for the whole module: linear regression, fwd+grad
# in a single value_and_grad jit (the cheapest real "training step" that
# still exercises checkpoint payloads, optimizer state, and determinism).

_OPT = optax.sgd(0.1)


@jax.jit
def _step(params, opt_state, batch):
    def loss_fn(p):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = _OPT.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss


def _make_batch(index: int):
    # fully index-derived (no RNG object): the offset shift after a
    # rollback IS the data/RNG-stream advance
    x = np.sin(np.arange(32, dtype=np.float32).reshape(8, 4) + index)
    y = np.cos(np.arange(8, dtype=np.float32) + index * 0.5)
    return {"x": x, "y": y}


def _init():
    params = {"w": jnp.zeros((4,)), "b": jnp.zeros(())}
    return params, _OPT.init(params)


def _payload(params, opt_state, offset=0):
    return {"params": params, "opt": opt_state,
            "loop": {"data_offset": jnp.int32(offset)}}


@pytest.fixture()
def events():
    """Fresh process-default event log per test — assertions against the
    timeline must not see a neighbor test's events."""
    log = EventLog()
    set_default_event_log(log)
    yield log
    set_default_event_log(None)


# ===================================================== checkpoint hardening


def test_manifest_write_verify_roundtrip(tmp_path, events):
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr:
        mgr.save(0, _payload(params, opt), wait=True)
        # manifest written at commit, checkpoint verifies clean
        assert os.path.exists(os.path.join(d, "manifests", "0.json"))
        assert verify_checkpoint(d, 0) == []
        # template structure check: drift in the tree fails loudly
        assert verify_template(d, 0, _payload(params, opt)) == []
        bad = {"params": {"w": jnp.zeros((5,))}}
        assert verify_template(d, 0, bad) != []


@pytest.mark.parametrize("mode", ["truncate", "bitflip"])
def test_corruption_detected_and_quarantined(tmp_path, events, mode):
    """Corrupt ckpt -> fallback: auto_resume restores the newest GOOD step,
    quarantines the bad one, and the skip lands on the timeline."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr:
        for s in range(3):
            mgr.save(s, _payload(params, opt, offset=s), wait=True)
        corrupt_checkpoint(d, step=2, mode=mode)
        assert verify_checkpoint(d, 2) != []
        # direct restore of the bad step raises, not garbage
        with pytest.raises(CheckpointCorruptError):
            mgr.restore(2, template=_payload(params, opt))
        start, state = auto_resume(mgr, _payload(params, opt))
        # walked back: resumed AFTER step 1, with step 1's payload
        assert start == 2
        assert int(state["loop"]["data_offset"]) == 1
        # bad step renamed aside for post-mortem, manager no longer sees it
        assert os.path.isdir(os.path.join(d + ".quarantine", "2"))
        assert mgr.latest_step() == 1
    quark = events.of_kind("ckpt_quarantine")
    assert len(quark) == 1 and quark[0]["step"] == 2, quark
    assert events.of_kind("fault_injected")[0]["fault"] == "ckpt_corrupt"


def test_unmanifested_corruption_still_walks_back(tmp_path, events):
    """A plain (manifest-less) manager's corrupt step is caught by the
    restore failure itself — auto_resume must still fall back."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with CheckpointManager(d, max_to_keep=4) as mgr:
        for s in range(2):
            mgr.save(s, _payload(params, opt, offset=s), wait=True)
        # wreck step 1 thoroughly: every file truncated to zero
        step_dir = os.path.join(d, "1")
        for root, _dirs, files in os.walk(step_dir):
            for f in files:
                with open(os.path.join(root, f), "r+b") as fh:
                    fh.truncate(0)
        start, state = auto_resume(mgr, _payload(params, opt))
        assert start == 1
        assert int(state["loop"]["data_offset"]) == 0
    assert [e["step"] for e in events.of_kind("ckpt_quarantine")] == [1]


def test_template_drift_reraises_not_quarantines(tmp_path, events):
    """A restore template that drifted from the checkpoint is a CALLER
    bug: auto_resume must fail loudly, not rename good checkpoints aside
    one by one until the run silently restarts from step 0."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr:
        for s in range(2):
            mgr.save(s, _payload(params, opt, offset=s), wait=True)
        drifted = {"params": {"w": jnp.zeros((5,))}}
        with pytest.raises(ValueError, match="does not match its recorded"):
            auto_resume(mgr, drifted)
        # every checkpoint survived untouched
        assert sorted(mgr.all_steps()) == [0, 1]
    assert not os.path.exists(d + ".quarantine")
    assert events.of_kind("ckpt_quarantine") == []


def test_manifestless_template_drift_reraises(tmp_path, events):
    """Even without a manifest, a readable checkpoint + failing restore is
    a template problem: the template-free probe proves the bytes fine and
    the original error surfaces instead of a quarantine."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with CheckpointManager(d, max_to_keep=4) as mgr:
        mgr.save(0, _payload(params, opt), wait=True)
        # orbax's own words for a template that is not the tree on disk
        # ("Key mismatch" before 0.11, "tree structures do not match" now)
        with pytest.raises(
                ValueError, match="[Kk]ey mismatch|structures do not match"):
            auto_resume(mgr, {"params": {"w": jnp.zeros((5,))}})
        assert mgr.latest_step() == 0
    assert events.of_kind("ckpt_quarantine") == []


def test_transient_oserror_retries_then_reraises(tmp_path, events):
    """Persistent OSError (storage down) must NOT quarantine: retry with
    backoff, then fail loudly with every checkpoint still in place."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with CheckpointManager(d, max_to_keep=4) as mgr:
        mgr.save(0, _payload(params, opt), wait=True)
        real_restore = mgr.restore
        mgr.restore = lambda *a, **k: (_ for _ in ()).throw(OSError("mount gone"))
        with pytest.raises(OSError, match="mount gone"):
            auto_resume(mgr, _payload(params, opt))
        mgr.restore = real_restore
        assert mgr.latest_step() == 0
    assert events.of_kind("ckpt_quarantine") == []
    assert len(events.of_kind("ckpt_retry")) == 3  # backoff was attempted


def test_with_retries_backoff_and_budget(events):
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return 42

    assert with_retries(flaky, retries=5, base_delay_s=0.001) == 42
    assert len(events.of_kind("ckpt_retry")) == 2
    with pytest.raises(OSError):
        with_retries(lambda: (_ for _ in ()).throw(OSError("down")),
                     retries=2, base_delay_s=0.001)
    # budget exhausted after exactly `retries` retry events more
    assert len(events.of_kind("ckpt_retry")) == 4


def test_manifests_pruned_with_retention(tmp_path):
    """Retention-removed steps must not leave manifests behind: the
    manifests dir stays bounded over a long run."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=2) as mgr:
        for s in range(5):
            mgr.save(s, _payload(params, opt, offset=s), wait=True)
        assert sorted(mgr.all_steps()) == [3, 4]
    mdir = os.path.join(d, "manifests")
    assert sorted(os.listdir(mdir)) == ["3.json", "4.json"]


def test_stale_manifest_pruned_at_init(tmp_path, events):
    """Fresh run, same directory: a manifest lingering from a previous
    run's step 0 must not condemn the new run's step 0."""
    import shutil

    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=3) as mgr:
        mgr.save(0, _payload(params, opt), wait=True)
    shutil.rmtree(os.path.join(d, "0"))  # steps cleared, manifests forgotten
    assert os.path.exists(os.path.join(d, "manifests", "0.json"))
    with GuardedCheckpointManager(d, max_to_keep=3) as mgr2:
        # construction pruned the orphaned manifest...
        assert not os.path.exists(os.path.join(d, "manifests", "0.json"))
        mgr2.save(0, _payload(params, opt, offset=7), wait=True)
        # ...so the recycled step 0 verifies against ITS manifest, clean
        assert verify_checkpoint(d, 0) == []
        start, state = auto_resume(mgr2, _payload(params, opt))
        assert start == 1 and int(state["loop"]["data_offset"]) == 7
    assert events.of_kind("ckpt_quarantine") == []


def test_stale_manifest_mtime_crosscheck(tmp_path):
    """verify_checkpoint ignores a manifest whose recorded files all
    postdate it (recycled step) but still flags real tampering."""
    import json as _json

    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=2) as mgr:
        mgr.save(0, _payload(params, opt), wait=True)
    mpath = os.path.join(d, "manifests", "0.json")
    with open(mpath) as f:
        manifest = _json.load(f)
    # poison a checksum: an APPLICABLE manifest must flag it...
    manifest["files"][0]["sha256"] = "0" * 64
    with open(mpath, "w") as f:
        _json.dump(manifest, f)
    assert any("checksum" in p for p in verify_checkpoint(d, 0))
    # ...but the same manifest pushed into the past (as if every file were
    # rewritten by a new incarnation of step 0) proves nothing
    manifest["files_max_mtime"] -= 10_000.0
    with open(mpath, "w") as f:
        _json.dump(manifest, f)
    assert verify_checkpoint(d, 0) == []


def test_ckpt_manager_ctx_waits_on_exception(tmp_path):
    """An exception between save() and teardown must not strand the async
    save: __exit__ waits for the commit before closing."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with pytest.raises(RuntimeError, match="boom"):
        with CheckpointManager(d, max_to_keep=2) as mgr:
            mgr.save(0, _payload(params, opt), wait=False)
            raise RuntimeError("boom")
    with CheckpointManager(d, max_to_keep=2) as mgr2:
        assert mgr2.latest_step() == 0  # the save committed anyway


# =========================================================== chaos parity


def test_armed_unfired_chaos_is_bit_identical(tmp_path, events):
    """Acceptance: chaos armed but silent == no resilience subsystem at
    all, bit for bit (losses AND final params)."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=3) as mgr:
        loop = ResilientLoop(
            _step, _make_batch, mgr, total_steps=6, save_every=2,
            chaos=ChaosMonkey(faults=[Fault("nan_spike", step=99)], seed=7))
        res = loop.run(params, opt)
    assert res.verdict == "clean" and res.summary["faults_injected"] == 0

    p, o = _init()
    hand = {}
    for s in range(6):
        p, o, loss = _step(p, o, _make_batch(s))
        hand[s] = float(loss)
    assert hand == res.losses
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        p, res.params)
    assert events.of_kind("rollback") == []


def test_nan_spike_rollback_exact_parity(tmp_path, events):
    """NaN spike at step 5 -> rollback to the step-3 checkpoint, data
    stream advanced past the poisoned window, and from there the recovered
    trajectory is bit-identical to a hand replay of the same checkpoint
    over the same shifted indices."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr:
        loop = ResilientLoop(
            _step, _make_batch, mgr, total_steps=10, save_every=2,
            max_rollbacks=2, chaos=ChaosMonkey([Fault("nan_spike", step=5)]))
        res = loop.run(params, opt)
    assert res.verdict == "recovered"
    assert res.summary["rollbacks"] == 1
    assert res.summary["data_offset"] == 2  # skipped window (3, 5]
    assert sorted(res.losses) == list(range(10))
    assert all(math.isfinite(v) for v in res.losses.values())

    rb = events.of_kind("rollback")
    assert len(rb) == 1
    assert rb[0]["from_step"] == 5 and rb[0]["to_step"] == 3
    fi = events.of_kind("fault_injected")
    assert len(fi) == 1 and fi[0]["fault"] == "nan_spike" and fi[0]["step"] == 5

    # parity golden: hand-replay from the step-3 checkpoint with the
    # shifted stream — every loss and the final params must match exactly
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr2:
        st = mgr2.restore(3, template=_payload(params, opt))
    p, o = st["params"], st["opt"]
    for s in range(4, 10):
        p, o, loss = _step(p, o, _make_batch(s + 2))
        assert float(loss) == res.losses[s], s
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        p, res.params)


def test_rollback_budget_spent_aborts_cleanly(tmp_path, events):
    """A persistent divergence exhausts max_rollbacks and the loop aborts
    with a verdict instead of looping forever or crashing."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr:
        loop = ResilientLoop(
            _step, _make_batch, mgr, total_steps=8, save_every=1,
            max_rollbacks=1,
            chaos=ChaosMonkey([Fault("nan_spike", step=3, repeat=True)]))
        res = loop.run(params, opt)
    assert res.aborted and res.verdict == "aborted"
    assert res.summary["rollbacks"] == 1
    ab = events.of_kind("resilience_abort")
    assert len(ab) == 1 and ab[0]["rollbacks_used"] == 1
    # checkpoints survive the abort: a babysitter relaunch can still resume
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr2:
        assert mgr2.latest_step() is not None


def test_sigterm_mid_run_resume_exact_trajectory(tmp_path, events):
    """Chaos SIGTERM -> grace-window save -> relaunch resumes -> the
    stitched trajectory equals an uninterrupted run exactly."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr:
        loop = ResilientLoop(
            _step, _make_batch, mgr, total_steps=8, save_every=3,
            chaos=ChaosMonkey([Fault("sigterm", step=4)]))
        res1 = loop.run(params, opt)
    assert res1.preempted and res1.verdict == "preempted"
    assert max(res1.losses) == 4  # finished the in-flight step, then saved
    pre = events.of_kind("preemption")
    assert len(pre) == 1 and pre[0]["signal"] == "SIGTERM"

    # relaunch: fresh objects, same dir, no chaos
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr2:
        loop2 = ResilientLoop(_step, _make_batch, mgr2, total_steps=8,
                              save_every=3)
        res2 = loop2.run(*_init())
    assert res2.verdict == "clean"
    assert sorted(res2.losses) == [5, 6, 7]

    p, o = _init()
    for s in range(8):
        p, o, loss = _step(p, o, _make_batch(s))
        got = res1.losses.get(s, res2.losses.get(s))
        assert float(loss) == got, s
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        p, res2.params)


def test_grace_save_forced_past_save_interval(tmp_path, events):
    """A manager with save_interval_steps > 1 declines off-interval saves;
    the preemption grace-window save must be FORCED through, and the
    reported last_checkpoint must be a checkpoint that actually exists."""
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=4,
                                  save_interval_steps=5) as mgr:
        loop = ResilientLoop(
            _step, _make_batch, mgr, total_steps=8, save_every=1,
            chaos=ChaosMonkey([Fault("sigterm", step=2)]))
        res = loop.run(params, opt)
    assert res.preempted
    # step 2 is off the 5-step interval — only the forced save committed it
    assert res.summary["last_checkpoint"] == 2
    with GuardedCheckpointManager(d, max_to_keep=4) as mgr2:
        assert mgr2.latest_step() == 2
        start, state = auto_resume(mgr2, _payload(params, opt))
        assert start == 3


def test_declined_forced_save_is_loud(tmp_path, events):
    """If even a forced save is declined, the summary must not claim the
    step was checkpointed — and the decline lands on the timeline."""

    class _DecliningManager(CheckpointManager):
        def save(self, step, state, wait=False, force=False):
            return False

    params, opt = _init()
    d = str(tmp_path / "run")
    with _DecliningManager(d, max_to_keep=2) as mgr:
        res = ResilientLoop(_step, _make_batch, mgr, total_steps=2,
                            save_every=1).run(params, opt)
    assert res.verdict == "clean"
    assert res.summary["last_checkpoint"] is None
    skipped = events.of_kind("checkpoint_save_skipped")
    assert skipped and skipped[-1]["forced"] and skipped[-1]["step"] == 1


def test_stall_trips_watchdog_hang_suspected(tmp_path, events):
    """Host stall (chaos sleep) longer than the watchdog timeout ->
    hang_suspected on the timeline; the beat after the stall resolves it."""
    params, opt = _init()
    d = str(tmp_path / "run")
    dog = Watchdog(timeout_s=0.15, poll_s=0.03)
    with GuardedCheckpointManager(d, max_to_keep=3) as mgr:
        loop = ResilientLoop(
            _step, _make_batch, mgr, total_steps=5, save_every=5,
            watchdog=dog,
            chaos=ChaosMonkey([Fault("stall", step=3, duration_s=0.5)]))
        res = loop.run(params, opt)
    assert res.verdict == "clean"  # a stall is latency, not divergence
    # the injected stall is flagged exactly once, at its step.  A loaded
    # host may also take longer than the 0.15 s timeout over some other
    # step (the suite runs six workers): that is the host's, so only the
    # bookkeeping is asserted of it: counted, and resolved by the next beat.
    # The last step (4: the forced save and the wait for it) has no next
    # beat, so an episode that opens there stays open when the loop ends
    sus = events.of_kind("hang_suspected")
    assert [e["last_step"] for e in sus].count(3) == 1
    assert res.summary["hang_suspected"] == len(sus)
    assert [e["fault"] for e in events.of_kind("fault_injected")] == ["stall"]
    beaten = [e for e in sus if e["last_step"] < 4]
    assert len(beaten) <= len(events.of_kind("hang_resolved")) <= len(sus)


# ============================================================== watchdog


def test_watchdog_abort_escalation_uses_exit_hook(events):
    """Silence past timeout+grace with abort=True calls the (injected)
    exit hook with the configured code — the babysitter-relaunch path."""
    exited = []
    dog = Watchdog(timeout_s=0.05, poll_s=0.02, abort=True,
                   abort_grace_s=0.05, exit_code=87,
                   _exit=lambda code: exited.append(code))
    with dog:
        dog.beat(0)
        deadline = 2.0
        t0 = os.times().elapsed
        while not exited and os.times().elapsed - t0 < deadline:
            threading.Event().wait(0.02)
    assert exited == [87]
    kinds = [e["kind"] for e in events.as_list()]
    assert "hang_suspected" in kinds and "hang_abort" in kinds


# ==================================================== consistency guards


def test_desync_detected_on_divergent_fingerprints(events):
    """Cross-host disagreement (simulated gather) -> desync_detected with
    the offending component named; agreement -> ok, silent."""
    labels, vec = consistency_fingerprint(step=7, config={"lr": 1e-3})
    ok = check_consistency(step=7, config={"lr": 1e-3},
                           _gathered=np.asarray([vec, vec]))
    assert ok["ok"] and ok["n_hosts"] == 2 and ok["mismatched"] == []
    assert events.of_kind("desync_detected") == []

    vec_b = list(vec)
    vec_b[labels.index("step")] += 1  # host 1 is a step ahead
    bad = check_consistency(step=7, config={"lr": 1e-3},
                            _gathered=np.asarray([vec, vec_b]))
    assert not bad["ok"] and bad["mismatched"] == ["step"]
    ev = events.of_kind("desync_detected")
    assert len(ev) == 1 and ev[0]["mismatched"] == ["step"]


def test_fingerprint_gather_is_exact():
    """The allgather must compare fingerprints exactly: float64 values
    travel bit-cast as int32 lanes, so step counters above 2**24 and
    param-checksum sums that a float32 gather would conflate stay
    distinct."""
    from torchdistpackage_tpu.resilience.watchdog import (
        _f64_to_lanes,
        _lanes_to_f64,
    )

    # values float32 provably conflates (same f32, different f64)
    pairs = [
        (float(2 ** 24), float(2 ** 24 + 1)),     # big step counters
        (1.0e9, 1.0e9 + 1.0),                      # param checksums
        (123456789.0, np.nextafter(123456789.0, np.inf)),  # 1-ulp drift
    ]
    for a, b in pairs:
        assert np.float32(a) == np.float32(b)  # the old failure mode
        vec_a, vec_b = [a, 7.0], [b, 7.0]
        gathered = _lanes_to_f64(
            np.stack([_f64_to_lanes(vec_a), _f64_to_lanes(vec_b)]), 2)
        assert gathered[0, 0] != gathered[1, 0]  # drift stays visible
        assert gathered[0, 1] == gathered[1, 1]
        np.testing.assert_array_equal(gathered[0], vec_a)
        np.testing.assert_array_equal(gathered[1], vec_b)
    # and agreement still compares equal through the round trip
    res = check_consistency(
        step=2 ** 30,
        _gathered=np.asarray([[float(2 ** 30)], [float(2 ** 30)]]))
    assert res["ok"]


def test_fingerprint_components():
    params = {"w": jnp.arange(4.0), "b": jnp.ones(())}
    assert param_checksum(params) == param_checksum(
        {"w": jnp.arange(4.0), "b": jnp.ones(())})
    assert param_checksum(params) != param_checksum(
        {"w": jnp.arange(4.0) + 1, "b": jnp.ones(())})
    assert config_fingerprint({"a": 1, "b": 2}) == config_fingerprint(
        {"b": 2, "a": 1})  # key order must not matter
    assert config_fingerprint({"a": 1}) != config_fingerprint({"a": 2})
    labels, vec = consistency_fingerprint(
        step=3, config={"x": 1}, params=params,
        rng_key=jax.random.PRNGKey(0), code=True)
    assert labels == ["step", "config_a", "config_b", "code_a", "code_b",
                      "rng", "params"]
    assert all(math.isfinite(v) for v in vec)
    with pytest.raises(ValueError, match="nothing to check"):
        check_consistency()


# ==================================================== divergence monitor


def test_divergence_monitor_matrix():
    m = DivergenceMonitor(window=16, zmax=3.0, min_history=4)
    assert m.check(float("nan")) == "nonfinite"
    assert m.check(float("inf")) == "nonfinite"
    assert m.check(1.0, grad_norm=float("nan")) == "nonfinite"
    # too little history: even a huge loss passes (warmup protection)
    assert m.check(1e9) == "ok"
    for v in (1.0, 1.1, 0.9, 1.0, 1.05, 0.95):
        m.observe(v)
    assert m.check(1.02) == "ok"
    assert m.check(50.0) == "spike"
    m.reset()
    assert m.check(50.0) == "ok"  # window cleared
    hard = DivergenceMonitor(max_loss=10.0)
    assert hard.check(11.0) == "spike"


# =============================================== GracefulShutdown upgrades


def test_graceful_shutdown_usr_signals_and_grace(events):
    with GracefulShutdown(signals=("SIGUSR1", "USR2"), grace_s=30.0) as stop:
        assert not stop.requested
        signal.raise_signal(signal.SIGUSR1)
        assert stop.requested
        assert stop.deadline_mono is not None
    ev = events.of_kind("preemption")
    assert len(ev) == 1
    assert ev[0]["signal"] == "SIGUSR1" and ev[0]["grace_s"] == 30.0
    assert ev[0]["grace_deadline_mono"] == stop.deadline_mono


def test_graceful_shutdown_rejects_non_main_thread():
    err = []

    def enter():
        try:
            with GracefulShutdown():
                pass
        except RuntimeError as e:
            err.append(str(e))

    t = threading.Thread(target=enter)
    t.start()
    t.join()
    assert err and "main thread" in err[0]


def test_graceful_shutdown_unknown_signal_name():
    with pytest.raises(ValueError, match="unknown signal"):
        GracefulShutdown(signals=("SIGNOPE",))


# ======================================================== chaos plumbing


def test_chaos_fault_validation_and_grad_injection():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("meteor_strike", step=0)
    chaos = ChaosMonkey([Fault("nan_spike", step=2)])
    grads = {"w": jnp.ones((3,)), "n": jnp.arange(3)}  # int leaf untouched
    out = chaos.perturb_grads(2, grads)
    assert bool(jnp.all(jnp.isnan(out["w"])))
    assert jnp.issubdtype(out["n"].dtype, jnp.integer)
    # fired once: a second pass is inert
    out2 = chaos.perturb_grads(2, grads)
    assert bool(jnp.all(jnp.isfinite(out2["w"])))
    # disabled harness never fires
    off = ChaosMonkey([Fault("nan_spike", step=0)], enabled=False)
    assert off.perturb_loss(0, 1.5) == 1.5 and off.fired_count == 0


def test_manifest_detects_unrecorded_file(tmp_path, events):
    params, opt = _init()
    d = str(tmp_path / "run")
    with GuardedCheckpointManager(d, max_to_keep=2) as mgr:
        mgr.save(0, _payload(params, opt), wait=True)
    extra = os.path.join(d, "0", "sneaky.bin")
    with open(extra, "wb") as f:
        f.write(b"tampered")
    problems = verify_checkpoint(d, 0)
    assert any("unrecorded" in p for p in problems), problems


def test_write_manifest_requires_committed_step(tmp_path):
    with pytest.raises(FileNotFoundError):
        write_manifest(str(tmp_path), 3)
