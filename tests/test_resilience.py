"""Resilience plane (znicz_tpu/resilience/): chaos tests driving the
REAL code paths — the supervisor resumes a crashed training run
bit-exactly (the snapshotter's exactness contract makes recovery
verifiable), poison snapshots are rejected by checksum, retries back off
deterministically, the NaN guard degrades gracefully, and the watchdog
catches hung steps."""

import os

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.resilience import faults
from znicz_tpu.resilience.retry import AttemptTimeout, RetryPolicy
from znicz_tpu.resilience.supervisor import (SupervisorExhausted,
                                             SupervisorPolicy,
                                             find_latest_valid_snapshot,
                                             run_supervised)
from znicz_tpu.snapshotter import (SnapshotCorruptError, collect_state,
                                   restore_state, verify_snapshot,
                                   write_snapshot)
from znicz_tpu.standard_workflow import StandardWorkflow

LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 24},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 6},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
]
LOADER = {"n_classes": 6, "sample_shape": (10, 10), "n_train": 240,
          "n_valid": 120, "minibatch_size": 40, "spread": 2.5, "noise": 1.0}


def build(max_epochs, snap_dir=None, seed=77, health=None, fused=True,
          defer_metrics=True):
    """Fresh, initialized workflow — the supervisor's factory discipline:
    re-seed the global PRNG exactly like a fresh process would."""
    prng.seed_all(seed)
    cfg = None
    if snap_dir is not None:
        cfg = {"directory": str(snap_dir), "prefix": "t",
               "only_improved": False, "keep_all": True}
    w = StandardWorkflow(
        name="ResTest", layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": max_epochs},
        snapshotter_config=cfg, health_config=health, fused=fused,
        defer_metrics=defer_metrics)
    w.initialize(device=XLADevice())
    return w


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    """A chaos test must never leak an armed plan into the suite."""
    yield
    faults.uninstall()


def fast_policy(**kw):
    kw.setdefault("sleep", lambda s: None)
    return SupervisorPolicy(**kw)


# -- retry policy ------------------------------------------------------------

def test_retry_succeeds_after_transient_failures():
    delays = []
    p = RetryPolicy(max_attempts=4, base_delay=0.1, multiplier=2.0,
                    sleep=delays.append, seed=3)
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise OSError("transient")
        return "done"

    assert p.call(flaky) == "done"
    assert calls[0] == 3
    assert len(delays) == 2
    # exponential shape survives the jitter band (+/-25%)
    assert 0.075 <= delays[0] <= 0.125
    assert 0.15 <= delays[1] <= 0.25
    assert p.total_retries == 2


def test_retry_jitter_is_seeded_deterministic():
    def schedule(seed):
        d = []
        p = RetryPolicy(max_attempts=5, base_delay=0.05, sleep=d.append,
                        seed=seed)
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] < 5:
                raise OSError("x")

        p.call(flaky)
        return d

    assert schedule(7) == schedule(7)
    assert schedule(7) != schedule(8)


def test_retry_exhaustion_reraises_last_error():
    p = RetryPolicy(max_attempts=3, sleep=lambda s: None)
    with pytest.raises(OSError, match="always"):
        p.call(lambda: (_ for _ in ()).throw(OSError("always")))
    assert p.total_attempts == 3


def test_retry_non_retryable_raises_immediately():
    p = RetryPolicy(max_attempts=5, sleep=lambda s: None)
    calls = [0]

    def broken():
        calls[0] += 1
        raise ValueError("a bug, not flakiness")

    with pytest.raises(ValueError):
        p.call(broken)
    assert calls[0] == 1


def test_retry_per_attempt_timeout():
    import time as _time

    p = RetryPolicy(max_attempts=2, timeout=0.15, base_delay=0.01,
                    sleep=lambda s: None)
    calls = [0]

    def wedges_once():
        calls[0] += 1
        if calls[0] == 1:
            _time.sleep(5.0)        # abandoned by the policy
        return "recovered"

    assert p.call(wedges_once) == "recovered"
    assert calls[0] == 2

    p2 = RetryPolicy(max_attempts=2, timeout=0.05, base_delay=0.01,
                     sleep=lambda s: None)
    with pytest.raises(AttemptTimeout):
        p2.call(lambda: _time.sleep(5.0))


# -- fault plan --------------------------------------------------------------

def test_fault_plan_hit_counting_and_once():
    plan = faults.FaultPlan(seed=0)
    plan.crash_at("site", at_hit=3)
    with faults.active(plan):
        faults.fault_hook("site")
        faults.fault_hook("site")
        with pytest.raises(faults.FaultInjected):
            faults.fault_hook("site")
        faults.fault_hook("site")            # once=True: disarmed now
    assert plan.hits["site"] == 4
    assert plan.log == [{"site": "site", "action": "crash", "hit": 3}]
    # no plan installed -> hooks are no-ops
    faults.fault_hook("site")
    assert faults.poison_hook("site", 1.5) == 1.5


def test_fault_plan_poison_nan():
    plan = faults.FaultPlan(seed=0)
    plan.nan_at("loss", at_hit=2)
    with faults.active(plan):
        assert faults.poison_hook("loss", 1.0) == 1.0
        poisoned = faults.poison_hook("loss", 1.0)
        assert np.isnan(poisoned)
        arr = faults.poison_hook("loss", np.ones(3))   # disarmed again
        np.testing.assert_array_equal(arr, 1.0)


def test_serve_engine_fault_hook():
    from znicz_tpu.serve.engine import BatchEngine

    eng = BatchEngine(lambda x: x * 2.0, max_batch=8)
    plan = faults.FaultPlan().crash_at("serve.run", at_hit=2)
    with faults.active(plan):
        np.testing.assert_allclose(eng.run(np.ones((2, 4))), 2.0)
        with pytest.raises(faults.FaultInjected):
            eng.run(np.ones((2, 4)))
        np.testing.assert_allclose(eng.run(np.ones((2, 4))), 2.0)


def test_restful_client_retries_through_server_fault():
    """predict_remote rides RetryPolicy: an injected engine crash kills
    the first request (connection-level failure at the client), the
    retry lands on a healed server."""
    from znicz_tpu.loader.restful import PredictionServer, predict_remote

    server = PredictionServer(lambda x: x + 1.0, max_batch=16)
    port = server.start()
    try:
        plan = faults.FaultPlan().crash_at("serve.run", at_hit=1)
        policy = RetryPolicy(max_attempts=3, base_delay=0.01,
                             retryable=(OSError,), seed=0)
        with faults.active(plan):
            out = predict_remote(f"http://127.0.0.1:{port}",
                                 [[1.0, 2.0]], policy=policy, timeout=5)
        np.testing.assert_allclose(out, [[2.0, 3.0]])
        assert policy.total_retries >= 1
    finally:
        server.stop()


# -- crash-safe snapshots ----------------------------------------------------

def test_snapshot_checksum_roundtrip_and_verify(tmp_path):
    w = build(1)
    w.run()
    arrays, meta = collect_state(w)
    path = str(tmp_path / "s.npz")
    write_snapshot(path, arrays, meta)
    assert verify_snapshot(path)
    w2 = build(1, seed=9)
    meta2 = restore_state(w2, path)
    assert int(meta2["checksum"]) > 0


def test_corrupt_snapshot_detected(tmp_path):
    w = build(1)
    w.run()
    arrays, meta = collect_state(w)
    path = str(tmp_path / "s.npz")
    write_snapshot(path, arrays, meta)
    blob = bytearray(open(path, "rb").read())
    mid = len(blob) // 2
    blob[mid:mid + 64] = b"\x00" * 64          # bit rot in the middle
    with open(path, "wb") as f:
        f.write(bytes(blob))
    assert not verify_snapshot(path)
    w2 = build(1, seed=9)
    with pytest.raises((SnapshotCorruptError, Exception)):
        restore_state(w2, path)


def test_checksum_mismatch_raises_on_restore(tmp_path):
    """A snapshot that is a VALID zip but carries tampered content must
    be caught by the checksum, not just by zip CRCs."""
    import json
    import numpy as _np

    w = build(1)
    w.run()
    arrays, meta = collect_state(w)
    path = str(tmp_path / "s.npz")
    write_snapshot(path, arrays, meta)
    with _np.load(path, allow_pickle=False) as zf:
        loaded_meta = json.loads(str(zf["__meta__"]))
        loaded = {k: zf[k] for k in zf.files if k != "__meta__"}
    key = next(k for k in loaded if k.startswith("forward."))
    loaded[key] = loaded[key] + 1.0            # tamper, then re-zip validly
    with open(path, "wb") as f:
        _np.savez_compressed(
            f, __meta__=_np.array(json.dumps(loaded_meta)), **loaded)
    assert not verify_snapshot(path)
    w2 = build(1, seed=9)
    with pytest.raises(SnapshotCorruptError, match="checksum"):
        restore_state(w2, path)


def test_snapshot_write_fault_retried(tmp_path):
    """One injected I/O failure in the write path is absorbed by the
    retry policy — the snapshot still lands and verifies."""
    w = build(1)
    w.run()
    arrays, meta = collect_state(w)
    path = str(tmp_path / "s.npz")
    plan = faults.FaultPlan().oserror_at("snapshot.write", at_hit=1)
    with faults.active(plan):
        write_snapshot(path, arrays, meta)
    assert plan.log and verify_snapshot(path)
    assert not os.path.exists(path + ".tmp")   # no temp litter


def test_failing_snapshot_write_keeps_previous_and_run_alive(tmp_path):
    """Write failures that exhaust the retries degrade gracefully: the
    run continues and the previously published snapshot stays the
    resume point."""
    plan = faults.FaultPlan()
    # epoch-1 snapshot publishes; every later attempt fails (3 armed
    # failures per retry round x 3 remaining epochs)
    for _ in range(9):
        plan.arm("snapshot.write", "oserror", when=lambda path:
                 not path.endswith("t_1.npz"))
    with faults.active(plan):
        w = build(4, tmp_path)
        w.run()
    assert len(w.decision.metrics_history) == 4    # training survived
    published = sorted(p for p in os.listdir(tmp_path)
                       if not p.endswith("_latest.npz"))
    assert published == ["t_1.npz"], published
    assert verify_snapshot(str(tmp_path / "t_1.npz"))


# -- supervised auto-resume (the acceptance chaos test) ----------------------

def test_supervised_resume_is_bit_exact_after_seeded_crash(tmp_path):
    """A training run killed at a SEEDED RANDOM epoch and auto-resumed by
    run_supervised reproduces the uninterrupted run's metric history
    bit-exactly (ISSUE 2 acceptance)."""
    full = build(4, tmp_path / "full")
    full.run()
    full_hist = full.decision.metrics_history
    assert len(full_hist) == 4

    rng = np.random.default_rng(1234)
    crash_epoch = int(rng.integers(1, 4))          # seeded "random" kill
    snap_dir = tmp_path / "chaos"
    plan = faults.FaultPlan(seed=1234)
    plan.crash_at("workflow.step", when=lambda workflow, unit:
                  int(workflow.decision.epoch_number) == crash_epoch)
    with faults.active(plan):
        report = run_supervised(lambda: build(4, snap_dir), str(snap_dir),
                                fast_policy())
    assert plan.log, "the armed crash never fired"
    assert report.restarts == 1
    assert report.resumed_from, "supervisor did not resume from a snapshot"
    hist = report.workflow.decision.metrics_history
    assert hist == full_hist, (crash_epoch, hist, full_hist)


def test_supervisor_rejects_corrupt_newest_snapshot(tmp_path):
    """ISSUE 2 acceptance: a corrupted NEWEST snapshot is detected by
    checksum and the supervisor falls back to the previous valid one."""
    full = build(4, tmp_path / "full")
    full.run()
    full_hist = full.decision.metrics_history

    snap_dir = tmp_path / "s"
    seed_run = build(3, snap_dir)                  # dies "mid-job" at 3
    seed_run.run()
    newest = snap_dir / "t_3.npz"
    blob = bytearray(newest.read_bytes())
    blob[len(blob) // 2:len(blob) // 2 + 128] = b"\xff" * 128
    newest.write_bytes(bytes(blob))
    assert not verify_snapshot(str(newest))

    rejected = []
    assert find_latest_valid_snapshot(str(snap_dir), rejected=rejected) \
        == str(snap_dir / "t_2.npz")
    assert rejected == [str(newest)]

    report = run_supervised(lambda: build(4, snap_dir), str(snap_dir),
                            fast_policy())
    assert str(newest) in report.rejected_snapshots
    assert report.resumed_from[0] == str(snap_dir / "t_2.npz")
    assert report.workflow.decision.metrics_history == full_hist


def test_supervisor_restart_budget_exhausts(tmp_path):
    plan = faults.FaultPlan()
    for _ in range(10):
        plan.crash_at("workflow.step", at_hit=None, once=True)
    with faults.active(plan):
        with pytest.raises(SupervisorExhausted):
            run_supervised(lambda: build(2, tmp_path), str(tmp_path),
                           fast_policy(max_restarts=2))


def test_supervisor_backoff_is_seeded_deterministic():
    a = SupervisorPolicy(seed=5)
    b = SupervisorPolicy(seed=5)
    assert [a.restart_delay(i) for i in (1, 2, 3)] == \
        [b.restart_delay(i) for i in (1, 2, 3)]


def test_watchdog_detects_injected_hang(tmp_path):
    """A hung step (no control-graph progress within step_timeout) is
    treated as a crash: the watchdog interrupts the injected hang, the
    supervisor restarts, and the final history still matches the
    uninterrupted run."""
    full = build(3, tmp_path / "full")
    full.run()
    full_hist = full.decision.metrics_history

    snap_dir = tmp_path / "hang"
    plan = faults.FaultPlan()
    plan.hang_at("workflow.step", seconds=60.0, when=lambda workflow, unit:
                 int(workflow.decision.epoch_number) == 1)
    with faults.active(plan):
        # step_timeout must sit above the worst single-step stall that is
        # NOT a hang (first-dispatch XLA compiles run ~1s on this mesh)
        report = run_supervised(
            lambda: build(3, snap_dir), str(snap_dir),
            fast_policy(step_timeout=2.0, hang_grace=5.0))
    assert plan.log and plan.log[0]["action"] == "hang"
    assert report.hang_events == 1
    assert report.restarts == 1
    assert report.workflow.decision.metrics_history == full_hist


def test_watchdog_captures_hung_stack_into_flight(tmp_path):
    """ISSUE 9 satellite: on hang detection the watchdog freezes the
    hung thread's stack (sys._current_frames) BEFORE interrupting it,
    and the flight artifact carries it — the post-mortem shows WHERE
    the step stalled (here: inside the injected hang's abort-wait in
    faults.py), not just that it did."""
    import json

    snap_dir = tmp_path / "hang"
    plan = faults.FaultPlan()
    plan.hang_at("workflow.step", seconds=60.0, when=lambda workflow, unit:
                 int(workflow.decision.epoch_number) == 1)
    with faults.active(plan):
        report = run_supervised(
            lambda: build(2, snap_dir), str(snap_dir),
            fast_policy(step_timeout=2.0, hang_grace=5.0))
    assert report.hang_events == 1
    assert report.flights, "no flight artifact dumped"
    with open(report.flights[0]) as f:
        doc = json.load(f)
    stack = doc["extra"].get("hung_stack")
    assert stack, "flight artifact carries no hung_stack"
    joined = "".join(stack)
    # the stack names the actual stall point: the injected hang's
    # cooperative wait inside the fault plan
    assert "faults.py" in joined and "_hang" in joined, joined[-2000:]


# -- NaN/Inf health guard ----------------------------------------------------

def test_health_guard_skip_batch_on_nan_loss(tmp_path):
    plan = faults.FaultPlan().nan_at("step.loss", at_hit=4)
    with faults.active(plan):
        w = build(3, health={"mode": "skip"})
        w.run()
    guard = w.health_guard
    assert plan.log, "the armed NaN never fired"
    assert guard.nan_trips == 1
    assert guard.skipped_batches == 1
    assert len(w.decision.metrics_history) == 3    # training completed
    w.stop()
    assert np.isfinite(w.forwards[0].weights.map_read()).all()
    snap = guard.snapshot()
    assert snap["mode"] == "skip" and snap["nan_trips"] == 1


def test_health_guard_skip_restores_poisoned_params(tmp_path):
    """NaN into the PARAMS (the observable effect of NaN grads): the
    poisoned pass publishes a non-finite loss, the guard restores the
    last CERTIFIED state, and training still completes with finite
    weights.  The hit lands in epoch 2 so at least two finite
    observations precede it — the double buffer needs one to capture
    and a later one to certify (an earlier hit is unrecoverable by
    design and only warns)."""
    plan = faults.FaultPlan().nan_at("step.params", at_hit=14)
    with faults.active(plan):
        w = build(3, health={"mode": "skip"})
        w.run()
    assert plan.log
    assert w.health_guard.nan_trips >= 1
    assert w.health_guard.skipped_batches >= 1
    w.stop()
    assert np.isfinite(w.forwards[0].weights.map_read()).all()
    assert np.isfinite(w.forwards[1].weights.map_read()).all()


def test_health_guard_skip_never_restores_uncertified_copy(tmp_path):
    """Double-buffer regression: the loss published at a step is a
    PRE-update forward, so the copy captured alongside a finite loss is
    not yet proven clean.  With per-minibatch metrics, poisoned params
    ride exactly one finite observation before the NaN surfaces — the
    guard must restore the older CERTIFIED copy, not the freshest one
    (a single-buffer guard restores the poison itself and wedges)."""
    plan = faults.FaultPlan().nan_at("step.params", at_hit=7)
    with faults.active(plan):
        w = build(3, health={"mode": "skip"}, defer_metrics=False)
        w.run()
    assert plan.log
    assert w.health_guard.nan_trips >= 1
    assert w.health_guard.skipped_batches >= 1
    assert len(w.decision.metrics_history) == 3
    w.stop()
    assert np.isfinite(w.forwards[0].weights.map_read()).all()
    assert np.isfinite(w.forwards[1].weights.map_read()).all()


def test_health_guard_rollback_mode(tmp_path):
    plan = faults.FaultPlan().nan_at("step.loss", at_hit=4)
    with faults.active(plan):
        w = build(3, health={"mode": "rollback",
                             "rollback": {"lr_cut": 0.5}})
        base_lr = float(w.gds[0].learning_rate)
        w.run()
    assert w.health_guard.rollbacks_forced == 1
    assert w.nn_rollback.rollback_count == 1
    assert float(w.gds[0].learning_rate) == base_lr * 0.5
    assert len(w.decision.metrics_history) == 3


def test_health_guard_counters_in_web_status():
    from znicz_tpu.web_status import WebStatus

    w = build(1, health={"mode": "skip"})
    w.run()
    status = WebStatus()
    status.register(w)
    status.register_health("trainer", w.health_guard)
    doc = status.snapshot()
    assert doc["health"]["trainer"]["nan_trips"] == 0
    assert doc["health"]["trainer"]["mode"] == "skip"


# -- progress counter (watchdog's heartbeat) ---------------------------------

def test_workflow_progress_counter_advances():
    w = build(1)
    assert w.signals_dispatched == 0
    w.run()
    assert w.signals_dispatched > 10


# -- extended chaos (slow lane: tools/chaos.sh runs it standalone) -----------

@pytest.mark.slow
def test_supervised_survives_repeated_crashes(tmp_path):
    """Three separate kills across one training job; every restart
    resumes from the newest valid snapshot and the final history is
    still bit-exact."""
    full = build(6, tmp_path / "full")
    full.run()
    full_hist = full.decision.metrics_history

    snap_dir = tmp_path / "multi"
    plan = faults.FaultPlan(seed=99)
    for epoch in (1, 3, 4):
        plan.crash_at("workflow.step",
                      when=lambda workflow, unit, e=epoch:
                      int(workflow.decision.epoch_number) == e)
    with faults.active(plan):
        report = run_supervised(lambda: build(6, snap_dir), str(snap_dir),
                                fast_policy(max_restarts=5))
    assert report.restarts == 3
    assert report.workflow.decision.metrics_history == full_hist
