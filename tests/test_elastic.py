"""Elastic multi-process training (ISSUE 9): the fleet supervisor
kills-and-resumes REAL worker processes.

The acceptance drill pins the cross-process analog of PR 2's in-process
contract: 2 CPU workers joined via ``launcher.multihost``, one
SIGKILL'd mid-epoch at a seeded step (``elastic.worker`` fault site,
armed through the ``ZNICZ_TPU_FAULT_PLAN`` worker env), supervised
resume at world size 1 AND at world size 2 — and the resumed metric
history is bit-identical to an uninterrupted run at the final world
size.  Satellites covered here: coordinator-connect retry, SIGTERM
snapshot-then-exit, rank-0-writes/all-ranks-verify snapshot election,
fault-plan env serialization, heartbeat hang detection.
"""

import glob
import importlib.util
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.launcher import (CoordinatorUnreachable, multihost,
                                wait_for_coordinator)
from znicz_tpu.observe import probe
from znicz_tpu.resilience import faults
from znicz_tpu.resilience.elastic import (ElasticExhausted, run_elastic,
                                          start_heartbeat)
from znicz_tpu.resilience.retry import RetryPolicy
from znicz_tpu.resilience.supervisor import SupervisorPolicy
from znicz_tpu.snapshotter import process_rank_world, verify_snapshot
from znicz_tpu.standard_workflow import StandardWorkflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKFLOW = os.path.join(REPO, "tools", "elastic_workflow.py")
EPOCHS = 6

#: the drill's seeded randomness (ISSUE 9 acceptance: "SIGKILL one
#: mid-epoch at a seeded step"): the kill step is drawn from a seeded
#: generator; the victim is rank 0 BY DESIGN — killing the snapshot
#: WRITER is the harder case (it also takes the jax.distributed
#: coordinator service down with it), and it makes the resume point
#: deterministic: no other rank writes, so the newest snapshot is
#: exactly the one before the victim's seeded death, immune to
#: boot/compile skew between the workers
KILL_AT_HIT = int(np.random.default_rng(1234).integers(40, 70))
VICTIM_RANK = 0                                       # the writer


def worker_env(epochs=EPOCHS, snap_dir=None):
    """Env for worker subprocesses: single local CPU device per process
    (the 8-device XLA_FLAGS override would be inherited), compile cache
    off (XLA's concurrent cache-write path is flaky on shared dirs —
    see conftest), repo importable."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["ZNICZ_TPU_COMPILE_CACHE"] = "off"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ZNICZ_TPU_ELASTIC_EPOCHS"] = str(epochs)
    if snap_dir is not None:
        env["ZNICZ_TPU_SNAP_DIR"] = str(snap_dir)
    return env


def read_history(snap_dir, rank=0):
    with open(os.path.join(str(snap_dir), f"history_{rank}.json")) as f:
        return json.load(f)["history"]


def fast_policy(**kw):
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("max_restarts", 2)
    return SupervisorPolicy(**kw)


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    yield
    faults.uninstall()


# -- fault-plan env serialization (satellite) --------------------------------

def test_fault_plan_env_roundtrip():
    plan = faults.FaultPlan(seed=9)
    plan.kill_at("elastic.worker", at_hit=33)
    plan.hang_at("workflow.step", at_hit=2, seconds=7.5, once=False)
    clone = faults.FaultPlan.from_env(plan.to_env())
    assert clone.seed == 9
    assert [(f.site, f.action, f.at_hit, f.seconds, f.once)
            for f in clone._faults] == \
        [("elastic.worker", "kill", 33, 30.0, True),
         ("workflow.step", "hang", 2, 7.5, False)]


def test_fault_plan_with_predicate_refuses_to_serialize():
    plan = faults.FaultPlan().crash_at("workflow.step",
                                       when=lambda **ctx: True)
    with pytest.raises(ValueError, match="predicate"):
        plan.to_env()


def test_fault_plan_env_install_is_loud_on_garbage(monkeypatch):
    monkeypatch.setenv(faults.PLAN_ENV_VAR, "{not json")
    with pytest.raises(ValueError, match="malformed"):
        faults.install_from_env()
    monkeypatch.delenv(faults.PLAN_ENV_VAR)
    assert faults.install_from_env() is None


def test_fault_plan_env_fires_in_subprocess(tmp_path):
    """The cross-process determinism contract: a plan serialized into a
    worker's env fires at exactly the armed hit in that process — the
    mechanism the elastic kill drill rides (jax-free, milliseconds)."""
    code = (
        "from znicz_tpu.resilience import faults\n"
        "plan = faults.install_from_env()\n"
        "assert plan is not None\n"
        "faults.fault_hook('drill.site')\n"
        "try:\n"
        "    faults.fault_hook('drill.site')\n"
        "    print('MISSED')\n"
        "except faults.FaultInjected as exc:\n"
        "    print('FIRED', exc)\n")
    env = worker_env()
    env[faults.PLAN_ENV_VAR] = \
        faults.FaultPlan(seed=3).crash_at("drill.site", at_hit=2).to_env()
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "FIRED" in out.stdout and "hit 2" in out.stdout


# -- coordinator-connect retry (satellite) -----------------------------------

def test_wait_for_coordinator_exhaustion_names_the_address():
    policy = RetryPolicy(max_attempts=3, base_delay=0.01,
                         sleep=lambda s: None)
    with pytest.raises(CoordinatorUnreachable, match="127.0.0.1:1 "):
        wait_for_coordinator("127.0.0.1:1", policy)
    assert policy.total_attempts == 3


def test_wait_for_coordinator_retries_until_listener_up():
    """The race multihost() actually loses: rank N boots before the
    rank-0 coordinator binds.  The probe retries until the listener
    appears instead of handing jax.distributed a dead address (which
    this jaxlib answers with a process abort, not an exception)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server = socket.socket()
    server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)

    def bind_late():
        time.sleep(0.3)
        server.bind(("127.0.0.1", port))
        server.listen(1)

    t = threading.Thread(target=bind_late, daemon=True)
    t.start()
    policy = RetryPolicy(max_attempts=40, base_delay=0.05, jitter=0.0)
    try:
        wait_for_coordinator(f"127.0.0.1:{port}", policy)
    finally:
        t.join()
        server.close()
    assert policy.total_retries >= 1


def test_multihost_rejects_malformed_coordinator():
    with pytest.raises(ValueError, match="host:port"):
        multihost("nonsense", num_processes=2, process_id=1)


# -- snapshot election (tentpole: rank 0 writes, all ranks verify) -----------

# one source of truth for the drill topology: the in-process election
# tests must exercise the SAME model/loader the subprocess drills run
_spec = importlib.util.spec_from_file_location("elastic_workflow",
                                               WORKFLOW)
_drill_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_drill_module)
LAYERS, LOADER = _drill_module.LAYERS, _drill_module.LOADER


def build_local(max_epochs, snap_dir, verify_timeout=0.3, seed=77):
    prng.seed_all(seed)
    w = StandardWorkflow(
        name="ElectTest", layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": max_epochs},
        snapshotter_config={"directory": str(snap_dir), "prefix": "t",
                            "only_improved": False, "keep_all": True,
                            "verify_timeout": verify_timeout})
    w.initialize(device=XLADevice())
    return w


def _published(snap_dir):
    return sorted(os.path.basename(p) for p in
                  glob.glob(os.path.join(str(snap_dir), "t_*.npz"))
                  if not p.endswith("_latest.npz"))


def test_rank_nonzero_never_writes_and_verifies_published(tmp_path,
                                                          monkeypatch):
    assert process_rank_world() == (0, 1)
    # rank 0 publishes the ground truth
    w0 = build_local(2, tmp_path)
    w0.run()
    assert _published(tmp_path) == ["t_1.npz", "t_2.npz"]
    written = {p: os.path.getmtime(os.path.join(str(tmp_path), p))
               for p in _published(tmp_path)}
    # an identical replicated rank-1 worker verifies instead of writing
    monkeypatch.setenv("ZNICZ_TPU_ELASTIC_RANK", "1")
    monkeypatch.setenv("ZNICZ_TPU_ELASTIC_WORLD", "2")
    assert process_rank_world() == (1, 2)
    w1 = build_local(2, tmp_path)
    w1.run()
    assert _published(tmp_path) == ["t_1.npz", "t_2.npz"]   # no new files
    for p, mtime in written.items():
        assert os.path.getmtime(os.path.join(str(tmp_path), p)) == mtime
    assert w1.snapshotter.verified_ok == 2
    assert w1.snapshotter.verified_failed == 0


def test_rank_nonzero_missing_snapshot_degrades_to_warning(tmp_path,
                                                           monkeypatch):
    """A dead rank 0 must not kill the verifiers: the wait times out,
    warns, and training continues (the fleet supervisor owns the
    failure)."""
    monkeypatch.setenv("ZNICZ_TPU_ELASTIC_RANK", "1")
    monkeypatch.setenv("ZNICZ_TPU_ELASTIC_WORLD", "2")
    w = build_local(2, tmp_path, verify_timeout=0.2)
    w.run()                                     # completes regardless
    assert len(w.decision.metrics_history) == 2
    assert _published(tmp_path) == []
    assert w.snapshotter.verified_failed == 2


# -- SIGTERM -> snapshot-then-exit (tentpole: launcher) ----------------------

def test_sigterm_worker_snapshots_and_exits_143(tmp_path):
    env = worker_env(epochs=200, snap_dir=tmp_path)   # far horizon
    proc = subprocess.Popen(
        [sys.executable, "-m", "znicz_tpu", WORKFLOW], env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not glob.glob(
                os.path.join(str(tmp_path), "ew_[0-9]*.npz")):
            if proc.poll() is not None:
                out, _ = proc.communicate()
                raise AssertionError(f"worker died early: {out}")
            time.sleep(0.1)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 143, out
    assert "SIGTERM" in out
    snaps = glob.glob(os.path.join(str(tmp_path), "ew_[0-9]*.npz"))
    assert snaps and all(verify_snapshot(p) for p in snaps)
    # terminated-as-asked is NOT completion: no history epilogue
    assert not os.path.exists(os.path.join(str(tmp_path),
                                           "history_0.json"))


# -- the acceptance drill ----------------------------------------------------

@pytest.fixture(scope="module")
def baseline_ws1(tmp_path_factory):
    """Uninterrupted single-process run of the drill workflow."""
    snap = tmp_path_factory.mktemp("base_ws1")
    out = subprocess.run(
        [sys.executable, "-m", "znicz_tpu", WORKFLOW],
        env=worker_env(snap_dir=snap), cwd=REPO, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 0, out.stdout
    return read_history(snap)


@pytest.fixture(scope="module")
def baseline_ws2(tmp_path_factory):
    """Uninterrupted 2-worker fleet (multihost-joined), no faults."""
    snap = tmp_path_factory.mktemp("base_ws2")
    report = run_elastic(
        [WORKFLOW], str(snap), workers=2, prefix="ew",
        policy=SupervisorPolicy(max_restarts=0, sleep=lambda s: None),
        env=worker_env(), term_grace=6.0, round_timeout=300.0)
    assert report.completed and report.restarts == 0
    h0 = read_history(snap, 0)
    if os.path.exists(os.path.join(str(snap), "history_1.json")):
        assert read_history(snap, 1) == h0, "replicated workers diverged"
    else:
        # rank 1 lagged past the straggler grace and was reaped after
        # rank 0 (the history owner) completed — still a clean round
        assert report.rounds[-1]["stragglers"] == [1]
    return h0


def test_uninterrupted_history_is_world_size_invariant(baseline_ws1,
                                                       baseline_ws2):
    """The drill workflow is replicated data-parallel: every world size
    computes the same history, which is what makes "bit-identical to an
    uninterrupted run at the final world size" one well-defined pin."""
    assert baseline_ws1 == baseline_ws2
    assert len(baseline_ws1) == EPOCHS
    # the loader is tuned so the error curve is NON-trivial: an all-zero
    # history would let a broken resume pass the bit-exactness assert
    assert any(row.get("metric_validation") for row in baseline_ws1)


@pytest.mark.parametrize("label,world_sizes", [("resume_ws1", [2, 1]),
                                               ("resume_ws2", [2, 2])])
def test_elastic_drill_seeded_kill_bit_exact_resume(tmp_path, label,
                                                    world_sizes,
                                                    baseline_ws1):
    """ISSUE 9 acceptance: 2 CPU workers, worker VICTIM_RANK SIGKILL'd
    mid-epoch at seeded step KILL_AT_HIT, fleet resumes at the new world
    size from the newest valid snapshot, and the final metric history is
    bit-identical to the uninterrupted run.  One flight artifact per
    restart; the znicz_elastic_* counters move by exactly the drill's
    event counts."""
    counts0 = probe.elastic_counts()
    snap = tmp_path / label
    plan = faults.FaultPlan(seed=1234).kill_at("elastic.worker",
                                               at_hit=KILL_AT_HIT)
    report = run_elastic(
        [WORKFLOW], str(snap), workers=2, world_sizes=world_sizes,
        prefix="ew", policy=fast_policy(),
        env=worker_env(), fault_plans={VICTIM_RANK: plan},
        term_grace=8.0, round_timeout=300.0)
    counts = probe.elastic_counts()
    assert report.completed
    assert report.restarts == 1
    assert report.world_size == world_sizes[-1]
    # the victim actually died of SIGKILL (returncode -9), mid-run
    assert any(d["cause"] == "signal" and d["code"] == -9
               for d in report.worker_deaths), report.worker_deaths
    assert len(report.resumed_from) == 1
    resumed_epoch = int(re.search(
        r"_(\d+)\.npz$", os.path.basename(report.resumed_from[0])).group(1))
    assert 0 < resumed_epoch < EPOCHS      # a genuinely mid-run snapshot
    # one flight artifact per restart, readable and elastic-stamped
    assert len(report.flights) == 1
    with open(report.flights[0]) as f:
        doc = json.load(f)
    assert doc["reason"] == "elastic_restart"
    assert doc["extra"]["world"] == 2
    # THE pin: resumed history == uninterrupted history, bit for bit
    final = read_history(snap)
    assert final == baseline_ws1, (resumed_epoch, final[:2])
    if world_sizes[-1] == 2:
        # completion is owned by rank 0: the replica either finished too
        # (identical history) or was reaped as a redundant straggler
        if os.path.exists(os.path.join(str(snap), "history_1.json")):
            assert read_history(snap, rank=1) == final
        else:
            assert report.rounds[-1]["stragglers"] == [1]
    # supervisor-side counters moved by exactly this drill's events
    assert counts["restarts"] - counts0["restarts"] == 1
    assert counts["resumes"] - counts0["resumes"] == 1
    assert counts["worker_deaths"] - counts0["worker_deaths"] >= 1
    assert counts["world_size"] == 0       # fleet down -> gauge zeroed


def test_elastic_hang_detected_by_progress_heartbeat(tmp_path,
                                                     baseline_ws1):
    """A worker whose process stays alive but whose step loop stalls
    (injected 120 s hang) is detected through the heartbeat's flat
    progress counter, killed, and the fleet resumes to a bit-exact
    completion."""
    plan = faults.FaultPlan(seed=7).hang_at("elastic.worker", at_hit=45,
                                            seconds=120.0)
    report = run_elastic(
        [WORKFLOW], str(tmp_path), workers=1, spmd=False, prefix="ew",
        policy=fast_policy(), env=worker_env(), fault_plans={0: plan},
        term_grace=1.0, progress_timeout=3.0, heartbeat_timeout=60.0,
        round_timeout=300.0)
    assert report.completed
    assert report.restarts == 1
    assert report.hang_events == 1
    assert read_history(tmp_path) == baseline_ws1


def test_supervisor_env_plan_is_scrubbed_from_workers(tmp_path,
                                                      baseline_ws1):
    """A fault plan in the SUPERVISOR'S environment must not leak into
    the workers: hit counters reset per process, so an inherited seeded
    kill would re-fire after every resume and the fleet could never
    complete.  With the scrub, this kill-at-hit-1 plan in the ambient
    env is inert and the fleet completes in one clean round."""
    env = worker_env()
    env[faults.PLAN_ENV_VAR] = \
        faults.FaultPlan().kill_at("elastic.worker", at_hit=1).to_env()
    report = run_elastic(
        [WORKFLOW], str(tmp_path), workers=1, spmd=False, prefix="ew",
        policy=fast_policy(max_restarts=0), env=env,
        round_timeout=300.0)
    assert report.completed and report.restarts == 0
    assert read_history(tmp_path) == baseline_ws1


def test_boot_hang_detected_by_boot_timeout(tmp_path):
    """A worker that wedges BEFORE its first step (where the progress
    watch is deliberately blind: a long first compile looks identical)
    is caught by the boot_timeout layer."""
    wedge = tmp_path / "wedge.py"
    wedge.write_text("import time\n"
                     "def run(load, main):\n"
                     "    time.sleep(300)\n")
    with pytest.raises(ElasticExhausted):
        run_elastic([str(wedge)], str(tmp_path / "s"), workers=1,
                    spmd=False, policy=fast_policy(max_restarts=0),
                    env=worker_env(), term_grace=1.0,
                    boot_timeout=8.0, round_timeout=120.0)


def test_elastic_cli_rejects_bad_fault_plan(capsys):
    from znicz_tpu.resilience.elastic import elastic_main

    with pytest.raises(SystemExit):
        elastic_main(["--snap-dir", "/tmp/x",
                      "--fault-plan", "nope", "wf.py"])
    with pytest.raises(SystemExit):
        elastic_main(["--snap-dir", "/tmp/x",
                      "--fault-plan", "0={not json", "wf.py"])
    err = capsys.readouterr().err
    assert "RANK=JSON" in err or "bad plan JSON" in err


def test_elastic_budget_exhausts(tmp_path):
    """A worker command that always dies spends the budget and raises —
    with a flight artifact per failed round (jax-free worker: python -c
    exit 3, so the whole soak is fast)."""
    report_dir = tmp_path / "runs"
    with pytest.raises(ElasticExhausted, match="gave up"):
        run_elastic(["--definitely-not-a-real-flag"], str(tmp_path),
                    workers=1, spmd=False,
                    policy=fast_policy(max_restarts=1),
                    run_dir=str(report_dir), env=worker_env(),
                    round_timeout=60.0)
    flights = glob.glob(os.path.join(str(report_dir), "flight_*.json"))
    assert len(flights) == 2               # one per failed round


def test_goodput_ledger_accounts_supervisor_wall(tmp_path, baseline_ws1):
    """ISSUE 20: across a seeded SIGKILL/restart drill the goodput
    ledger's categories tile the supervisor's wall time (productive +
    lost + snapshot + idle ≈ wall), the drill genuinely loses the
    killed round's post-snapshot remainder, and the ledger survives
    into the restart flight artifact as its own "goodput" plane."""
    plan = faults.FaultPlan(seed=77).kill_at("elastic.worker",
                                             at_hit=KILL_AT_HIT)
    t0 = time.monotonic()
    report = run_elastic(
        [WORKFLOW], str(tmp_path), workers=1, spmd=False, prefix="ew",
        policy=fast_policy(), env=worker_env(), fault_plans={0: plan},
        term_grace=2.0, round_timeout=300.0)
    wall = time.monotonic() - t0
    assert report.completed and report.restarts == 1
    assert read_history(tmp_path) == baseline_ws1    # resume still exact
    good = report.goodput
    assert set(good["totals"]) == {"productive", "lost", "snapshot",
                                   "idle"}
    rank0 = good["per_rank"]["0"]
    accounted = sum(rank0.values())
    # THE pin: the monotonic-cursor ledger tiles the supervisor's wall
    # (slack only for aggregator setup before the ledger starts and the
    # return path after its final flush)
    assert abs(accounted - wall) <= max(0.05 * wall, 2.0), (rank0, wall)
    assert rank0["productive"] > 0.0
    # the killed round ran PAST its newest snapshot before dying — that
    # remainder is the drill's genuine lost compute
    assert rank0["lost"] > 0.0, rank0
    assert all(v >= 0.0 for v in rank0.values())
    assert 0.0 < good["ratio"] <= 1.0
    # the probe families carry the same accounting (cumulative across
    # the process, so >= this drill's figures)
    totals = probe.goodput_totals()
    assert totals["productive"] >= rank0["productive"] - 1e-6
    assert totals["lost"] >= rank0["lost"] - 1e-6
    # the restart flight artifact embeds the ledger-at-failure
    assert report.flights
    with open(report.flights[0]) as f:
        doc = json.load(f)
    plane = doc["planes"]["goodput"]
    assert plane["per_rank"]["0"]["productive"] > 0.0
    assert set(plane["totals"]) == {"productive", "lost", "snapshot",
                                    "idle"}


# -- heartbeat plumbing ------------------------------------------------------

def test_heartbeat_thread_writes_progress(tmp_path):
    path = str(tmp_path / "hb")
    values = iter([3, 17, 17, 29])
    start_heartbeat(path, interval=0.02,
                    progress=lambda: next(values, 29))
    deadline = time.monotonic() + 10
    seen = set()
    while time.monotonic() < deadline and 29 not in seen:
        try:
            with open(path) as f:
                ts_text, _, progress = f.read().strip().partition(" ")
            float(ts_text)
            seen.add(int(progress))
        except (OSError, ValueError):
            pass
        time.sleep(0.01)
    assert 29 in seen, seen
