"""ISSUE 14 — train-while-serve: the continuous-learning subsystem.

Covers the feedback spool's crash-safety + exactly-once cursor, the
streaming loader's determinism and snapshot replay, publish/adopt
machinery, the fleet-status satellite, the fingerprint cache
satellite, and the ACCEPTANCE overlap chaos drill: one trainer + two
serve workers on one box, training, serving, a seeded mid-stream
trainer SIGKILL and a seeded worker SIGKILL all overlapping a
publish-triggered rollout — ledger closes exactly, fleet converges on
the trainer's newest fingerprint, the resumed trainer's history is
bit-identical to an uninterrupted run, steady-state compile delta 0.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.learn.bridge import AdoptionBridge
from znicz_tpu.learn.publish import (latest_manifest, manifest_path,
                                     publish_package)
from znicz_tpu.learn.spool import (FeedbackSpool, SpoolGone, SpoolReader,
                                   SpoolTimeout, initial_cursor,
                                   list_segments, read_cursor_file)
from znicz_tpu.loader.spool import SpoolSequenceLoader
from znicz_tpu.observe import REGISTRY

CHARMAP = list("abcdefgh ")


def _fill_spool(directory, n=120, seed=7, lo=10, hi=40):
    sp = FeedbackSpool(directory)
    rng = np.random.default_rng(seed)
    for i in range(n):
        sp.append_generate(
            f"r{i}", rng.integers(0, len(CHARMAP), 6).tolist(),
            rng.integers(0, len(CHARMAP), int(rng.integers(lo, hi)))
            .tolist())
    sp.close()
    return sp


def _counter_value(name: str) -> float:
    snap = REGISTRY.snapshot_flat(skip_zero=False)
    return sum(v for k, v in snap.items() if k.startswith(name))


# ---------------------------------------------------------------------------
# spool primitives
# ---------------------------------------------------------------------------

def test_spool_round_trip_exactly_once(tmp_path):
    spool = str(tmp_path / "spool")
    _fill_spool(spool, n=10)
    reader = SpoolReader(spool)
    c0 = initial_cursor(spool)
    recs, c1 = reader.read(c0, 10, wait_s=1.0)
    assert [r["rid"] for r in recs] == [f"r{i}" for i in range(10)]
    # exactly-once replay from a saved cursor
    again, c1b = reader.read(dict(c0), 10, wait_s=1.0)
    assert again == recs and c1b == c1
    # split reads land on the same cursor
    a, ca = reader.read(dict(c0), 4, wait_s=1.0)
    b, cb = reader.read(ca, 6, wait_s=1.0)
    assert a + b == recs and cb == c1
    # nothing more: bounded wait raises, never blocks forever
    with pytest.raises(SpoolTimeout):
        reader.read(c1, 1, wait_s=0.1)


def test_spool_torn_final_line_skipped_counted_replayed(tmp_path):
    """Satellite: a SIGKILL-torn final line is skipped with a counted
    ``znicz_learn_spool_torn_total``, never a loader crash, and the
    durable cursor replays exactly once."""
    spool = str(tmp_path / "spool")
    _fill_spool(spool, n=9)
    seg = os.path.join(spool, "seg_00000000.jsonl")
    size = os.path.getsize(seg)
    with open(seg, "r+b") as f:          # SIGKILL mid-append: the last
        f.truncate(size - 5)             # record loses its tail
    # a respawned worker appends AFTER the fragment (newline-prefix
    # protocol: only the fragment is lost, not the new record)
    FeedbackSpool(spool).append_generate("r9", [1], [2, 3])
    torn0 = _counter_value("znicz_learn_spool_torn_total")
    reader = SpoolReader(spool)
    c0 = initial_cursor(spool)
    recs, c1 = reader.read(c0, 9, wait_s=1.0)
    assert [r["rid"] for r in recs] == \
        [f"r{i}" for i in range(8)] + ["r9"]
    assert _counter_value("znicz_learn_spool_torn_total") == torn0 + 1
    # exactly-once: the replay sees the identical record set (the torn
    # skip is part of the byte-stable stream)
    again, c1b = reader.read(dict(c0), 9, wait_s=1.0)
    assert again == recs and c1b == c1


def test_spool_rotation_retention_and_gone(tmp_path):
    spool = str(tmp_path / "spool")
    sp = FeedbackSpool(spool, segment_bytes=200, max_segments=3)
    for i in range(40):
        sp.append_generate(f"r{i}", list(range(8)), list(range(8)))
    segs = list_segments(spool)
    assert len(segs) <= 4 and segs[0] > 0   # old segments dropped
    assert _counter_value(
        "znicz_learn_spool_dropped_segments_total") > 0
    reader = SpoolReader(spool)
    with pytest.raises(SpoolGone):
        reader.read({"seg": 0, "offset": 0, "records": 0}, 1,
                    wait_s=0.1)
    # a cold start anchors at the oldest RETAINED segment
    recs, _ = reader.read(initial_cursor(spool), 3, wait_s=1.0)
    assert len(recs) == 3


def test_spool_end_cursor_canonical_across_later_rotation(tmp_path):
    """Review regression: a read satisfied exactly at a segment's end
    must return (seg, end) whether or not a later rotation exists —
    else a snapshot's stored span fails its replay check after the
    spool rolls (a false 'spool bytes changed' on every elastic
    resume)."""
    spool = str(tmp_path / "spool")
    _fill_spool(spool, n=6)
    reader = SpoolReader(spool)
    recs, end = reader.read(initial_cursor(spool), 6, wait_s=1.0)
    assert end["seg"] == 0
    # the spool rolls AFTER the snapshot stored `end`
    tiny = FeedbackSpool(spool, segment_bytes=1, max_segments=4)
    tiny.append_generate("later", [1], [2])
    assert list_segments(spool)[-1] > 0
    again, end2 = reader.read(initial_cursor(spool), 6, wait_s=1.0)
    assert again == recs and end2 == end, \
        "end cursor drifted across the rotation"


def test_spool_lag_does_not_recount_torn(tmp_path):
    """Review regression: lag probes re-scan the backlog every epoch
    and must not re-increment the torn counter for the same dead
    line."""
    spool = str(tmp_path / "spool")
    _fill_spool(spool, n=4)
    seg = os.path.join(spool, "seg_00000000.jsonl")
    with open(seg, "r+b") as f:
        f.truncate(os.path.getsize(seg) - 3)
    FeedbackSpool(spool).append_generate("after", [1], [2, 3])
    reader = SpoolReader(spool)
    before = _counter_value("znicz_learn_spool_torn_total")
    assert reader.lag(initial_cursor(spool)) == 4   # 3 intact + after
    assert reader.lag(initial_cursor(spool)) == 4
    assert _counter_value("znicz_learn_spool_torn_total") == before
    # the consuming read still counts it (once per consume)
    reader.read(initial_cursor(spool), 4, wait_s=1.0)
    assert _counter_value("znicz_learn_spool_torn_total") == before + 1


def test_spool_multi_writer_shared_order(tmp_path):
    """Two writer processes (simulated: two instances) interleave
    whole records into one total order both readers agree on."""
    spool = str(tmp_path / "spool")
    a, b = FeedbackSpool(spool), FeedbackSpool(spool)
    for i in range(20):
        (a if i % 2 else b).append_generate(f"w{i}", [i], [i, i])
    reader = SpoolReader(spool)
    recs, c = reader.read(initial_cursor(spool), 20, wait_s=1.0)
    assert sorted(r["rid"] for r in recs) == \
        sorted(f"w{i}" for i in range(20))
    again, c2 = reader.read(initial_cursor(spool), 20, wait_s=1.0)
    assert [r["rid"] for r in again] == [r["rid"] for r in recs]
    assert c2 == c


# ---------------------------------------------------------------------------
# streaming loader
# ---------------------------------------------------------------------------

def _make_loader(spool, **kw):
    kw.setdefault("seq_len", 8)
    kw.setdefault("records_per_epoch", 4)
    kw.setdefault("minibatch_size", 4)
    kw.setdefault("wait_timeout_s", 2.0)
    ld = SpoolSequenceLoader(None, spool_dir=spool, charmap=CHARMAP,
                             **kw)
    ld._common_init()
    return ld


def test_loader_deterministic_stream(tmp_path):
    spool = str(tmp_path / "spool")
    _fill_spool(spool, n=200)
    prng.seed_all(3)
    first = _make_loader(spool)
    seen = []
    for _ in range(30):
        first._serve()
        seen.append((first.minibatch_data.mem.copy(),
                     first.epoch_number, first.minibatch_size))
    assert first.epoch_number > 2          # crossed epoch boundaries
    prng.seed_all(3)
    second = _make_loader(spool)
    for i in range(30):
        second._serve()
        assert np.array_equal(second.minibatch_data.mem, seen[i][0])
        assert second.epoch_number == seen[i][1]
        assert second.minibatch_size == seen[i][2]
    # the durable cursor file tracks the epoch floor
    cur = read_cursor_file(spool)
    assert cur is not None and cur["records"] > 0


def test_loader_snapshot_replay_exactly_once(tmp_path):
    """The snapshot cursor re-reads the exact stream span: a resumed
    loader serves bit-identical batches (the elastic-resume
    exactly-once pin, loader-level)."""
    spool = str(tmp_path / "spool")
    _fill_spool(spool, n=200)
    prng.seed_all(3)
    ld = _make_loader(spool)
    state, pr = None, None
    while state is None:
        ld._serve()
        if ld.epoch_ended and ld.epoch_number == 2:
            state = ld.state_dict()
            pr = prng.state_dict()
    post = []
    for _ in range(10):
        ld._serve()
        post.append((ld.minibatch_data.mem.copy(),
                     ld.minibatch_labels.mem.copy()))
    prng.seed_all(3)                      # cold boot, then restore
    resumed = _make_loader(spool)
    prng.load_state_dict(pr)
    resumed.load_state_dict(state)
    for i in range(10):
        resumed._serve()
        assert np.array_equal(resumed.minibatch_data.mem, post[i][0])
        assert np.array_equal(resumed.minibatch_labels.mem, post[i][1])


def test_loader_restore_refuses_changed_charmap(tmp_path):
    spool = str(tmp_path / "spool")
    _fill_spool(spool, n=40)
    prng.seed_all(3)
    ld = _make_loader(spool)
    ld._serve()
    state = ld.state_dict()
    state["charmap"] = list("xy")
    with pytest.raises(ValueError, match="charmap"):
        ld.load_state_dict(state)


def test_loader_pipelined_matches_sync(tmp_path):
    """The spool loader through the async BatchPrefetcher serves the
    byte-identical stream (the ISSUE 4 determinism contract extended
    to the streaming dataset)."""
    from znicz_tpu.pipeline import attach_prefetcher

    spool = str(tmp_path / "spool")
    _fill_spool(spool, n=200)
    prng.seed_all(9)
    sync = _make_loader(spool)
    stream = []
    for _ in range(24):
        sync._serve()
        stream.append((sync.minibatch_data.mem.copy(),
                       sync.epoch_number, sync.minibatch_size))
    prng.seed_all(9)
    piped = _make_loader(spool)
    attach_prefetcher(piped, depth=2)
    try:
        for i in range(24):
            piped.numpy_run()
            assert np.array_equal(piped.minibatch_data.mem,
                                  stream[i][0]), f"batch {i} diverged"
            assert piped.epoch_number == stream[i][1]
    finally:
        piped.stop()


def test_records_trained_counter_moves(tmp_path):
    spool = str(tmp_path / "spool")
    _fill_spool(spool, n=40)
    before = _counter_value("znicz_learn_records_trained_total")
    prng.seed_all(3)
    _make_loader(spool)
    assert _counter_value("znicz_learn_records_trained_total") >= \
        before + 4


# ---------------------------------------------------------------------------
# fingerprint cache (satellite)
# ---------------------------------------------------------------------------

def test_package_fingerprint_cached_until_file_changes(tmp_path,
                                                       monkeypatch):
    import hashlib

    from znicz_tpu.utils import naming

    pkg = tmp_path / "pkg.npz"
    pkg.write_bytes(b"a" * 4096)
    calls = {"n": 0}
    real = hashlib.sha256

    def counting_sha256(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(naming.hashlib, "sha256", counting_sha256)
    fp1 = naming.package_fingerprint(str(pkg))
    fp2 = naming.package_fingerprint(str(pkg))
    assert fp1 == fp2 and calls["n"] == 1   # probe polling: no re-hash
    # an atomic replace (mtime/size move) re-hashes
    tmp = tmp_path / "pkg.npz.tmp"
    tmp.write_bytes(b"b" * 8192)
    os.replace(tmp, pkg)
    fp3 = naming.package_fingerprint(str(pkg))
    assert calls["n"] == 2
    assert fp3["sha256"] != fp1["sha256"] and fp3["bytes"] == 8192
    # mutation returned to the caller must not poison the cache
    fp3["sha256"] = "poison"
    assert naming.package_fingerprint(str(pkg))["sha256"] != "poison"


# ---------------------------------------------------------------------------
# publish + bridge + fleet-status satellite
# ---------------------------------------------------------------------------

class _FakeStep:
    """export_lm stand-in: writes deterministic bytes per 'epoch'."""

    def __init__(self):
        self.exports = 0

    def export_lm(self, path):
        self.exports += 1
        with open(path, "wb") as f:
            f.write(b"model-bytes-%d" % self.exports)
        return path


def test_publish_manifest_and_counter(tmp_path):
    step = _FakeStep()
    before = _counter_value("znicz_learn_publishes_total")
    doc = publish_package(step, str(tmp_path / "pub"), epoch=2, seq=1)
    assert os.path.isfile(doc["package"])
    assert os.path.isfile(manifest_path(str(tmp_path / "pub")))
    read = latest_manifest(str(tmp_path / "pub"))
    assert read == doc
    assert read["fingerprint"]["sha256"]
    assert _counter_value("znicz_learn_publishes_total") == before + 1


def test_publish_retention_bounds_the_dir(tmp_path):
    """Review regression: superseded packages are unlinked past
    ``keep`` — a long-running trainer must not grow the disk one dead
    package per K epochs.  The manifest's current package always
    survives."""
    pub = str(tmp_path / "pub")
    step = _FakeStep()
    for epoch in range(2, 13, 2):
        doc = publish_package(step, pub, epoch=epoch,
                              seq=epoch // 2, keep=2)
    pkgs = sorted(n for n in os.listdir(pub)
                  if n.startswith("lm_e") and n.endswith(".npz"))
    assert pkgs == ["lm_e00010.npz", "lm_e00012.npz"]
    assert os.path.isfile(doc["package"])
    assert latest_manifest(pub)["epoch"] == 12


class _FakePool:
    def __init__(self, sha):
        self.expected_fingerprint = {"sha256": sha}


class _FakeRollout:
    def __init__(self, pool, outcome="done"):
        self.pool = pool
        self.outcome = outcome
        self.started: list = []
        self.rolling = False

    def start(self, package):
        from znicz_tpu.utils.naming import package_fingerprint

        self.started.append(package)
        if self.outcome == "done":
            self.pool.expected_fingerprint = \
                package_fingerprint(package)

    def join(self, timeout_s=0):
        return {"state": self.outcome, "error": None
                if self.outcome == "done" else "gate failed"}

    def status(self):
        return {"state": "idle"}


def test_bridge_adopts_each_new_fingerprint_once(tmp_path):
    pub = str(tmp_path / "pub")
    step = _FakeStep()
    publish_package(step, pub, epoch=2, seq=1)
    pool = _FakePool("old-sha")
    rollout = _FakeRollout(pool)
    bridge = AdoptionBridge(pub, pool, rollout, poll_s=0.05)
    report = bridge.poll_once()
    assert report["state"] == "done" and len(rollout.started) == 1
    assert bridge.adoptions == 1 and bridge.last_adoption_s is not None
    # same manifest again: fleet already on it — no second rollout
    assert bridge.poll_once() is None and len(rollout.started) == 1
    # a NEW publish adopts again
    publish_package(step, pub, epoch=4, seq=2)
    assert bridge.poll_once()["state"] == "done"
    assert bridge.adoptions == 2


def test_bridge_failed_adoption_waits_for_new_publish(tmp_path):
    pub = str(tmp_path / "pub")
    step = _FakeStep()
    publish_package(step, pub, epoch=2, seq=1)
    pool = _FakePool("old-sha")
    rollout = _FakeRollout(pool, outcome="failed")
    bridge = AdoptionBridge(pub, pool, rollout, poll_s=0.05)
    assert bridge.poll_once()["state"] == "failed"
    assert bridge.failures == 1
    # the same bad sha is not retried (no rollout storm)...
    assert bridge.poll_once() is None and len(rollout.started) == 1
    # ...but a fresh publish is
    publish_package(step, pub, epoch=4, seq=2)
    bridge.poll_once()
    assert len(rollout.started) == 2


def test_fleet_status_surfaces_package_and_rollout_top_level(tmp_path):
    """Satellite: /fleet/status.json carries the fleet's current
    package fingerprint + rollout state top-level, so the learn bridge
    and operators gate adoption on one field."""
    from znicz_tpu.fleet.rollout import RollingUpdate
    from znicz_tpu.fleet.router import FleetRouter
    from znicz_tpu.fleet.workers import WorkerPool

    pkg = tmp_path / "pkg.npz"
    pkg.write_bytes(b"some-package-bytes")
    pool = WorkerPool(str(pkg), run_dir=str(tmp_path / "fleet"))
    try:
        router = FleetRouter(pool)
        router.attach_rollout(RollingUpdate(pool))
        doc = pool.aggregator.status_doc()
        assert doc["package"]["fingerprint"]["sha256"] == \
            pool.expected_fingerprint["sha256"]
        assert doc["package"]["converged"] is False   # no workers yet
        assert doc["rollout"]["state"] == "idle"
        assert "steps" not in doc["rollout"]
        # providers must not break the JSON surface
        json.dumps(doc)
        # a dead provider degrades to an error block, never a crash
        pool.aggregator.register_status_provider(
            "learn", lambda: (_ for _ in ()).throw(RuntimeError("x")))
        assert "error" in pool.aggregator.status_doc()["learn"]
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# the ACCEPTANCE overlap chaos drill
# ---------------------------------------------------------------------------

def _export_base_package(tmp) -> str:
    from znicz_tpu.parallel.params import init_params
    from znicz_tpu.utils.export import export_lm

    params = init_params(np.random.default_rng(31), 2, 32, 4, 64,
                         len(CHARMAP))
    pkg = os.path.join(tmp, "lm.npz")
    export_lm(params, pkg, heads=4, charmap=CHARMAP, name="lm_v1")
    return pkg


def _trainer_argv(spool, pkg, pub):
    return ["znicz_tpu/learn/trainer_workflow.py",
            "-o", f"root.learn.spool_dir={spool}",
            "-o", f"root.learn.package={pkg}",
            "-o", f"root.learn.publish_dir={pub}",
            "-o", "root.learn.publish_every=2",
            "-o", "root.learn.max_epochs=4",
            "-o", "root.learn.records_per_epoch=6",
            # drill traffic records are 8 ids (2 prompt + 6 tokens):
            # the window (seq_len + 1) must fit inside one record
            "-o", "root.learn.seq_len=6",
            # 3 minibatches per epoch, so the run is long enough in
            # control-graph signals for the seeded at_hit=40 kill to
            # land mid-epoch (1 mb/epoch finished under the trigger)
            "-o", "root.learn.minibatch_size=2",
            "-o", "root.learn.wait_timeout_s=120",
            "--random-seed", "11"]


def _post_stream(base, prompt, max_tokens=6, timeout=90):
    req = urllib.request.Request(
        base + "/generate",
        data=json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                         "timeout_s": 60}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return [json.loads(raw) for raw in r]


def test_overlap_chaos_drill_train_serve_kill_rollout(tmp_path):
    """ISSUE 14 acceptance: trainer + 2 serve workers, training,
    serving, a seeded trainer SIGKILL and a seeded worker SIGKILL
    overlapping publish-triggered rollouts — zero lost admitted
    requests, fleet converges on the newest published fingerprint,
    resumed trainer history bit-identical to an uninterrupted run,
    post-drill steady-state compile delta 0."""
    from znicz_tpu.fleet.rollout import RollingUpdate
    from znicz_tpu.fleet.router import FleetRouter
    from znicz_tpu.fleet.workers import WorkerPool
    from znicz_tpu.resilience import faults
    from znicz_tpu.resilience.elastic import run_elastic
    from znicz_tpu.resilience.supervisor import SupervisorPolicy

    tmp = str(tmp_path)
    pkg = _export_base_package(tmp)
    spool = os.path.join(tmp, "spool")
    os.makedirs(spool)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ZNICZ_TPU_COMPILE_CACHE="off")
    pool = WorkerPool(
        pkg, plane="generate", env=env,
        worker_args=("--slots", "2", "--max-len", "48",
                     "--feedback-spool", spool),
        run_dir=os.path.join(tmp, "fleet"))
    router = None
    stop_traffic = threading.Event()
    results: list = []
    res_lock = threading.Lock()
    trainer_box: dict = {}
    try:
        pool.spawn()
        # the chaos victim: a seeded generate.step SIGKILL sized to
        # land while traffic + the publish-triggered rollout overlap
        victim_plan = faults.FaultPlan(seed=13).kill_at(
            "generate.step", at_hit=90).to_env()
        pool.spawn(env_extra={faults.PLAN_ENV_VAR: victim_plan})
        assert pool.wait_all_ready(timeout_s=240), pool.snapshot()
        pool.start_probes()
        router = FleetRouter(pool)
        rollout = RollingUpdate(pool)
        router.attach_rollout(rollout)
        port = router.start()
        base = f"http://127.0.0.1:{port}"
        pub = os.path.join(tmp, "publish")
        bridge = AdoptionBridge(pub, pool, rollout, poll_s=0.25)
        bridge.start()

        def client(cid: int) -> None:
            n = 0
            while not stop_traffic.wait(0.05):
                n += 1
                try:
                    lines = _post_stream(base,
                                         "ab" if cid % 2 else "cd")
                except urllib.error.HTTPError as exc:
                    exc.read()
                    with res_lock:
                        results.append(("rejected", exc.code))
                    continue
                except Exception as exc:  # noqa: BLE001 — judged below
                    with res_lock:
                        results.append(("broken", repr(exc)))
                    continue
                terminals = [ln for ln in lines if ln.get("done")]
                with res_lock:
                    if len(terminals) != 1 or lines[-1] != terminals[0]:
                        results.append(("bad_terminal", lines))
                    elif "error" in terminals[0]:
                        results.append(("errored", terminals[0]))
                    else:
                        results.append(("completed", n))

        threads = [threading.Thread(target=client, args=(c,),
                                    daemon=True) for c in range(3)]
        for t in threads:
            t.start()

        def train() -> None:
            # seeded mid-epoch SIGKILL; the supervisor resumes from
            # the newest snapshot with the spool cursor inside it
            plan = faults.FaultPlan(seed=5).kill_at("elastic.worker",
                                                    at_hit=40)
            try:
                trainer_box["report"] = run_elastic(
                    _trainer_argv(spool, pkg, pub),
                    os.path.join(tmp, "snaps"), workers=1, spmd=False,
                    env=env, fault_plans={0: plan},
                    run_dir=os.path.join(tmp, "trainer"),
                    policy=SupervisorPolicy(max_restarts=3))
            except Exception as exc:  # noqa: BLE001 — judged below
                trainer_box["error"] = exc

        trainer = threading.Thread(target=train, daemon=True)
        trainer.start()
        # the loop: traffic feeds the spool, the trainer trains +
        # publishes, the bridge rolls the fleet — wait for the FINAL
        # adoption (epoch-4 publish) to converge
        deadline = time.monotonic() + 420
        while time.monotonic() < deadline:
            if "error" in trainer_box:
                raise AssertionError(
                    f"trainer supervision failed: "
                    f"{trainer_box['error']!r}")
            manifest = latest_manifest(pub)
            if "report" in trainer_box and manifest is not None and \
                    not rollout.rolling and \
                    (pool.expected_fingerprint or {}).get("sha256") == \
                    manifest["fingerprint"]["sha256"]:
                break
            time.sleep(0.5)
        else:
            raise AssertionError(
                f"loop never converged: trainer={trainer_box}, "
                f"manifest={latest_manifest(pub)}, "
                f"rollout={rollout.status()}")
        time.sleep(1.0)                   # post-adoption traffic tail
        stop_traffic.set()
        for t in threads:
            t.join(timeout=120)
        bridge.stop()

        # -- the trainer was killed AND resumed, bit-exactly ---------
        report = trainer_box["report"]
        assert report.completed and report.restarts >= 1, \
            report.as_dict()
        assert report.resumed_from, "resume never used a snapshot"
        drill_history = json.load(open(os.path.join(
            tmp, "snaps", "history_0.json")))

        # -- zero lost admitted requests -----------------------------
        with res_lock:
            kinds: dict = {}
            for kind, _ in results:
                kinds[kind] = kinds.get(kind, 0) + 1
        assert not kinds.get("broken") and \
            not kinds.get("bad_terminal"), \
            f"lost/garbled streams: {kinds}; tail {results[-6:]}"
        assert kinds.get("completed", 0) >= 8, \
            f"too little completed traffic to trust the drill: {kinds}"
        ledger = router.snapshot()
        assert ledger["admitted"] == ledger["completed"] + \
            ledger["failed"] + ledger["client_gone"], ledger

        # -- the seeded worker kill fired and was replaced -----------
        assert pool.replacements >= 1, \
            "the victim worker's seeded SIGKILL never fired"
        assert bridge.adoptions >= 1 and bridge.last_adoption_s > 0

        # -- fleet converged on the trainer's NEWEST fingerprint -----
        manifest = latest_manifest(pub)
        assert manifest["epoch"] == 4
        pool.probe_once()
        shas = {(w.fingerprint or {}).get("sha256")
                for w in pool.workers()}
        assert shas == {manifest["fingerprint"]["sha256"]}, \
            f"torn mix after the drill: {pool.snapshot()}"
        status = pool.aggregator.status_doc()
        assert status["package"]["converged"] is True

        # -- steady state: compile delta 0 ---------------------------
        def get_json(url):
            with urllib.request.urlopen(url, timeout=15) as r:
                return json.loads(r.read())

        bases = [w.base for w in pool.workers()]
        before = [get_json(b + "/metrics")["decoder"]["compile_count"]
                  for b in bases]
        for _ in range(3):
            lines = _post_stream(base, "ef", max_tokens=4)
            assert lines[-1].get("done") and "error" not in lines[-1]
        after = [get_json(b + "/metrics")["decoder"]["compile_count"]
                 for b in bases]
        assert before == after, f"steady state recompiled: " \
                                f"{before} -> {after}"
    finally:
        stop_traffic.set()
        if router is not None:
            router.stop()
        pool.stop()

    # -- resumed history bit-identical to an uninterrupted run -------
    # the spool is frozen now (workers stopped): a clean trainer over
    # the SAME stream from the same origin must reproduce the drill
    # trainer's history exactly — the spool's append-time total order
    # is what makes "the next R records after cursor C" time-invariant
    from znicz_tpu.resilience.elastic import run_elastic
    from znicz_tpu.resilience.supervisor import SupervisorPolicy

    clean = run_elastic(
        _trainer_argv(spool, pkg, os.path.join(tmp, "publish_clean")),
        os.path.join(tmp, "snaps_clean"), workers=1, spmd=False,
        env=env, run_dir=os.path.join(tmp, "trainer_clean"),
        policy=SupervisorPolicy(max_restarts=1))
    assert clean.completed and clean.restarts == 0
    clean_history = json.load(open(os.path.join(
        tmp, "snaps_clean", "history_0.json")))
    assert drill_history == clean_history, (
        f"resumed trainer history diverged from the uninterrupted "
        f"run:\n{drill_history}\nvs\n{clean_history}")
    # and the published weights are byte-identical too
    clean_manifest = latest_manifest(os.path.join(tmp,
                                                  "publish_clean"))
    assert clean_manifest["fingerprint"]["sha256"] == \
        latest_manifest(os.path.join(tmp, "publish"))["fingerprint"][
            "sha256"]


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_learn_cli_rejects_bad_args(tmp_path, capsys):
    from znicz_tpu.learn.cli import learn_main

    pkg = tmp_path / "lm.npz"
    pkg.write_bytes(b"x")
    assert learn_main([str(pkg), "--workers", "0"]) == 2
    assert "--workers" in capsys.readouterr().err


def test_main_dispatches_learn(monkeypatch):
    import znicz_tpu.__main__ as main_mod

    called = {}

    def fake_learn_main(argv):
        called["argv"] = argv
        return 0

    import znicz_tpu.learn.cli as cli_mod
    monkeypatch.setattr(cli_mod, "learn_main", fake_learn_main)
    assert main_mod.main(["learn", "pkg.npz", "--workers", "2"]) == 0
    assert called["argv"] == ["pkg.npz", "--workers", "2"]
