"""Serving-fleet tests (ISSUE 13): the front-end router (least-loaded
pick, bounded retry on idempotent admission failures, streaming relay
with the synthesized-terminal guarantee, X-Request-Id propagation),
the liveness/readiness split on both worker planes, the SLO autoscaler
(deterministic ticks over a fake pool), the rolling-update state
machine, and the acceptance chaos drill: a REAL 2-worker fleet under
threaded traffic rolls onto a new package while a seeded fault plan
SIGKILLs one worker mid-rollout — zero admitted requests lost, every
stream exactly one terminal event, the fleet converges on the new
fingerprint.

In-process tests ride tiny KVDecoder-backed GenerateServers (the
test_generate convention); only the drill spawns real worker
processes.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from znicz_tpu import observe
from znicz_tpu.observe import flight
from znicz_tpu.resilience import faults
from znicz_tpu.serve.continuous import ContinuousBatcher
from znicz_tpu.serve.server import GenerateServer, ServeServer

N_LAYERS, D, HEADS, FF = 2, 32, 4, 64
CHARMAP = list("abcdefghijklmnopqrstuvwxyz .,!?")


@pytest.fixture(autouse=True)
def _clean_globals():
    yield
    faults.uninstall()
    flight.configure()
    observe.set_enabled(True)


@pytest.fixture(scope="module")
def params():
    from znicz_tpu.parallel.params import init_params

    return init_params(np.random.default_rng(3), N_LAYERS, D, HEADS,
                       FF, len(CHARMAP))


def _gen_server(params, package_info=None, slots=2):
    from znicz_tpu.serve.kvcache import KVDecoder

    dec = KVDecoder(params, heads=HEADS, max_len=32, batch=slots)
    server = GenerateServer(ContinuousBatcher(dec), charmap=CHARMAP,
                            package_info=package_info)
    server.start()
    return server


def _pool(tmp_path, **kw):
    from znicz_tpu.fleet import WorkerPool

    pkg = tmp_path / "pool_pkg.npz"
    pkg.write_bytes(b"not a real package, fingerprint fodder")
    return WorkerPool(str(pkg), plane="generate", **kw)


def _post(url, doc, headers=(), timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json", **dict(headers)})
    return urllib.request.urlopen(req, timeout=timeout)


def _stream(url, doc, headers=(), timeout=60):
    with _post(url, doc, headers=headers, timeout=timeout) as r:
        return r.headers.get("X-Request-Id"), \
            [json.loads(line) for line in r]


def _settled(read, want, timeout=5.0):
    """Poll ``read()`` until it equals ``want`` — terminal ledger
    updates land a beat after the last byte reaches the client."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = read()
        if got == want:
            return got
        time.sleep(0.02)
    return read()


# -- satellite: liveness vs readiness split ----------------------------------

def test_generate_readiness_split_and_fingerprint(params):
    fp = {"sha256": "cafe" * 16, "file": "lm.npz", "bytes": 7}
    server = _gen_server(params, package_info=fp)
    base = f"http://127.0.0.1:{server.port}"
    try:
        with urllib.request.urlopen(base + "/livez", timeout=5) as r:
            assert r.status == 200
        with urllib.request.urlopen(base + "/readyz", timeout=5) as r:
            doc = json.load(r)
            assert r.status == 200 and doc["status"] == "ready"
            assert doc["package"] == fp
        assert json.loads(urllib.request.urlopen(
            base + "/", timeout=5).read())["package"] == fp
        # draining: readiness drops, liveness stays up
        server.batcher.stop(drain=True)
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/readyz", timeout=5)
        assert exc.value.code == 503
        assert json.loads(exc.value.read())["status"] == "draining"
        with urllib.request.urlopen(base + "/livez", timeout=5) as r:
            assert r.status == 200       # alive: do NOT replace me
    finally:
        server.stop()


def test_serve_readiness_split(params):
    del params
    server = ServeServer(lambda x: x * 2.0, max_batch=4,
                         package_info={"sha256": "00", "file": "f",
                                       "bytes": 1})
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        with urllib.request.urlopen(base + "/livez", timeout=5) as r:
            assert r.status == 200
        with urllib.request.urlopen(base + "/readyz", timeout=5) as r:
            assert json.load(r)["package"]["sha256"] == "00"
    finally:
        server.stop()


def test_request_id_honored_end_to_end(params):
    """A router-minted X-Request-Id must be adopted by the worker (not
    re-minted) on both planes, so cross-process spans share a track."""
    server = _gen_server(params)
    try:
        rid, lines = _stream(
            f"http://127.0.0.1:{server.port}/generate",
            {"prompt": "ab", "max_tokens": 2},
            headers=(("X-Request-Id", "feed-123"),))
        assert rid == "feed-123"
        assert lines[-1]["done"] is True
        from znicz_tpu.observe import TRACER
        from znicz_tpu.observe.federation import request_track

        track = request_track("feed-123")
        spans = [e for e in TRACER.export_dict()["traceEvents"]
                 if e.get("args") and e["args"].get("rid") == "feed-123"]
        assert spans and all(e["tid"] == track for e in spans)
    finally:
        server.stop()


# -- router: pick / retry / relay --------------------------------------------

def test_router_least_loaded_pick_and_exclude(tmp_path):
    from znicz_tpu.fleet import FleetRouter, NoReadyWorker

    pool = _pool(tmp_path)
    try:
        a = pool.adopt("http://127.0.0.1:1")
        b = pool.adopt("http://127.0.0.1:2")
        c = pool.adopt("http://127.0.0.1:3")
        router = FleetRouter(pool)
        a.ready, b.ready, c.ready = True, True, True
        a.depth, b.depth, c.depth = 5.0, 1.0, 3.0
        assert router.pick() is b
        b.inflight = 9                  # in-flight covers the scrape gap
        assert router.pick() is c
        c.retiring = True               # a draining worker leaves
        assert router.pick() is a       # rotation immediately
        assert router.pick(exclude={a.rank}) is b
        with pytest.raises(NoReadyWorker):
            router.pick(exclude={a.rank, b.rank})
    finally:
        pool.aggregator.close()


def test_router_retries_admission_failures_only(params, tmp_path):
    """503 queue-full and connection-refused move to another worker;
    a worker VERDICT (400) is relayed verbatim, never retried."""
    from znicz_tpu.fleet import FleetRouter

    class Refusing(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            body = json.dumps({"error": "queue full"}).encode()
            self.send_response(503)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    refuser = ThreadingHTTPServer(("127.0.0.1", 0), Refusing)
    threading.Thread(target=refuser.serve_forever, daemon=True).start()
    good = _gen_server(params)
    pool = _pool(tmp_path)
    router = None
    try:
        w_dead = pool.adopt("http://127.0.0.1:1")       # refused conn
        w_503 = pool.adopt(
            f"http://127.0.0.1:{refuser.server_address[1]}")
        w_good = pool.adopt(f"http://127.0.0.1:{good.port}")
        for w in (w_dead, w_503, w_good):
            w.ready = True
        # force pick order dead -> 503 -> good
        w_dead.depth, w_503.depth, w_good.depth = 0.0, 1.0, 2.0
        router = FleetRouter(pool, max_retries=2)
        port = router.start()
        rid, lines = _stream(f"http://127.0.0.1:{port}/generate",
                             {"prompt": "ab", "max_tokens": 2})
        assert lines[-1].get("done") and "error" not in lines[-1]
        snap = _settled(
            lambda: {k: router.snapshot()[k]
                     for k in ("retries", "completed")},
            {"retries": 2, "completed": 1})
        assert snap == {"retries": 2, "completed": 1}
        # a worker verdict must NOT be retried: unknown chars -> one 400
        w_dead.ready = w_503.ready = False
        before = router.snapshot()["retries"]
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(f"http://127.0.0.1:{port}/generate",
                  {"prompt": "éé", "max_tokens": 2})
        assert exc.value.code == 400
        assert router.snapshot()["retries"] == before
    finally:
        if router is not None:
            router.stop()
        refuser.shutdown()
        refuser.server_close()
        good.stop()
        pool.aggregator.close()


def test_router_rejects_when_rotation_empty(tmp_path):
    from znicz_tpu.fleet import FleetRouter

    pool = _pool(tmp_path)
    router = FleetRouter(pool, max_retries=1)
    port = router.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(f"http://127.0.0.1:{port}/predict", {"input": [[0.0]]})
        assert exc.value.code == 503
        assert exc.value.headers["Retry-After"] == "1"
        snap = router.snapshot()
        assert snap["rejected"] == 1 and snap["admitted"] == 0
        # router readiness mirrors rotation emptiness
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/readyz",
                                   timeout=5)
        assert exc.value.code == 503
    finally:
        router.stop()
        pool.aggregator.close()


def test_router_synthesizes_terminal_on_broken_stream(tmp_path):
    """A worker that dies mid-stream (the chaos shape) must still leave
    the client with EXACTLY ONE terminal event — synthesized by the
    router, since the worker can no longer honor its contract."""
    from znicz_tpu.fleet import FleetRouter

    class Breaking(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.end_headers()
            for tok in (1, 2):
                self.wfile.write(
                    (json.dumps({"token": tok}) + "\n").encode())
                self.wfile.flush()
            # die without a terminal line (SIGKILL closes sockets
            # without ceremony)
            self.wfile.close()

    breaker = ThreadingHTTPServer(("127.0.0.1", 0), Breaking)
    threading.Thread(target=breaker.serve_forever, daemon=True).start()
    pool = _pool(tmp_path)
    router = FleetRouter(pool)
    try:
        w = pool.adopt(f"http://127.0.0.1:{breaker.server_address[1]}")
        w.ready = True
        port = router.start()
        _, lines = _stream(f"http://127.0.0.1:{port}/generate",
                           {"prompt": "ab", "max_tokens": 8})
        terminals = [ln for ln in lines if ln.get("done")]
        assert len(terminals) == 1 and "error" in terminals[0]
        assert [ln["token"] for ln in lines if "token" in ln] == [1, 2]
        assert _settled(lambda: router.snapshot()["failed"], 1) == 1
    finally:
        router.stop()
        breaker.shutdown()
        breaker.server_close()
        pool.aggregator.close()


def test_router_metric_families_live(params, tmp_path):
    from znicz_tpu.fleet import FleetRouter

    good = _gen_server(params)
    pool = _pool(tmp_path)
    router = FleetRouter(pool)
    try:
        w = pool.adopt(f"http://127.0.0.1:{good.port}")
        w.ready = True
        port = router.start()
        _stream(f"http://127.0.0.1:{port}/generate",
                {"prompt": "ab", "max_tokens": 2})
        prom = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.prom",
            timeout=5).read().decode()
        for family in ("znicz_router_requests_total",
                       "znicz_router_proxy_seconds",
                       "znicz_router_inflight",
                       "znicz_router_workers_ready",
                       "znicz_fleet_scale_workers"):
            assert family in prom, f"{family} missing"
    finally:
        router.stop()
        good.stop()
        pool.aggregator.close()


# -- autoscaler: deterministic control ---------------------------------------

class _FakeWorker:
    def __init__(self, rank):
        self.rank = rank
        self.ready = True
        self.retiring = False


class _FakePool:
    """The five-method pool surface Autoscaler declares."""

    def __init__(self, n=1):
        self.workers_ = [_FakeWorker(i) for i in range(n)]
        self._next = n
        self.events = []

    def worker_count(self):
        return len(self.workers_)

    def ready_workers(self):
        return [w for w in self.workers_
                if w.ready and not w.retiring]

    def ready_count(self):
        return len(self.ready_workers())

    def spawn(self, event=None, env_extra=None):
        w = _FakeWorker(self._next)
        self._next += 1
        self.workers_.append(w)
        self.events.append(("spawn", event))
        return w

    def wait_ready(self, worker, timeout_s=None,
                   expect_fingerprint=None):
        return True

    def retire(self, worker, drain=True, event=None, wait=True):
        worker.retiring = True
        self.workers_.remove(worker)
        self.events.append(("retire", event))
        return True

    def reap(self, worker):
        return True


def _scaler_fixture(queue_depth_box, n=1, **kw):
    from znicz_tpu.fleet import Autoscaler
    from znicz_tpu.observe.federation import FleetAggregator

    agg = FleetAggregator(min_refresh_s=0.0, stale_s=1e9)
    agg.add_source(0, lambda: (
        "# TYPE znicz_generate_queue_depth gauge\n"
        f"znicz_generate_queue_depth {queue_depth_box[0]}\n"))
    pool = _FakePool(n=n)
    scaler = Autoscaler(pool, agg, queue_high=8.0, breach_for_s=2.0,
                        cooldown_s=10.0, idle_down_s=20.0, **kw)
    return agg, pool, scaler


def test_autoscaler_scales_up_on_breach_with_cooldown():
    depth = [20.0]
    agg, pool, scaler = _scaler_fixture(depth, n=1, min_workers=1,
                                        max_workers=3)
    try:
        assert scaler.tick(now=1000.0) is None      # breach starts
        assert scaler.tick(now=1001.0) is None      # for_s not met
        assert scaler.tick(now=1003.0) == "up"      # continuous breach
        assert pool.worker_count() == 2
        assert scaler.tick(now=1005.0) is None      # cooldown holds
        assert scaler.tick(now=1014.0) == "up"      # still breaching
        assert pool.worker_count() == 3
        assert scaler.tick(now=1030.0) is None      # at max_workers
        assert pool.events == [("spawn", "up"), ("spawn", "up")]
    finally:
        agg.close()


def test_autoscaler_scales_down_after_idle_window_only():
    depth = [0.0]
    agg, pool, scaler = _scaler_fixture(depth, n=3, min_workers=1,
                                        max_workers=3)
    try:
        assert scaler.tick(now=2000.0) is None      # idle window opens
        assert scaler.tick(now=2010.0) is None      # 10s < idle_down_s
        depth[0] = 3.0                              # a burst (below the
        assert scaler.tick(now=2015.0) is None      # breach level)...
        depth[0] = 0.0                              # ...resets the
        assert scaler.tick(now=2016.0) is None      # hysteresis window
        assert scaler.tick(now=2030.0) is None      # 14s idle again
        assert scaler.tick(now=2037.0) == "down"    # 21s idle: retire 1
        assert pool.worker_count() == 2
        assert scaler.tick(now=2048.0) is None      # fresh window gates
        assert scaler.tick(now=2069.0) == "down"    # the next retire
        assert pool.worker_count() == 1
        assert scaler.tick(now=2095.0) is None      # min_workers floor
        assert pool.events == [("retire", "down"), ("retire", "down")]
    finally:
        agg.close()


def test_autoscaler_validates_bounds():
    from znicz_tpu.fleet import Autoscaler
    from znicz_tpu.observe.federation import FleetAggregator

    agg = FleetAggregator(min_refresh_s=0.0)
    try:
        with pytest.raises(ValueError):
            Autoscaler(_FakePool(), agg, min_workers=3, max_workers=2)
    finally:
        agg.close()


# -- rolling update: state machine over a fake pool --------------------------

class _RolloutPool(_FakePool):
    """Fake pool with the package/fingerprint surface rollout drives."""

    def __init__(self, n=2):
        super().__init__(n=n)
        self.package = "old.npz"
        self.fp = {"sha256": "old"}
        self.gate_ok = True
        for w in self.workers_:
            w.fingerprint = {"sha256": "old"}
            w.gone = False
            w.live = True
            w.proc = object()

    def set_package(self, package):
        self.package = package
        self.fp = {"sha256": f"fp:{os.path.basename(package)}"}
        return self.fp

    def workers(self):
        return list(self.workers_)

    def spawn(self, event=None, env_extra=None):
        w = super().spawn(event=event)
        w.fingerprint = dict(self.fp)   # boots the CURRENT package
        w.gone = False
        w.live = True
        w.proc = object()
        return w

    def wait_ready(self, worker, timeout_s=None,
                   expect_fingerprint=None):
        if not self.gate_ok:
            return False
        if expect_fingerprint is not None:
            return worker.fingerprint.get("sha256") == \
                expect_fingerprint.get("sha256")
        return True

    def retire(self, worker, drain=True, event=None, wait=True):
        worker.retiring = True
        self.events.append(("retire", event))
        if wait:
            return self.reap(worker)
        return True

    def reap(self, worker):
        worker.gone = True
        worker.live = False
        if worker in self.workers_:
            self.workers_.remove(worker)
        self.events.append(("reap", worker.rank))
        return True

    def probe_once(self):
        """The real probe loop's replace-on-unexpected-death shape."""
        for w in list(self.workers_):
            if not w.live and not w.retiring:
                w.gone = True
                self.workers_.remove(w)
                self.spawn(event="replace")


def test_rollout_one_at_a_time_and_converges():
    from znicz_tpu.fleet import RollingUpdate

    pool = _RolloutPool(n=2)
    ru = RollingUpdate(pool, converge_timeout_s=5.0)
    report = ru.run("new.npz")
    assert report["state"] == "done" and report["adopted"] == 2
    assert {w.fingerprint["sha256"] for w in pool.workers()} == \
        {"fp:new.npz"}
    # strict one-at-a-time interleave: retire(0), spawn, reap(0),
    # retire(1), spawn, reap(1) — never two old workers down at once
    kinds = [e[0] for e in pool.events]
    assert kinds == ["retire", "spawn", "reap", "retire", "spawn",
                     "reap"]
    assert ru.status()["history"][-1]["sha256"] == "fp:new.npz"


def test_rollout_skips_already_dead_worker():
    """A worker SIGKILL'd mid-rollout is converged through its crash
    replacement (which boots the NEW package — set_package flipped
    first), not re-rolled."""
    from znicz_tpu.fleet import RollingUpdate

    pool = _RolloutPool(n=2)
    pool.workers_[1].live = False       # the chaos victim: the fake
    #                                     probe loop replaces it during
    #                                     converge, on the new package
    ru = RollingUpdate(pool, converge_timeout_s=5.0)
    report = ru.run("new.npz")
    assert report["adopted"] == 1       # victim skipped, not adopted
    outcomes = [s["outcome"] for s in report["steps"]]
    assert "already_dead" in outcomes
    assert ("spawn", "replace") in pool.events
    assert {w.fingerprint["sha256"] for w in pool.workers()} == \
        {"fp:new.npz"}


def test_rollout_gate_failure_fails_safe():
    from znicz_tpu.fleet import RollingUpdate, RolloutError

    pool = _RolloutPool(n=2)
    pool.gate_ok = False                # replacements never gate ready
    ru = RollingUpdate(pool, converge_timeout_s=1.0)
    with pytest.raises(RolloutError):
        ru.run("bad.npz")
    status = ru.status()
    assert status["state"] == "failed" and status["error"]
    # only the FIRST target was touched — the rest keep serving
    untouched = [w for w in pool.workers()
                 if w.fingerprint["sha256"] == "old"]
    assert len(untouched) == 1


def test_rollout_refuses_overlap():
    from znicz_tpu.fleet import RollingUpdate

    pool = _RolloutPool(n=1)
    ru = RollingUpdate(pool)
    ru._state["state"] = "rolling"
    with pytest.raises(ValueError):
        ru.run("new.npz")


# -- the acceptance chaos drill (real processes) -----------------------------

def _build_pkg(tmp_path, seed, name):
    from znicz_tpu.parallel.params import init_params
    from znicz_tpu.utils.export import export_lm

    p = init_params(np.random.default_rng(seed), N_LAYERS, D, HEADS,
                    FF, len(CHARMAP))
    path = str(tmp_path / f"{name}.npz")
    export_lm(p, path, heads=HEADS, charmap=CHARMAP, name=name)
    return path


def test_rollout_chaos_drill_zero_lost_requests(tmp_path):
    """The ISSUE 13 acceptance pin: N=2 real workers, continuous
    threaded traffic through the router, a full rolling weight update
    with a seeded SIGKILL (fault plan, ``generate.step``) landing on a
    worker mid-rollout.  Every admitted stream gets exactly one
    terminal event, the fleet converges on the new package's
    fingerprint, and steady-state decode recompiles nothing."""
    from znicz_tpu.fleet import FleetRouter, RollingUpdate, WorkerPool
    from znicz_tpu.utils.naming import package_fingerprint

    pkg_a = _build_pkg(tmp_path, 7, "lm_a")
    pkg_b = _build_pkg(tmp_path, 8, "lm_b")
    fp_b = package_fingerprint(pkg_b)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ZNICZ_TPU_COMPILE_CACHE="off")
    pool = WorkerPool(pkg_a, plane="generate",
                      worker_args=("--slots", "2", "--max-len", "48"),
                      env=env, run_dir=str(tmp_path / "fleet"),
                      probe_interval_s=0.25)
    router = None
    stop_traffic = threading.Event()
    results = []        # (kind, detail) per attempted request
    res_lock = threading.Lock()
    try:
        pool.spawn()
        # the seeded chaos victim: SIGKILL its own pid at the 25th
        # decode step — under the drill's continuous traffic that lands
        # squarely inside the rollout window (traffic only starts with
        # the rollout; worker 0 drains first, so the steps concentrate
        # here)
        plan = faults.FaultPlan(seed=13).kill_at("generate.step",
                                                 at_hit=25)
        pool.spawn(env_extra={faults.PLAN_ENV_VAR: plan.to_env()})
        assert pool.wait_all_ready(timeout_s=240), \
            f"workers never ready: {pool.snapshot()}"
        pool.start_probes()
        router = FleetRouter(pool, max_retries=2)
        port = router.start()
        rollout = RollingUpdate(pool, converge_timeout_s=240.0)

        def client(cid):
            rng = np.random.default_rng(cid)
            while not stop_traffic.is_set():
                prompt = "".join(
                    CHARMAP[i] for i in rng.integers(
                        0, 26, size=int(rng.integers(2, 6))))
                try:
                    _, lines = _stream(
                        f"http://127.0.0.1:{port}/generate",
                        {"prompt": prompt, "max_tokens": 6,
                         "timeout_s": 30}, timeout=90)
                except urllib.error.HTTPError as exc:
                    exc.read()
                    with res_lock:      # never admitted — not lost
                        results.append(("rejected", exc.code))
                    time.sleep(0.05)
                    continue
                except Exception as exc:  # noqa: BLE001 — a silent
                    with res_lock:        # stream IS a lost request
                        results.append(("broken", repr(exc)))
                    continue
                terminals = [ln for ln in lines if ln.get("done")]
                with res_lock:
                    if len(terminals) != 1:
                        results.append(("bad_terminal", lines))
                    elif "error" in terminals[0]:
                        results.append(("errored", terminals[0]))
                    else:
                        results.append(("completed", len(lines) - 1))

        threads = [threading.Thread(target=client, args=(c,),
                                    daemon=True) for c in range(4)]
        for t in threads:
            t.start()
        try:
            report = rollout.run(pkg_b)
        finally:
            time.sleep(1.0)             # a tail of traffic post-roll
            stop_traffic.set()
            for t in threads:
                t.join(timeout=120)
        assert report["state"] == "done", report
        # the workers the rollout retired drained CLEAN (exit 0, every
        # admitted request completed) — only the chaos victim may die
        reaps = [s for s in report["steps"]
                 if s["outcome"] in ("drained", "killed")]
        assert reaps and all(s["outcome"] == "drained"
                             for s in reaps), report
        # the seeded kill actually landed and was replaced on the NEW
        # package by the probe loop
        assert pool.replacements >= 1, pool.snapshot()
        # convergence: every live worker reports pkg_b's fingerprint
        pool.probe_once()
        fps = {(w.fingerprint or {}).get("sha256")
               for w in pool.workers()}
        assert fps == {fp_b["sha256"]}, pool.snapshot()
        # THE pin: no admitted request lost — every stream either
        # completed or carried exactly one terminal error; nothing
        # broke silently, nothing double-terminated
        with res_lock:
            kinds = {}
            for kind, _ in results:
                kinds[kind] = kinds.get(kind, 0) + 1
        assert kinds.get("broken", 0) == 0, (kinds, results[-10:])
        assert kinds.get("bad_terminal", 0) == 0, (kinds, results[-10:])
        assert kinds.get("completed", 0) >= 10, kinds
        # the router ledger closes: admitted == one terminal each
        assert _settled(
            lambda: (lambda s: s["admitted"] - s["completed"] -
                     s["failed"] - s["client_gone"])(router.snapshot()),
            0) == 0, router.snapshot()
        # steady state on the new fleet: a fresh request streams clean
        # and decode compiles nothing further
        stats0 = [json.loads(urllib.request.urlopen(
            w.base + "/metrics", timeout=10).read())["decoder"]
            ["compile_count"] for w in pool.ready_workers()]
        _, lines = _stream(f"http://127.0.0.1:{port}/generate",
                           {"prompt": "hello", "max_tokens": 4})
        assert lines[-1].get("done") and "error" not in lines[-1]
        stats1 = [json.loads(urllib.request.urlopen(
            w.base + "/metrics", timeout=10).read())["decoder"]
            ["compile_count"] for w in pool.ready_workers()]
        assert stats0 == stats1, (stats0, stats1)
    finally:
        stop_traffic.set()
        if router is not None:
            router.stop()
        pool.stop()
