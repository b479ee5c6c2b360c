"""serve/ subsystem tests: bucketed engine (zero steady-state
recompiles), micro-batcher contract (coalescing, backpressure,
deadlines, oversize chunking, graceful drain — every admitted request
gets exactly one response), serving metrics, the HTTP front end, and the
``python -m znicz_tpu serve`` CLI."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from znicz_tpu.serve import (BatchEngine, DeadlineExceeded, MicroBatcher,
                             QueueFull, ServeServer, ServingMetrics,
                             bucket_sizes)


class RecordingModel:
    """``x * 2`` callable that records every batch shape it executes."""

    def __init__(self, delay_s: float = 0.0, input_shape=(3,)) -> None:
        self.shapes = []
        self.delay_s = delay_s
        self.input_shape = tuple(input_shape)
        self.meta = {"name": "recording"}

    def __call__(self, x):
        self.shapes.append(np.asarray(x).shape)
        if self.delay_s:
            time.sleep(self.delay_s)
        return np.asarray(x) * 2.0


def make_batcher(delay_s=0.0, max_batch=8, max_wait_ms=1.0, **kw):
    model = RecordingModel(delay_s=delay_s)
    engine = BatchEngine(model, max_batch=max_batch)
    return MicroBatcher(engine, max_wait_ms=max_wait_ms, **kw), model


# -- engine ------------------------------------------------------------------

def test_bucket_sizes_powers_of_two_plus_ceiling():
    assert bucket_sizes(16) == (1, 2, 4, 8, 16)
    assert bucket_sizes(12) == (1, 2, 4, 8, 12)
    assert bucket_sizes(1) == (1,)
    with pytest.raises(ValueError):
        bucket_sizes(0)


def test_engine_pads_to_buckets_and_slices_back():
    model = RecordingModel()
    engine = BatchEngine(model, max_batch=8)
    for n in (1, 3, 5, 8, 3):
        x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
        y = engine.run(x)
        assert y.shape == (n, 3)
        np.testing.assert_allclose(y, x * 2)
    # executed shapes are bucket shapes, and a repeated bucket reuses it
    assert [s[0] for s in model.shapes] == [1, 4, 8, 8, 4]
    assert engine.compile_count == 3            # buckets 1, 4, 8
    assert engine.run_count == 5
    assert engine.rows_served == 1 + 3 + 5 + 8 + 3


def test_engine_warmup_then_zero_recompiles():
    jax = pytest.importorskip("jax")
    traces = []

    @jax.jit
    def model(x):
        traces.append(x.shape)          # trace-time only: one per compile
        return x * 3.0

    engine = BatchEngine(model, max_batch=8, input_shape=(4,))
    assert engine.warmup() == len(engine.buckets) == 4
    assert len(traces) == 4             # jit really compiled once a bucket
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 5, 7, 8, 6, 4):
        x = rng.normal(size=(n, 4)).astype(np.float32)
        np.testing.assert_allclose(engine.run(x), x * 3.0, rtol=1e-6)
    assert engine.compile_count == 4    # flat after warmup...
    assert len(traces) == 4             # ...and jit agrees: no recompiles


def test_engine_rejects_oversize_and_bad_shape():
    engine = BatchEngine(RecordingModel(), max_batch=4)
    with pytest.raises(ValueError, match="max_batch"):
        engine.run(np.zeros((5, 3), np.float32))
    with pytest.raises(ValueError, match="input shape"):
        engine.run(np.zeros((2, 7), np.float32))


def test_engine_skips_padding_for_dynamic_backends():
    model = RecordingModel()
    model.static_shapes = False         # the NativeForward contract
    engine = BatchEngine(model, max_batch=8)
    engine.run(np.zeros((3, 3), np.float32))
    assert [s[0] for s in model.shapes] == [3]   # exact size, no pad
    assert engine.compile_count == 0


# -- micro-batcher contract --------------------------------------------------

def test_batcher_coalesces_requests_queued_behind_a_batch():
    batcher, model = make_batcher(delay_s=0.05, max_batch=8)
    try:
        # the worker picks up the first request alone; the rest arrive
        # while the engine sleeps and must coalesce into ONE batch
        first = batcher.submit(np.full((1, 3), 0.0, np.float32))
        time.sleep(0.02)
        rest = [batcher.submit(np.full((1, 3), float(i + 1), np.float32))
                for i in range(5)]
        outs = [f.result(timeout=10) for f in [first] + rest]
        for i, out in enumerate(outs):
            np.testing.assert_allclose(out, np.full((1, 3), 2.0 * i))
        sizes = {int(k): v
                 for k, v in batcher.metrics.snapshot()
                 ["batch_size_histogram"].items()}
        assert max(sizes) >= 5          # the stragglers rode one batch
    finally:
        batcher.stop()


def test_deadline_expired_request_gets_timeout_error_not_silent_drop():
    batcher, _ = make_batcher(delay_s=0.15, max_batch=8)
    try:
        slow = batcher.submit(np.zeros((1, 3), np.float32))
        time.sleep(0.02)                # worker is inside the 150 ms run
        doomed = batcher.submit(np.zeros((1, 3), np.float32),
                                timeout_s=0.05)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        assert slow.result(timeout=10).shape == (1, 3)
        snap = batcher.metrics.snapshot()
        assert snap["timed_out"] == 1
        assert snap["completed"] == 1
    finally:
        batcher.stop()


def test_queue_full_rejects_immediately():
    batcher, _ = make_batcher(delay_s=0.1, max_batch=8, max_queue=1)
    try:
        served = batcher.submit(np.zeros((1, 3), np.float32))
        time.sleep(0.03)                # worker popped it, engine busy
        queued = batcher.submit(np.zeros((1, 3), np.float32))
        t0 = time.monotonic()
        with pytest.raises(QueueFull):
            batcher.submit(np.zeros((1, 3), np.float32))
        assert time.monotonic() - t0 < 0.5      # fast failure, no wait
        assert batcher.metrics.snapshot()["rejected"] == 1
        for f in (served, queued):
            assert f.result(timeout=10) is not None
    finally:
        batcher.stop()


def test_oversize_request_is_chunked_and_reassembled_in_order():
    batcher, model = make_batcher(max_batch=4)
    try:
        x = np.arange(11 * 3, dtype=np.float32).reshape(11, 3)
        out = batcher.predict(x)
        assert out.shape == (11, 3)
        np.testing.assert_allclose(out, x * 2)  # rows in submission order
        assert max(s[0] for s in model.shapes) <= 4
        snap = batcher.metrics.snapshot()
        assert snap["admitted"] == 1 and snap["completed"] == 1
    finally:
        batcher.stop()


def test_shutdown_drains_inflight_requests():
    batcher, _ = make_batcher(delay_s=0.03, max_batch=1)
    futures = [batcher.submit(np.full((1, 3), float(i), np.float32))
               for i in range(5)]
    batcher.stop(drain=True)            # rejects new, services queued
    for i, f in enumerate(futures):
        np.testing.assert_allclose(f.result(timeout=0.1),
                                   np.full((1, 3), 2.0 * i))
    with pytest.raises(QueueFull):
        batcher.submit(np.zeros((1, 3), np.float32))


def test_stop_without_drain_fails_queued_loudly():
    batcher, _ = make_batcher(delay_s=0.1, max_batch=1)
    first = batcher.submit(np.zeros((1, 3), np.float32))
    time.sleep(0.03)
    queued = batcher.submit(np.zeros((1, 3), np.float32))
    batcher.stop(drain=False)
    assert first.result(timeout=10) is not None     # in-flight finishes
    with pytest.raises(QueueFull):
        queued.result(timeout=10)


def test_expired_chunk_at_queue_head_cannot_overflow_the_batch():
    """Coalescing must size-check the chunk it actually takes, not the
    queue head: an expired head chunk being skipped must not let a
    larger chunk behind it push the batch past max_batch."""
    batcher, _ = make_batcher(delay_s=0.1, max_batch=8)
    try:
        busy = batcher.submit(np.zeros((1, 3), np.float32))
        time.sleep(0.02)                # worker inside the 100 ms run
        c1 = batcher.submit(np.full((5, 3), 1.0, np.float32))
        doomed = batcher.submit(np.zeros((2, 3), np.float32),
                                timeout_s=0.03)     # expires mid-run
        c3 = batcher.submit(np.full((8, 3), 3.0, np.float32))
        np.testing.assert_allclose(c1.result(timeout=10),
                                   np.full((5, 3), 2.0))
        np.testing.assert_allclose(c3.result(timeout=10),
                                   np.full((8, 3), 6.0))
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        assert busy.result(timeout=10) is not None
        snap = batcher.metrics.snapshot()
        assert snap["errors"] == 0      # no oversize batch hit the engine
        assert max(int(k) for k in snap["batch_size_histogram"]) <= 8
    finally:
        batcher.stop()


def test_mismatched_widths_fail_the_batch_not_the_worker():
    """With no declared input_shape the width check happens at
    concatenation; a mismatched batch must fail its requests and leave
    the worker serving."""
    def bare_model(x):                  # no input_shape attribute
        time.sleep(0.03)
        return np.asarray(x) * 2.0

    engine = BatchEngine(bare_model, max_batch=8)
    batcher = MicroBatcher(engine, max_wait_ms=5.0)
    try:
        busy = batcher.submit(np.zeros((1, 3), np.float32))
        time.sleep(0.01)                # next two coalesce behind it
        a = batcher.submit(np.zeros((1, 3), np.float32))
        b = batcher.submit(np.zeros((1, 5), np.float32))
        assert busy.result(timeout=10) is not None
        failures = 0
        for f in (a, b):
            try:
                f.result(timeout=10)
            except Exception:
                failures += 1
        assert failures >= 1            # the mismatch surfaced loudly
        out = batcher.predict(np.ones((1, 3), np.float32))   # still alive
        np.testing.assert_allclose(out, np.full((1, 3), 2.0))
    finally:
        batcher.stop()


def test_cancelled_future_does_not_kill_the_worker():
    batcher, _ = make_batcher(delay_s=0.05, max_batch=8)
    try:
        busy = batcher.submit(np.zeros((1, 3), np.float32))
        time.sleep(0.01)                # worker inside the engine run
        gone = batcher.submit(np.full((1, 3), 5.0, np.float32))
        assert gone.cancel()            # client walks away pre-service
        assert busy.result(timeout=10) is not None
        # the worker survived servicing the cancelled chunk
        after = batcher.predict(np.full((1, 3), 7.0, np.float32))
        np.testing.assert_allclose(after, np.full((1, 3), 14.0))
    finally:
        batcher.stop()


def test_non_positive_timeout_is_rejected_not_infinite():
    batcher, _ = make_batcher()
    try:
        for bad in (0, -1):
            with pytest.raises(ValueError, match="timeout_s"):
                batcher.submit(np.zeros((1, 3), np.float32), timeout_s=bad)
    finally:
        batcher.stop()


def test_never_admittable_request_is_bad_input_not_backpressure():
    """A request needing more chunks than the whole queue can hold must
    fail as ValueError (HTTP 400), not a retryable-looking QueueFull."""
    batcher, _ = make_batcher(max_batch=2, max_queue=3)
    try:
        with pytest.raises(ValueError, match="never|whole queue"):
            batcher.submit(np.zeros((8, 3), np.float32))   # 4 chunks > 3
        assert batcher.metrics.snapshot()["rejected"] == 0
    finally:
        batcher.stop()


def test_engine_failure_fails_the_batch_but_not_the_batcher():
    class Flaky(RecordingModel):
        def __call__(self, x):
            if float(np.asarray(x).ravel()[0]) < 0:
                raise RuntimeError("poison batch")
            return super().__call__(x)

    engine = BatchEngine(Flaky(), max_batch=4)
    batcher = MicroBatcher(engine, max_wait_ms=1.0)
    try:
        bad = batcher.submit(np.full((1, 3), -1.0, np.float32))
        with pytest.raises(RuntimeError, match="poison"):
            bad.result(timeout=10)
        good = batcher.predict(np.full((1, 3), 1.0, np.float32))
        np.testing.assert_allclose(good, np.full((1, 3), 2.0))
        assert batcher.metrics.snapshot()["errors"] == 1
    finally:
        batcher.stop()


def test_failed_request_ledger_closes_exactly():
    """ISSUE 10 small fix: `errors` counts failed BATCHES; `failed`
    counts failed REQUESTS (whatever the cause — engine error,
    deadline, shutdown flush), so admitted == completed + failed holds
    with equality, not >=."""
    class Flaky(RecordingModel):
        def __call__(self, x):
            if float(np.asarray(x).ravel()[0]) < 0:
                raise RuntimeError("poison batch")
            return super().__call__(x)

    engine = BatchEngine(Flaky(), max_batch=4)
    batcher = MicroBatcher(engine, max_wait_ms=1.0)
    try:
        bad = batcher.submit(np.full((1, 3), -1.0, np.float32))
        with pytest.raises(RuntimeError):
            bad.result(timeout=10)      # rides its own poisoned batch
        ok = batcher.submit(np.full((1, 3), 1.0, np.float32))
        assert ok.result(timeout=10) is not None
        # a deadline lapse is also a failed request in the ledger
        busy = batcher.submit(np.full((1, 3), 2.0, np.float32))
        doomed = None
        engine.model.delay_s = 0.15
        busy2 = batcher.submit(np.full((1, 3), 3.0, np.float32))
        time.sleep(0.02)
        doomed = batcher.submit(np.full((1, 3), 4.0, np.float32),
                                timeout_s=0.05)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        for f in (busy, busy2):
            assert f.result(timeout=10) is not None
    finally:
        batcher.stop()
    snap = batcher.metrics.snapshot()
    assert snap["errors"] == 1          # one poisoned batch
    assert snap["timed_out"] == 1
    assert snap["failed"] == 2          # the poisoned + the timed out
    assert snap["admitted"] == snap["completed"] + snap["failed"]


# -- acceptance load test ----------------------------------------------------

def test_load_concurrent_clients_coalesce_with_zero_recompiles():
    """ISSUE acceptance: >= 8 threaded clients, coalesced batches > 1,
    zero engine recompiles after bucket warmup, and every admitted
    request gets exactly one correct response."""
    jax = pytest.importorskip("jax")
    traces = []

    @jax.jit
    def model(x):
        traces.append(x.shape)
        return x * 2.0

    engine = BatchEngine(model, max_batch=16, input_shape=(4,))
    engine.warmup()
    compiles_after_warmup = engine.compile_count
    traces_after_warmup = len(traces)
    batcher = MicroBatcher(engine, max_wait_ms=5.0, max_queue=256,
                           default_timeout_s=60.0)
    n_clients, per_client = 8, 20
    errors, results = [], {}

    def client(cid):
        rng = np.random.default_rng(cid)
        try:
            for i in range(per_client):
                n = int(rng.integers(1, 4))
                x = rng.normal(size=(n, 4)).astype(np.float32)
                y = batcher.predict(x)
                np.testing.assert_allclose(y, x * 2.0, rtol=1e-6)
                results[(cid, i)] = y.shape
        except Exception as exc:  # noqa: BLE001 — surface in main thread
            errors.append((cid, repr(exc)))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    batcher.stop()
    assert not errors, errors
    # exactly one response per admitted request, no drops, no duplicates
    assert len(results) == n_clients * per_client
    snap = batcher.metrics.snapshot()
    assert snap["admitted"] == snap["completed"] == n_clients * per_client
    assert snap["rejected"] == 0 and snap["timed_out"] == 0
    # real coalescing happened
    sizes = {int(k): v for k, v in snap["batch_size_histogram"].items()}
    assert max(sizes) > 1, f"no coalescing observed: {sizes}"
    # zero recompiles after warmup — engine counter AND jit trace count
    assert engine.compile_count == compiles_after_warmup
    assert len(traces) == traces_after_warmup
    assert snap["latency"]["count"] == n_clients * per_client
    assert snap["qps"] > 0


# -- metrics -----------------------------------------------------------------

def test_latency_histogram_percentiles_land_in_bucket():
    m = ServingMetrics()
    for ms in (1.2, 1.4, 1.6, 1.8, 90.0):
        m.on_complete(ms / 1000.0)
    snap = m.snapshot()["latency"]
    assert snap["count"] == 5
    assert 1.0 <= snap["p50_ms"] <= 2.0         # bucket (1, 2]
    assert 50.0 <= snap["p99_ms"] <= 100.0      # bucket (50, 100]
    assert snap["buckets_ms"]["2"] == 4
    assert snap["buckets_ms"]["100"] == 1


def test_metrics_snapshot_is_json_roundtrippable():
    m = ServingMetrics()
    m.on_admit(2)
    m.on_batch(2)
    m.on_dequeue(2)
    m.on_complete(0.003)
    doc = json.loads(json.dumps(m.snapshot()))
    assert doc["admitted"] == 1 and doc["queue_depth"] == 0
    assert doc["batch_size_histogram"] == {"2": 1}


# -- HTTP front end + web_status + CLI --------------------------------------

def _http_json(url, data=None, timeout=10):
    req = urllib.request.Request(
        url, data=None if data is None else json.dumps(data).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def test_serve_server_endpoints():
    server = ServeServer(RecordingModel(), max_batch=8, max_wait_ms=1.0)
    port = server.start()
    base = f"http://127.0.0.1:{port}"
    try:
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        out = _http_json(f"{base}/predict", {"input": x.tolist()})
        np.testing.assert_allclose(np.asarray(out["output"]), x * 2)
        assert _http_json(f"{base}/healthz")["status"] == "ok"
        snap = _http_json(f"{base}/metrics")
        assert snap["serving"]["completed"] == 1
        # 4 warmup batches (one per bucket) + the one request
        assert snap["engine"]["run_count"] == 5
        assert snap["engine"]["compile_count"] == 4
        assert snap["engine"]["buckets"] == [1, 2, 4, 8]
        meta = _http_json(f"{base}/")
        assert meta["n_requests"] == 1 and meta["max_batch"] == 8
        # malformed request -> 400; wrong path -> 404
        for path, data, code in (("/predict", {"wrong": 1}, 400),
                                 ("/nope", {"input": [[0.0] * 3]}, 404)):
            try:
                _http_json(f"{base}{path}", data)
                raise AssertionError(f"{path} accepted")
            except urllib.error.HTTPError as exc:
                assert exc.code == code
    finally:
        server.stop()


def test_serve_server_backpressure_maps_to_503():
    server = ServeServer(RecordingModel(delay_s=0.3), max_batch=1,
                         max_queue=1, max_wait_ms=1.0)
    port = server.start()
    url = f"http://127.0.0.1:{port}/predict"
    doc = {"input": [[0.0] * 3]}
    background = [threading.Thread(target=_http_json, args=(url, doc))
                  for _ in range(2)]
    try:
        background[0].start()           # worker picks this up
        time.sleep(0.1)
        background[1].start()           # sits in the queue: now full
        time.sleep(0.1)
        try:
            _http_json(url, doc)
            raise AssertionError("admitted past a full queue")
        except urllib.error.HTTPError as exc:
            assert exc.code == 503
            assert exc.headers.get("Retry-After") == "1"
    finally:
        for t in background:
            t.join(timeout=30)
        server.stop()


def test_stop_drains_before_closing_listener():
    """During ServeServer.stop(drain=True) the listener must stay up so
    /healthz reports 503 draining (load balancers bleed traffic off)
    instead of connection-refused."""
    server = ServeServer(RecordingModel(delay_s=0.3), max_batch=1,
                         max_wait_ms=1.0)
    port = server.start()
    fut = server.batcher.submit(np.zeros((1, 3), np.float32))
    time.sleep(0.05)                    # worker inside the 300 ms run
    stopper = threading.Thread(target=server.stop)
    stopper.start()
    time.sleep(0.1)                     # stop() is blocked in the drain
    try:
        _http_json(f"http://127.0.0.1:{port}/healthz")
        raise AssertionError("healthz should be 503 during drain")
    except urllib.error.HTTPError as exc:
        assert exc.code == 503
        assert json.loads(exc.read())["status"] == "draining"
    stopper.join(timeout=30)
    assert not stopper.is_alive()
    assert fut.result(timeout=1) is not None    # drained, not dropped


def test_server_rejects_conflicting_max_batch():
    engine = BatchEngine(RecordingModel(), max_batch=8)
    with pytest.raises(ValueError, match="max_batch"):
        ServeServer(engine, max_batch=128)
    server = ServeServer(engine, max_batch=8)   # matching value is fine
    assert server.engine is engine
    server.batcher.stop()


def test_web_status_reports_serving_metrics():
    from znicz_tpu.web_status import WebStatus

    server = ServeServer(RecordingModel(), max_batch=4)
    server.batcher.predict(np.zeros((1, 3), np.float32))
    ws = WebStatus().register_serving("recording", server)
    snap = ws.snapshot()
    assert snap["serving"]["recording"]["serving"]["completed"] == 1
    assert snap["serving"]["recording"]["engine"]["max_batch"] == 4
    server.batcher.stop()


def _export_tiny_package(tmp_path):
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.standard_workflow import StandardWorkflow
    from znicz_tpu.utils.export import export_forward

    prng.seed_all(23)
    w = StandardWorkflow(
        name="SrvCLI", loss_function="softmax",
        layers=[{"type": "all2all_tanh", "->": {"output_sample_shape": 8}},
                {"type": "softmax", "->": {"output_sample_shape": 3}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 3, "sample_shape": (6,), "n_train": 60,
                       "n_valid": 0, "minibatch_size": 20},
        decision_config={"max_epochs": 1})
    w.initialize(device=XLADevice())
    w.run()
    pkg = str(tmp_path / "srv_cli.npz")
    export_forward(w, pkg)
    return pkg


def test_cli_serve_smoke_over_exported_package(tmp_path, capsys):
    from znicz_tpu.__main__ import main as cli_main

    pkg = _export_tiny_package(tmp_path)
    rc = cli_main(["serve", pkg, "--port", "0", "--max-batch", "8",
                   "--smoke-test"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["smoke"] == "ok"
    # warmup compiled every bucket; the smoke request recompiled nothing
    assert doc["metrics"]["engine"]["compile_count"] == 4
    assert doc["metrics"]["serving"]["completed"] == 1


def test_cli_serve_missing_package_fails_cleanly(capsys):
    from znicz_tpu.__main__ import main as cli_main

    assert cli_main(["serve", "/nonexistent/pkg.npz"]) == 2
    assert "cannot load" in capsys.readouterr().out


# -- chaos: kill-mid-request against an AOT-booted engine (ISSUE 9) ----------

def test_chaos_kill_mid_request_aot_boot_exact_terminal_responses(tmp_path):
    """Elastic-PR satellite: an AOT-booted engine (zero-JIT,
    ``compile_count == 0``) is crashed mid-traffic by injected
    ``serve.run`` faults.  Every admitted request still gets EXACTLY ONE
    terminal response (a result or an error — never silence, never a
    duplicate), and after the drain the engine has still compiled
    nothing: crash recovery must not smuggle recompiles into the
    zero-JIT serving contract."""
    pytest.importorskip("jax")
    from znicz_tpu.resilience import faults
    from znicz_tpu.utils.export import ExportedForward, attach_aot

    pkg = _export_tiny_package(tmp_path)
    attach_aot(pkg, max_batch=8)
    fwd = ExportedForward(pkg)
    assert fwd.aot_fallback_reason is None
    engine = BatchEngine(fwd, max_batch=8, input_shape=(6,))
    assert engine.warmup() == 0                 # AOT boot: nothing to JIT
    assert engine.compile_count == 0
    batcher = MicroBatcher(engine, max_wait_ms=2.0, max_queue=256,
                           default_timeout_s=60.0)
    plan = faults.FaultPlan(seed=11)
    for hit in (4, 9, 15):                      # three seeded mid-run kills
        plan.crash_at("serve.run", at_hit=hit)
    n_clients, per_client = 6, 8
    outcomes: dict = {}
    lock = threading.Lock()

    def client(cid):
        rng = np.random.default_rng(cid)
        for i in range(per_client):
            n = int(rng.integers(1, 5))
            x = rng.normal(size=(n, 6)).astype(np.float32)
            try:
                y = batcher.predict(x)
                kind = ("ok", y.shape)
            except Exception as exc:  # noqa: BLE001 — terminal error
                kind = ("error", type(exc).__name__)
            with lock:
                # exactly-once: a duplicate terminal response would
                # overwrite and be caught by the count below
                assert (cid, i) not in outcomes
                outcomes[(cid, i)] = kind

    with faults.active(plan):
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        batcher.stop(drain=True)
    assert len(plan.log) == 3, plan.log         # every armed kill fired
    assert len(outcomes) == n_clients * per_client
    oks = sum(1 for kind in outcomes.values() if kind[0] == "ok")
    errs = sum(1 for kind in outcomes.values() if kind[0] == "error")
    assert errs >= 1 and oks >= 1
    snap = batcher.metrics.snapshot()
    # ledger closes EXACTLY (ISSUE 10 small fix): "errors" counts the 3
    # failed BATCHES; "failed" counts the REQUESTS that rode them, so
    # admitted == completed + failed with no slack — nothing timed out,
    # nothing vanished in the drain
    assert snap["errors"] == 3
    assert snap["admitted"] == snap["completed"] + snap["failed"]
    assert snap["failed"] == errs
    assert snap["failed"] >= snap["errors"]
    assert snap["timed_out"] == 0
    assert snap["completed"] >= oks             # oversize requests chunk
    # THE satellite pin: chaos + drain never compiled anything
    assert engine.compile_count == 0
    assert engine.stats()["aot_count"] >= 1
