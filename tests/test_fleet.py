"""Fleet telemetry tests (ISSUE 11): the federation parser/exporter/
aggregator, fleet-level SLO rules over the rank-merged view, the seeded
single-rank fault drill with fleet flight embedding, per-request
distributed tracing through the serving plane, trace merging, and the
concurrent-scrape soak.

jax is only touched by the tests that run a real KVDecoder (the drill,
span linking, and the scrape soak); everything else is stdlib-fast.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from znicz_tpu import observe
from znicz_tpu.observe import federation as fed
from znicz_tpu.observe import flight
from znicz_tpu.observe.registry import Registry
from znicz_tpu.resilience import faults

N_LAYERS, D, HEADS, FF, VOCAB = 2, 32, 4, 64, 31
CHARMAP = list("abcdefghijklmnopqrstuvwxyz .,!?")


@pytest.fixture(autouse=True)
def _clean_globals():
    """No leaked fault plans, flight config, or disabled plane."""
    yield
    faults.uninstall()
    flight.configure()
    observe.set_enabled(True)


@pytest.fixture(scope="module")
def decoder():
    from znicz_tpu.parallel.params import init_params
    from znicz_tpu.serve.kvcache import KVDecoder

    params = init_params(np.random.default_rng(3), N_LAYERS, D, HEADS,
                         FF, VOCAB)
    return KVDecoder(params, heads=HEADS, max_len=32, batch=2)


def _two_serve_registries():
    """Two private per-'worker' registries with the serve families the
    fleet rules watch."""
    regs = []
    for _ in range(2):
        r = Registry()
        r.gauge("znicz_serve_queue_depth", "q")
        r.histogram("znicz_serve_latency_seconds", "lat",
                    buckets=(0.01, 0.1, 1.0))
        r.counter("znicz_recompiles_total", "rc", labelnames=("fn",))
        regs.append(r)
    return regs


# -- prometheus text ingestion ------------------------------------------------

def test_parse_prometheus_round_trip():
    r = Registry()
    r.counter("znicz_a_total", "with labels",
              labelnames=("event",)).labels(event="ok").inc(3)
    r.gauge("znicz_g", "a gauge").set(7.5)
    h = r.histogram("znicz_h_seconds", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    families, samples = fed.parse_prometheus(r.render_prometheus())
    assert families["znicz_a_total"]["type"] == "counter"
    assert families["znicz_h_seconds"]["type"] == "histogram"
    assert families["znicz_g"]["help"] == "a gauge"
    flat = {f"{name}{{{inner}}}" if inner else name: v
            for _, name, inner, v in samples}
    assert flat['znicz_a_total{event="ok"}'] == 3.0
    assert flat["znicz_g"] == 7.5
    # cumulative buckets, the exposition convention
    assert flat['znicz_h_seconds_bucket{le="0.1"}'] == 1.0
    assert flat['znicz_h_seconds_bucket{le="+Inf"}'] == 2.0
    assert flat["znicz_h_seconds_count"] == 2.0
    # histogram children attach to the declared family
    assert all(fam == "znicz_h_seconds" for fam, name, _, _ in samples
               if name.startswith("znicz_h_seconds"))


def test_parse_prometheus_rejects_torn_text():
    # a scrape torn mid-line must fail loudly, never half-merge
    with pytest.raises(ValueError):
        fed.parse_prometheus("znicz_ok_total 1\nznicz_torn_total 12.3.4")
    with pytest.raises(ValueError):
        fed.parse_prometheus('znicz_unclosed{a="b" 3')


def test_parse_prometheus_foreign_exposition_shapes():
    # trailing timestamps are valid 0.0.4 (foreign exporters emit
    # them): the VALUE is the first field after the labels, never the
    # stamp — and label values may carry spaces and raw braces
    _, samples = fed.parse_prometheus(
        'znicz_x_total{a="b c",q="x}y"} 5 1700000000\n'
        "znicz_plain 2 1700000000\n")
    assert samples[0] == ("znicz_x_total", "znicz_x_total",
                          'a="b c",q="x}y"', 5.0)
    assert samples[1] == ("znicz_plain", "znicz_plain", "", 2.0)


def test_inject_rank():
    assert fed.inject_rank("", 0) == 'rank="0"'
    assert fed.inject_rank('le="0.5"', 2) == 'le="0.5",rank="2"'
    # an aggregator-of-aggregators must not double-tag
    assert fed.inject_rank('rank="1"', 2) == 'rank="1"'


# -- worker-side exporter -----------------------------------------------------

def test_metrics_exporter_envelope_and_final_write(tmp_path,
                                                   monkeypatch):
    monkeypatch.setenv("ZNICZ_TPU_ELASTIC_RANK", "3")
    observe.counter("znicz_fleet_test_export_total", "t").inc(2)
    path = str(tmp_path / "m.json")
    exporter = fed.MetricsExporter(path, interval_s=30.0)
    exporter.start()
    deadline = time.monotonic() + 10.0
    while not os.path.exists(path) and time.monotonic() < deadline:
        time.sleep(0.01)
    exporter.stop()                     # also publishes a final write
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == fed.EXPORT_SCHEMA
    assert doc["rank"] == 3 and doc["pid"] == os.getpid()
    assert doc["ts"] <= time.time()
    _, samples = fed.parse_prometheus(doc["prom"])
    assert any(name == "znicz_fleet_test_export_total" and v == 2.0
               for _, name, _, v in samples)


# -- aggregator merge ---------------------------------------------------------

def test_aggregator_merges_with_rank_labels():
    r0, r1 = _two_serve_registries()
    r0.get("znicz_serve_queue_depth").set(4)
    r1.get("znicz_serve_queue_depth").set(9)
    r1.get("znicz_recompiles_total").labels(fn="step").inc(2)
    agg = fed.FleetAggregator(min_refresh_s=0.0)
    agg.add_source(0, r0.render_prometheus)
    agg.add_source(1, r1.render_prometheus)
    try:
        flat = agg.snapshot_flat(skip_zero=False, buckets=True)
        assert flat['znicz_serve_queue_depth{rank="0"}'] == 4.0
        assert flat['znicz_serve_queue_depth{rank="1"}'] == 9.0
        assert flat['znicz_recompiles_total{fn="step",rank="1"}'] == 2.0
        assert flat['znicz_fleet_worker_up{rank="0"}'] == 1.0
        # the merged exposition re-parses and declares each family once
        prom = agg.render_prometheus()
        families, samples = fed.parse_prometheus(prom)
        assert prom.count("# TYPE znicz_serve_queue_depth gauge") == 1
        assert families["znicz_fleet_worker_up"]["type"] == "gauge"
        ranks = {inner for _, name, inner, _ in samples
                 if name == "znicz_serve_queue_depth"}
        assert ranks == {'rank="0"', 'rank="1"'}
        # JSON views carry per-rank health without the bulky flat dump
        doc = agg.metrics_doc()
        assert doc["workers"]["0"]["ok"] and "flat" not in \
            doc["workers"]["0"]
        assert doc["flat"]['znicz_serve_queue_depth{rank="1"}'] == 9.0
        status = agg.status_doc()
        assert set(status["workers"]) == {"0", "1"}
        assert "rules" in status["watchtower"]
    finally:
        agg.close()


def test_aggregator_staleness_drops_gauges_keeps_counters(tmp_path):
    r0, _ = _two_serve_registries()
    r0.get("znicz_serve_queue_depth").set(64)
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps(
        {"schema": fed.EXPORT_SCHEMA, "rank": 1,
         "ts": time.time() - 3600.0,     # an hour-dead worker
         "prom": "# TYPE znicz_serve_queue_depth gauge\n"
                 "znicz_serve_queue_depth 99\n"
                 "# TYPE znicz_recompiles_total counter\n"
                 "znicz_recompiles_total 40\n"}))
    agg = fed.FleetAggregator(min_refresh_s=0.0, stale_s=5.0)
    agg.add_source(0, r0.render_prometheus)
    agg.add_file_source(1, str(stale))
    agg.add_file_source(2, str(tmp_path / "never_written.json"))
    try:
        flat = agg.snapshot_flat(skip_zero=False)
        # the dead rank's GAUGE must not read saturated forever...
        assert 'znicz_serve_queue_depth{rank="1"}' not in flat
        # ...but its COUNTER carries forward: vanishing it to 0 and
        # snapping back on recovery would read as lifetime-sized
        # in-window growth and falsely trip every delta rule
        assert flat['znicz_recompiles_total{rank="1"}'] == 40.0
        assert flat['znicz_fleet_worker_up{rank="1"}'] == 0.0
        assert flat['znicz_fleet_worker_up{rank="2"}'] == 0.0
        assert flat['znicz_serve_queue_depth{rank="0"}'] == 64.0
        workers = agg.status_doc()["workers"]
        assert workers["1"]["ok"]                 # parsed, just stale
        assert not workers["2"]["ok"] and workers["2"]["error"]
    finally:
        agg.close()


def test_transient_scrape_failure_keeps_serving_cached_data():
    """One failed scrape must not vanish a live worker's series (the
    snap-back would falsely trip delta rules); the cached data serves
    until it ages past stale_s."""
    r0, _ = _two_serve_registries()
    r0.get("znicz_serve_queue_depth").set(7)
    r0.get("znicz_recompiles_total").labels(fn="step").inc(3)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 2:
            raise OSError("transient connect failure")
        return r0.render_prometheus()

    agg = fed.FleetAggregator(min_refresh_s=0.0, stale_s=60.0)
    agg.add_source(0, flaky)
    try:
        assert agg.snapshot_flat(
            skip_zero=False)['znicz_serve_queue_depth{rank="0"}'] == 7.0
        flat = agg.snapshot_flat(skip_zero=False)     # the failing pass
        assert calls["n"] == 2
        assert flat['znicz_serve_queue_depth{rank="0"}'] == 7.0
        assert flat['znicz_recompiles_total{fn="step",rank="0"}'] == 3.0
        assert flat['znicz_fleet_worker_up{rank="0"}'] == 1.0
        workers = agg.status_doc()["workers"]         # 3rd: recovers
        assert workers["0"]["ok"] and calls["n"] == 3
    finally:
        agg.close()


# -- fleet SLO rules over the merged view -------------------------------------

def test_fleet_rules_total_and_per_rank():
    r0, r1 = _two_serve_registries()
    agg = fed.FleetAggregator(min_refresh_s=0.0)
    agg.add_source(0, r0.render_prometheus)
    agg.add_source(1, r1.render_prometheus)
    trips = []
    total = agg.add_rule(fed.fleet_queue_saturation(
        depth=50, for_s=0.0, action=lambda r, v: trips.append(v)))
    per_rank = agg.add_rule_per_rank(
        lambda r: fed.any_rank_recompile_storm(r, max_in_window=3,
                                               window_s=60.0))
    try:
        ts = 1000.0
        r0.get("znicz_serve_queue_depth").set(10)
        r1.get("znicz_serve_queue_depth").set(10)
        # touch the recompile child so the baseline sample records its
        # 0 — a delta rule needs the before, not just the after
        r1.get("znicz_recompiles_total").labels(fn="step")
        agg.tower.observe_now(ts=ts)
        assert total.trips == 0
        # rank 1 saturates: the FLEET total (10 + 60) crosses, and only
        # rank 1's recompile rule sees its storm
        r1.get("znicz_serve_queue_depth").set(60)
        r1.get("znicz_recompiles_total").labels(fn="step").inc(5)
        agg.tower.observe_now(ts=ts + 5)
        assert total.trips == 1 and trips == [70.0]
        assert [r.trips for r in per_rank] == [0, 1]
    finally:
        agg.close()


def test_fleet_p95_latency_across_ranks():
    r0, r1 = _two_serve_registries()
    agg = fed.FleetAggregator(min_refresh_s=0.0)
    agg.add_source(0, r0.render_prometheus)
    agg.add_source(1, r1.render_prometheus)
    rule = agg.add_rule(fed.fleet_latency_slo(p95_s=0.5, window_s=60.0,
                                              min_count=4))
    try:
        ts = 2000.0
        agg.tower.observe_now(ts=ts)
        # rank 0 fast, rank 1 slow and busier: the fleet p95 over the
        # rank-MERGED bucket deltas lands in rank 1's bucket
        for _ in range(4):
            r0.get("znicz_serve_latency_seconds").observe(0.005)
        for _ in range(16):
            r1.get("znicz_serve_latency_seconds").observe(0.9)
        agg.tower.observe_now(ts=ts + 5)
        assert rule.trips == 1
        assert rule.last_value == pytest.approx(0.91, abs=0.2)
    finally:
        agg.close()


def test_seeded_single_rank_fault_trips_fleet_rule_and_flight(
        decoder, tmp_path):
    """The acceptance drill: a seeded fault on ONE rank's decode loop
    trips a rank-filtered fleet rule, and the trip's flight artifact
    embeds BOTH workers' last snapshots plus the live admission
    ledger."""
    from znicz_tpu.serve.continuous import ContinuousBatcher

    flight.configure(dir=str(tmp_path), min_interval_s=0.0)
    # rank 0 = a REAL worker in this process (global registry); rank 1 =
    # a quiet synthetic peer
    _, r1 = _two_serve_registries()
    agg = fed.FleetAggregator(min_refresh_s=0.0)
    agg.add_source(0, observe.REGISTRY.render_prometheus)
    agg.add_source(1, r1.render_prometheus)
    rule = agg.add_rule(observe.Rule(
        "fleet_rank0_failures",
        'znicz_generate_requests_total{event="failed",rank="0"}',
        lambda d: d > 0, window_s=60.0, reduce="delta",
        description="rank 0 failed a generation"))
    batcher = ContinuousBatcher(decoder, default_timeout_s=30.0)
    try:
        # touch the failed-event child so the pre-fault baseline sample
        # records its current value (delta rules need the before)
        observe.counter("znicz_generate_requests_total",
                        labelnames=("event",)).labels(event="failed")
        agg.tower.observe_now(ts=3000.0)        # pre-fault baseline
        faults.install(faults.FaultPlan(seed=11).crash_at(
            "generate.step", at_hit=1))
        stream = batcher.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(Exception):
            stream.result(timeout_s=30.0)       # the error sentinel
        agg.tower.observe_now(ts=3005.0)
        assert rule.trips == 1 and rule.last_value >= 1.0
        artifacts = sorted(tmp_path.glob("flight_*.json"))
        assert artifacts, "rule trip did not auto-dump a fleet flight"
        doc = flight.load(str(artifacts[-1]))
        assert set(doc["planes"]["fleet"]) == {"0", "1"}
        rank0 = doc["planes"]["fleet"]["0"]
        assert any(k.startswith("znicz_generate_requests_total")
                   for k in rank0["flat"])
        ledger = doc["planes"]["generate_ledger"]
        assert ledger["admitted"] == \
            ledger["completed"] + ledger["failed"] + ledger["abandoned"]
        assert ledger["failed"] >= 1
    finally:
        batcher.stop(drain=False)
        agg.close()


# -- per-request distributed tracing ------------------------------------------

def test_request_phase_spans_share_rid_and_track(decoder):
    from znicz_tpu.serve.continuous import ContinuousBatcher

    batcher = ContinuousBatcher(decoder, default_timeout_s=30.0)
    try:
        stream = batcher.submit([4, 5], max_new_tokens=3)
        stream.result(timeout_s=30.0)
    finally:
        batcher.stop()
    rid = stream.request_id
    assert rid                           # minted at admission
    spans = [e for e in observe.TRACER.export_dict()["traceEvents"]
             if (e.get("args") or {}).get("rid") == rid]
    names = {e["name"] for e in spans}
    assert {"generate.queue", "generate.prefill",
            "generate.decode"} <= names
    assert len({e["tid"] for e in spans}) == 1   # one request track
    assert {e["tid"] for e in spans} == {fed.request_track(rid)}
    decode = next(e for e in spans if e["name"] == "generate.decode")
    assert decode["args"]["n_tokens"] == 3
    # phases are ordered on the shared clock: queue ends before decode
    queue = next(e for e in spans if e["name"] == "generate.queue")
    assert queue["ts"] <= decode["ts"]
    # batched per-step spans carry the step counter
    steps = [e for e in observe.TRACER.export_dict()["traceEvents"]
             if e["name"] == "generate.decode_step"]
    assert steps and all("step" in e["args"] for e in steps)


def test_micro_batcher_request_spans():
    from znicz_tpu.serve.batcher import MicroBatcher

    class _Engine:
        max_batch = 8
        input_shape = None

        def run(self, x):
            return np.asarray(x) * 2.0

    b = MicroBatcher(_Engine(), max_wait_ms=1.0)
    try:
        out = b.submit([[1.0, 2.0]], request_id="test-rid-1").result(
            timeout=10)
        assert out.tolist() == [[2.0, 4.0]]
    finally:
        b.stop()
    spans = [e for e in observe.TRACER.export_dict()["traceEvents"]
             if (e.get("args") or {}).get("rid") == "test-rid-1"]
    assert [e["name"] for e in spans] == ["serve.request"]
    assert spans[0]["tid"] == fed.request_track("test-rid-1")
    infer = [e for e in observe.TRACER.export_dict()["traceEvents"]
             if e["name"] == "serve.infer"]
    assert infer and infer[-1]["args"]["rows"] >= 1


def test_generate_server_request_id_and_stream_span(decoder):
    from znicz_tpu.serve.continuous import ContinuousBatcher
    from znicz_tpu.serve.server import GenerateServer

    batcher = ContinuousBatcher(decoder, default_timeout_s=30.0)
    server = GenerateServer(batcher, charmap=CHARMAP, port=0)
    port = server.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"prompt": "ab", "max_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            rid = r.headers["X-Request-Id"]
            lines = [json.loads(raw) for raw in r]
        assert rid and lines[-1]["done"]
        spans = [e for e in observe.TRACER.export_dict()["traceEvents"]
                 if (e.get("args") or {}).get("rid") == rid]
        names = {e["name"] for e in spans}
        assert {"generate.queue", "generate.prefill", "generate.decode",
                "generate.stream"} <= names
        assert len({e["tid"] for e in spans}) == 1
        # non-stream replies carry the id in the body too
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"prompt": "c", "max_tokens": 2,
                             "stream": False}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            doc = json.load(r)
        assert doc["request_id"] == r.headers["X-Request-Id"]
    finally:
        server.stop()


# -- trace merging ------------------------------------------------------------

def _worker_trace(rank, origin, names):
    t = observe.Tracer(capacity=64)
    for name in names:
        with t.span(name):
            pass
    doc = t.export_dict()
    doc["rank"] = rank
    doc["origin_unix_ts"] = origin
    return doc


def test_merge_traces_aligns_clocks_and_ranks():
    a = _worker_trace(0, 1000.0, ["w0.step"])
    b = _worker_trace(1, 1002.5, ["w1.step"])
    merged = fed.merge_traces([a, b])
    ev0 = next(e for e in merged["traceEvents"] if e["name"] == "w0.step")
    ev1 = next(e for e in merged["traceEvents"] if e["name"] == "w1.step")
    assert ev0["pid"] == 0 and ev1["pid"] == 1
    # rank 1's origin is 2.5s later: its events shift +2.5e6 us
    raw1 = next(e for e in b["traceEvents"] if e["name"] == "w1.step")
    assert ev1["ts"] == pytest.approx(raw1["ts"] + 2.5e6, abs=1.0)
    pnames = {e["pid"]: e["args"]["name"]
              for e in merged["traceEvents"]
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert pnames == {0: "rank 0", 1: "rank 1"}
    assert merged["origins"] == {"0": 1000.0, "1": 1002.5}


def test_fleet_trace_cli_merges_files(tmp_path, capsys):
    p0, p1 = str(tmp_path / "t0.json"), str(tmp_path / "t1.json")
    with open(p0, "w") as f:
        json.dump(_worker_trace(0, 500.0, ["a.x"]), f)
    with open(p1, "w") as f:
        json.dump(_worker_trace(1, 501.0, ["b.x"]), f)
    out = str(tmp_path / "merged.json")
    assert fed.fleet_trace_main([p0, p1, "-o", out]) == 0
    with open(out) as f:
        merged = json.load(f)
    assert {e["pid"] for e in merged["traceEvents"]
            if e["ph"] != "M"} == {0, 1}
    assert fed.fleet_trace_main([str(tmp_path / "missing.json"),
                                 "-o", out]) == 2


def test_tracer_export_carries_fleet_anchors(monkeypatch):
    monkeypatch.setenv("ZNICZ_TPU_ELASTIC_RANK", "7")
    doc = observe.Tracer(capacity=8).export_dict()
    assert doc["rank"] == 7
    assert doc["origin_unix_ts"] == pytest.approx(time.time(), abs=60.0)


# -- satellite: rank-tagged JSONL sink ----------------------------------------

def test_jsonl_sink_carries_fleet_rank(tmp_path, monkeypatch):
    import logging

    from znicz_tpu.core import logger as zlogger

    monkeypatch.setenv("ZNICZ_TPU_ELASTIC_RANK", "2")
    path = str(tmp_path / "rank_tagged.jsonl")
    zlogger.configure(jsonl_path=path)
    try:
        logging.getLogger("znicz_tpu.fleet_test").warning("tagged line")
        observe.instant("fleet.test_event", detail=1)
        with open(path) as f:
            docs = [json.loads(line) for line in f]
    finally:
        # detach the handler: _jsonl_paths is process-global and the
        # tmp path dies with this test
        for h in list(logging.getLogger().handlers):
            if isinstance(h, zlogger.JsonlHandler) and \
                    h.baseFilename == path:
                logging.getLogger().removeHandler(h)
                h.close()
        zlogger._jsonl_paths.discard(path)
    line = next(d for d in docs if d["msg"] == "tagged line")
    assert line["rank"] == 2
    event = next(d for d in docs if d.get("event") == "fleet.test_event")
    assert event["rank"] == 2


# -- satellite: flight planes -------------------------------------------------

def test_flight_planes_register_unregister_and_degrade(tmp_path):
    flight.register_plane("fleet_test_ok", lambda: {"n": 1})
    flight.register_plane("fleet_test_dead",
                          lambda: (_ for _ in ()).throw(RuntimeError("x")))
    try:
        doc = flight.load(flight.dump(dir=str(tmp_path), reason="p"))
        assert doc["schema"] == "znicz_tpu.flight/2"
        assert doc["planes"]["fleet_test_ok"] == {"n": 1}
        assert "RuntimeError" in doc["planes"]["fleet_test_dead"]["error"]
    finally:
        flight.unregister_plane("fleet_test_ok")
        flight.unregister_plane("fleet_test_dead")
    # conditional unregister: a stale owner must not evict the newer one
    newer = dict.fromkeys              # any distinct callables
    flight.register_plane("fleet_test_cond", newer)
    flight.unregister_plane("fleet_test_cond", fn=lambda: None)
    assert flight._planes["fleet_test_cond"] is newer
    flight.unregister_plane("fleet_test_cond", fn=newer)
    assert "fleet_test_cond" not in flight._planes


def test_old_flight_schema_still_loads(tmp_path):
    legacy = tmp_path / "flight_old.json"
    legacy.write_text(json.dumps({"schema": "znicz_tpu.flight/1",
                                  "reason": "legacy"}))
    assert flight.load(str(legacy))["reason"] == "legacy"


# -- HTTP surfaces ------------------------------------------------------------

def test_webstatus_mounts_fleet_and_standalone_server():
    from znicz_tpu.web_status import WebStatus

    r0, _ = _two_serve_registries()
    r0.get("znicz_serve_queue_depth").set(3)
    agg = fed.FleetAggregator(min_refresh_s=0.0)
    agg.add_source(0, r0.render_prometheus)
    ws = WebStatus(port=0).register_fleet(agg)
    port = ws.start()
    try:
        base = f"http://127.0.0.1:{port}"
        prom = urllib.request.urlopen(base + "/fleet/metrics.prom",
                                      timeout=10).read().decode()
        assert 'znicz_serve_queue_depth{rank="0"} 3' in prom
        doc = json.load(urllib.request.urlopen(
            base + "/fleet/status.json", timeout=10))
        assert doc["workers"]["0"]["ok"]
        trace_doc = json.load(urllib.request.urlopen(
            base + "/fleet/trace.json", timeout=10))
        assert trace_doc["missing"] == [0]      # callable: no trace
        # unmounted paths still behave (fall through to the dashboard)
        assert urllib.request.urlopen(base + "/status.json",
                                      timeout=10).status == 200
    finally:
        ws.stop()
    fleet_port = agg.serve(port=0)
    try:
        doc = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{fleet_port}/fleet/metrics", timeout=10))
        assert doc["flat"]['znicz_serve_queue_depth{rank="0"}'] == 3.0
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{fleet_port}/fleet/nope", timeout=10)
    finally:
        agg.close()


# -- satellite: concurrent scrape soak under live decode traffic --------------

def test_concurrent_scrape_soak_under_decode_traffic(decoder):
    """Threaded soak of /metrics, /metrics.prom, /trace.json and
    /timeseries.json while generations stream: no 500s, no torn
    Prometheus text (every body parses whole, cumulative buckets stay
    monotone)."""
    from znicz_tpu.serve.continuous import ContinuousBatcher
    from znicz_tpu.serve.server import GenerateServer
    from znicz_tpu.web_status import WebStatus

    batcher = ContinuousBatcher(decoder, default_timeout_s=30.0)
    server = GenerateServer(batcher, charmap=CHARMAP, port=0)
    gport = server.start()
    status = WebStatus(port=0)
    sport = status.start()
    errors: list = []
    stop = threading.Event()

    def client(seed: int) -> None:
        while not stop.is_set():
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{gport}/generate",
                    data=json.dumps({"tokens": [1 + seed, 2],
                                     "max_tokens": 4}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as r:
                    for _ in r:
                        pass
            except Exception as exc:  # noqa: BLE001
                errors.append(f"client: {exc!r}")
                return

    def scraper(url: str, check_prom: bool) -> None:
        while not stop.is_set():
            try:
                with urllib.request.urlopen(url, timeout=30) as r:
                    body = r.read().decode()
                    if r.status != 200:
                        errors.append(f"{url} -> {r.status}")
                        return
                if check_prom:
                    _, samples = fed.parse_prometheus(body)
                    by_family: dict = {}
                    for _, name, inner, v in samples:
                        if name.endswith("_bucket"):
                            by_family.setdefault(
                                name + inner.split("le=")[0], []).append(v)
                    for counts in by_family.values():
                        if counts != sorted(counts):
                            errors.append(f"non-monotone buckets in "
                                          f"{url}")
                            return
                else:
                    json.loads(body)
            except Exception as exc:  # noqa: BLE001
                errors.append(f"{url}: {exc!r}")
                return

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(3)]
    for url, is_prom in (
            (f"http://127.0.0.1:{gport}/metrics", False),
            (f"http://127.0.0.1:{gport}/metrics.prom", True),
            (f"http://127.0.0.1:{gport}/trace.json", False),
            (f"http://127.0.0.1:{sport}/timeseries.json", False),
            (f"http://127.0.0.1:{sport}/metrics", True)):
        threads.append(threading.Thread(target=scraper,
                                        args=(url, is_prom)))
    for t in threads:
        t.start()
    time.sleep(4.0)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    status.stop()
    server.stop()
    assert not errors, errors[:5]
    snap = batcher.metrics.snapshot()
    assert snap["completed"] >= 3       # traffic actually flowed
    assert snap["admitted"] == snap["completed"] + snap["failed"] + \
        snap["abandoned"]
