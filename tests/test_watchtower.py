"""Watchtower + flight recorder (ISSUE 6): the retained time-series
ring stays bounded and reconstructs exactly, SLO rules trip
deterministically (including under seeded fault injection), metric
histories are bit-exact with the sampler on or off, the shared
histogram-quantile estimator replaces the serving plane's private
percentile code, the JSONL sink rotates at its byte bound, and a
seeded `workflow.step` crash under `run_supervised` leaves a valid
flight artifact carrying the crashing span, the fault's resilience
instant, and at least one time-series sample (the acceptance chaos
test)."""

import json
import logging
import os
import time

import numpy as np
import pytest

from znicz_tpu import observe
from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.core.logger import JsonlHandler
from znicz_tpu.observe import flight, probe, watchtower
from znicz_tpu.observe.registry import REGISTRY, Registry, \
    quantile_from_buckets
from znicz_tpu.observe.watchtower import (Rule, TimeSeriesRing,
                                          Watchtower, bucket_counts,
                                          match_keys)
from znicz_tpu.resilience import faults
from znicz_tpu.resilience.supervisor import SupervisorPolicy, \
    run_supervised
from znicz_tpu.serve.metrics import LatencyHistogram
from znicz_tpu.standard_workflow import StandardWorkflow
from znicz_tpu.web_status import WebStatus

LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 24},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 6},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
]
LOADER = {"n_classes": 6, "sample_shape": (10, 10), "n_train": 240,
          "n_valid": 120, "minibatch_size": 40, "spread": 2.5,
          "noise": 1.0}


def build(max_epochs, snap_dir=None, seed=77, tower=None):
    prng.seed_all(seed)
    cfg = None
    if snap_dir is not None:
        cfg = {"directory": str(snap_dir), "prefix": "t",
               "only_improved": False, "keep_all": True}
    w = StandardWorkflow(
        name="TowerTest", layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": max_epochs},
        snapshotter_config=cfg)
    w.initialize(device=XLADevice())
    if tower is not None:
        tower.attach(w)
    return w


@pytest.fixture(autouse=True)
def _clean_globals():
    """No leaked fault plans, flight auto-dump config, or disabled
    plane between tests."""
    yield
    faults.uninstall()
    flight.configure()                   # dir=None: auto_dump off again
    observe.set_enabled(True)


# -- TimeSeriesRing ----------------------------------------------------------

def test_ring_stores_deltas_and_reconstructs():
    ring = TimeSeriesRing(capacity=8, registry=Registry())
    d1 = ring.sample(flat={"a_total": 1.0, "b": 5.0}, ts=10.0)
    d2 = ring.sample(flat={"a_total": 1.0, "b": 7.0}, ts=11.0)
    d3 = ring.sample(flat={"a_total": 2.0, "b": 7.0}, ts=12.0)
    assert d1 == {"a_total": 1.0, "b": 5.0}
    assert d2 == {"b": 7.0}              # only the changed key
    assert d3 == {"a_total": 2.0}
    assert ring.current() == {"a_total": 2.0, "b": 7.0}
    assert ring.series("b") == [(10.0, 5.0), (11.0, 7.0), (12.0, 7.0)]
    assert ring.series("b", window_s=1.5) == [(11.0, 7.0), (12.0, 7.0)]


def test_ring_bounded_under_10k_sample_soak():
    ring = TimeSeriesRing(capacity=64, registry=Registry())
    for i in range(10_000):
        ring.sample(flat={"soak_total": float(i), "const": 1.0},
                    ts=float(i))
    assert len(ring) == 64               # ring, not a log
    doc = ring.to_dict()
    assert len(doc["samples"]) == 64
    # evicted deltas folded into base: reconstruction is still exact
    replay = dict(doc["base"])
    for row in doc["samples"]:
        replay.update(row["delta"])
    assert replay == {"soak_total": 9999.0, "const": 1.0}
    assert doc["base_ts"] == 9935.0      # stamp of newest folded sample
    series = ring.series("soak_total")
    assert len(series) == 64 and series[-1] == (9999.0, 9999.0)


def test_ring_summary_and_counter_rate():
    ring = TimeSeriesRing(capacity=8, registry=Registry())
    for ts, v in ((0.0, 0.0), (5.0, 5.0), (10.0, 30.0)):
        ring.sample(flat={"ev_total": v, "depth": 10.0 - v}, ts=ts)
    s = ring.summary()
    assert s["ev_total"] == {"min": 0.0, "mean": pytest.approx(35 / 3),
                             "max": 30.0, "last": 30.0,
                             "rate_per_s": 3.0}
    assert "rate_per_s" not in s["depth"]          # gauges get no rate
    assert s["depth"]["min"] == -20.0 and s["depth"]["last"] == -20.0


def test_ring_nan_provider_recorded_as_zero_and_json_safe():
    """A dead scrape-time gauge provider reads NaN by design; the ring
    must neither bloat every delta (NaN != NaN) nor serialize a bare
    NaN token into /timeseries.json."""
    ring = TimeSeriesRing(capacity=8, registry=Registry())
    nan = float("nan")
    ring.sample(flat={"live": 3.0, "dead": 2.0}, ts=0.0)
    d2 = ring.sample(flat={"live": 3.0, "dead": nan}, ts=1.0)
    assert d2 == {"dead": 0.0}           # NaN == vanish, explicit zero
    d3 = ring.sample(flat={"live": 3.0, "dead": nan}, ts=2.0)
    assert d3 == {}                      # ...and stays quiet after
    assert ring.sample(flat={"never": nan}, ts=3.0) == {"live": 0.0}
    json.loads(json.dumps(ring.to_dict(), allow_nan=False))
    json.loads(json.dumps(ring.summary(), allow_nan=False))


def test_ring_to_dict_last_n_folds_head_into_base():
    ring = TimeSeriesRing(capacity=16, registry=Registry())
    for i in range(6):
        ring.sample(flat={"c_total": float(i)}, ts=float(i))
    doc = ring.to_dict(last_n=2)
    assert len(doc["samples"]) == 2
    assert doc["base"] == {"c_total": 3.0} and doc["base_ts"] == 3.0
    replay = dict(doc["base"])
    for row in doc["samples"]:
        replay.update(row["delta"])
    assert replay == ring.current()      # trimmed view replays exactly
    assert len(ring.to_dict()["samples"]) == 6   # untrimmed untouched


def test_rule_matching_flag_surfaces_dead_selectors():
    reg = Registry()
    tower = Watchtower(capacity=8, registry=reg)
    live = tower.add_rule(Rule("live", "depth", lambda v: False))
    dead = tower.add_rule(Rule("dead", "no_such_metric",
                               lambda v: False))
    reg.gauge("depth").set(1.0)
    tower.observe_now(ts=1.0)
    assert live.snapshot()["matching"] is True
    assert dead.snapshot()["matching"] is False
    assert dead.last_value is None       # never actually evaluated


def test_ring_capacity_validation():
    with pytest.raises(ValueError):
        TimeSeriesRing(capacity=0)
    with pytest.raises(ValueError):
        Watchtower(step_every=0)


def test_match_keys_exact_family_and_label_filter():
    flat = {"a_total": 1.0,
            'ev_total{kind="fault",site="x"}': 2.0,
            'ev_total{kind="nan",site="x"}': 3.0,
            "a_total_extra": 9.0}
    assert match_keys("a_total", flat) == ["a_total"]
    assert sorted(match_keys("ev_total", flat)) == \
        ['ev_total{kind="fault",site="x"}', 'ev_total{kind="nan",site="x"}']
    assert match_keys('ev_total{kind="fault"}', flat) == \
        ['ev_total{kind="fault",site="x"}']
    assert match_keys("missing", flat) == []


# -- Rule --------------------------------------------------------------------

def test_rule_reduces():
    def run(reduce, seq, window_s=100.0):
        r = Rule("r", "m", lambda v: False, reduce=reduce,
                 window_s=window_s)
        for ts, v in seq:
            r.observe(ts, v)
        return r.last_value

    seq = [(0.0, 4.0), (10.0, 2.0), (20.0, 8.0)]
    assert run("last", seq) == 8.0
    assert run("min", seq) == 2.0
    assert run("max", seq) == 8.0
    assert run("mean", seq) == pytest.approx(14 / 3)
    assert run("delta", seq) == 4.0
    assert run("rate", seq) == pytest.approx(4 / 20)
    assert run("ratio_to_first", seq) == 2.0
    with pytest.raises(ValueError):
        Rule("r", "m", lambda v: True, reduce="p999")
    with pytest.raises(ValueError):
        Rule("r", "m", lambda v: True, reduce="rate")   # needs window_s


def test_rule_window_keeps_trailing_anchor():
    r = Rule("r", "m", lambda v: False, reduce="delta", window_s=10.0)
    for ts, v in ((0.0, 0.0), (5.0, 1.0), (10.0, 2.0), (15.0, 3.0)):
        r.observe(ts, v)
    # cutoff is ts=5: the (5.0, 1.0) sample anchors the window's
    # trailing edge, so delta measures 15s-vs-5s, not vs a survivor
    assert r.last_value == 2.0


def test_rule_for_duration_and_rearm():
    r = Rule("r", "m", lambda v: v > 10.0, for_s=5.0)
    assert r.observe(0.0, 20.0) is None            # breach starts
    assert r.observe(4.0, 20.0) is None            # not held long enough
    assert r.observe(5.0, 20.0) == 20.0            # trip
    assert r.observe(6.0, 20.0) is None            # no storm: stays tripped
    assert r.trips == 1 and r.last_trip_ts == 5.0
    assert r.observe(7.0, 1.0) is None             # recovery re-arms
    assert r.observe(8.0, 20.0) is None
    assert r.observe(13.0, 20.0) == 20.0           # second full cycle
    assert r.trips == 2


def test_rule_trip_fires_counter_instant_and_action():
    reg = Registry()
    tower = Watchtower(capacity=8, registry=reg)
    fired = []
    tower.add_rule(Rule("boom", "depth", lambda v: v > 3.0,
                        action=lambda rule, v: fired.append((rule.name, v))))
    gauge = reg.gauge("depth")
    gauge.set(1.0)
    tower.observe_now(ts=1.0)
    assert fired == [] and tower.rules[0].trips == 0
    gauge.set(5.0)
    n_events = len(observe.TRACER)
    tower.observe_now(ts=2.0)
    assert fired == [("boom", 5.0)]
    trips = REGISTRY.snapshot_flat()
    assert trips['znicz_watchtower_trips_total{rule="boom"}'] >= 1.0
    names = [e["name"] for e in observe.TRACER.tail(
        len(observe.TRACER) - n_events)]
    assert "watchtower.trip" in names


def test_rule_action_exception_does_not_kill_sampler():
    reg = Registry()
    tower = Watchtower(capacity=8, registry=reg)

    def broken(rule, value):
        raise RuntimeError("boom")

    tower.add_rule(Rule("broken", "depth", lambda v: v > 0.0,
                        action=broken))
    reg.gauge("depth").set(1.0)
    tower.observe_now(ts=1.0)            # must not raise
    tower.observe_now(ts=2.0)
    assert tower.rules[0].trips == 1


def test_rule_trips_deterministically_under_seeded_fault_injection():
    """Seeded fault firings drive the resilience counter; a rule with a
    label filter on kind="fault" trips at exactly the sample where the
    third firing lands — same seed, same trip, every run."""
    base = REGISTRY.snapshot_flat().get(
        'znicz_resilience_events_total{kind="fault",site="tower.site"}',
        0.0)
    tower = Watchtower(capacity=32)
    rule = tower.add_rule(Rule(
        "fault_burst",
        'znicz_resilience_events_total{kind="fault",site="tower.site"}',
        lambda v: v >= base + 3.0))
    plan = faults.FaultPlan(seed=42)
    plan.oserror_at("tower.site", once=False)      # fire on every hit
    with faults.active(plan):
        for i in range(5):
            with pytest.raises(OSError):
                faults.fault_hook("tower.site")
            tower.observe_now(ts=float(i))
    assert rule.trips == 1
    assert rule.last_trip_ts == 2.0      # the third firing's sample


# -- windowed quantile rules -------------------------------------------------

def test_bucket_counts_from_flat_snapshot():
    reg = Registry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    flat = reg.snapshot_flat(skip_zero=False, buckets=True)
    edges, counts = bucket_counts("lat_seconds", flat)
    assert edges == (0.1, 1.0)
    assert counts == (1.0, 2.0, 1.0)     # per-bucket, overflow last
    assert bucket_counts("missing", flat) is None
    # no buckets in the snapshot at all -> None, not a crash
    assert bucket_counts("lat_seconds", reg.snapshot_flat()) is None


def test_bucket_counts_sums_and_filters_labelsets():
    reg = Registry()
    h = reg.histogram("rt_seconds", buckets=(1.0,),
                      labelnames=("route",))
    h.labels(route="a").observe(0.5)
    h.labels(route="a").observe(2.0)
    h.labels(route="b").observe(0.5)
    flat = reg.snapshot_flat(skip_zero=False, buckets=True)
    _, summed = bucket_counts("rt_seconds", flat)
    assert summed == (2.0, 1.0)          # both labelsets
    _, only_a = bucket_counts('rt_seconds{route="a"}', flat)
    assert only_a == (1.0, 1.0)


def test_rule_quantile_validation():
    with pytest.raises(ValueError):      # quantile reduce needs quantile=
        Rule("r", "m", lambda v: True, reduce="window_quantile",
             window_s=10.0)
    with pytest.raises(ValueError):      # scalar reduce rejects one
        Rule("r", "m", lambda v: True, reduce="last", quantile=0.95)
    with pytest.raises(ValueError):      # quantile out of (0, 1)
        Rule("r", "m", lambda v: True, reduce="window_quantile",
             window_s=10.0, quantile=1.5)
    with pytest.raises(ValueError):      # windowed reduce needs window_s
        Rule("r", "m", lambda v: True, reduce="quantile_ratio",
             quantile=0.95)
    with pytest.raises(ValueError):      # window bound must hold 2+
        Rule("r", "m", lambda v: True, max_window=1)


def test_rule_window_entry_bound():
    r = Rule("r", "m", lambda v: False, reduce="mean", window_s=1e9,
             max_window=8)
    for i in range(1000):
        r.observe(float(i), float(i))
    assert len(r._window) == 8           # count-bounded, not just time
    assert r.last_value == pytest.approx(sum(range(992, 1000)) / 8)


def test_window_quantile_rule_trips_through_observe_now():
    """The sampler feeds histogram-family rules bucket-count vectors;
    the p95 of only the WINDOW's observations trips the rule as soon
    as slow observations land, however long the fast history is."""
    reg = Registry()
    tower = Watchtower(capacity=32, registry=reg)
    rule = tower.add_rule(Rule(
        "slow_p95", "lat_seconds", lambda p: p > 1.0,
        reduce="window_quantile", quantile=0.95, window_s=100.0,
        min_count=4))
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    for _ in range(4):
        h.observe(0.05)
    tower.observe_now(ts=0.0)
    assert rule.last_value is None       # one entry: no delta yet
    for _ in range(4):
        h.observe(0.05)
    tower.observe_now(ts=1.0)
    assert rule.trips == 0
    assert rule.last_value is not None and rule.last_value <= 0.1
    for _ in range(8):
        h.observe(5.0)
    tower.observe_now(ts=2.0)
    assert rule.trips == 1               # window p95 now in (1, 10]
    assert rule.last_value > 1.0


def test_quantile_ratio_detects_midrun_regression():
    """quantile_ratio judges the newer half-window's p95 against the
    older half's — the trailing-baseline regression detector the
    lifetime `_p95` estimate cannot be (cumulative buckets damp a
    mid-run regression in proportion to process age)."""
    edges = (0.1, 1.0, 10.0)

    def entry(fast, slow):               # (<=0.1, <=1, <=10, +Inf)
        return (edges, (float(fast), 0.0, float(slow), 0.0))

    r = Rule("reg", "lat_seconds", lambda x: x > 2.0,
             reduce="quantile_ratio", quantile=0.95, window_s=100.0,
             min_count=4)
    tripped = []
    for ts, (f, s) in enumerate(
            ((0, 0), (8, 0), (16, 0), (16, 8), (16, 16))):
        tripped.append(r.observe(float(ts), entry(f, s)))
    # at the trip: older half e0->e2 is 16 fast obs, newer half
    # e2->e3 is 8 slow obs — ratio blows past the factor
    assert tripped[:3] == [None, None, None]
    assert tripped[3] is not None and tripped[3] > 2.0
    assert tripped[4] is None            # stays tripped, no storm
    assert r.trips == 1
    # a re-declared histogram (different edges) is dropped, not
    # mis-subtracted: the window collapses to < 2 comparable entries
    r2 = Rule("reg2", "m", lambda x: True, reduce="window_quantile",
              quantile=0.5, window_s=100.0)
    r2.observe(0.0, (edges, (4.0, 0.0, 0.0, 0.0)))
    assert r2.observe(1.0, ((0.5,), (4.0, 4.0))) is None
    assert r2.last_value is None


def test_step_latency_regression_factory_shape():
    r = watchtower.step_latency_regression(factor=3.0)
    assert r.metric == "znicz_workflow_step_seconds"
    assert r.reduce == "quantile_ratio" and r.quantile == 0.95
    assert r.predicate(3.5) and not r.predicate(2.5)
    assert r.snapshot()["quantile"] == 0.95


# -- sampler determinism + workflow attachment -------------------------------

def test_metric_history_bit_exact_with_sampler_on_off():
    bare = build(2)
    bare.run()
    bare_hist = bare.decision.metrics_history
    bare.stop()

    tower = Watchtower(step_every=4)
    for make_rule in (watchtower.step_latency_regression,
                      watchtower.serve_queue_saturation,
                      watchtower.nan_guard_trip_rate,
                      watchtower.recompile_storm,
                      watchtower.pipeline_consumer_starvation):
        tower.add_rule(make_rule())
    sampled = build(2, tower=tower)
    sampled.run()
    sampled_hist = sampled.decision.metrics_history
    sampled.stop()

    assert len(tower.ring) > 0, "attached tower never sampled"
    assert sampled_hist == bare_hist     # sampling only READS


def test_on_step_strides_and_detach():
    tower = Watchtower(capacity=8, registry=Registry(), step_every=4)
    w = build(1, tower=tower)
    try:
        assert tower in w.watchtowers
        for _ in range(8):
            tower.on_step()
        assert len(tower.ring) == 2      # every 4th delivery
        tower.detach(w)
        assert w.watchtowers == []
    finally:
        w.stop()


def test_observe_now_noop_while_plane_disabled():
    tower = Watchtower(capacity=8, registry=Registry())
    observe.set_enabled(False)
    assert tower.observe_now() is None
    assert len(tower.ring) == 0
    observe.set_enabled(True)
    assert tower.observe_now() is not None
    assert len(tower.ring) == 1


def test_background_sampler_thread():
    tower = Watchtower(capacity=16, registry=Registry())
    tower.start(interval_s=0.005)
    try:
        with pytest.raises(RuntimeError):
            tower.start(interval_s=0.005)
        deadline = time.monotonic() + 5.0
        while len(tower.ring) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(tower.ring) >= 2
    finally:
        tower.stop()
    assert tower._thread is None
    tower.stop()                         # idempotent


# -- shared histogram quantiles (satellite) ----------------------------------

def test_quantile_from_buckets_matches_serve_percentiles():
    lat = LatencyHistogram()
    rng = np.random.default_rng(7)
    samples = rng.gamma(2.0, 0.015, size=500)      # seconds, ~30ms scale
    for s in samples:
        lat.record(float(s))
    for p in (50.0, 95.0, 99.0):
        shared = quantile_from_buckets(
            lat.edges, lat.counts, p / 100.0,
            overflow_hi=max(lat.edges[-1], lat.sum_ms / lat.total))
        assert lat.percentile(p) == pytest.approx(shared)
    # sanity vs the true sample quantile: same bucket neighbourhood
    true_p95_ms = float(np.quantile(samples, 0.95)) * 1000.0
    assert lat.percentile(95.0) == pytest.approx(true_p95_ms, rel=0.5)


def test_quantiles_in_snapshot_flat():
    reg = Registry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.2, 0.3, 0.5, 2.0):
        h.observe(v)
    flat = reg.snapshot_flat()
    assert flat["lat_seconds_count"] == 5
    for key in ("lat_seconds_p50", "lat_seconds_p95", "lat_seconds_p99"):
        assert key in flat
    assert 0.1 <= flat["lat_seconds_p50"] <= 1.0   # 3rd of 5 samples
    child = h._solo()
    assert child.quantile(0.5) == flat["lat_seconds_p50"]
    assert Registry().histogram("empty", buckets=(1.0,))._solo() \
        .quantile(0.95) == 0.0


def test_quantile_from_buckets_edge_cases():
    assert quantile_from_buckets((1.0, 2.0), (0, 0, 0), 0.95) == 0.0
    # all mass in the overflow bucket interpolates toward overflow_hi
    v = quantile_from_buckets((1.0, 2.0), (0, 0, 4), 0.5,
                              overflow_hi=10.0)
    assert 2.0 < v <= 10.0
    # ... and clamps to the last edge without one
    assert quantile_from_buckets((1.0, 2.0), (0, 0, 4), 0.5) == 2.0


# -- cold-compile metrics (satellite) ----------------------------------------

def test_time_compiles_records_first_call_only():
    calls = []

    def fn(x):
        calls.append(x)
        return x * 2

    key = 'znicz_compile_seconds_count{fn="TowerTestFn"}'
    base = REGISTRY.snapshot_flat().get(key, 0.0)
    wrapped = probe.time_compiles("TowerTestFn", fn)
    assert probe.time_compiles("TowerTestFn", None) is None
    assert wrapped(3) == 6 and wrapped(4) == 8
    assert calls == [3, 4]
    flat = REGISTRY.snapshot_flat()
    assert flat[key] == base + 1.0       # only the cold call lands
    assert wrapped._cache_size() == 0    # no _cache_size on a plain fn
    names = [e["name"] for e in observe.TRACER.tail(16)]
    assert "compile.cold" in names


# -- JSONL sink rotation (satellite) -----------------------------------------

def test_jsonl_sink_rotates_at_byte_bound(tmp_path):
    path = str(tmp_path / "events.jsonl")
    handler = JsonlHandler(path, max_bytes=512)
    log = logging.getLogger("znicz_tpu.test_rotation")
    log.propagate = False
    log.setLevel(logging.INFO)
    log.addHandler(handler)
    try:
        for i in range(50):
            log.info("rotation probe %04d padding-padding-padding", i)
    finally:
        log.removeHandler(handler)
        handler.close()
    assert os.path.isfile(path) and os.path.isfile(path + ".1")
    assert os.path.getsize(path) <= 512
    assert os.path.getsize(path + ".1") <= 512
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    assert lines, "live file empty after rollover"
    assert lines[-1]["msg"].startswith("rotation probe 0049")
    with open(path + ".1") as f:
        for ln in f:
            json.loads(ln)               # rollover file is intact JSONL


def test_jsonl_unbounded_by_default(tmp_path):
    path = str(tmp_path / "events.jsonl")
    handler = JsonlHandler(path)
    log = logging.getLogger("znicz_tpu.test_rotation2")
    log.propagate = False
    log.setLevel(logging.INFO)
    log.addHandler(handler)
    try:
        for i in range(50):
            log.info("unbounded %04d", i)
    finally:
        log.removeHandler(handler)
        handler.close()
    assert not os.path.exists(path + ".1")
    with open(path) as f:
        assert len(f.readlines()) == 50


# -- flight recorder ---------------------------------------------------------

#: the pinned artifact schema: a reader of flight/2 may rely on exactly
#: these keys being present ("planes" — registered live-subsystem
#: snapshot providers — is the /1 -> /2 addition, ISSUE 11)
FLIGHT_KEYS = {"schema", "reason", "ts", "iso", "host", "pid", "extra",
               "spans", "timeseries", "metrics", "planes", "config",
               "log_tail"}


def test_flight_artifact_schema_pinned(tmp_path):
    path = flight.dump(dir=str(tmp_path), reason="schema pin",
                       extra={"k": 1})
    assert os.path.basename(path).startswith("flight_")
    doc = flight.load(path)
    assert doc["schema"] == "znicz_tpu.flight/2"
    assert set(doc) == FLIGHT_KEYS
    assert doc["reason"] == "schema pin" and doc["extra"] == {"k": 1}
    assert doc["pid"] == os.getpid()
    ts = doc["timeseries"]
    assert {"capacity", "base_ts", "base", "samples", "summary",
            "rules"} <= set(ts)
    assert len(ts["samples"]) >= 1       # dump takes a fresh sample
    assert isinstance(doc["metrics"], dict) and doc["metrics"]
    assert "argv" in doc["config"]
    assert not os.path.exists(path + ".tmp")   # atomic publish


def test_flight_load_rejects_non_artifacts(tmp_path):
    bogus = tmp_path / "x.json"
    bogus.write_text('{"schema": "something/else"}')
    with pytest.raises(ValueError):
        flight.load(str(bogus))


def test_flight_span_window_limit(tmp_path):
    for i in range(40):
        observe.instant("flight.filler", i=i)
    doc = flight.build_artifact("window", last_spans=8)
    assert len(doc["spans"]) == 8
    assert doc["spans"][-1]["name"] in ("flight.filler",)


def test_auto_dump_gated_and_rate_limited(tmp_path):
    assert flight.auto_dump("unconfigured") is None
    assert not list(tmp_path.iterdir())
    flight.configure(dir=str(tmp_path), min_interval_s=3600.0)
    first = flight.auto_dump("fault", site="x")
    assert first is not None and os.path.isfile(first)
    assert flight.auto_dump("fault", site="x") is None   # rate-limited
    flight.configure()                   # opt back out
    assert flight.auto_dump("fault") is None


def test_fault_firing_auto_dumps_when_configured(tmp_path):
    flight.configure(dir=str(tmp_path), min_interval_s=0.0)
    plan = faults.FaultPlan(seed=0)
    plan.oserror_at("flight.site", at_hit=1)
    with faults.active(plan):
        with pytest.raises(OSError):
            faults.fault_hook("flight.site")
    dumps = sorted(tmp_path.glob("flight_*_fault.json"))
    assert len(dumps) == 1
    doc = flight.load(str(dumps[0]))
    assert doc["reason"] == "fault"
    assert doc["extra"]["site"] == "flight.site"


def test_flight_cli_pretty_print_and_json(tmp_path, capsys):
    path = flight.dump(dir=str(tmp_path), reason="cli check")
    assert flight.flight_main([path]) == 0
    out = capsys.readouterr().out
    assert "cli check" in out and "timeseries:" in out
    assert flight.flight_main([path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == flight.SCHEMA
    assert flight.flight_main([]) == 2
    assert flight.flight_main([str(tmp_path / "missing.json")]) == 1


# -- the acceptance chaos test -----------------------------------------------

def test_supervised_crash_leaves_valid_flight_artifact(tmp_path):
    """Seeded workflow.step crash under run_supervised: the supervisor
    dumps a flight BEFORE restore-and-resume, and the artifact carries
    the crashing span (error-marked), the fault's resilience instant,
    and >= 1 time-series sample (ISSUE 6 acceptance)."""
    tower = Watchtower(step_every=4)
    snap_dir = tmp_path / "chaos"
    plan = faults.FaultPlan(seed=1234)
    plan.crash_at("workflow.step", when=lambda workflow, unit:
                  int(workflow.decision.epoch_number) == 1)
    with faults.active(plan):
        report = run_supervised(
            lambda: build(3, snap_dir, tower=tower), str(snap_dir),
            SupervisorPolicy(sleep=lambda s: None))
    assert plan.log, "the armed crash never fired"
    assert report.restarts == 1
    assert len(report.flights) == 1
    path = report.flights[0]
    assert os.path.dirname(path) == str(snap_dir)

    doc = flight.load(path)              # schema-checked read
    assert doc["reason"] == "restart"
    assert doc["extra"]["error_type"] == "FaultInjected"
    assert len(doc["timeseries"]["samples"]) >= 1

    spans = doc["spans"]
    crashing = [e for e in spans if e["name"] == "workflow.step"
                and e.get("args", {}).get("error")]
    assert crashing, "flight lost the crashing step span"
    instants = [e for e in spans if e["name"] == "resilience.fault"]
    assert instants, "flight lost the fault's resilience instant"
    # the fault instant precedes the crashing span's END on the ring:
    # same timeline, ordered
    assert spans.index(instants[-1]) <= spans.index(crashing[-1]) + 1

    # the supervised run still finishes training after the dump
    assert len(report.workflow.decision.metrics_history) == 3
    report.workflow.stop()


def test_supervisor_flight_recorder_opt_out(tmp_path):
    plan = faults.FaultPlan(seed=7)
    plan.crash_at("workflow.step", at_hit=5)
    snap_dir = tmp_path / "noflight"
    with faults.active(plan):
        report = run_supervised(
            lambda: build(2, snap_dir), str(snap_dir),
            SupervisorPolicy(sleep=lambda s: None,
                             flight_recorder=False))
    assert report.restarts == 1 and report.flights == []
    assert not list(snap_dir.glob("flight_*.json"))
    report.workflow.stop()


# -- scrape surfaces ---------------------------------------------------------

def test_status_json_and_timeseries_endpoint():
    observe.WATCHTOWER.observe_now()
    status = WebStatus()
    doc = status.snapshot()
    assert "watchtower" in doc
    assert doc["watchtower"]["samples"] == len(observe.WATCHTOWER.ring)
    json.dumps(doc)                      # wire-serializable

    import urllib.request
    port = status.start()
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/timeseries.json", timeout=10)
        assert resp.status == 200
        ts_doc = json.load(resp)
        assert ts_doc["capacity"] == observe.WATCHTOWER.ring.capacity
        assert ts_doc["samples"], "served ring is empty"
        replay = dict(ts_doc["base"])
        for row in ts_doc["samples"]:
            replay.update(row["delta"])
        assert replay == observe.WATCHTOWER.ring.current()
    finally:
        status.stop()
