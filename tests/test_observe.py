"""Unified telemetry plane (znicz_tpu/observe/): the shared metrics
registry (Counter/Gauge/Histogram, labels, Prometheus text exposition),
the bounded-ring span tracer (Chrome-trace export), the automatic
probes wired through the workflow run loop, and the scrape surfaces
(`WebStatus` `/metrics` + `/trace.json`, `snapshot()` merge).  The
plane's contract with training: instrumentation disabled reduces the
walk to the bare loop with bit-exact metric histories, and the ring
buffer stays bounded under a 10k-step soak."""

import json
import logging
import math
import re
import threading
import urllib.request

import pytest

from znicz_tpu import observe
from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.core.logger import EVENT_LOGGER, configure, event_log
from znicz_tpu.observe import probe
from znicz_tpu.observe.registry import Registry
from znicz_tpu.observe.trace import Tracer
from znicz_tpu.resilience import faults
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


def run_workflow(max_epochs=2, seed=77, name="ObserveTest"):
    prng.seed_all(seed)
    w = StandardWorkflow(
        name=name, layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": max_epochs})
    w.initialize(device=XLADevice())
    w.run()
    return w


@pytest.fixture(autouse=True)
def _observe_on():
    """Every test leaves the plane the way production boots it."""
    yield
    observe.set_enabled(True)


# -- registry primitives ----------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = Registry()
    c = reg.counter("c_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.get() == 3.5
    g = reg.gauge("g")
    g.set(4.0)
    g.dec(1.5)
    assert g.get() == 2.5
    g.set_function(lambda: 9.0)
    assert g.get() == 9.0
    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    d = h._solo().hist_dict()
    assert d["count"] == 3 and d["sum"] == pytest.approx(5.55)
    assert d["buckets"] == {"0.1": 1, "1": 1, "+Inf": 1}


def test_registry_get_or_create_idempotent_and_type_safe():
    reg = Registry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a          # same family back
    with pytest.raises(ValueError):
        reg.gauge("x_total")                    # re-typed -> error
    reg.counter("lbl_total", labelnames=("site",))
    with pytest.raises(ValueError):
        reg.counter("lbl_total", labelnames=("other",))
    reg.histogram("lat", buckets=(0.1, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("lat", buckets=(5.0, 10.0))  # silent re-bucketing
    assert reg.histogram("lat", buckets=(0.1, 1.0)) is not None


def test_registry_labels():
    reg = Registry()
    fam = reg.counter("ev_total", labelnames=("kind", "site"))
    fam.labels(kind="fault", site="a").inc()
    fam.labels(kind="fault", site="a").inc()
    fam.labels(kind="retry", site="b").inc(3)
    snap = reg.snapshot()["ev_total"]
    got = {tuple(sorted(v["labels"].items())): v["value"]
           for v in snap["values"]}
    assert got[(("kind", "fault"), ("site", "a"))] == 2
    assert got[(("kind", "retry"), ("site", "b"))] == 3
    with pytest.raises(ValueError):
        fam.labels(kind="fault")                # missing label
    with pytest.raises(ValueError):
        fam.inc()                               # labeled family, no labels


def test_registry_gauge_provider_failure_is_nan_not_crash():
    reg = Registry()
    g = reg.gauge("live")

    def dead():
        raise RuntimeError("provider torn down")

    g.set_function(dead)
    assert math.isnan(g.get())
    assert "live" in reg.render_prometheus()         # scrape survives


def test_snapshot_flat_drops_zero_series():
    reg = Registry()
    reg.counter("a_total").inc(2)
    reg.counter("zero_total")
    h = reg.histogram("lat", buckets=(1.0,))
    h.observe(0.5)
    flat = reg.snapshot_flat()
    assert flat["a_total"] == 2
    assert "zero_total" not in flat
    assert flat["lat_count"] == 1 and flat["lat_sum"] == 0.5


# -- Prometheus text exposition ---------------------------------------------

_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? (-?[0-9.e+-]+|nan|inf)$")
_META = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*( .*)?$")


def _parse_exposition(text):
    """Minimal format-0.0.4 checker: every line is HELP/TYPE metadata or
    a sample; every sample belongs to a declared family.  Returns
    {family: type} and {sample_name: [(labels_str, value)]}."""
    types, samples = {}, {}
    assert text.endswith("\n")
    for line in text.splitlines():
        if line.startswith("#"):
            assert _META.match(line), f"bad metadata line: {line!r}"
            if line.startswith("# TYPE"):
                _, _, name, mtype = line.split(" ", 3)
                types[name] = mtype
            continue
        m = _SAMPLE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, labels, value = m.groups()
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in types:
                base = name[:-len(suffix)]
        assert base in types, f"sample {name!r} has no TYPE declaration"
        samples.setdefault(name, []).append((labels or "", float(value)))
    return types, samples


def test_render_prometheus_parses_and_histogram_is_cumulative():
    reg = Registry()
    reg.counter("req_total", "requests", labelnames=("code",)) \
       .labels(code="200").inc(7)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.05, 0.5, 20.0):
        h.observe(v)
    types, samples = _parse_exposition(reg.render_prometheus())
    assert types == {"req_total": "counter", "lat_seconds": "histogram"}
    assert samples['req_total'] == [('{code="200"}', 7.0)]
    buckets = samples["lat_seconds_bucket"]
    counts = [v for _, v in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert buckets[-1][0] == '{le="+Inf"}'
    assert buckets[-1][1] == samples["lat_seconds_count"][0][1] == 4.0
    assert samples["lat_seconds_sum"][0][1] == pytest.approx(20.6)


def test_global_registry_stable_metric_names():
    """The catalogue names docs/OBSERVABILITY.md promises are what a
    scraper keys dashboards on — pin them."""
    import znicz_tpu.pipeline.prefetcher          # noqa: F401 — declares
    import znicz_tpu.serve.metrics                # noqa: F401 — declares
    text = observe.REGISTRY.render_prometheus()
    types, _ = _parse_exposition(text)
    for name, mtype in (
            ("znicz_workflow_step_seconds", "histogram"),
            ("znicz_workflow_signals_total", "counter"),
            ("znicz_unit_runs_total", "counter"),
            ("znicz_unit_run_seconds_total", "counter"),
            ("znicz_recompiles_total", "counter"),
            ("znicz_resilience_events_total", "counter"),
            ("znicz_pipeline_bytes_staged_total", "counter"),
            ("znicz_pipeline_queue_fill", "gauge"),
            ("znicz_serve_requests_total", "counter"),
            ("znicz_serve_latency_seconds", "histogram"),
            ("znicz_serve_qps", "gauge")):
        assert types.get(name) == mtype, (name, types.get(name))


# -- tracer ------------------------------------------------------------------

def test_tracer_ring_bounded_under_10k_step_soak():
    tr = Tracer(capacity=512)
    for step in range(10_000):
        with tr.span("workflow.step", step=step):
            pass
    assert len(tr) == 512                       # memory flat, newest kept
    doc = tr.export_dict()
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 512
    assert spans[-1]["args"]["step"] == 9_999   # newest window survives


def test_tracer_export_chrome_trace_shape(tmp_path):
    tr = Tracer()
    with tr.span("workflow.step", step=1):
        tr.instant("resilience.fault", site="workflow.step")
    out = tmp_path / "trace.json"
    n = tr.export(str(out))
    assert n == 2
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}
    span = evs["workflow.step"]
    inst = evs["resilience.fault"]
    assert span["ph"] == "X" and span["dur"] >= 0 and \
        span["cat"] == "workflow"
    assert inst["ph"] == "i" and inst["s"] == "t" and \
        inst["args"]["site"] == "workflow.step"
    # the instant fired INSIDE the span: same timeline, nested stamps
    assert span["ts"] <= inst["ts"] <= span["ts"] + span["dur"]
    names = [e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert names == ["znicz_tpu"]


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    s1 = tr.span("a")
    s2 = tr.span("b", k=1)
    assert s1 is s2                             # shared no-op singleton
    with s1:
        pass
    tr.instant("x")
    tr.complete("y", 0.0, 1.0)
    assert len(tr) == 0


# -- probes -------------------------------------------------------------------

class _FakeJitted:
    def __init__(self):
        self.size = 0

    def _cache_size(self):
        return self.size


def test_watch_compiles_counts_cache_growth():
    before = observe.TRACER.enabled
    fn = _FakeJitted()
    probe.watch_compiles("test_fake_step", fn, object())  # non-jit dropped
    try:
        assert probe.check_recompiles() == 0    # baseline swallowed
        fn.size = 1                             # first compile
        assert probe.check_recompiles() == 1
        assert probe.check_recompiles() == 0    # steady state
        fn.size = 3                             # surprise recompiles
        assert probe.check_recompiles() == 2
        fam = observe.REGISTRY.get("znicz_recompiles_total")
        assert fam.labels(fn="test_fake_step").get() == 3
    finally:
        probe.unwatch_compiles("test_fake_step")
        observe.TRACER.enabled = before


def test_watch_compiles_per_instance_keys_share_a_label():
    """Two live steps of one class watch independently (separate keys,
    one metric label); a dead step's entry is reaped via its weakrefs
    instead of masking the survivor."""
    before = observe.TRACER.enabled
    a, b = _FakeJitted(), _FakeJitted()
    probe.watch_compiles("fake-a", a, label="test_fake_shared")
    probe.watch_compiles("fake-b", b, label="test_fake_shared")
    fam = observe.REGISTRY.get("znicz_recompiles_total")
    base = fam.labels(fn="test_fake_shared").get()
    try:
        a.size = 1
        b.size = 2
        assert probe.check_recompiles() == 3    # both still polled
        assert fam.labels(fn="test_fake_shared").get() == base + 3
        del b                                   # one step dies
        a.size = 2
        assert probe.check_recompiles() == 1    # survivor still watched
        assert "fake-b" not in probe._watched   # dead entry reaped
    finally:
        probe.unwatch_compiles("fake-a")
        probe.unwatch_compiles("fake-b")
        observe.TRACER.enabled = before


def test_resilience_events_share_counter_and_timeline():
    fam = observe.REGISTRY.get("znicz_resilience_events_total")
    child = fam.labels(kind="fault", site="observe.test")
    base_counter = child.get()
    base_ring = len(observe.TRACER)
    plan = faults.FaultPlan(seed=0).crash_at("observe.test", at_hit=1)
    try:
        with faults.active(plan):
            with pytest.raises(faults.FaultInjected):
                faults.fault_hook("observe.test")
    finally:
        faults.uninstall()
    assert child.get() == base_counter + 1
    newest = list(observe.TRACER._events)[-1]
    assert newest[0] == "i" and newest[1] == "resilience.fault"
    assert len(observe.TRACER) == base_ring + 1


def test_disabled_plane_stops_probes_but_scrape_still_parses():
    events = observe.REGISTRY.get("znicz_resilience_events_total")
    staged = observe.REGISTRY.get("znicz_pipeline_bytes_staged_total")
    child = events.labels(kind="fault", site="observe.disabled")
    observe.set_enabled(False)
    assert not probe.enabled() and not observe.TRACER.enabled
    ev_before, st_before = child.get(), staged.get()
    ring_before = len(observe.TRACER)
    probe.resilience_event("fault", site="observe.disabled")
    probe.staged_bytes(100)
    assert probe.check_recompiles() == 0
    assert child.get() == ev_before and staged.get() == st_before
    assert len(observe.TRACER) == ring_before
    # families stay registered while values hold still: a scrape during
    # a disabled window still parses
    _parse_exposition(observe.REGISTRY.render_prometheus())


# -- workflow integration -----------------------------------------------------

def test_workflow_run_populates_registry_and_trace():
    w = run_workflow(max_epochs=2, name="ObserveRunA")
    try:
        types, samples = _parse_exposition(
            observe.REGISTRY.render_prometheus())
        # step-latency histogram moved, one observation per dispatch
        count = samples["znicz_workflow_step_seconds_count"][0][1]
        assert count >= w.signals_dispatched > 0
        # per-unit counters mirror the units' own timers
        fam = observe.REGISTRY.get("znicz_unit_runs_total")
        for u in w.units:
            if u._run_count:
                assert fam.labels(workflow="ObserveRunA",
                                  unit=u.name).get() == u._run_count
        # the jitted step registered with the recompile watcher and its
        # first compile was observed
        rec = observe.REGISTRY.get("znicz_recompiles_total")
        assert rec.labels(fn="FusedTrainStep").get() >= 1
        # step spans landed on the timeline
        names = {e[1] for e in observe.TRACER._events}
        assert "workflow.step" in names and "workflow.run" in names
    finally:
        w.stop()


def test_timing_table_reads_from_registry():
    w = run_workflow(max_epochs=2, name="ObserveTimingB")
    try:
        table = w.timing_table()
        fam = observe.REGISTRY.get("znicz_unit_runs_total")
        for u in w.units:
            if u._run_count:
                assert u.name in table
                assert fam.labels(workflow="ObserveTimingB",
                                  unit=u.name).get() == u._run_count
    finally:
        w.stop()


def test_timing_table_falls_back_to_unit_timers_when_disabled():
    """observe.set_enabled(False) must not blank the table — the units'
    local timers (pre-telemetry behavior) are the fallback source."""
    observe.set_enabled(False)
    try:
        w = run_workflow(max_epochs=2, name="ObserveDisabledTable")
        table = w.timing_table()
        w.stop()
    finally:
        observe.set_enabled(True)
    for u in w.units:
        if u._run_count:
            assert u.name in table, table


def test_add_unit_invalidates_cached_observer_labels():
    """A unit that ran standalone (workflow="") and is then adopted must
    donate to the adopting workflow's series, not the stale label."""
    from znicz_tpu.core.units import Unit
    from znicz_tpu.core.workflow import Workflow

    class Tick(Unit):
        def run(self):
            pass

    prng.seed_all(1)
    t = Tick(name="AdoptedTick")
    t._timed_run()                       # caches workflow="" children
    w = Workflow(name="ObserveAdopter")
    w.add_unit(t)
    assert t._observers is None          # cache dropped on adoption
    t._timed_run()
    fam = observe.REGISTRY.get("znicz_unit_runs_total")
    assert fam.labels(workflow="ObserveAdopter",
                      unit="AdoptedTick").get() == 1
    assert fam.labels(workflow="", unit="AdoptedTick").get() == 1


def test_serve_metrics_mirrors_honor_master_switch():
    from znicz_tpu.serve.metrics import ServingMetrics

    reqs = observe.REGISTRY.get("znicz_serve_requests_total")
    lat = observe.REGISTRY.get("znicz_serve_latency_seconds")
    done = reqs.labels(event="completed")
    base_done, base_lat = done.get(), lat._solo().hist_dict()["count"]
    m = ServingMetrics()
    observe.set_enabled(False)
    try:
        m.on_admit()
        m.on_batch(4)
        m.on_complete(0.01)
    finally:
        observe.set_enabled(True)
    assert m.admitted == 1 and m.completed == 1   # instance truth moves
    assert done.get() == base_done                # shared plane holds
    assert lat._solo().hist_dict()["count"] == base_lat
    m.on_complete(0.01)                           # re-enabled -> moves
    assert done.get() == base_done + 1


def test_metric_history_bit_exact_with_plane_disabled():
    """ISSUE 5 acceptance: spans/probes off => the bare pre-telemetry
    walk, bit-exact metric histories (same discipline as the pipeline
    prefetch bit-exactness harness)."""
    w_on = run_workflow(max_epochs=3, seed=91, name="ObserveOn")
    hist_on = w_on.decision.metrics_history
    w_on.stop()
    observe.set_enabled(False)
    try:
        w_off = run_workflow(max_epochs=3, seed=91, name="ObserveOff")
        hist_off = w_off.decision.metrics_history
        w_off.stop()
    finally:
        observe.set_enabled(True)
    assert hist_on == hist_off
    # toggling mid-run sequence changes nothing either
    w_again = run_workflow(max_epochs=3, seed=91, name="ObserveOn2")
    assert w_again.decision.metrics_history == hist_on
    w_again.stop()


# -- WebStatus merge + endpoints ---------------------------------------------

def test_web_status_snapshot_merges_all_blocks_without_collisions():
    w = run_workflow(max_epochs=1, name="ObserveMergeC")
    status = (WebStatus()
              .register(w)
              .register_serving("front", lambda: {"qps": 1.5})
              .register_health("trainer", lambda: {"nan_trips": 0})
              .register_pipeline("train_input", lambda: {"depth": 2}))
    try:
        doc = status.snapshot()
    finally:
        w.stop()
    assert set(doc) == {"workflows", "serving", "health", "pipeline",
                        "metrics", "watchtower"}   # disjoint, no collisions
    assert doc["workflows"][0]["name"] == "ObserveMergeC"
    assert doc["serving"] == {"front": {"qps": 1.5}}
    assert doc["health"] == {"trainer": {"nan_trips": 0}}
    assert doc["pipeline"] == {"train_input": {"depth": 2}}
    assert doc["metrics"]["znicz_workflow_signals_total"]["type"] == \
        "counter"
    json.dumps(doc)                               # wire-serializable


def test_web_status_dead_provider_isolated():
    def dead():
        raise RuntimeError("boom")

    doc = WebStatus().register_serving("dead", dead).snapshot()
    assert "error" in doc["serving"]["dead"]
    assert "metrics" in doc                       # the plane still rides


def test_metrics_and_trace_endpoints():
    w = run_workflow(max_epochs=1, name="ObserveHttpD")
    status = WebStatus().register(w)
    port = status.start()
    base = f"http://127.0.0.1:{port}"
    try:
        resp = urllib.request.urlopen(base + "/metrics")
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        types, samples = _parse_exposition(resp.read().decode())
        assert types["znicz_workflow_step_seconds"] == "histogram"
        assert samples["znicz_workflow_signals_total"][0][1] > 0

        resp = urllib.request.urlopen(base + "/trace.json")
        assert resp.status == 200
        doc = json.load(resp)
        assert any(e["name"] == "workflow.step"
                   for e in doc["traceEvents"])

        doc = json.load(urllib.request.urlopen(base + "/status.json"))
        assert "metrics" in doc and doc["workflows"]
    finally:
        status.stop()
        w.stop()


# -- structured JSONL log stream ---------------------------------------------

def test_jsonl_log_handler_interleaves_events_and_log_lines(tmp_path):
    path = tmp_path / "run.jsonl"
    configure(jsonl_path=str(path))
    try:
        logging.getLogger("znicz_tpu.test").warning("plain %s", "line")
        event_log("compile.recompile", {"fn": "step", "new": 1})
        observe.instant("resilience.restart", attempt=2)
    finally:
        root_logger = logging.getLogger()
        for h in list(root_logger.handlers):
            if getattr(h, "baseFilename", None) == str(path):
                root_logger.removeHandler(h)
                h.close()
    docs = [json.loads(line) for line in
            path.read_text().strip().splitlines()]
    assert len(docs) == 3
    assert docs[0]["msg"] == "plain line" and docs[0]["level"] == "WARNING"
    assert docs[0]["logger"] == "znicz_tpu.test"
    assert docs[1]["event"] == "compile.recompile"
    assert docs[1]["args"] == {"fn": "step", "new": 1}
    assert docs[1]["logger"] == EVENT_LOGGER
    # tracer instants ride the same stream (trace -> event_log)
    assert docs[2]["event"] == "resilience.restart"
    assert docs[2]["args"] == {"attempt": 2}


# -- CLI ----------------------------------------------------------------------

def test_cli_trace_subcommand_usage():
    from znicz_tpu.__main__ import main
    assert main(["trace"]) == 2
    assert main(["trace", "out.json"]) == 2
