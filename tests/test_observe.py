"""Unified telemetry plane (znicz_tpu/observe/): the shared metrics
registry (Counter/Gauge/Histogram, labels, Prometheus text exposition),
the bounded-ring span tracer (Chrome-trace export), the automatic
probes wired through the workflow run loop, and the scrape surfaces
(`WebStatus` `/metrics` + `/trace.json`, `snapshot()` merge).  The
plane's contract with training: instrumentation disabled reduces the
walk to the bare loop with bit-exact metric histories, and the ring
buffer stays bounded under a 10k-step soak."""

import importlib.util
import json
import logging
import math
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from znicz_tpu import observe
from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.core.logger import EVENT_LOGGER, configure, event_log
from znicz_tpu.observe import probe
from znicz_tpu.observe import trace as trace_mod
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


# -- ISSUE 24: one tracer, two sinks; named scopes; the readers ---------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONV_LAYERS = [
    {"type": "conv_str", "->": {"n_kernels": 4, "kx": 3, "ky": 3},
     "<-": {"learning_rate": 0.01}},
    {"type": "norm", "->": {"alpha": 1e-4, "beta": 0.75, "k": 2.0, "n": 3}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2, "sliding": (2, 2)}},
    {"type": "dropout", "->": {"dropout_ratio": 0.5}},
    {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
     "<-": {"learning_rate": 0.01}},
    {"type": "activation_tanh"},
    {"type": "softmax", "->": {"output_sample_shape": 6},
     "<-": {"learning_rate": 0.01}},
]
CONV_SCOPES = ["conv.00_", "norm.01_", "pool.02_", "dropout.03_", "fc.04_",
               "act.05_", "fc.06_"]


@pytest.fixture(scope="module")
def conv_workflow():
    """A tiny fused step with one unit of every scope group, run once."""
    prng.seed_all(5)
    w = StandardWorkflow(
        name="ScopeTest", layers=CONV_LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier",
        loader_config={**LOADER, "sample_shape": (10, 10, 1)},
        decision_config={"max_epochs": 1})
    w.initialize(device=XLADevice())
    w.run()
    return w


def _reader(name):
    """A reader of the benchmark, imported by path (the tier-1 command
    does not collect benchmark/tests)."""
    bench = os.path.join(REPO, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        f"_reader_{name}", os.path.join(bench, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_live_span_lies_in_the_profilers_host_plane(tmp_path):
    """Under ``jax.profiler.start_trace`` a span is in /host:CPU with the
    ring's name, its args, and the ring's duration within 5 %."""
    import glob

    import jax

    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("obs.live", unit="u1"):
            time.sleep(0.05)
        with tracer.timed("obs.timed", {"unit": "u2"}) as sp:
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    host = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("obs."):
                        host[ev.name] = (ev.duration_ns, dict(ev.stats))
    ring = {e[1]: e for e in tracer._events}
    assert set(host) == {"obs.live", "obs.timed"} == tracer.live_names
    for name, (dur_ns, stats) in host.items():
        assert dur_ns / 1e3 == pytest.approx(ring[name][3], rel=0.05)
        assert stats["unit"] == ring[name][5]["unit"]
    assert sp.dt * 1e6 == ring["obs.timed"][3]        # the same two reads


def test_disabled_tracer_annotates_nothing_and_imports_no_jax():
    """``observe/trace.py`` alone (no package import) in a fresh process:
    spans work, enabled or not, and jax is never imported for them."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('t', "
        f"{os.path.join(REPO, 'znicz_tpu', 'observe', 'trace.py')!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "off = m.Tracer(enabled=False)\n"
        "assert off.span('a') is m._NOOP\n"
        "on = m.Tracer()\n"
        "with on.span('b', k=1): pass\n"
        "with on.timed('c') as sp: pass\n"
        "assert m._annotation('b', None) is None\n"
        "assert [e[1] for e in on._events] == ['b', 'c'] and sp.dt >= 0\n"
        "assert 'jax' not in sys.modules\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    # in this process jax is loaded: a disabled tracer still makes none
    made = []
    real = trace_mod._annotation
    trace_mod._annotation = lambda *a: made.append(a) or real(*a)
    try:
        off = Tracer(enabled=False)
        with off.span("x"):
            pass
        with off.timed("y") as sp:
            pass
    finally:
        trace_mod._annotation = real
    assert made == [] and len(off) == 0 and sp.dt >= 0.0


def test_timed_span_track_override_and_error_mark():
    tracer = Tracer()
    with pytest.raises(KeyError):
        with tracer.timed("obs.err", {"unit": "u"}, tid=4242):
            raise KeyError("boom")
    ph, name, _, _, tid, args = tracer._events[-1]
    assert (ph, name, tid) == ("X", "obs.err", 4242)
    assert args == {"unit": "u", "error": True}


def test_workflow_step_error_span_lands_when_a_unit_raises():
    from znicz_tpu.core.units import Unit

    w = run_workflow(max_epochs=1, name="ObserveErr")

    class Boom(Unit):
        def run(self):
            raise RuntimeError("unit failed")

    boom = Boom(w, name="Boom")
    boom.link_from(w.start_point)
    w.initialize(device=XLADevice())
    w.decision.complete.set(False)
    observe.TRACER.clear()
    with pytest.raises(RuntimeError, match="unit failed"):
        w.run()
    spans = [e for e in observe.TRACER.export_dict()["traceEvents"]
             if e["name"] == "workflow.step"]
    assert spans[-1]["args"] == {"unit": "Boom", "error": True}
    assert all("error" not in e["args"] for e in spans[:-1])


def test_fused_step_spans_and_step_cadence_by_default(conv_workflow):
    """No switch: a default run leaves ``train.dispatch`` and
    ``train.metrics_read`` spans and observations in
    ``znicz_anatomy_step_seconds{plane="fused"}``."""
    before = observe.REGISTRY.snapshot_flat(skip_zero=False).get(
        'znicz_anatomy_step_seconds_count{plane="fused"}', 0.0)
    observe.TRACER.clear()
    w = run_workflow(max_epochs=1, name="ObserveCadence")
    names = [e["name"] for e in observe.TRACER.export_dict()["traceEvents"]]
    n_dispatch = names.count("train.dispatch")
    assert n_dispatch == 9                # 6 train + 3 validation batches
    assert names.count("train.metrics_read") == 2    # one a class pass
    after = observe.REGISTRY.snapshot_flat(skip_zero=False)[
        'znicz_anatomy_step_seconds_count{plane="fused"}']
    assert after - before == n_dispatch - 1
    assert {"train.dispatch", "train.metrics_read",
            "workflow.step"} <= observe.TRACER.live_names
    assert w.step.loss >= 0.0


def test_lowered_fused_step_holds_every_scope_once_per_layer(conv_workflow):
    st = conv_workflow.step
    fn = st._train_fn_idx
    text = fn.lower(*fn._abstract[0]).as_text(debug_info=True)
    unit_scopes = sorted(n for n in probe._scope_names if "." in n and
                         n.split(".")[0] in ("conv", "fc", "norm", "pool",
                                             "dropout", "act") and
                         n.endswith(tuple(f.name for f in st.forwards)))
    assert [s[:len(p)] for s, p in zip(unit_scopes, sorted(CONV_SCOPES))] \
        == sorted(CONV_SCOPES)
    for scope in unit_scopes:
        assert f"jvp({scope})/" in text, scope
        if not scope.startswith(("dropout", "act")):
            assert f"transpose(jvp({scope}))/" in text, scope
    for scope in ("gather_batch", "update", "grad_reduce"):
        assert f"/{scope}/" in text, scope
    assert "jvp(loss)/" in text and "transpose(jvp(loss))/" in text
    assert "zero_gather" not in text      # no shard_params here


def test_scope_map_covers_the_program_and_is_built_only_when_called(
        conv_workflow):
    from znicz_tpu import compilecache

    calls = []
    real = probe.parse_scopes
    probe.parse_scopes = lambda *a, **k: calls.append(1) or real(*a, **k)
    try:
        stats = compilecache.stats()
        run_workflow(max_epochs=1, name="ObserveLazy")
        assert calls == []               # a run never builds the map
        assert compilecache.stats()["misses"] >= stats["misses"]
        scopes = probe.scope_map()
    finally:
        probe.parse_scopes = real
    assert calls
    train = scopes["jit__local_train_idx"]
    fn = conv_workflow.step._train_fn_idx
    text = fn.lower(*fn._abstract[0]).compile().as_text()
    names = {m.group(2) for m in map(probe._INSTRUCTION.match,
                                     text.splitlines())
             if m and m.group(3) not in probe.TRIVIAL_OPCODES}
    assert names and names <= set(train)
    found = {c.rstrip(")").rsplit("(", 1)[-1] for c in train.values()}
    assert {"gather_batch", "loss", "update"} <= found
    assert all(any(f.startswith(p) for f in found) for p in CONV_SCOPES)
    unscoped = [n for n, c in train.items() if not c]
    assert len(unscoped) <= 0.05 * len(train), unscoped[:10]


def test_scope_map_survives_a_cache_entry_from_an_unscoped_build(
        tmp_path, monkeypatch):
    """The persistent cache's key leaves metadata out, so a scoped step
    may run an executable that an unscoped build of the same program put
    there (as the parent commit's did on the chip): the map must still
    come from this build's own annotation."""
    import contextlib
    import gc

    from znicz_tpu import compilecache

    prev = compilecache.active_dir()
    compilecache.configure(cache_dir=str(tmp_path), min_compile_time_s=0.0,
                           force=True)
    try:
        monkeypatch.setattr(probe, "scope",
                            lambda name: contextlib.nullcontext())
        run_workflow(max_epochs=1, name="ScopeStaleA")
        monkeypatch.undo()
        gc.collect()
        hits = compilecache.stats()["hits"]
        w = run_workflow(max_epochs=1, name="ScopeStaleB")
        assert compilecache.stats()["hits"] > hits      # A's executable
        fn = w.step._train_fn_idx
        memo = fn.lower(*fn._abstract[0]).compile().as_text()
        assert "fc.00_" not in memo          # what runs has A's metadata
        train = probe.scope_map()["jit__local_train_idx"]
    finally:
        compilecache.configure(cache_dir=prev, force=True)
    found = {c.rstrip(")").rsplit("(", 1)[-1] for c in train.values()}
    assert {"gather_batch", "loss", "update"} <= found
    assert any(f.startswith("fc.00_") for f in found)
    assert sum(1 for c in train.values() if not c) <= 0.05 * len(train)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/jit(main)/conv.00_c/conv_general_dilated", "conv.00_c"),
    ("jit(f)/jvp(conv.00_c)/inner/mul", "jvp(conv.00_c)"),
    ("jit(f)/transpose(jvp(conv.00_c))/mul", "transpose(jvp(conv.00_c))"),
    ("jit(f)/while/body/update/sub", "update"),
    ("jit(f)/jit(_threefry_split)/xor", ""),
])
def test_scope_of_keeps_the_component_as_it_stands(op_name, scope):
    assert probe.scope_of(op_name, {"conv.00_c", "update"}) == scope


def test_parse_scopes_fusion_root_and_bare_copies_inherit():
    hlo = """HloModule jit_step, is_scheduled=true

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/jvp(fc.00_a)/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %copy.1 = f32[8]{0:T(8)} copy(%a)
  %bitcast.2 = f32[8]{0} bitcast(%copy.1)
  %fusion.3 = f32[8]{0} fusion(%bitcast.2), kind=kLoop, calls=%fused_computation
  %copy.4 = f32[8]{0} copy(%fusion.3)
  %add.5 = f32[8]{0} add(%fusion.3, %fusion.3), metadata={op_name="jit(step)/jit(other)/add"}
  ROOT %sub.6 = f32[8]{0} subtract(%copy.4, %add.5), metadata={op_name="jit(step)/update/sub"}
}
"""
    module, scopes = probe.parse_scopes(hlo, {"fc.00_a", "update"})
    assert module == "jit_step"
    assert scopes == {"mul.1": "jvp(fc.00_a)", "copy.1": "jvp(fc.00_a)",
                      "fusion.3": "jvp(fc.00_a)", "copy.4": "update",
                      "add.5": "update", "sub.6": "update"}


def test_transformer_step_scopes():
    import jax
    import numpy as np

    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.params import init_params
    from znicz_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])
    step, _ = tfm.make_train_step(mesh, 2, 16, 2, 32, 11, lr=0.1)
    params = init_params(np.random.default_rng(0), 2, 16, 2, 32, 11)
    tok = np.zeros((2, 8), np.int32)
    text = step.lower(params, tok, tok).as_text(debug_info=True)
    for scope in ("embed", "block0.attn", "block0.mlp", "block1.attn",
                  "block1.mlp", "ce"):
        assert f"jvp({scope})/" in text, scope
        assert f"transpose(jvp({scope}))/" in text, scope
    assert "/update/" in text


def test_every_pallas_call_is_named():
    import ast

    unnamed = []
    root = os.path.join(REPO, "znicz_tpu", "ops", "pallas")
    for fname in sorted(os.listdir(root)):
        if not fname.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(root, fname)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "pallas_call" and \
                    not any(k.arg == "name" for k in node.keywords):
                unnamed.append(f"{fname}:{node.lineno}")
    assert unnamed == []


# the readers, on hand-made traces (times in ns)

def test_reader_scope_seconds_by_hand():
    sd = _reader("scope_device")
    scopes = {"jit_step": {"fusion.1": "jvp(conv.00_c)",
                           "copy.2": "transpose(jvp(conv.00_c))",
                           "while.3": "update", "fusion.4": "update",
                           "pad.5": "jvp(fc.01_f)", "add.9": ""}}
    modules = [(0, 1000, "jit_step"), (2000, 2100, "jit_add")]
    ops = [(0, 100, "fusion.1", "fusion"),
           (100, 250, "copy.2", "copy"),
           (300, 700, "while.3", "while"),
           (350, 450, "fusion.4", "fusion"),     # inside the while
           (700, 760, "pad.5", "pad"),
           (800, 830, "add.9", "add"),           # no scope in the map
           (2000, 2100, "fusion.1", "fusion")]   # another program
    got = sd.scope_seconds(ops, modules, scopes)
    ns = {k: round(v * 1e9) for k, v in got.items()}
    assert ns == {("conv.00_c", "fwd", "other"): 100,
                  ("conv.00_c", "bwd", "copy"): 150,
                  ("update", "fwd", "other"): 400,
                  ("fc.01_f", "fwd", "pad"): 60,
                  (sd.UNSCOPED, "fwd", "other"): 130}
    assert sum(ns.values()) == 100 + 150 + 400 + 60 + 30 + 100   # the union
    rows = {r[0]: r[1:] for r in sd.table(got, steps=2)}
    assert rows["conv.00_c"] == pytest.approx(
        (50e-6, 0, 0, 75e-6, 75e-6, 0))
    assert [sd.group_of(s) for s in ("conv.00_c", "update", "loss")] == \
        ["conv", "update", "loss"]


def test_reader_gap_owners_by_hand():
    ps = _reader("program_spans")
    ops = [(0, 1000), (31000, 32000), (52000, 53000), (53001, 54000)]
    spans = [(500, 31500, "workflow.step"),          # covers gap 1 fully
             (900, 30300, "train.metrics_read"),     # most of it: inner
             (40000, 47000, "train.dispatch")]       # 7 of gap 2's 20 us
    # the runtime's np.asarray span over gap 1 is not a program span: the
    # reader filters by name before it calls gap_owners
    owners, innermost, total = ps.gap_owners(ops, spans)
    # the read ends before the gap does, so the enclosing delivery has
    # the longer overlap and owns the whole gap (as on the chip, PR 24)
    assert owners == {"workflow.step": 30000.0, "train.dispatch": 20000.0}
    assert total == 50000.0                              # 1 ns gap skipped
    assert innermost == {"train.metrics_read": 29300.0,
                         "workflow.step": 700.0,
                         "train.dispatch": 7000.0, ps.NO_SPAN: 13000.0}
    # both cover the gap fully: a tie, and the shorter (inner) span wins
    tie = [spans[0], (900, 31200, "train.metrics_read")]
    owners, innermost, total = ps.gap_owners(ops, tie)
    assert owners == {"train.metrics_read": 30000.0, ps.NO_SPAN: 20000.0}
    assert innermost == {"train.metrics_read": 30000.0,
                         ps.NO_SPAN: 20000.0}


def test_reader_step_host_and_sink_agreement_by_hand():
    ps = _reader("program_spans")
    step = {"name": "workflow.step", "args": {"unit": "FusedStep"}}
    ring = [{**step, "ts": 0.0, "dur": 300.0},
            {"name": "train.metrics_read", "ts": 100.0, "dur": 150.0},
            {**step, "ts": 1000.0, "dur": 200.0},
            {"name": "workflow.step", "args": {"unit": "Loader"},
             "ts": 1300.0, "dur": 900.0},
            {"name": "train.metrics_read", "ts": 5000.0, "dur": 50.0}]
    assert ps.step_host_ms(ring, "FusedStep", "train.metrics_read") == \
        pytest.approx((300 + 200 - 150) / 2 / 1e3)
    assert ps.step_host_ms(ring, "Nobody", "train.metrics_read") is None
    # ring origin at unix 10 s; the profile ran from 10.0005 s for 1 ms
    window = (10.0005e9, 10.0015e9)
    host = [(500e3, 700e3, "workflow.step"),       # the ring's ts=1000 span
            (800e3, 1700e3, "workflow.step")]      # open at the stop
    rows = ps.sink_agreement(ring, host, {"workflow.step"}, 10.0, window)
    assert rows == [("workflow.step", 1, pytest.approx(200e-6), 1,
                     pytest.approx(200e-6))]
