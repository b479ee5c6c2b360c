"""Set-up in its parts (ISSUE 37): jax's compile durations into
``znicz_compile_phase_seconds_total{phase}`` and ``compile.<phase>`` spans
(``compilecache``'s listener, ``probe.compile_phase``), the launcher's and
the units' ``setup.<phase>`` spans and ``znicz_setup_seconds{phase}``
(``probe.setup_phase``), the ring that keeps them past a clearing
(``probe.SETUP_RING``), and ``cpu_us`` on the spans that ask for it.
"""

import time

import pytest

from znicz_tpu import compilecache
from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.observe import probe, registry
from znicz_tpu.observe.trace import TRACER, Tracer

EVENTS = {
    "trace": "/jax/core/compile/jaxpr_trace_duration",
    "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "backend_compile": "/jax/core/compile/backend_compile_duration",
    "cache_load": "/jax/compilation_cache/cache_retrieval_time_sec",
}


def _phase_seconds(phase: str) -> float:
    return registry.REGISTRY.snapshot_flat(skip_zero=False).get(
        f'znicz_compile_phase_seconds_total{{phase="{phase}"}}', 0.0)


def _spans(ring, name: str) -> list:
    return [e for e in ring.export_dict()["traceEvents"]
            if e["name"] == name]


@pytest.fixture()
def listener():
    """The listener, and both rings emptied: a worker that has compiled
    much before this file fills them (8,192 events), and a full ring drops
    its oldest span for the new one, so a count of spans stands still."""
    compilecache._register_listener()
    TRACER.clear()
    probe.SETUP_RING.clear()


@pytest.mark.parametrize("phase", sorted(EVENTS))
def test_each_jax_duration_lands_in_its_counter_and_span(listener, phase):
    import jax.monitoring

    before = _phase_seconds(phase)
    n_ring = len(_spans(TRACER, f"compile.{phase}"))
    n_kept = len(_spans(probe.SETUP_RING, f"compile.{phase}"))
    t0 = time.perf_counter()
    jax.monitoring.record_event_duration_secs(EVENTS[phase], 0.25,
                                              fun_name="probe_me")
    assert _phase_seconds(phase) - before == pytest.approx(0.25)
    for ring, n in ((TRACER, n_ring), (probe.SETUP_RING, n_kept)):
        spans = _spans(ring, f"compile.{phase}")
        assert len(spans) == n + 1
        new = spans[-1]
        assert new["dur"] == pytest.approx(0.25e6)
        assert new["args"] == {"fn": "probe_me"}
        # it ends now: start = now - duration, on the tracer's clock
        end = (new["ts"] + new["dur"]) / 1e6
        assert end == pytest.approx(t0 - TRACER._origin, abs=0.05)
    # a duration under a millisecond is counted and leaves no span
    jax.monitoring.record_event_duration_secs(EVENTS[phase], 2e-4)
    assert _phase_seconds(phase) - before == pytest.approx(0.2502)
    assert len(_spans(TRACER, f"compile.{phase}")) == n_ring + 1
    # and an event that is none of the four moves nothing
    jax.monitoring.record_event_duration_secs("/jax/other", 9.0)
    assert _phase_seconds(phase) - before == pytest.approx(0.2502)


def test_nested_traces_count_once_and_less_what_they_compiled(listener):
    """jax fires the trace event for every nested jit, each inside its
    caller's duration; the scalar event at a trace's start gives the
    depth, so only the outermost counts, less what was lowered and
    compiled while it ran."""
    import jax.monitoring as m

    before = {p: _phase_seconds(p) for p in EVENTS}
    trace = EVENTS["trace"]
    m.record_scalar(trace, 0.0, fun_name="outer")
    m.record_scalar(trace, 0.0, fun_name="inner")
    m.record_event_duration_secs(trace, 0.4, fun_name="inner")
    m.record_event_duration_secs(EVENTS["lower"], 0.1)      # an eager op
    m.record_event_duration_secs(EVENTS["cache_load"], 0.05)
    m.record_event_duration_secs(EVENTS["backend_compile"], 0.2)
    m.record_scalar(trace, 0.0, fun_name="inner")
    m.record_event_duration_secs(trace, 0.0, fun_name="inner")
    m.record_event_duration_secs(trace, 1.0, fun_name="outer")
    got = {p: _phase_seconds(p) - before[p] for p in EVENTS}
    assert got == pytest.approx({"trace": 0.7, "lower": 0.1,
                                 "backend_compile": 0.2,
                                 "cache_load": 0.05})


def test_a_real_compile_feeds_trace_lower_and_backend(listener):
    import jax
    import jax.numpy as jnp

    before = {p: _phase_seconds(p) for p in EVENTS}

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + jnp.cos(x)

    fresh(jnp.ones((7, 5))).block_until_ready()
    for phase in ("trace", "lower", "backend_compile"):
        assert _phase_seconds(phase) > before[phase], phase


def test_listener_is_registered_without_a_cache_directory(monkeypatch):
    """The benchmark's path whether or not a directory is set:
    ``configure`` registers before it decides."""
    monkeypatch.delenv(compilecache.JAX_ENV_VAR, raising=False)
    monkeypatch.setattr(compilecache, "_listener_registered", False)
    calls = []
    monkeypatch.setattr(compilecache, "_register_listener",
                        lambda: calls.append(1))
    try:
        assert compilecache.configure(cache_dir="off", force=True) is None
    finally:
        compilecache._reset_for_tests()
    assert calls == [1]


def test_disabled_probe_counts_no_phase_and_no_setup(listener):
    import jax.monitoring

    before = _phase_seconds("lower")
    n = len(probe.SETUP_RING)
    probe.set_enabled(False)
    try:
        jax.monitoring.record_event_duration_secs(EVENTS["lower"], 0.5)
        with probe.setup_phase("load"):
            pass
    finally:
        probe.set_enabled(True)
    assert _phase_seconds("lower") == before and len(probe.SETUP_RING) == n


def test_setup_phase_is_live_span_gauge_and_kept_ring():
    flat = registry.REGISTRY.snapshot_flat(skip_zero=False)
    before = flat.get('znicz_setup_seconds{phase="unit_test"}', 0.0)
    with probe.setup_phase("unit_test"):
        time.sleep(0.01)
    with probe.setup_phase("unit_test"):
        pass
    flat = registry.REGISTRY.snapshot_flat(skip_zero=False)
    assert flat['znicz_setup_seconds{phase="unit_test"}'] - before >= 0.01
    assert "setup.unit_test" in TRACER.live_names     # the host plane too
    kept = len(_spans(probe.SETUP_RING, "setup.unit_test"))
    TRACER.clear()                         # what the benchmark does
    assert _spans(TRACER, "setup.unit_test") == []
    assert len(_spans(probe.SETUP_RING, "setup.unit_test")) == kept >= 2
    assert probe.SETUP_RING._origin == TRACER._origin


def test_launcher_leaves_load_and_initialize_in_ring_and_gauge():
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.models import wine

    def seconds():
        flat = registry.REGISTRY.snapshot_flat(skip_zero=False)
        return {p: flat.get(f'znicz_setup_seconds{{phase="{p}"}}', 0.0)
                for p in ("load", "initialize", "init_params", "place")}

    prng.seed_all(3)
    before = seconds()
    TRACER.clear()
    launcher = Launcher(device=XLADevice())
    launcher.load(wine.build, max_epochs=1, n_train=60, n_valid=30,
                  minibatch_size=10)
    launcher.main()
    after = seconds()
    names = {e["name"]: e for e in TRACER.export_dict()["traceEvents"]}
    for phase in ("load", "initialize", "init_params", "place"):
        assert f"setup.{phase}" in names, phase
        assert after[phase] > before[phase], phase
    init, run = names["setup.initialize"], names["workflow.run"]
    assert names["setup.load"]["ts"] < init["ts"] < run["ts"]
    for inner in ("setup.init_params", "setup.place"):
        assert init["ts"] <= names[inner]["ts"] and \
            names[inner]["ts"] + names[inner]["dur"] <= \
            init["ts"] + init["dur"] + 1.0, inner
    # the step's first call is the third ingredient a reader needs
    assert [e for e in _spans(probe.SETUP_RING, "compile.cold")
            if e["ts"] > init["ts"]]


@pytest.mark.parametrize("cpu", [False, True])
def test_cpu_us_is_on_the_span_only_where_asked(cpu):
    tracer = Tracer()
    with tracer.timed("busy", {"unit": "u"}, cpu=cpu):
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.005:
            pass
        time.sleep(0.02)                   # a wait moves the wall alone
    (event,) = tracer.export_dict()["traceEvents"][1:2]
    assert event["args"]["unit"] == "u"
    if cpu:
        assert 4e3 <= event["args"]["cpu_us"] < event["dur"] - 15e3
    else:
        assert "cpu_us" not in event["args"]


def test_workflow_asks_cpu_of_the_step_unit_alone():
    from tests.test_observe import run_workflow

    TRACER.clear()
    w = run_workflow(max_epochs=1, name="CpuOnStep")
    steps = [e for e in TRACER.export_dict()["traceEvents"]
             if e["name"] == "workflow.step"]
    with_cpu = {e["args"]["unit"] for e in steps if "cpu_us" in e["args"]}
    assert with_cpu == {w.step.name}
    assert all("cpu_us" in e["args"] for e in steps
               if e["args"]["unit"] == w.step.name)
