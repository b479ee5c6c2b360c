"""The readers ISSUE 37 added (``setup_parts``, ``stall``, ``span_cpu``) on
a hand-made ring and registry: a stall in sub-window 3 of 6 is logged
there and shares the window's wall, ``setup_unnamed_s`` is the remainder,
what was stamped before ``setup.load`` or after the window's opening comes
off the counters, and a tree without the counters reads as nothing.  A
traced rehearsal on the CPU reports all seven."""

import types

import pytest

import benchlib
import run
import tiny

NEW = ["setup_trace_lower_s", "setup_compile_s", "setup_cache_load_s",
       "setup_initialize_s", "setup_unnamed_s", "window_stall_share",
       "step_host_cpu_ms_per_step"]
K = 4


def _step(ts_ms: float, dur_ms: float, unit: str, **more) -> dict:
    return {"ph": "X", "name": "workflow.step", "ts": ts_ms * 1e3,
            "dur": dur_ms * 1e3, "args": {"unit": unit, **more}}


def window_ring(n_windows: int = 6, step_ms: float = 100.0,
                stall_in: int | None = 3, stall_ms: float = 900.0):
    """``(ring, walls)`` of a window as the harness leaves it: the tap's
    opening span, then K steps a sub-window, each a step unit's span
    (40 ms wall, 3 ms CPU) and the tap's (the last of a sub-window holds
    the fence); one sub-window holds a stall and lasts that much longer."""
    ring = [_step(990.0, 10.0, "BenchTap")]
    t, walls = 1000.0, []
    for i in range(n_windows):
        t0 = t
        for j in range(K):
            ring.append(_step(t, 40.0, "Step", cpu_us=3000.0))
            last = j == K - 1
            wait = stall_ms if last and i == stall_in else 0.0
            if wait:
                ring.append({"ph": "X", "name": "stall", "ts": (t + 60) * 1e3,
                             "dur": wait * 1e3, "args": {
                                 "kind": "device", "plane": "fused",
                                 "typical_ms": step_ms, "pending": 3,
                                 "watcher_late_ms": 0.4,
                                 "frames": ["step.py:1 run"],
                                 "threads": {"states": {"S": 9},
                                             "busy": []}}})
            ring.append(_step(t + 40.0, step_ms - 40.0 + wait, "BenchTap"))
            t += step_ms + wait
        walls.append((t - t0) / 1e3)
    ring.append({"ph": "X", "name": "workflow.run", "ts": 5.0,
                 "dur": t * 1e3, "args": {}})
    return ring, walls


def _rc(ring, walls, metric: dict, setup_s: float = 0.0):
    logged: list = []
    rc = types.SimpleNamespace(
        samples={"kind": "train", "k": K, "walls": walls,
                 "program_spans": ring, "step_unit": "Step"},
        metric=metric, setup_s=setup_s, log=logged.append)
    return rc, logged


def test_metric_files_agree_with_benchmark_json():
    roots = benchlib.Roots()
    bench = benchlib.benchmark_json(roots)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        spec, entry = roots.data("metrics", name), entries[name]
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "workloads" not in entry
        assert hasattr(roots.module("readers", spec["reader"]), "read")
    # no list of cells: every cell that reports what they move reads them
    for cell in (w["name"] for w in bench["workloads"]):
        got = {m["name"] for m in run._cell_metrics(bench, cell)[1]}
        assert set(NEW) <= got, cell


def test_a_stall_in_sub_window_three_of_six_is_logged_there():
    roots = benchlib.Roots()
    reader = roots.module("readers", "stall")
    ring, walls = window_ring()
    rc, logged = _rc(ring, walls, roots.data("metrics", "window_stall_share"))
    share = reader.read(rc)
    assert share == pytest.approx(100.0 * 0.9 / sum(walls))
    (line,) = [m for m in logged if m.startswith("stall: device")]
    assert "900.0 ms in sub-window #3" in line and "step.py:1 run" in line
    assert "watcher late 0.4 ms" in line and "pending 3" in line
    # the slowest sub-window is named with the span that grew in it
    slow = [m for m in logged if m.startswith("stall: sub-window")]
    assert len(slow) == 3 and slow[0].startswith("stall: sub-window #3 1300")
    assert "workflow.step{BenchTap} 1140.0 / 240.0" in slow[0]
    assert "stall 900.0 / 0.0" in slow[0]
    # a quiet window reads 0, not nothing
    ring, walls = window_ring(stall_in=None)
    rc, logged = _rc(ring, walls, rc.metric)
    assert reader.read(rc) == 0.0
    assert not [m for m in logged if m.startswith("stall: device")]


def test_a_stall_between_sub_windows_is_outside_them():
    """What a traced run does between two sub-windows (the profiler's
    start) is in no wall: a stall there is logged and shares nothing."""
    reader = benchlib.Roots().module("readers", "stall")
    ring, walls = window_ring(stall_in=None)
    ring.append({"ph": "X", "name": "stall", "ts": 500e3, "dur": 300e3,
                 "args": {"kind": "host"}})
    rc, logged = _rc(ring, walls, {})
    assert reader.read(rc) == 0.0
    assert any("outside the window's sub-windows" in m for m in logged)
    # and too few tap spans for the walls place nothing
    rc, logged = _rc(ring[:10], walls, {})
    assert reader.read(rc) is None


def test_step_host_cpu_is_the_step_spans_cpu():
    roots = benchlib.Roots()
    ring, walls = window_ring()
    rc, _ = _rc(ring, walls,
                roots.data("metrics", "step_host_cpu_ms_per_step"))
    assert roots.module("readers", "span_cpu").read(rc) == \
        pytest.approx(3.0)
    for e in ring:
        e["args"].pop("cpu_us", None)          # an older program's spans
    assert roots.module("readers", "span_cpu").read(rc) is None


def _setup_program(monkeypatch):
    """A registry and a set-up ring of the test's own, filled as a run
    fills them: the reference's compile before ``setup.load``, the
    set-up, and the scope join's compile after the window opened (at
    60 s on the ring's clock)."""
    from znicz_tpu.observe import probe, registry
    from znicz_tpu.observe.trace import Tracer

    reg = registry.Registry()
    phase = reg.counter("znicz_compile_phase_seconds_total", "",
                        labelnames=("phase",))
    setup = reg.gauge("znicz_setup_seconds", "", labelnames=("phase",))
    first = reg.histogram("znicz_compile_seconds", "", labelnames=("fn",))
    ring = Tracer(origin=0.0)

    def event(name, start, dur):
        ring.complete(name, start, dur)
        kind, _, label = name.partition(".")
        if name == "compile.cold":
            first.labels(fn="Step").observe(dur)
        elif kind == "compile":
            phase.labels(phase=label).inc(dur)
        else:
            setup.labels(phase=label).inc(dur)

    event("compile.trace", 3.0, 2.0)            # the reference's
    event("compile.backend_compile", 5.0, 4.0)
    event("setup.load", 10.0, 1.0)
    event("compile.trace", 12.0, 0.5)           # the harness's weights
    event("setup.init_params", 13.0, 2.0)
    event("setup.place", 15.0, 3.0)
    event("setup.initialize", 13.0, 6.0)
    event("compile.trace", 20.0, 4.0)
    event("compile.lower", 24.0, 3.0)
    event("compile.cache_load", 27.5, 6.0)
    event("compile.backend_compile", 27.0, 7.0)
    event("compile.cold", 20.0, 16.0)           # the step's first call
    event("compile.trace", 70.0, 5.0)           # the scope join's
    event("compile.backend_compile", 75.0, 20.0)
    phase.labels(phase="trace").inc(0.25)       # under a millisecond each
    monkeypatch.setattr(registry, "REGISTRY", reg)
    monkeypatch.setattr(probe, "SETUP_RING", ring)


@pytest.mark.parametrize("name,want", [
    ("setup_trace_lower_s", 0.5 + 4.0 + 3.0 + 0.25),
    ("setup_compile_s", 7.0 - 6.0),
    ("setup_cache_load_s", 6.0),
    ("setup_initialize_s", 6.0),
    ("setup_unnamed_s", 50.0 - 1.0 - 6.0 - 16.0 - 0.75),
])
def test_setup_parts_on_a_hand_made_ring_and_registry(monkeypatch, name,
                                                      want):
    roots = benchlib.Roots()
    reader = roots.module("readers", "setup_parts")
    _setup_program(monkeypatch)
    ring, walls = window_ring()
    for e in ring:                              # the window opens at 60 s
        e["ts"] += 59e6
    rc, logged = _rc(ring, walls, roots.data("metrics", name), setup_s=50.0)
    reader._CACHE.clear()
    assert reader.read(rc) == pytest.approx(want)
    line = next(m for m in logged if m.startswith("setup: setup_s 50.000"))
    assert "init_params 2.000, place 3.000, backend 0.000" in line
    assert "compile phases inside first calls 14.000: first executions " \
        "2.000) + compile phases of programs in none of them 0.750" in line
    assert line.endswith("+ unnamed 26.250")
    line = next(m for m in logged if "before setup.load" in m)
    assert "before setup.load (the reference's, not in setup_s, and the " \
        "harness's own): trace 2.000, lower 0.000, backend_compile 4.000" \
        in line
    assert "(the scope join's): trace 5.000, lower 0.000, " \
        "backend_compile 20.000" in line


def test_a_tree_without_the_counters_reads_as_nothing(monkeypatch):
    from znicz_tpu.observe import probe, registry

    roots = benchlib.Roots()
    ring, walls = window_ring()
    monkeypatch.setattr(registry, "REGISTRY", registry.Registry())
    for name in NEW[:6]:
        rc, _ = _rc(ring, walls, roots.data("metrics", name), setup_s=9.0)
        reader = roots.module("readers", rc.metric["reader"])
        assert reader.read(rc) is None, name
    monkeypatch.undo()
    monkeypatch.delattr(probe, "SETUP_RING")
    rc, _ = _rc(ring, walls, roots.data("metrics", NEW[0]), setup_s=9.0)
    assert roots.module("readers", "setup_parts").read(rc) is None


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    return tiny.write_overlay(str(tmp_path_factory.mktemp("overlay")))


def test_traced_rehearsal_reports_all_seven(overlay):
    rc, result, outcome = run.execute(
        ["--workload", "alexnet_train", "--seed", "3700000013", "--seconds",
         "2", "--trace", "1"], roots_extra=[overlay], allow_cpu=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = result["metrics"]
    assert set(NEW) <= set(got)
    parts = {n: got[n]["value"] for n in NEW}
    assert parts["window_stall_share"] == 0.0
    assert parts["step_host_cpu_ms_per_step"] > 0.0
    assert parts["setup_initialize_s"] > 0.0
    assert parts["setup_trace_lower_s"] > 0.0
    assert parts["setup_unnamed_s"] > 0.0
