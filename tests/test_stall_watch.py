"""The stall watch (ISSUE 37): ``anatomy.StepCadence`` hands every
dispatch's newest output to ``anatomy.StallWatch``, whose loop body
(:meth:`StallWatch.wake`) is driven here with a clock of the test's own and
handles whose ``is_ready`` the test flips, so no test waits for a stop.
"""

import json
import os
import threading
import time

import pytest

from znicz_tpu.observe import anatomy, flight, probe, registry
from znicz_tpu.observe.anatomy import (FLOOR_S, WAKE_S, StallWatch,
                                       StepCadence)
from znicz_tpu.observe.trace import TRACER


class Clock:
    def __init__(self, t: float = 100.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


class Handle:
    def __init__(self) -> None:
        self.done = False

    def is_ready(self) -> bool:
        return self.done


class Sim:
    """A cadence under a threadless watch.  ``dispatch`` ticks now with a
    handle that completes ``done_in`` seconds from now; ``advance`` moves
    the clock wake by wake, flipping the handles that fall due."""

    def __init__(self, plane: str, proc: str = "/nonexistent") -> None:
        self.clock = Clock()
        self.watch = StallWatch(clock=self.clock, proc=proc, threaded=False)
        self.cadence = StepCadence(plane, watch=self.watch)
        self.plane = plane
        self.due: list = []

    def dispatch(self, done_in: float) -> Handle:
        handle = Handle()
        self.cadence.tick(self.clock.t, handle)
        self.due.append((self.clock.t + done_in, handle))
        return handle

    def advance(self, seconds: float, step: float = WAKE_S) -> None:
        end = self.clock.t + seconds
        while self.clock.t < end - 1e-9:
            self.clock.t = min(self.clock.t + step, end)
            for at, handle in self.due:
                if at <= self.clock.t + 1e-9:
                    handle.done = True
            self.due = [(at, h) for at, h in self.due if not h.done]
            self.watch.wake()

    def steady(self, steps: int, step_s: float = 0.1) -> None:
        """``steps`` steps, each dispatched as the one before completes."""
        for _ in range(steps):
            self.dispatch(step_s)
            self.advance(step_s)

    def stalls(self) -> list:
        return [e for e in TRACER.export_dict()["traceEvents"]
                if e["name"] == "stall" and e["args"]["plane"] == self.plane]

    def counters(self, kind: str) -> tuple:
        flat = registry.REGISTRY.snapshot_flat(skip_zero=False)
        key = f'{{plane="{self.plane}",kind="{kind}"}}'
        return (flat["znicz_stalls_total" + key],
                flat["znicz_stall_seconds_total" + key])


def test_counters_are_pretouched_for_the_three_kinds():
    sim = Sim("sw_pretouch")
    assert [sim.counters(k) for k in anatomy.KINDS] == [(0.0, 0.0)] * 3
    # and on /metrics before any stall
    text = registry.REGISTRY.render_prometheus()
    assert 'znicz_stall_seconds_total{plane="sw_pretouch",kind="frozen"} 0' \
        in text and "# TYPE znicz_stalls_total counter" in text


def test_nothing_before_eight_ticks():
    sim = Sim("sw_early")
    for _ in range(anatomy.MIN_TICKS - 1):     # first steps: compiles
        sim.dispatch(5.0)
        sim.advance(5.0, step=0.5)
    sim.advance(20.0, step=0.5)                # and a long silence
    assert sim.stalls() == []
    assert sim.cadence._w.typical() is None


def test_the_benchmarks_rhythm_is_no_stall():
    """K quick dispatches, then a fence many steps long during which the
    completions arrive on time: the dispatch gap (0.385 s) is far over
    three typical steps (0.3 s), and nothing is recorded."""
    sim = Sim("sw_rhythm")
    for _ in range(12):
        for i in range(4):
            sim.dispatch(0.1 * (i + 1) - 0.005 * i)
            sim.advance(0.005, step=0.005)
        sim.advance(0.385)
    assert sim.cadence._w.typical() == pytest.approx(0.1, rel=0.05)
    assert sim.stalls() == []
    assert sim.counters("device") == (0.0, 0.0)
    assert sim.counters("host") == (0.0, 0.0)


def test_device_stall_with_evidence_counters_and_one_flight_artifact(
        tmp_path):
    sim = Sim("sw_device")
    sim.steady(12)
    flight.configure(dir=str(tmp_path), min_interval_s=0.0)
    try:
        sim.dispatch(2.0)                      # queued, and nothing completes
        sim.dispatch(2.1)
        sim.advance(2.2)
    finally:
        flight.configure()
    (stall,) = sim.stalls()
    args = stall["args"]
    assert args["kind"] == "device" and args["pending"] == 2
    # from the last completion + one typical step to the wake that saw
    # the next one
    assert 1.85 <= stall["dur"] / 1e6 <= 2.0
    # the mean interval of the 14 ticks (the last two at one instant)
    assert args["typical_ms"] == pytest.approx(1200.0 / 13, rel=0.01)
    assert args["watcher_late_ms"] == 0.0
    assert any("test_stall_watch.py" in f for f in args["frames"])
    assert len(args["frames"]) <= 8
    assert len(json.dumps(args)) < anatomy.EVIDENCE_BYTES
    count, seconds = sim.counters("device")
    assert count == 1.0 and seconds == pytest.approx(stall["dur"] / 1e6)
    (artifact,) = os.listdir(tmp_path)
    doc = flight.load(str(tmp_path / artifact))
    assert doc["reason"] == "stall" and doc["extra"]["kind"] == "device"
    assert stall["tid"] == threading.get_ident()


def test_host_stall_and_none_after_close():
    sim = Sim("sw_host")
    sim.steady(12)
    sim.advance(1.5)                           # queue empty, no dispatch
    assert sim.stalls() == []                  # still open
    sim.dispatch(0.1)
    sim.advance(0.1)
    (stall,) = sim.stalls()
    assert stall["args"]["kind"] == "host" and stall["args"]["pending"] == 0
    # from the last completion + one typical step to the next tick
    assert 1.35 <= stall["dur"] / 1e6 <= 1.45
    assert sim.counters("host")[0] == 1.0
    sim.cadence.close()                        # workflow.stop
    sim.advance(5.0)
    assert len(sim.stalls()) == 1
    assert sim.cadence not in sim.watch._cadences      # let go
    sim.dispatch(0.1)                          # the next run joins again
    assert sim.cadence in sim.watch._cadences


def test_a_cadence_closed_inside_a_stall_closes_it():
    sim = Sim("sw_closed_open")
    sim.steady(12)
    sim.advance(1.0)
    sim.cadence.close()
    sim.advance(0.1)
    (stall,) = sim.stalls()
    assert stall["args"]["kind"] == "host"
    assert 0.85 <= stall["dur"] / 1e6 <= 1.05


def test_an_overrun_wake_is_a_frozen_stall():
    sim = Sim("sw_frozen")
    sim.steady(12)
    sim.dispatch(0.1)
    sim.advance(2.0, step=2.0)                 # one wake, 1.95 s late
    sim.steady(3)
    (stall,) = sim.stalls()                    # and no host stall beside it
    assert stall["args"]["kind"] == "frozen"
    assert stall["args"]["watcher_late_ms"] == pytest.approx(1950.0, abs=1)
    assert stall["dur"] / 1e6 == pytest.approx(1.95, abs=0.01)
    assert sim.counters("frozen")[0] == 1.0
    assert sim.counters("host")[0] == 0.0


def test_a_late_wake_under_the_threshold_is_nothing():
    sim = Sim("sw_late")
    sim.steady(12)
    sim.dispatch(0.1)
    sim.advance(FLOOR_S * 0.9, step=FLOOR_S * 0.9)
    assert sim.stalls() == []


STAT = ("4242 (tpu (worker) 3) D 1 4242 4242 0 -1 4194368 10 0 0 0 "
        "700 55 0 0 20 0 90 0 1000 0 0\n")


@pytest.mark.parametrize("text,want", [
    (STAT, ("tpu (worker) 3", "D", 755)),
    ("1 (python) S 0 1 1 0 -1 0 0 0 0 0 12 3 0 0 20 0 1 0 5 0 0",
     ("python", "S", 15)),
    ("", None),
    ("garbage without brackets", None),
    ("7 (short) R 1 2", None),
    ("1 (python) S 0 1 1 0 -1 0 0 0 0 0 x y 0 0 20 0 1 0 5 0 0", None),
])
def test_parse_task_stat(text, want):
    assert anatomy.parse_task_stat(text) == want


@pytest.mark.parametrize("text,want", [
    ("some avg10=1.25 avg60=0.50 avg300=0.10 total=12345\n"
     "full avg10=0.00 avg60=0.00 avg300=0.00 total=0\n", 1.25),
    ("full avg10=3.00 avg60=0.00 avg300=0.00 total=0\n", None),
    ("some avg10=oops\n", None),
    ("", None),
])
def test_parse_pressure(text, want):
    assert anatomy.parse_pressure(text) == want


def _fake_proc(root, ticks: dict) -> str:
    for tid, (name, state, cpu) in ticks.items():
        d = root / "self" / "task" / tid
        d.mkdir(parents=True, exist_ok=True)
        (d / "stat").write_text(
            f"{tid} ({name}) {state} 1 1 1 0 -1 0 0 0 0 0 {cpu} 0 0 0 20 0 "
            f"1 0 5 0 0\n")
        (d / "wchan").write_text("futex_wait_queue" if state == "S"
                                 else "io_schedule" if state == "D" else "0")
    (root / "pressure").mkdir(exist_ok=True)
    (root / "pressure" / "cpu").write_text(
        "some avg10=7.50 avg60=1.00 avg300=0.10 total=1\n")
    (root / "loadavg").write_text("3.10 2.00 1.00 4/512 99\n")
    return str(root)


def test_evidence_reads_threads_pressure_and_load(tmp_path):
    before = {"11": ("main", "S", 100), "12": ("tpu-driver", "S", 40),
              "13": ("idle", "S", 7)}
    proc = _fake_proc(tmp_path, before)
    was = anatomy.thread_ticks(proc)
    assert was == before
    _fake_proc(tmp_path, {"11": ("main", "S", 100),
                          "12": ("tpu-driver", "S", 49),
                          "13": ("idle", "D", 7)})
    ev = anatomy.evidence(threading.get_ident(), 0.012, was, proc)
    assert ev["watcher_late_ms"] == 12.0
    assert ev["threads"]["states"] == {"S": 2, "D": 1}
    assert ev["threads"]["busy"] == [
        ["tpu-driver", "S", "futex_wait_queue", 9],
        ["idle", "D", "io_schedule", 0]]
    assert ev["pressure"] == {"cpu": 7.5} and ev["loadavg"] == "3.10 2.00 1.00"
    assert any("test_stall_watch.py" in f for f in ev["frames"])
    # no earlier reading: the threads that are running now
    _fake_proc(tmp_path, {"12": ("tpu-driver", "R", 50)})
    rows = anatomy.evidence(None, 0.0, None, proc)["threads"]["busy"]
    assert ["tpu-driver", "R", "0", None] in rows


def test_evidence_on_a_machine_without_proc(tmp_path):
    ev = anatomy.evidence(threading.get_ident(), 0.0, None,
                          str(tmp_path / "no_proc"))
    assert set(ev) == {"watcher_late_ms", "frames"}
    assert anatomy.thread_ticks(str(tmp_path / "no_proc")) == {}


def test_evidence_stays_under_four_kilobytes(tmp_path):
    proc = _fake_proc(tmp_path, {
        str(i): ("x" * 40 + str(i), "R", i) for i in range(300)})
    ev = anatomy.evidence(threading.get_ident(), 0.0, None, proc)
    assert len(json.dumps(ev)) <= anatomy.EVIDENCE_BYTES
    assert len(ev["threads"]["busy"]) <= 12
    assert ev["threads"]["states"] == {"R": 300}


def test_the_suspected_stop_takes_the_reading_its_ticks_count_from(tmp_path):
    proc = _fake_proc(tmp_path, {"21": ("runtime", "S", 10)})
    sim = Sim("sw_before", proc=proc)
    sim.steady(12)
    sim.dispatch(1.0)
    sim.advance(0.30)                     # past two thirds of the 0.3 s
    assert sim.cadence._w.before == {"21": ("runtime", "S", 10)}
    _fake_proc(tmp_path, {"21": ("runtime", "S", 16)})
    sim.advance(0.70)                     # the next wake opens the stall
    (stall,) = sim.stalls()
    assert stall["args"]["threads"]["busy"] == [
        ["runtime", "S", "futex_wait_queue", 6]]


def test_a_handle_that_raises_counts_as_done():
    class Gone:
        def is_ready(self):
            raise RuntimeError("deleted")

    sim = Sim("sw_gone")
    sim.steady(12)
    sim.cadence.tick(sim.clock.t, Gone())
    sim.cadence.tick(sim.clock.t, object())        # and one with no is_ready
    sim.advance(0.05)
    assert not sim.cadence._w.pending


def test_disabled_probe_keeps_no_handle_no_thread_no_span():
    watch = StallWatch()                   # a threaded one, as the process's
    cadence = StepCadence("sw_off", watch=watch)
    probe.set_enabled(False)
    try:
        cadence.tick(1.0, Handle())
        cadence.tick(2.0, Handle())
        assert not cadence._ticks and not watch.running
        assert cadence not in watch._cadences
    finally:
        probe.set_enabled(True)
    before = len(TRACER)
    cadence.tick(3.0, Handle())            # enabled: joins, thread starts
    assert watch.running and len(cadence._ticks) + len(
        cadence._w.pending) == 1
    assert len(TRACER) == before
    cadence.close()                        # and the thread ends with it
    deadline = time.monotonic() + 0.3
    while watch.running and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not watch.running


def test_the_exit_hook_ends_the_thread_with_a_cadence_still_joined():
    """A process that exits with its cadence open (a worker whose workflow
    never stopped) must not leave the daemon thread to the finalizing
    interpreter: the hook ends it within a wake, and none starts after."""
    watch = StallWatch()
    cadence = StepCadence("sw_exit", watch=watch)
    cadence.tick(1.0, Handle())
    thread = watch._thread
    assert watch.running and thread.is_alive()
    watch._halt()                          # what atexit calls
    assert not thread.is_alive()
    cadence.close()
    StepCadence("sw_exit2", watch=watch).tick(2.0, Handle())
    assert watch._thread is thread         # no new thread after the hook


def test_step_units_hand_their_output_and_close_on_stop(monkeypatch):
    """The fused step's dispatch ticks with an output of the step that a
    wake finds ready, and ``workflow.stop`` closes the cadence."""
    from tests.test_observe import run_workflow

    watch = StallWatch(threaded=False)
    monkeypatch.setattr(anatomy, "WATCH", watch)
    w = run_workflow(max_epochs=1, name="StallWatchFused")
    cadence = w.step._cadence
    assert cadence in watch._cadences
    assert len(cadence._ticks) == 9       # 6 train + 3 validation batches
    assert all(hasattr(h, "is_ready") for _, h in cadence._ticks)
    watch.wake()
    assert cadence._w.n == 9 and not cadence._w.pending
    w.stop()
    assert not cadence._joined
    watch.wake()
    assert cadence not in watch._cadences
