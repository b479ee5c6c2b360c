"""What the two training builders share: a tap in the unit graph that
drives one workflow run through its three phases, and the arithmetic on
what the tap recorded.

The tap is a leaf unit linked after the workflow's train step (a copy of
``chip_smoke._attach_tap``'s idea), so ``Launcher.main`` owns the loop as it
does for a user.  One run of the loop is:

1. the first three steps: after each, the fenced loss; after the first,
   the norm of each leaf's gradient from the optimizer's state; after the
   third, the norm of each leaf's change.  The same object goes on into
2. a warm sub-window, so the first measured step meets warm queues; then
3. the window: sub-windows of K steps, each closed by one
   ``block_until_ready`` on the step's newest output, until ``--seconds``
   have passed at a sub-window boundary.  A traced run profiles a few
   sub-windows in the middle and then stops.
"""

from __future__ import annotations

import gc
import math
import os
import resource
import time

FIRST_STEPS = 3


class HostProbe:
    """What the host did between two calls of :meth:`lap`, so that a slow
    sub-window is named and not guessed: CPU seconds of this process, all
    its threads (a wall far above the usual share of it: the process
    waited or was descheduled), its involuntary context switches and
    major page faults, the seconds Python's cyclic GC ran (the program
    leaves it on, so the benchmark does), and from ``/proc/stat`` the CPU
    seconds stolen from this machine and spent waiting on I/O, over all
    cores.  A lap costs some tens of microseconds."""

    def __init__(self) -> None:
        self._gc_s, self._gc_t0 = 0.0, None
        self._tick = 1.0 / os.sysconf("SC_CLK_TCK")
        gc.callbacks.append(self._on_gc)
        self._last = self._read()

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self._gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def _read(self) -> tuple:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        iowait = steal = 0.0
        try:
            with open("/proc/stat", encoding="ascii") as f:
                cpu = f.readline().split()
            iowait, steal = int(cpu[5]) * self._tick, int(cpu[8]) * self._tick
        except (OSError, IndexError, ValueError):
            pass                         # no /proc: those two read 0
        return (time.process_time(), steal, iowait, self._gc_s,
                ru.ru_nivcsw, ru.ru_majflt)

    def lap(self) -> dict:
        now, last = self._read(), self._last
        self._last = now
        return dict(zip(("cpu_s", "steal_s", "iowait_s", "gc_s", "nivcsw",
                         "majflt"), (a - b for a, b in zip(now, last))))

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


class TrainTap:
    """The state machine behind the tap.  ``cell`` is the builder's
    :class:`TrainCell`: it knows the workflow (``fence``, ``read_loss``,
    ``grad_norms``, ``delta_norms``, ``stop_workflow``)."""

    def __init__(self, ctx, cell, k: int, trace_windows: int = 4,
                 trace_from: int = 2) -> None:
        self.ctx, self.cell, self.k = ctx, cell, int(k)
        self.trace_windows, self.trace_from = trace_windows, trace_from
        self.n = 0
        self.readings = {"loss": []}
        self.walls: list = []        # wall seconds of each sub-window
        self.host: list = []         # what the host did in each (HostProbe)
        self.probe = HostProbe()
        self.losses: list = []       # loss read at each boundary
        self.t_prev = None
        self.done = False
        self.traced = (None, None)   # sub-window indices the trace covers

    def on_step(self) -> None:
        if self.done:
            return
        self.n += 1
        n, cell = self.n, self.cell
        if n <= FIRST_STEPS:
            self.readings["loss"].append(cell.read_loss())
            if n == 1:
                self.readings["grad_norm"] = cell.grad_norms()
            if n == FIRST_STEPS:
                self.readings["delta_norm"] = cell.delta_norms()
            return
        in_window = n - FIRST_STEPS - self.k      # steps since the window opened
        if in_window < 0:
            return
        if in_window == 0:
            cell.fence()
            cell.read_loss()
            cell.window_opening()
            self.ctx.open_window()
            self.probe.lap()
            self.t_prev = time.perf_counter()
            return
        if in_window % self.k:
            return
        cell.fence()
        self.losses.append(cell.read_loss())
        now2 = time.perf_counter()
        self.walls.append(now2 - self.t_prev)
        self.host.append(self.probe.lap())
        self.t_prev = now2
        idx = len(self.walls)
        ctx = self.ctx
        if ctx.trace:
            # a traced run profiles sub-windows [trace_from, trace_from +
            # trace_windows) and stops there, whatever --seconds says
            if idx == self.trace_from:
                ctx.trace_start()
                self.probe.lap()
                self.t_prev = time.perf_counter()
            elif idx == self.trace_from + self.trace_windows:
                ctx.trace_stop()
                self.traced = (self.trace_from, idx)
                self._finish()
        elif now2 - ctx.t_open >= ctx.seconds:
            self._finish()

    def _finish(self) -> None:
        self.done = True
        self.probe.close()
        self.ctx.close_window()
        self.cell.stop_workflow()

    # -- what the harness reads afterwards ----------------------------------
    def samples(self, batch: int, chips: int) -> dict:
        lo, hi = self.traced
        return {"kind": "train", "k": self.k, "batch": batch, "chips": chips,
                "walls": list(self.walls), "host": list(self.host),
                "traced_windows": None if lo is None else [lo, hi],
                "steps_in_window": self.k * len(self.walls)}

    def losses_finite(self) -> bool:
        return bool(self.losses) and all(math.isfinite(v)
                                         for v in self.losses)


class TrainCell:
    """What the two training builders' cells share.  A subclass sets
    ``ctx``, ``cfg``, ``traffic``, ``ref`` and, once built, ``w`` (the
    workflow), and gives ``read_loss``, ``grad_norms``, ``delta_norms``."""

    def fence(self) -> None:
        import jax

        jax.block_until_ready(self.w.step._params)

    def window_opening(self) -> None:
        from znicz_tpu.observe.trace import TRACER

        TRACER.clear()

    def stop_workflow(self) -> None:
        self.w.decision.complete.set(True)

    def reference_first_steps(self, chips: int) -> dict:
        """Run the reference before the program's state exists; its time
        is not set-up."""
        t0 = time.perf_counter()
        readings = self.ref.first_steps(self.ctx.seed, self.cfg,
                                        self.traffic, chips)
        took = time.perf_counter() - t0
        self.ctx.exclude(took)
        self.ctx.log(f"reference: first steps in {took:.1f} s (not counted "
                     f"in setup_s)")
        return readings

    def outcome(self, tap: TrainTap, ref_readings: dict, batch: int,
                chips: int, flops_per_sample: float) -> dict:
        """Compare, judge and gather what the readers need."""
        from benchlib import (BenchmarkError, compare_train_readings,
                              judge)
        from znicz_tpu.observe.trace import TRACER

        if not tap.done:
            raise BenchmarkError("the workflow ended before the window did")
        ctx, ref = self.ctx, self.ref
        readings = compare_train_readings(tap.readings, ref_readings)
        ok, lines = judge(readings, ref.LIMITS)
        finite = tap.losses_finite()
        lines.append(f"check loss finite at every sub-window boundary: "
                     f"{'ok' if finite else 'FAILED'}")
        samples = tap.samples(batch, chips)
        samples["flops_per_sample"] = flops_per_sample
        samples["program_spans"] = [
            e for e in TRACER.export_dict()["traceEvents"]
            if e.get("ph") == "X"]
        samples["step_unit"] = self.w.step.name
        samples["config_as_run"] = self.cfg
        samples["readings"] = {k: v[0] for k, v in readings.items()}
        if ctx.control:
            control = compare_train_readings(
                ref.first_steps(ctx.seed, self.cfg, self.traffic, chips,
                                precision="fp8"), ref_readings)
            samples["control_readings"] = {k: v[0]
                                           for k, v in control.items()}
        return {"correct": ok and finite, "lines": lines,
                "attempted": samples["steps_in_window"], "failed": 0,
                "samples": samples}


def attach_tap(workflow, driver: TrainTap, on_initialize=None):
    """Link the leaf unit after ``workflow.step``."""
    from znicz_tpu.core.units import Unit

    class WindowTap(Unit):
        def __init__(self, wf) -> None:
            super().__init__(wf, name="BenchTap")

        def initialize(self, device=None, **kwargs) -> None:
            super().initialize(device=device, **kwargs)
            if on_initialize is not None:
                on_initialize()

        def run(self) -> None:
            driver.on_step()

    tap = WindowTap(workflow)
    tap.link_from(workflow.step)
    return tap


def apply_engine(settings: dict) -> dict:
    """Set ``root.common.engine`` keys a configuration or traffic file
    names (the program's own documented switches); returns the previous
    values for :func:`restore_engine`."""
    from znicz_tpu.core.config import root

    prev = {}
    for key, value in (settings or {}).items():
        prev[key] = root.common.engine.get(key, None)
        setattr(root.common.engine, key, value)
    return prev


def restore_engine(prev: dict) -> None:
    from znicz_tpu.core.config import root

    for key, value in prev.items():
        setattr(root.common.engine, key, value)
