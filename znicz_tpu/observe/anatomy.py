"""Step-time anatomy — phase-attributed accounting for train steps and
decode rounds (ISSUE 20 tentpole), and the stall watch (ISSUE 37).

The observe plane could already say *that* a step got slow (histograms,
watchtower rules) but not *why*.  This module is the attribution layer:
producers hand over phases they timed themselves and the accountant turns
them into

- ``znicz_anatomy_phase_seconds{plane,phase}`` histograms — wall seconds
  of one phase of one step (``plane`` names the producer: ``pipeline``,
  ``serve``);
- ``znicz_anatomy_step_seconds{plane}`` — the wall between consecutive
  dispatches of a train plane's step unit (``fused``, ``transformer``;
  :class:`StepCadence`);
- ``znicz_anatomy_steps_total{plane}`` — step count (the delta-rule
  friendly companion; pre-touched at init per the PR 11 lesson);
- complete-spans ``anatomy.<plane>.<phase>`` on the shared tracer ring,
  so phase breakdowns land on the SAME timeline as compiles, faults and
  unit firings.

Phase taxonomy (the label vocabulary — producers reuse, never invent):

==============  =============================================================
phase           meaning
==============  =============================================================
``input_wait``  consumer blocked on the input pipeline (prefetcher ring
                empty — the loader is the bottleneck)
``stage``       host->device staging of one batch (H2D put + ring fence)
``zero_gather`` ZeRO shard_params regather: flat shards -> full weights
``grad``        forward + backward compute producing per-rank local grads
``collective``  the explicit gradient psum (quantized or f32 — the
                cross-rank reduction dispatch)
``update``      optimizer apply: grads + state -> new params
``prefill``     serving: prompt attach / KV-cache prefill of admitted rows
``decode``      serving: one batched decode dispatch over live rows
``verify``      serving: speculative draft+verify round (scoring the
                draft's proposals with the target model)
==============  =============================================================

**The stall watch.**  A train step's host queues work and then waits on
it, so the gap between two dispatches says nothing: many steps of it are
the normal rhythm of a fenced sub-window or an epoch's read.  What a stop
is: work is queued and nothing completes, or nothing is queued and
nothing is dispatched.  Every dispatch hands :meth:`StepCadence.tick` its
newest output; one daemon thread a process (:class:`StallWatch`, 20 wakes
a second) asks each pending output ``is_ready()`` -- it never blocks on
one and never calls into jax otherwise, which may be what is stuck -- and
when completions or dispatches stay out for longer than
``max(3 x the typical step, FLOOR_S)`` it looks at the process ONCE while
the stop lasts (:func:`evidence`) and, when the stop ends, records a
``stall`` span, ``znicz_stall_seconds_total{plane,kind}`` and
``znicz_stalls_total{plane,kind}`` (docs/OBSERVABILITY.md "Stall watch").
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import sys
import threading
import time
import weakref
from collections import deque
from typing import Optional, Sequence

from znicz_tpu.observe import registry as _reg
from znicz_tpu.observe import trace as _trace

#: the closed phase vocabulary (docs/OBSERVABILITY.md catalogue) —
#: :func:`pretouch` materializes exactly these children per plane
PHASES = ("input_wait", "stage", "zero_gather", "grad", "collective",
          "update", "prefill", "decode", "verify")

_PHASE_SECONDS = _reg.histogram(
    "znicz_anatomy_phase_seconds",
    "wall seconds of one phase of one step, attributed at dispatch "
    "boundaries (phase taxonomy in docs/OBSERVABILITY.md)",
    labelnames=("plane", "phase"))
_STEP_SECONDS = _reg.histogram(
    "znicz_anatomy_step_seconds",
    "whole-step wall seconds measured at the same clock as the phase "
    "stamps (per-phase sums reconcile against this)",
    labelnames=("plane",))
_STEPS = _reg.counter(
    "znicz_anatomy_steps_total",
    "steps accounted by the anatomy layer (delta-rule companion to the "
    "histograms)", labelnames=("plane",))
_STALL_SECONDS = _reg.counter(
    "znicz_stall_seconds_total",
    "seconds of stops the stall watch caught on a train plane: device "
    "(work queued, nothing completed), host (nothing queued, nothing "
    "dispatched) or frozen (the watcher itself woke that late)",
    labelnames=("plane", "kind"))
_STALLS = _reg.counter(
    "znicz_stalls_total",
    "stops the stall watch caught (the companion of "
    "znicz_stall_seconds_total)", labelnames=("plane", "kind"))

_log = logging.getLogger("znicz_tpu.anatomy")


def _probe_enabled() -> bool:
    # late import: probe imports registry/trace like we do, and keeping
    # anatomy off probe's import path lets probe expose thin delegating
    # hooks without a cycle
    from znicz_tpu.observe import probe as _probe
    return _probe.enabled()


def pretouch(plane: str, phases: Optional[Sequence[str]] = None) -> None:
    """Materialize every child this plane will ever emit, BEFORE the
    first fleet sample (the PR 11 delta-rule lesson: a labeled child
    absent at the baseline sample makes a fleet delta/quantile rule
    silently never trip).  Histogram/gauge children materialize on
    ``labels()``; the counter additionally takes an ``inc(0)`` so a
    ``skip_zero`` snapshot keeps it too."""
    for phase in (phases if phases is not None else PHASES):
        _PHASE_SECONDS.labels(plane=plane, phase=phase)
    _STEP_SECONDS.labels(plane=plane)
    _STEPS.labels(plane=plane).inc(0.0)


def observe_phase(plane: str, phase: str, dt_s: float,
                  t0: Optional[float] = None) -> None:
    """One already-timed phase from a producer that owns its own clock
    (prefetcher input-wait/stage, the serving batcher's round phases):
    histogram observation + a complete-span on the tracer ring.  ``t0``
    is the phase's ``time.perf_counter()`` start when the producer has
    it (exact span placement); defaults to now-minus-duration."""
    if not _probe_enabled():
        return
    _PHASE_SECONDS.labels(plane=plane, phase=phase).observe(dt_s)
    start = t0 if t0 is not None else time.perf_counter() - dt_s
    _trace.TRACER.complete(f"anatomy.{plane}.{phase}", start, dt_s)




# -- the stall watch (ISSUE 37) ------------------------------------------------

#: seconds between two wakes of the watcher
WAKE_S = 0.05
#: no stop counts below this, however short the typical step (the watcher
#: sees a completion up to one wake late, and a fenced sub-window's first
#: step waits for the host's boundary work)
FLOOR_S = 0.2
#: the typical step is the mean tick interval over this many ticks: a
#: long-run rate, so bursts of dispatches and the fences between them
#: average out; none before MIN_TICKS, so first steps wait for nothing
TYPICAL_TICKS = 32
MIN_TICKS = 8
KINDS = ("device", "host", "frozen")
#: the evidence of one stall as JSON stays under this
EVIDENCE_BYTES = 4096
_MAX_FRAMES = 8
_MAX_THREAD_ROWS = 12


def parse_task_stat(text: str) -> Optional[tuple]:
    """``(name, state, cpu ticks)`` from one ``/proc/<pid>/task/<tid>/
    stat`` line; the name may hold spaces and brackets, so the fields are
    counted from its LAST closing bracket.  None where the line is not
    one."""
    lo, hi = text.find("("), text.rfind(")")
    rest = text[hi + 1:].split()
    if lo < 0 or hi < lo or len(rest) < 13:
        return None
    try:
        return text[lo + 1:hi], rest[0], int(rest[11]) + int(rest[12])
    except ValueError:
        return None


def parse_pressure(text: str) -> Optional[float]:
    """``some avg10`` of one ``/proc/pressure/<resource>`` file."""
    for line in text.splitlines():
        if line.startswith("some"):
            for field in line.split():
                if field.startswith("avg10="):
                    try:
                        return float(field[6:])
                    except ValueError:
                        return None
    return None


def _read(path: str) -> Optional[str]:
    try:
        with open(path, encoding="ascii", errors="replace") as f:
            return f.read(512)
    except OSError:
        return None


def thread_ticks(proc: str = "/proc") -> dict:
    """``{tid: (name, state, cpu ticks)}`` of this process's native
    threads; empty on a machine without ``/proc``."""
    base = os.path.join(proc, "self", "task")
    try:
        tids = os.listdir(base)
    except OSError:
        return {}
    out = {}
    for tid in tids:
        row = parse_task_stat(_read(os.path.join(base, tid, "stat")) or "")
        if row is not None:
            out[tid] = row
    return out


def thread_table(now: dict, before: Optional[dict],
                 proc: str = "/proc") -> dict:
    """The process's native threads at one instant: ``states`` counts
    them by state; ``busy`` lists ``[name, state, wchan, cpu ticks since
    `before`]`` for those that ran since ``before`` (None where no
    earlier reading exists: then those in state R) or sit in ``D``, the
    hungriest first, at most a dozen."""
    states: dict = {}
    busy = []
    for tid, (name, state, ticks) in now.items():
        states[state] = states.get(state, 0) + 1
        ran = None
        if before is not None and tid in before:
            ran = ticks - before[tid][2]
        if state == "D" or (ran if ran is not None else state == "R"):
            wchan = (_read(os.path.join(proc, "self", "task", tid,
                                        "wchan")) or "").strip()
            busy.append([name[:24], state, wchan[:32], ran])
    busy.sort(key=lambda r: -(r[3] or 0))
    return {"states": states, "busy": busy[:_MAX_THREAD_ROWS]}


def thread_frames(ident: Optional[int]) -> list:
    """The innermost frames of one Python thread, innermost first, as
    ``file:line function`` (at most eight)."""
    frame = sys._current_frames().get(ident) if ident is not None else None
    out = []
    while frame is not None and len(out) < _MAX_FRAMES:
        code = frame.f_code
        out.append(f"{os.path.basename(code.co_filename)}:"
                   f"{frame.f_lineno} {code.co_name}")
        frame = frame.f_back
    return out


def evidence(thread: Optional[int], late_s: float,
             before: Optional[dict] = None, proc: str = "/proc") -> dict:
    """What the process looks like while a stop lasts, taken once:
    ``watcher_late_ms`` (how far the watcher's own sleep overran: every
    thread late together means the process was frozen or descheduled, not
    the runtime stuck), ``frames`` of the dispatching thread,
    ``threads`` (:func:`thread_table`), the machine's ``pressure`` (``some
    avg10`` of cpu, memory and io) and ``loadavg``, where ``/proc`` has
    them.  Nothing here calls into jax or the runtime."""
    out = {"watcher_late_ms": round(late_s * 1e3, 1),
           "frames": thread_frames(thread)}
    now = thread_ticks(proc)
    if now:
        out["threads"] = thread_table(now, before, proc)
    pressure = {}
    for res in ("cpu", "memory", "io"):
        value = parse_pressure(
            _read(os.path.join(proc, "pressure", res)) or "")
        if value is not None:
            pressure[res] = value
    if pressure:
        out["pressure"] = pressure
    loadavg = _read(os.path.join(proc, "loadavg"))
    if loadavg:
        out["loadavg"] = " ".join(loadavg.split()[:3])
    while len(json.dumps(out)) > EVIDENCE_BYTES and \
            out.get("threads", {}).get("busy"):
        out["threads"]["busy"].pop()
    return out


def _ready(handle) -> bool:
    """Has the device finished this output?  Never blocks; an output
    without the question (or one deleted meanwhile) counts as done."""
    try:
        return bool(handle.is_ready())
    except Exception:  # noqa: BLE001 -- the watcher must outlive any handle
        return True


class _Watched:
    """The watcher's side of one cadence (its thread alone writes it)."""

    __slots__ = ("stamps", "n", "pending", "last_tick", "last_done",
                 "floor", "open", "before")

    def __init__(self) -> None:
        self.stamps: deque = deque(maxlen=TYPICAL_TICKS + 1)
        self.n = 0
        self.pending: deque = deque()
        self.last_tick = self.last_done = self.floor = float("-inf")
        self.open: Optional[dict] = None
        self.before: Optional[dict] = None

    def typical(self) -> Optional[float]:
        if self.n < MIN_TICKS:
            return None
        return (self.stamps[-1] - self.stamps[0]) / (len(self.stamps) - 1)


class StallWatch:
    """The watcher: :meth:`wake` is its loop's whole body, so a test
    drives it with a clock of its own and no thread.  The process's one
    instance (:data:`WATCH`) starts its daemon thread with the first
    cadence that joins and lets it end when none is left."""

    def __init__(self, clock=time.perf_counter, proc: str = "/proc",
                 threaded: bool = True) -> None:
        self._clock, self._proc, self._threaded = clock, proc, threaded
        self._cadences: "weakref.WeakSet" = weakref.WeakSet()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._due: Optional[float] = None
        self._halted = threading.Event()
        if threaded:
            atexit.register(self._halt)

    def add(self, cadence: "StepCadence") -> None:
        with self._lock:
            self._cadences.add(cadence)
            if self._threaded and self._thread is None and \
                    not self._halted.is_set():
                self._due = None
                self._thread = threading.Thread(
                    target=self._loop, name="znicz-stall-watch", daemon=True)
                self._thread.start()

    def _halt(self) -> None:
        """End the thread before the interpreter does: a daemon thread
        that the finalizing interpreter stops inside ``is_ready()`` (C++
        frames on its stack) aborts the process ("FATAL: exception not
        rethrown", exit code -6 after a run that had finished)."""
        self._halted.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=2.0)

    def _release(self, cadence: "StepCadence", w: _Watched) -> None:
        """Let go of a closed cadence and of the outputs it still held;
        its next tick joins again."""
        with self._lock:
            if not cadence._joined:
                self._cadences.discard(cadence)
                w.pending.clear()
                w.before = None

    @property
    def running(self) -> bool:
        return self._thread is not None

    def _loop(self) -> None:
        while not self._halted.wait(WAKE_S):
            with self._lock:
                if not self._cadences:
                    self._thread = None
                    return
            try:
                self.wake()
            except Exception:  # noqa: BLE001 -- a watcher that dies sees
                _log.debug("stall watch: wake failed", exc_info=True)

    def wake(self) -> None:
        """One look at every cadence."""
        now = self._clock()
        late = 0.0 if self._due is None else max(now - self._due, 0.0)
        for cadence in list(self._cadences):
            self._look(cadence, cadence._w, now, late)
        self._due = self._clock() + WAKE_S

    def _look(self, cadence: "StepCadence", w: _Watched, now: float,
              late: float) -> None:
        ticks, first_new = cadence._ticks, None
        while ticks:
            t0, handle = ticks.popleft()
            if first_new is None:
                first_new = t0
            w.stamps.append(t0)
            w.n += 1
            w.last_tick = t0
            w.pending.append((t0, handle))
        pending, done = w.pending, 0
        while pending and _ready(pending[0][1]):
            pending.popleft()
            done += 1
        if done:
            w.last_done = now
        stall = w.open
        if stall is not None:
            if stall["kind"] == "device" and done or not cadence._joined:
                self._close(cadence, w, now)
            elif stall["kind"] == "host" and first_new is not None:
                self._close(cadence, w, first_new)
            return
        if not cadence._joined:
            self._release(cadence, w)
            return
        typical = w.typical()
        if typical is None:
            return
        limit = max(3.0 * typical, FLOOR_S)
        if late > limit:
            # every thread of the process was late with the watcher; what
            # the rules below would make of the same seconds is this
            w.open = self._opened("frozen", now - late - typical, typical,
                                  len(pending), cadence, late, None)
            self._close(cadence, w, now)
            w.floor = now
            return
        if pending:
            kind = "device"
            since = max(w.last_done, w.floor, pending[0][0])
        else:
            kind = "host"
            since = max(w.last_tick, w.last_done, w.floor)
        waited = now - since
        if waited > limit:
            w.open = self._opened(kind, since, typical, len(pending),
                                  cadence, late, w.before)
            w.before = None
        elif waited > limit * 2.0 / 3.0:
            # a stop may be coming: the reading its threads' CPU ticks
            # are counted from (a quiet wake reads nothing of /proc)
            w.before = thread_ticks(self._proc)
        else:
            w.before = None

    def _opened(self, kind: str, since: float, typical: float,
                pending: int, cadence: "StepCadence", late: float,
                before: Optional[dict]) -> dict:
        return {"kind": kind, "start": since + typical,
                "typical": typical, "pending": pending,
                "evidence": evidence(cadence._thread, late, before,
                                     self._proc)}

    def _close(self, cadence: "StepCadence", w: _Watched,
               end: float) -> None:
        stall, w.open = w.open, None
        seconds = max(end - stall["start"], 0.0)
        plane, kind = cadence.plane, stall["kind"]
        args = {"plane": plane, "kind": kind,
                "typical_ms": round(stall["typical"] * 1e3, 3),
                "pending": stall["pending"], **stall["evidence"]}
        _trace.TRACER.complete("stall", stall["start"], seconds, args,
                               tid=cadence._thread)
        _STALL_SECONDS.labels(plane=plane, kind=kind).inc(seconds)
        _STALLS.labels(plane=plane, kind=kind).inc()
        from znicz_tpu.observe import flight
        flight.auto_dump("stall", seconds=round(seconds, 3), **args)


#: THE process's stall watch
WATCH = StallWatch()


class StepCadence:
    """A train plane's dispatch cadence: the producer of
    ``znicz_anatomy_step_seconds{plane}`` (the wall between consecutive
    dispatches of one step unit, what the fleet watchtower's straggler
    rule compares across ranks) and the stall watch's source.
    :meth:`tick` takes the dispatch span's own start stamp and the
    dispatch's newest output that the next step does not donate; it costs
    the dispatching thread one append.  What the chip does inside a step
    is read from the profiler (named scopes, docs/OBSERVABILITY.md)."""

    __slots__ = ("plane", "_step_child", "_steps", "_last", "_ticks",
                 "_thread", "_watch", "_joined", "_w", "__weakref__")

    def __init__(self, plane: str,
                 watch: Optional[StallWatch] = None) -> None:
        pretouch(plane, ())
        for kind in KINDS:
            _STALL_SECONDS.labels(plane=plane, kind=kind).inc(0.0)
            _STALLS.labels(plane=plane, kind=kind).inc(0.0)
        self.plane = str(plane)
        self._step_child = _STEP_SECONDS.labels(plane=plane)
        self._steps = _STEPS.labels(plane=plane)
        self._last: Optional[float] = None
        #: (dispatch start, newest output): appended here, popped by the
        #: watcher (deque appends and pops are atomic); bounded, so that a
        #: watcher that is gone (a forked child has no thread) holds no
        #: growing row of device scalars
        self._ticks: deque = deque(maxlen=4096)
        self._thread: Optional[int] = None
        self._watch = watch if watch is not None else WATCH
        #: under the watch: from a tick to the next :meth:`close`
        self._joined = False
        self._w = _Watched()

    def tick(self, now: float, handle=None) -> None:
        last, self._last = self._last, now
        if not _probe_enabled():
            return
        if last is not None:
            self._step_child.observe(now - last)
            self._steps.inc()
        if not self._joined:
            self._join()
        self._ticks.append((now, handle))

    def _join(self) -> None:
        """The first tick (and the first after :meth:`close`) names the
        dispatching thread and puts the cadence under the watch."""
        self._thread = threading.get_ident()
        self._joined = True             # before add(): see _release
        self._watch.add(self)

    def close(self) -> None:
        """The step unit stopped (``workflow.stop``): no dispatch is due,
        so none is missed.  The watcher closes what it has open at its
        next wake and then lets go."""
        self._joined = False
