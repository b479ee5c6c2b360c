"""The run's ``setup_s`` in the parts the program itself names
(``params.what``), read from the process's registry in place:

``trace_lower``  ``znicz_compile_phase_seconds_total{trace}`` + ``{lower}``
``compile``      ``{backend_compile}`` less the ``{cache_load}`` inside it
                 (jax measures the compile around the cache's lookup)
``cache_load``   ``{cache_load}``
``initialize``   ``znicz_setup_seconds{initialize}``
``unnamed``      ``setup_s`` less ``setup.load``, ``setup.initialize``,
                 the first calls (``znicz_compile_seconds`` sums) outside
                 them, and the compile phases of programs that are in
                 none of the three (the harness's own small programs,
                 which the first three metrics count): imports, the
                 client's start, the harness's own data, the fenced first
                 steps' reads, the warm sub-window

A counter counts the whole process; the set-up is the part of it between
``Launcher.load`` and the window's opening.  The program keeps its set-up
events with their stamps in a ring that the harness's clearing does not
reach (``probe.SETUP_RING``), so what was stamped outside that span comes
off each sum: the reference's compiles before ``setup.load`` (their time
is not in ``setup_s`` either) and the scope join's after the window.  A
program without these counters (an older tree) reads as nothing.

The log gives the parts of ``initialize`` beside it, first calls less the
compile phases inside them (first executions), and ``unnamed`` split as
far as the process can know.
"""

from __future__ import annotations

import os
import sys
import time

PHASES = ("trace", "lower", "backend_compile", "cache_load")
SETUP = ("load", "initialize", "init_params", "place", "backend")
_CACHE: dict = {}


def _program():
    """``(registry, set-up ring)`` or None on a tree without them."""
    from znicz_tpu.observe import probe
    from znicz_tpu.observe.registry import REGISTRY

    ring = getattr(probe, "SETUP_RING", None)
    if ring is None or \
            REGISTRY.get("znicz_compile_phase_seconds_total") is None or \
            REGISTRY.get("znicz_setup_seconds") is None:
        return None
    return REGISTRY, ring


def totals(registry) -> dict:
    """``{event name: seconds so far}`` of the three families, under the
    names their events carry in the ring."""
    out = {}
    for key, child in registry.get(
            "znicz_compile_phase_seconds_total").items():
        out[f"compile.{key[0]}"] = child.get()
    for key, child in registry.get("znicz_setup_seconds").items():
        out[f"setup.{key[0]}"] = child.get()
    first = registry.get("znicz_compile_seconds")
    out["compile.cold"] = sum(child.raw()[1] for _, child in first.items()) \
        if first is not None else 0.0
    return out


def _inside(events, name: str, lo: float, hi: float, spans=None) -> float:
    """Seconds of the ring's ``name`` events that start in ``[lo, hi)``
    us (and inside one of ``spans``, where given)."""
    return sum(e["dur"] for e in events if e["name"] == name and
               lo <= e["ts"] < hi and
               (spans is None or any(s <= e["ts"] < t for s, t in spans))
               ) / 1e6


def parts(events, total: dict, t_open_us: float, setup_s: float) -> dict:
    """The account.  ``events``: the set-up ring's ``X`` events (us on the
    tracer's clock); ``total``: :func:`totals`; ``t_open_us``: the window's
    opening on the same clock."""
    loads = [e["ts"] for e in events
             if e["name"] == "setup.load" and e["ts"] < t_open_us]
    lo = max(loads) if loads else float("-inf")

    def in_setup(name: str) -> float:
        outside = sum(e["dur"] for e in events if e["name"] == name and
                      not lo <= e["ts"] < t_open_us) / 1e6
        return max(total.get(name, 0.0) - outside, 0.0)

    acc = {name: in_setup(name) for name in
           [f"compile.{p}" for p in PHASES] + [f"setup.{p}" for p in SETUP]
           + ["compile.cold"]}
    named = [(e["ts"], e["ts"] + e["dur"]) for e in events
             if e["name"] in ("setup.load", "setup.initialize") and
             lo <= e["ts"] < t_open_us]
    cold = [(e["ts"], e["ts"] + e["dur"]) for e in events
            if e["name"] == "compile.cold" and lo <= e["ts"] < t_open_us]
    first_outside = acc["compile.cold"] - _inside(
        events, "compile.cold", lo, t_open_us, named)
    made = ("trace", "lower", "backend_compile")
    in_first = sum(_inside(events, f"compile.{p}", lo, t_open_us, cold)
                   for p in made)
    in_named = sum(_inside(events, f"compile.{p}", lo, t_open_us, named)
                   for p in made)
    elsewhere = sum(acc[f"compile.{p}"] for p in made) - in_first - in_named
    tail_from = max([t for _, t in named + cold], default=lo)
    before = {p: _inside(events, f"compile.{p}", float("-inf"), lo)
              for p in PHASES} if loads else {}
    after = {p: _inside(events, f"compile.{p}", t_open_us, float("inf"))
             for p in PHASES}
    return {
        "trace_lower": acc["compile.trace"] + acc["compile.lower"],
        "compile": max(acc["compile.backend_compile"] -
                       acc["compile.cache_load"], 0.0),
        "cache_load": acc["compile.cache_load"],
        "initialize": acc["setup.initialize"],
        "unnamed": setup_s - acc["setup.load"] - acc["setup.initialize"] -
        first_outside - elsewhere,
        "acc": acc, "first_outside": first_outside, "in_first": in_first,
        "elsewhere": elsewhere,
        "tail_compile": sum(
            _inside(events, f"compile.{p}", tail_from, t_open_us)
            for p in made),
        "tail_from_us": tail_from,
        "before_load": before, "after_window": after,
        "load_start_us": lo if loads else None,
        "init_end_us": max((t for _, t in named), default=None),
    }


def window_opening_us(ring) -> float:
    """The window's opening on the ring's clock: the harness clears the
    ring inside the tap's delivery that opens the window, so that
    delivery's span is the first of the tap's to end in the ring (a span
    recorded later may have STARTED earlier: ``workflow.run``)."""
    taps = [e["ts"] + e["dur"] for e in ring
            if e["name"] == "workflow.step" and
            (e.get("args") or {}).get("unit") == "BenchTap"]
    return min(taps or [e["ts"] + e["dur"] for e in ring])


def _process_age_s() -> float | None:
    """Seconds since this process started, from ``/proc``."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


def _log(rc, p: dict, tracer, t_open_us: float) -> None:
    acc = p["acc"]
    rc.log(f"setup: setup_s {rc.setup_s:.3f} = load {acc['setup.load']:.3f}"
           f" + initialize {acc['setup.initialize']:.3f} (of it init_params "
           f"{acc['setup.init_params']:.3f}, place {acc['setup.place']:.3f},"
           f" backend {acc['setup.backend']:.3f}) + first calls outside "
           f"them {p['first_outside']:.3f} (compile phases inside first "
           f"calls {p['in_first']:.3f}: first executions "
           f"{acc['compile.cold'] - p['in_first']:.3f}) + compile phases of "
           f"programs in none of them {p['elsewhere']:.3f} (the harness's "
           f"weights and the first steps' reads: small programs) + unnamed "
           f"{p['unnamed']:.3f}")
    rc.log("setup: compile phases between setup.load and the window: " +
           ", ".join(f"{ph} {acc['compile.' + ph]:.3f}" for ph in PHASES) +
           "; before setup.load (the reference's, not in setup_s, and the "
           "harness's own): " +
           ", ".join(f"{k} {v:.3f}" for k, v in p["before_load"].items()) +
           "; after the window opened (the scope join's): " +
           ", ".join(f"{k} {v:.3f}" for k, v in p["after_window"].items()))
    # the run's clock started at run.py's import (its _T0); everything is
    # put on the tracer's clock, whose zero is the tracer's creation
    main = sys.modules.get("__main__")
    t0 = getattr(main, "_T0", None)
    origin = getattr(tracer, "_origin", None)
    if t0 is None or origin is None or p["load_start_us"] is None:
        return
    t0_us = (t0 - origin) * 1e6
    age, now_us = _process_age_s(), (time.perf_counter() - origin) * 1e6
    excluded = (t_open_us - t0_us) / 1e6 - rc.setup_s
    between = ((p["init_end_us"] or t_open_us) - p["load_start_us"]) / 1e6 \
        - acc["setup.load"] - acc["setup.initialize"]
    tail = (t_open_us - p["tail_from_us"]) / 1e6
    rc.log(
        "setup: unnamed split: "
        + (f"process start to run.py's clock "
           f"{age - (now_us - t0_us) / 1e6:.3f} (the interpreter; not in "
           f"setup_s); " if age is not None else "")
        + f"run.py's clock to the tracer's origin {-t0_us / 1e6:.3f} "
        f"(imports and the client's start: run.py asks for its chips "
        f"before it imports the program); from there to setup.load "
        f"{p['load_start_us'] / 1e6 - excluded:.3f} (the harness's data; "
        f"the reference's {excluded:.3f} taken off); between load and "
        f"initialize {between:.3f} (the harness's weights, their programs' "
        f"compile phases included); after the last first call {tail:.3f} "
        f"(fenced first steps and their reads, the warm sub-window; of it "
        f"compile phases {p['tail_compile']:.3f})")


def read(rc):
    s = rc.samples
    prog = _program()
    if prog is None or s.get("kind") != "train" or \
            not s.get("program_spans"):
        return None
    key = id(s)
    if key not in _CACHE:
        _CACHE.clear()
        registry, ring = prog
        from znicz_tpu.observe.trace import TRACER

        t_open_us = window_opening_us(s["program_spans"])
        events = [e for e in ring.export_dict()["traceEvents"]
                  if e.get("ph") == "X"]
        _CACHE[key] = parts(events, totals(registry), t_open_us, rc.setup_s)
        _log(rc, _CACHE[key], TRACER, t_open_us)
    return _CACHE[key][rc.metric["params"]["what"]]
