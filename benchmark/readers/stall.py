"""``window_stall_share``: the program's own ``stall`` spans (its stall
watch: a stop caught while it lasts, ``znicz_tpu/observe/anatomy.py``)
that lie inside the window's sub-windows, summed, over the window's wall
(``sum(walls)``), in %.  0 in a quiet run; nothing where the program has
no stall watch.

A sub-window is found in the ring as the harness closed it: the tap's
``workflow.step{BenchTap}`` span in which the ring was cleared opens the
window, and every K-th after it closes one, which lasted ``walls[i]``
from the end of the span before.  What a traced run does between two
sub-windows (the profiler's start and stop) is outside all of them.

For every stall the reader logs the sub-window it fell in, its length,
kind, ``watcher_late_ms``, the dispatching thread's innermost frame and
the thread table; and for the run's three slowest sub-windows the summed
ring time by span name against the median sub-window's of that name, so
that a slow sub-window without a stall is named too.
"""

from __future__ import annotations

import bisect
import statistics

TAP = "BenchTap"


def has_stall_watch() -> bool:
    from znicz_tpu.observe.registry import REGISTRY

    return REGISTRY.get("znicz_stall_seconds_total") is not None


def _label(event: dict) -> str:
    if event["name"] == "workflow.step":
        return f"workflow.step{{{(event.get('args') or {}).get('unit')}}}"
    return event["name"]


def sub_windows(ring, k: int, walls) -> list | None:
    """``[(start us, end us)]`` of the window's sub-windows on the ring's
    clock, or None where the ring does not hold a tap span for each."""
    ends = sorted(e["ts"] + e["dur"] for e in ring
                  if _label(e) == f"workflow.step{{{TAP}}}")
    if len(ends) < k * len(walls) + 1:
        return None
    return [(ends[i * k], ends[i * k] + wall * 1e6)
            for i, wall in enumerate(walls)]


def place(stall: dict, windows) -> tuple:
    """``(index of the sub-window the stall overlaps longest or None, us
    of it inside any sub-window)``."""
    s, e = stall["ts"], stall["ts"] + stall["dur"]
    over = [max(min(e, hi) - max(s, lo), 0.0) for lo, hi in windows]
    inside = sum(over)
    return (over.index(max(over)) if inside > 0.0 else None), inside


def name_sums(ring, windows) -> list:
    """``{span label: summed us}`` for each sub-window, a span counted
    where it starts."""
    sums = [{} for _ in windows]
    starts = [lo for lo, _ in windows]          # back to back, so sorted
    for e in ring:
        i = bisect.bisect_right(starts, e["ts"]) - 1
        if i >= 0 and e["ts"] < windows[i][1]:
            label = _label(e)
            sums[i][label] = sums[i].get(label, 0.0) + e["dur"]
    return sums


def describe(stall: dict, idx, inside_us: float) -> str:
    a = stall.get("args") or {}
    where = f"sub-window #{idx}" if idx is not None else \
        "outside the window's sub-windows"
    frames = a.get("frames") or ["?"]
    threads = a.get("threads") or {}
    return (f"stall: {a.get('kind')} {stall['dur'] / 1e3:.1f} ms in {where} "
            f"({inside_us / 1e3:.1f} ms of it inside), typical step "
            f"{a.get('typical_ms')} ms, pending {a.get('pending')}, "
            f"watcher late {a.get('watcher_late_ms')} ms, at {frames[0]}; "
            f"threads {threads.get('states')} busy {threads.get('busy')}; "
            f"pressure {a.get('pressure')} loadavg {a.get('loadavg')}")


def slowest_lines(sums: list, walls, top: int = 3, names: int = 5) -> list:
    """For the ``top`` slowest sub-windows, the span names whose summed
    time lies furthest above the median sub-window's."""
    labels = sorted({n for s in sums for n in s})
    median = {n: statistics.median(s.get(n, 0.0) for s in sums)
              for n in labels}
    lines = []
    for i in sorted(range(len(walls)), key=lambda i: -walls[i])[:top]:
        over = sorted(labels, key=lambda n: median[n] - sums[i].get(n, 0.0))
        lines.append(
            f"stall: sub-window #{i} {walls[i] * 1e3:.1f} ms (median "
            f"{statistics.median(walls) * 1e3:.1f}); ring ms by span, this "
            f"one / the median one: " + ", ".join(
                f"{n} {sums[i].get(n, 0.0) / 1e3:.1f} / "
                f"{median[n] / 1e3:.1f}" for n in over[:names]))
    return lines


def read(rc):
    s = rc.samples
    if s.get("kind") != "train" or not s.get("walls") or \
            not has_stall_watch():
        return None
    ring, walls = s["program_spans"], s["walls"]
    windows = sub_windows(ring, int(s["k"]), walls)
    if windows is None:
        rc.log("stall: the ring holds fewer tap spans than the window has "
               "steps; sub-windows cannot be placed")
        return None
    total = 0.0
    for stall in (e for e in ring if e["name"] == "stall"):
        idx, inside = place(stall, windows)
        total += inside
        rc.log(describe(stall, idx, inside))
    for line in slowest_lines(name_sums(ring, windows), walls):
        rc.log(line)
    return 100.0 * total / 1e6 / sum(walls)
