"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
union, per-operation self time, kernel and collective time, and the idle
gaps attributed to what the host was doing in them.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.  The
reduction is code so that every PR computes the same number the same way;
``tests/test_trace_reduce.py`` pins it on a small recorded trace.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds the operations (properly nested: a ``while`` holds its body) and
a host plane (``/host:CPU``) with one line per thread of ``TraceMe`` spans,
among them the benchmark's own ``jax.profiler.TraceAnnotation`` spans; all
on one clock.
"""

from __future__ import annotations

import glob
import os
import re

_OPS_LINE = "XLA Ops"
_SKIP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
               "Framework Name Scope", "Source code")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")
_SUFFIX = re.compile(r"\.\d+")
#: the opcode of an HLO line: the word before the first "(" that follows a
#: closed shape ("...} fusion(", "...) copy-start(", "s32[] add(")
_OPCODE = re.compile(r"[\}\)\]] ([a-z][a-z0-9_\-]*)\(")
#: host spans that only say "the runtime is running": never the reason
_HOST_NOISE = ("ThreadpoolListener", "ThunkExecutor")


def stable_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``: numbering shifts between compiles."""
    return _SUFFIX.sub("", name.split(" ")[0].lstrip("%"))[:64]


def split_hlo(text: str) -> tuple[str, str]:
    """An XLA Ops event's name is the whole HLO line, ``%name = shape
    opcode(operands)``: -> ``(name, opcode)``.  The name is JAX's (a
    ``lax.psum`` is ``%psum.3 = ... all-reduce(...)``), so collectives are
    found by opcode and kernels by name; and neither may be looked for in
    the operands, where every consumer of a kernel's result names it."""
    name, sep, rest = text.partition(" = ")
    m = _OPCODE.search(rest) if sep else None
    return name.lstrip("%"), (m.group(1) if m else "")


def newest_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir!r}")
    return max(files, key=os.path.getmtime)


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end)]`` (any order, overlaps
    and nesting allowed)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals, lo: float, hi: float):
    """The complement of the union inside ``[lo, hi]`` as ``[(s, e)]``."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
    if cursor < hi:
        out.append((cursor, hi))
    return out


def self_times(events) -> dict:
    """``{stable name: seconds}`` of self time on one properly nested
    line: an event's duration minus what its children cover, so the
    names sum to the busy union."""
    out: dict = {}
    stack: list = []                  # [end, name, self_ns]

    def close(until):
        while stack and stack[-1][0] <= until:
            _, name, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns / 1e9

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, stable_name(name), e - s])
    close(float("inf"))
    return out


class Trace:
    """The reduced view of one ``.xplane.pb``."""

    def __init__(self, path: str) -> None:
        import jax

        self.path = path
        data = jax.profiler.ProfileData.from_file(path)
        self.devices: dict = {}       # plane -> [(s, e, name, opcode)] ns
        self.host: list = []          # [(s, e, name)] ns, every host line
        for plane in data.planes:
            if "/device:" in plane.name and "TPU" in plane.name:
                lines = list(plane.lines)
                ops = [ln for ln in lines if ln.name == _OPS_LINE] or \
                    [ln for ln in lines if ln.name not in _SKIP_LINES]
                self.devices[plane.name] = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     *split_hlo(ev.name))
                    for ln in ops for ev in ln.events]
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    self.host.extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in ln.events if not ev.name.startswith("$"))
        self.device_names = sorted(self.devices)

    # -- device ---------------------------------------------------------------
    def span(self) -> tuple[float, float]:
        """First start and last end of any device operation, ns."""
        evs = [ev for d in self.devices.values() for ev in d]
        if not evs:
            return (0.0, 0.0)
        return (min(e[0] for e in evs), max(e[1] for e in evs))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_length([(ev[0], ev[1]) for ev in evs])
                   for evs in self.devices.values()) / len(self.devices) / 1e9

    def op_self_times(self, device: int = 0) -> list:
        """``[(name, seconds)]`` by self time, most first, one chip."""
        if not self.devices:
            return []
        evs = self.devices[self.device_names[device]]
        return sorted(self_times([ev[:3] for ev in evs]).items(),
                      key=lambda kv: -kv[1])

    def matching_s(self, patterns, device: int = 0,
                   opcode: bool = False) -> tuple[float, int]:
        """Summed duration and count of the operations on one chip whose
        name (or, with ``opcode``, whose HLO opcode) contains any of
        ``patterns`` (outermost match only, so a kernel inside a matching
        envelope is not counted twice)."""
        if not self.devices:
            return 0.0, 0
        field = 3 if opcode else 2
        evs = sorted((e for e in self.devices[self.device_names[device]]
                      if any(p in e[field] for p in patterns)),
                     key=lambda ev: (ev[0], -ev[1]))
        total, count, end = 0.0, 0, -1
        for s, e, *_ in evs:
            if s >= end:
                total += e - s
                count += 1
                end = e
        return total / 1e9, count

    def collective_s(self, device: int = 0) -> tuple[float, int]:
        return self.matching_s(_COLLECTIVES, device, opcode=True)

    # -- the host in the device's idle time ------------------------------------
    def idle_gaps(self, device: int = 0, min_gap_ns: float = 5e3,
                  top: int = 10) -> list:
        """``[(host span name, seconds)]``: each idle gap of one chip,
        between its first and last operation, goes to the host span that
        overlaps it longest (the shortest such span on a tie, which is
        the innermost); gaps nothing overlaps go to ``(no host span)``."""
        if not self.devices:
            return []
        evs = self.devices[self.device_names[device]]
        if not evs:
            return []
        lo, hi = min(e[0] for e in evs), max(e[1] for e in evs)
        host = sorted((h for h in self.host
                       if not any(n in h[2] for n in _HOST_NOISE)),
                      key=lambda h: h[0])
        out: dict = {}
        live: list = []               # host spans that may still overlap
        nxt = 0
        for s, e in gaps_of([(ev[0], ev[1]) for ev in evs], lo, hi):
            if e - s < min_gap_ns:
                continue
            # one sweep: gaps come in order, so a span that ended before
            # this gap began can never matter again
            while nxt < len(host) and host[nxt][0] < e:
                live.append(host[nxt])
                nxt += 1
            live = [h for h in live if h[1] > s]
            best, best_key = "(no host span)", (0, 0)
            for hs, he, name in live:
                overlap = min(e, he) - max(s, hs)
                if overlap > 0:
                    key = (overlap, -(he - hs))
                    if key > best_key:
                        best, best_key = name, key
            name = best[:64]
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return sorted(out.items(), key=lambda kv: -kv[1])[:top]

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [[n, s] for n, s in
                               self.op_self_times()[:top]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps(top=top)]}

    def describe(self, limit: int = 6) -> str:
        """What the file holds, for a first look at a new platform."""
        import jax

        data = jax.profiler.ProfileData.from_file(self.path)
        rows = []
        for plane in data.planes:
            rows.append(f"plane {plane.name!r}")
            for ln in plane.lines:
                evs = list(ln.events)
                names = [e.name for e in evs[:limit]]
                rows.append(f"  line {ln.name!r}: {len(evs)} events {names}")
        return "\n".join(rows)
