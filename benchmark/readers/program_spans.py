"""What the program's own spans say about the host side of a step and
about the chip's idle time (``params.what``):

``metrics_read_idle``  ms per step of chip 0's idle-gap time whose
                       innermost *program* span is ``params.span``
``unnamed_idle_share`` % of idle-gap time that no program span overlaps
``step_host``          ms per pass of the step unit's ``workflow.step``
                       spans, less the ``params.minus`` spans inside them
                       (the ring, over the whole window)

A program span is told from the runtime's ``TraceMe`` spans by name: the
names in ``rc.samples["program_spans"]`` (the tracer's ring).  Since ISSUE
24 a live span is also a ``jax.profiler.TraceAnnotation``, so it lies in
the profile's host plane on the device's clock; the reader takes them from
the profile itself (``rc.trace.path``), which also gives ``workflow.step``
its unit.  A program whose tracer has no second sink
(``Tracer.live_names`` missing) reads as nothing.

Once per traced run the reader logs, for every live span name, count and
summed duration in the ring and in the host plane over the traced window:
the check that the two sinks share a call site and a clock.
"""

from __future__ import annotations

import bisect

from benchlib import traced_steps
from trace_reduce import gaps_of

MIN_GAP_NS = 5e3          # as Trace.idle_gaps
NO_SPAN = "(no program span)"
_CACHE: dict = {}


def _tracer():
    from znicz_tpu.observe.trace import TRACER

    return TRACER if hasattr(TRACER, "live_names") else None


def gap_owners(ops, spans, min_gap_ns: float = MIN_GAP_NS) -> tuple:
    """``(owners, innermost, all gap ns)`` over the idle gaps of one chip
    between its first and last operation, both ``{span name: ns}``.
    ``owners``: a whole gap goes to the span that overlaps it longest (the
    shortest such span on a tie: the innermost), or to :data:`NO_SPAN`.
    ``innermost``: every instant of a gap goes to the shortest span that
    covers it, so a gap that straddles two spans inside a third is split
    between the two; ``innermost[NO_SPAN]`` is the gap time no span
    overlaps at all.  ``ops`` are ``(start, end, ...)``, ``spans``
    ``(start, end, name)``."""
    if not ops:
        return {}, {}, 0.0
    lo, hi = min(o[0] for o in ops), max(o[1] for o in ops)
    spans = sorted(spans)
    owners: dict = {}
    innermost: dict = {}
    total = 0.0
    live: list = []
    nxt = 0
    for s, e in gaps_of([(o[0], o[1]) for o in ops], lo, hi):
        if e - s < min_gap_ns:
            continue
        while nxt < len(spans) and spans[nxt][0] < e:
            live.append(spans[nxt])
            nxt += 1
        live = [h for h in live if h[1] > s]
        over = [h for h in live if min(e, h[1]) > max(s, h[0])]
        best, best_key = NO_SPAN, (0, 0)
        for hs, he, name in over:
            key = (min(e, he) - max(s, hs), -(he - hs))
            if key > best_key:
                best, best_key = name, key
        owners[best] = owners.get(best, 0.0) + (e - s)
        cuts = sorted({s, e, *(min(max(t, s), e)
                               for h in over for t in h[:2])})
        for a, b in zip(cuts, cuts[1:]):
            cover = [h for h in over if h[0] <= a and h[1] >= b]
            name = min(cover, key=lambda h: h[1] - h[0])[2] if cover \
                else NO_SPAN
            innermost[name] = innermost.get(name, 0.0) + (b - a)
        total += e - s
    return owners, innermost, total


def step_host_ms(ring, step_unit: str, minus: str) -> float | None:
    """Mean ms of the step unit's ``workflow.step`` spans less the
    ``minus`` spans that lie inside them (``ring``: Chrome ``X`` events,
    microseconds)."""
    steps = [(e["ts"], e["ts"] + e["dur"]) for e in ring
             if e["name"] == "workflow.step" and
             (e.get("args") or {}).get("unit") == step_unit]
    if not steps:
        return None
    steps.sort()
    total = sum(e - s for s, e in steps)
    starts = [s for s, _ in steps]
    for ev in ring:
        if ev["name"] != minus:
            continue
        i = bisect.bisect_right(starts, ev["ts"]) - 1
        if i >= 0 and ev["ts"] + ev["dur"] <= steps[i][1] + 1e-3:
            total -= ev["dur"]
    return total / len(steps) / 1e3


def profile_spans(path: str, names) -> tuple:
    """``([(start, end, label)], window)`` from the profile itself: the
    host plane's events whose name is in ``names`` (``workflow.step``
    labelled ``workflow.step{<unit>}`` from its ``unit`` argument), and
    ``(profile_start_time, profile_stop_time)`` in unix ns, or None."""
    import jax

    spans, window = [], None
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name not in names:
                        continue
                    label = ev.name
                    if label == "workflow.step":
                        unit = dict(ev.stats).get("unit")
                        label = f"workflow.step{{{unit}}}"
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  label))
        else:
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                window = (float(stats["profile_start_time"]),
                          float(stats["profile_stop_time"]))
    return spans, window


def sink_agreement(ring, host, names, origin_unix_s: float,
                   window: tuple) -> list:
    """``[(name, ring n, ring s, host n, host s)]`` over the profile's
    window.  Ring spans are put on the profile's clock (ns since
    ``profile_start_time``) through the wall clock; a span counts where it
    lies wholly inside the window, on either side."""
    t0, t1 = window
    span_ns = t1 - t0
    rows = []
    for name in sorted(names):
        ring_d = [e["dur"] * 1e3 for e in ring if e["name"] == name and
                  0.0 <= origin_unix_s * 1e9 + e["ts"] * 1e3 - t0 and
                  origin_unix_s * 1e9 + (e["ts"] + e["dur"]) * 1e3 - t0
                  <= span_ns]
        host_d = [e - s for s, e, n in host if n == name and s >= 0.0 and
                  e <= span_ns]
        rows.append((name, len(ring_d), sum(ring_d) / 1e9, len(host_d),
                     sum(host_d) / 1e9))
    return rows


def _profile(rc, tracer, ring) -> list:
    """The program's spans in the host plane; logs the two sinks'
    agreement once a run."""
    key = id(rc.trace)
    if key in _CACHE:
        return _CACHE[key]
    _CACHE.clear()
    names = {e["name"] for e in ring}
    spans, window = profile_spans(rc.trace.path, names)
    _CACHE[key] = spans
    if window is None:
        rc.log("spans: the profile states no start time; ring and host "
               "plane are not compared")
        return spans
    origin = tracer.export_dict()["origin_unix_ts"]
    rc.log("spans: ring against the profiler's host plane over the traced "
           "window (count, summed seconds)")
    host = [(s, e, label.split("{")[0]) for s, e, label in spans]
    for name, rn, rs, hn, hs in sink_agreement(
            ring, host, tracer.live_names, origin, window):
        ok = abs(rn - hn) <= 2 and abs(rs - hs) <= 0.02 * max(rs, hs, 1e-9)
        rc.log(f"spans: check {name}: ring {rn} {rs:.6f} host {hn} "
               f"{hs:.6f} {'ok' if ok else 'DIFFER'}")
    ring_only = sorted(names - set(tracer.live_names))
    rc.log(f"spans: ring-only (complete(), never annotated): {ring_only}")
    return spans


def read(rc):
    tracer = _tracer()
    s = rc.samples
    if tracer is None or s.get("kind") != "train":
        return None
    params = rc.metric["params"]
    ring = s["program_spans"]
    if params["what"] == "step_host":
        return step_host_ms(ring, s["step_unit"], params["minus"])
    steps = traced_steps(s)
    if rc.trace is None or not steps or not rc.trace.devices:
        return None
    ops = rc.trace.devices[rc.trace.device_names[0]]
    owners, innermost, total = gap_owners(ops, _profile(rc, tracer, ring))
    if params["what"] == "unnamed_idle_share":
        return 100.0 * innermost.get(NO_SPAN, 0.0) / total if total else 0.0
    for title, table in (("whole gaps to the longest-overlapping span",
                          owners),
                         ("gap time to the innermost span", innermost)):
        top = sorted(table.items(), key=lambda kv: -kv[1])[:8]
        rc.log(f"spans: idle of chip 0, ms per step, {title}: " +
               ", ".join(f"{n} {ns / 1e6 / steps:.3f}" for n, ns in top))
    # by time under the innermost span, not by whole gaps: the delivery
    # that encloses the read overlaps every gap a little longer than the
    # read does and would own them all (0.009 against 0.485 ms, PR 24)
    return innermost.get(params["span"], 0.0) / 1e6 / steps
