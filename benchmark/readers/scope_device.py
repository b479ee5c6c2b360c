"""Device time by the program's named scopes: the four ``*_device_ms_per_
step`` metrics (``params.groups``) and ``unscoped_device_share``
(``params.unscoped``), all from chip 0 of the traced sub-windows.

The join from a device operation to its scope is the program's:
``znicz_tpu.observe.probe.scope_map()`` gives ``{module: {instruction:
scope}}`` from the optimised HLO of the programs it has watched, compiled
once more after the window (an ``XLA Ops`` event carries the HLO line
without its metadata).  An operation's
module is the ``XLA Modules`` event that contains it.  Self time is
``trace_reduce.self_times``, so the rows sum to the busy union.  A program
that has no ``scope_map`` (every commit before ISSUE 24) reads as nothing.

Once per traced run the reader logs the unit-level table: every scope with
its self time per step, forward and backward apart, and within each the
time of ``copy`` and ``pad`` operations.
"""

from __future__ import annotations

import bisect
import re

from benchlib import traced_steps
from trace_reduce import self_times, stable_name

#: scope groups of the forward units (``<group>.<index>_<unit>``)
UNIT_GROUPS = ("conv", "fc", "norm", "pool", "dropout", "act")
UNSCOPED = "(unscoped)"
_MODULE_ID = re.compile(r"\(\d+\)$")
_CACHE: dict = {}


def split_scope(component: str) -> tuple[str, str]:
    """``transpose(jvp(conv.00_c))`` -> ``("conv.00_c", "bwd")``; any
    other form is the forward pass."""
    name = component.rstrip(")").rsplit("(", 1)[-1]
    return name, "bwd" if component.startswith("transpose(") else "fwd"


def group_of(scope: str) -> str:
    head = scope.split(".", 1)[0]
    return head if head in UNIT_GROUPS else scope


def kind_of(name: str, opcode: str) -> str:
    """``copy`` / ``pad`` (by opcode, or by the fusion's name) / ``other``."""
    for kind in ("copy", "pad"):
        if opcode == kind or opcode.startswith(kind + "-") or \
                stable_name(name).split("_")[0] == kind:
            return kind
    return "other"


def module_events(path: str, plane_name: str) -> list:
    """``[(start, end, module name)]`` of one chip's ``XLA Modules``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                return sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     _MODULE_ID.sub("", ev.name)) for ev in line.events)
    return []


def scope_seconds(ops, modules, scopes: dict) -> dict:
    """``{(scope, "fwd"|"bwd", "copy"|"pad"|"other"): seconds}`` of self
    time.  ``ops`` are ``(start, end, name, opcode)`` of one chip,
    ``modules`` ``(start, end, module)`` sorted, ``scopes`` the program's
    scope map; what no scope covers goes under :data:`UNSCOPED`."""
    starts = [m[0] for m in modules]
    keys: dict = {}                   # key -> token self_times can carry
    tokens: list = []
    events = []
    for s, e, name, opcode in ops:
        i = bisect.bisect_right(starts, s) - 1
        module = modules[i][2] if i >= 0 and s < modules[i][1] else ""
        component = scopes.get(module, {}).get(name, "")
        scope, way = split_scope(component) if component else \
            (UNSCOPED, "fwd")
        key = (scope, way, kind_of(name, opcode))
        if key not in keys:
            # letters only: self_times strips numbered suffixes
            n, token = len(tokens), "k"
            while True:
                token += chr(ord("a") + n % 26)
                n //= 26
                if not n:
                    break
            keys[key] = token
            tokens.append(key)
        events.append((s, e, keys[key]))
    by_token = self_times(events)
    return {key: by_token.get(token, 0.0) for key, token in keys.items()}


def table(seconds: dict, steps: int) -> list:
    """Rows ``(scope, fwd ms, fwd copy, fwd pad, bwd ms, bwd copy, bwd
    pad)`` per step, largest first; ``fwd``/``bwd`` include their copy and
    pad."""
    rows: dict = {}
    for (scope, way, kind), sec in seconds.items():
        row = rows.setdefault(scope, [0.0] * 6)
        base = 0 if way == "fwd" else 3
        ms = 1e3 * sec / steps
        row[base] += ms
        if kind != "other":
            row[base + (1 if kind == "copy" else 2)] += ms
    return sorted(((scope, *row) for scope, row in rows.items()),
                  key=lambda r: -(r[1] + r[4]))


def _reduced(rc):
    """``(seconds by key, steps)`` of this run, computed and logged once."""
    key = id(rc.trace)
    if key in _CACHE:
        return _CACHE[key]
    _CACHE.clear()
    out = None
    steps = traced_steps(rc.samples)
    if rc.trace is not None and steps and rc.trace.devices:
        from znicz_tpu.observe import probe

        build = getattr(probe, "scope_map", None)
        scopes = build() if build is not None else {}
        for module, names in scopes.items():
            rc.log(f"scopes: map of {module}: {len(names)} instructions, "
                   f"{sum(1 for c in names.values() if c)} under a scope")
        if scopes:
            plane = rc.trace.device_names[0]
            seconds = scope_seconds(rc.trace.devices[plane],
                                    module_events(rc.trace.path, plane),
                                    scopes)
            out = (seconds, steps)
            _log_table(rc, seconds, steps)
        else:
            rc.log("scopes: the program gives no scope map; the scope "
                   "metrics read nothing")
    _CACHE[key] = out
    return out


def _log_table(rc, seconds: dict, steps: int) -> None:
    rows = table(seconds, steps)
    rc.log(f"scopes: self time on chip 0, ms per step over {steps} steps "
           f"(fwd and bwd include their copy and pad)")
    rc.log(f"scopes: {'scope':<28}{'fwd':>9}{'copy':>8}{'pad':>8}"
           f"{'bwd':>9}{'copy':>8}{'pad':>8}")
    for scope, *cols in rows:
        rc.log(f"scopes: {scope:<28}{cols[0]:>9.3f}{cols[1]:>8.3f}"
               f"{cols[2]:>8.3f}{cols[3]:>9.3f}{cols[4]:>8.3f}"
               f"{cols[5]:>8.3f}")
    total = sum(r[1] + r[4] for r in rows)
    rc.log(f"scopes: rows sum to {total:.3f} ms per step (the busy union "
           f"of chip 0)")


def read(rc):
    reduced = _reduced(rc)
    if reduced is None:
        return None
    seconds, steps = reduced
    params = rc.metric["params"]
    if params.get("unscoped"):
        total = sum(seconds.values())
        rest = sum(sec for (scope, _, _), sec in seconds.items()
                   if scope == UNSCOPED)
        return 100.0 * rest / total if total else None
    groups = set(params["groups"])
    return 1e3 * sum(sec for (scope, _, _), sec in seconds.items()
                     if group_of(scope) in groups) / steps
