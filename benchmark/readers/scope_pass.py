"""Device time by pass, by the rule that gave an operation its scope and by
the parts of a scope: ``remat_`` / ``bwd_device_ms_per_step``
(``params.way``), ``lent_scope_`` / ``mixed_fusion_device_share``
(``params.share``) and the ``*_device_ms_per_step`` of scopes opened inside
another (``params.innermost``, regular expressions matched whole), all from
chip 0 of the traced sub-windows.

The join is the program's: ``znicz_tpu.observe.probe.scope_table()`` gives
``{module: {instruction: row}}`` from the same one compile a program that
``scope_map()`` (reader ``scope_device``) projects its scopes from.  A row
says which scopes stand in the operation's ``op_name`` (``path``), which
pass it belongs to (``way``: ``fwd`` / ``remat`` / ``bwd``, read off any
component of the path, so inside scans and checkpointed layers too), which
rule gave it the scope (``how``: ``own`` / ``root`` / ``inside`` / ``lent``),
whether its fused instructions are of more than one scope or pass
(``mixed``), what it holds (``holds``: ``kernel`` / ``product`` / ``stack``
/ ``copy`` / ``pad`` / ``other``) and the ``(scope, way)`` of the work it
does (``by_work``).  The passes are ``by_work``'s; they sum to the busy
union, so forward time is ``step_device_ms`` less the other two.

An operation's module is the ``XLA Modules`` event that contains it
(``scope_device.module_events``); self time is ``trace_reduce.self_times``.
A program that has no ``scope_table`` (every commit before ISSUE 50) reads
as nothing; one that has it reads a number, 0.0 where no row matches.

Once per traced run the reader logs one table: every scope (by the work)
with its forward, recomputed and backward time per step, the time it holds
by a neighbour's scope and in mixed fusions, its time in kernels, products
and stacking writes and reads, and the time it gains from and loses to
other scopes where ``by_work`` names another scope than the scope map.
"""

from __future__ import annotations

import bisect
import re
from typing import NamedTuple

from benchlib import traced_steps
from trace_reduce import self_times

UNSCOPED = "(unscoped)"
WAYS = ("fwd", "remat", "bwd")
HELD = ("kernel", "product", "stack")
_CACHE: dict = {}


class Key(NamedTuple):
    """What self time is summed by."""

    work: str       # the row's by_work scope
    scope: str      # the scope map's (outermost, bare)
    inner: str      # the innermost scope of the row's path
    way: str        # by_work's pass
    how: str
    mixed: bool
    holds: str


NO_ROW = Key(UNSCOPED, UNSCOPED, UNSCOPED, "fwd", "none", False, "other")


def key_of(row) -> Key:
    path = row.path or (UNSCOPED,)
    return Key(row.by_work[0] or UNSCOPED, path[0], path[-1],
               row.by_work[1], row.how, bool(row.mixed), row.holds)


def _token(n: int) -> str:
    """Letters only: ``self_times`` strips numbered suffixes."""
    token = "k"
    while True:
        token += chr(ord("a") + n % 26)
        n //= 26
        if not n:
            return token


def pass_seconds(ops, modules, rows: dict) -> dict:
    """``{Key: seconds}`` of self time.  ``ops`` are ``(start, end, name,
    opcode)`` of one chip, ``modules`` ``(start, end, module)`` sorted,
    ``rows`` the program's scope table; an operation the table lacks goes
    under :data:`NO_ROW`."""
    starts = [m[0] for m in modules]
    tokens: dict = {}
    events = []
    for s, e, name, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        module = modules[i][2] if i >= 0 and s < modules[i][1] else ""
        row = rows.get(module, {}).get(name)
        key = NO_ROW if row is None else key_of(row)
        events.append((s, e, tokens.setdefault(key, _token(len(tokens)))))
    by_token = self_times(events)
    return {key: by_token.get(token, 0.0) for key, token in tokens.items()}


COLUMNS = (*WAYS, "lent", "mixed", *HELD, "gained", "lost")


def table(seconds: dict, steps: int) -> list:
    """Rows ``(scope, fwd, remat, bwd, lent, mixed, kernel, product, stack,
    gained, lost)`` in ms per step, largest first.  A scope is the work's;
    ``gained`` is its time that the scope map books elsewhere, ``lost`` the
    time the scope map books to it and the work does not."""
    rows: dict = {}
    for key, sec in seconds.items():
        ms = 1e3 * sec / steps
        row = rows.setdefault(key.work, dict.fromkeys(COLUMNS, 0.0))
        row[key.way] += ms
        if key.how == "lent":
            row["lent"] += ms
        if key.mixed:
            row["mixed"] += ms
        if key.holds in HELD:
            row[key.holds] += ms
        if key.work != key.scope:
            row["gained"] += ms
            rows.setdefault(key.scope,
                            dict.fromkeys(COLUMNS, 0.0))["lost"] += ms
    return sorted(((scope, *(row[c] for c in COLUMNS))
                   for scope, row in rows.items()),
                  key=lambda r: -sum(r[1:4]))


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

        build = getattr(probe, "scope_table", None)
        rows = build() if build is not None else {}
        if rows:
            plane = rc.trace.device_names[0]
            modules = rc.roots.module("readers", "scope_device") \
                .module_events(rc.trace.path, plane)
            seconds = pass_seconds(rc.trace.devices[plane], modules, rows)
            out = (seconds, steps)
            _log_table(rc, seconds, steps)
        else:
            rc.log("passes: the program gives no scope table; the pass "
                   "metrics read nothing")
    _CACHE[key] = out
    return out


def _log_table(rc, seconds: dict, steps: int) -> None:
    rows = table(seconds, steps)
    rc.log(f"passes: self time on chip 0 by the work's scope, ms per step "
           f"over {steps} steps (lent, mixed, kernel, product, stack, "
           f"gained are parts of fwd + remat + bwd; lost is booked to the "
           f"scope by the scope map alone)")
    rc.log("passes: " + f"{'scope':<28}" +
           "".join(f"{c:>9}" for c in COLUMNS))
    for scope, *cols in rows:
        rc.log(f"passes: {scope:<28}" + "".join(f"{c:>9.3f}" for c in cols))
    total = [sum(r[i] for r in rows) for i in range(1, len(COLUMNS) + 1)]
    rc.log(f"passes: {'(all)':<28}" + "".join(f"{c:>9.3f}" for c in total))
    rc.log(f"passes: fwd + remat + bwd sum to {sum(total[:3]):.3f} ms per "
           f"step (the busy union of chip 0)")


def read(rc):
    reduced = _reduced(rc)
    if reduced is None:
        return None
    seconds, steps = reduced
    params = rc.metric["params"]
    if "share" in params:
        total = sum(seconds.values())
        part = sum(sec for key, sec in seconds.items()
                   if (key.mixed if params["share"] == "mixed"
                       else key.how == params["share"]))
        return 100.0 * part / total if total else 0.0
    if "way" in params:
        found = (sec for key, sec in seconds.items()
                 if key.way == params["way"])
    else:
        patterns = [re.compile(p) for p in params["innermost"]]
        found = (sec for key, sec in seconds.items()
                 if any(p.fullmatch(key.inner) for p in patterns))
    return 1e3 * sum(found) / steps
