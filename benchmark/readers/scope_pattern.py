"""Device time per step of the program's scopes whose name matches, whole,
any of the regular expressions in ``params.patterns``: what
``scope_device`` does by exact group name, for scopes that carry an index
(``block3.moe.experts``).  The reduction (the join through
``probe.scope_map()``, self times, the table in the log) is
``scope_device``'s and is made once a traced run; a program without a scope
map, or without a scope that matches, reads as nothing."""

from __future__ import annotations

import re


def read(rc):
    reduced = rc.roots.module("readers", "scope_device")._reduced(rc)
    if reduced is None:
        return None
    seconds, steps = reduced
    patterns = [re.compile(p) for p in rc.metric["params"]["patterns"]]
    found = [sec for (scope, _, _), sec in seconds.items()
             if any(p.fullmatch(scope) for p in patterns)]
    return 1e3 * sum(found) / steps if found else None
