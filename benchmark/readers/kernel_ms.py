"""Device time per step of the kernels whose operation names contain any
of ``params.patterns`` (chip 0)."""

from benchlib import traced_steps


def read(rc):
    steps = traced_steps(rc.samples)
    if rc.trace is None or not steps:
        return None
    seconds, count = rc.trace.matching_s(rc.metric["params"]["patterns"])
    if not count:
        return None
    return 1e3 * seconds / steps
