"""``collective_ms_per_step``: summed device time of all-reduce,
reduce-scatter, all-gather (and permute / all-to-all) operations on chip 0
per step.  Not the exposed part: that needs spans inside the program."""

from benchlib import traced_steps


def read(rc):
    steps = traced_steps(rc.samples)
    if rc.trace is None or not steps:
        return None
    seconds, count = rc.trace.collective_s()
    if not count:
        return None
    rc.log(f"collectives on chip 0: {count} operations in the trace")
    return 1e3 * seconds / steps
