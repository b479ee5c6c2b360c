"""``step_device_ms``: the union of device-operation intervals in the
traced sub-windows, averaged over the chips, per step."""

from benchlib import traced_steps


def read(rc):
    steps = traced_steps(rc.samples)
    if rc.trace is None or not steps:
        return None
    return 1e3 * rc.trace.busy_s() / steps
