"""Idle share of the device: 1 - busy union over the traced window."""


def read(rc):
    if rc.trace is None or not rc.trace_window_s:
        return None
    return 100.0 * (1.0 - rc.trace.busy_s() / rc.trace_window_s)
