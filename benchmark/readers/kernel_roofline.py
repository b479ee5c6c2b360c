"""A kernel's share of its roofline: the least time the chip could take for
the calls of one step (operations over peak FLOP/s or bytes over peak
bytes/s, whichever is larger, call by call, from
``kernels/<params.kernel>.py``) over the kernel's device time per step.  An
earlier line says which bound holds."""

from benchlib import traced_steps


def read(rc):
    steps = traced_steps(rc.samples)
    if rc.trace is None or rc.peaks is None or not steps:
        return None
    p = rc.metric["params"]
    calls = rc.roots.module("kernels", p["kernel"]).calls_per_step(
        rc.config, rc.traffic)
    least, measured, bounds = 0.0, 0.0, []
    for call in calls:
        seconds, count = rc.trace.matching_s([call["pattern"]])
        if not count:
            return None
        t_ops = call["flops"] / rc.peaks["bf16_flops"]
        t_mem = call["bytes"] / rc.peaks["hbm_bytes_per_s"]
        least += max(t_ops, t_mem) * call["count"]
        measured += seconds / steps
        bounds.append(f"{call['pattern']}: "
                      f"{'compute' if t_ops >= t_mem else 'memory'}-bound, "
                      f"least {max(t_ops, t_mem) * call['count'] * 1e3:.3f} "
                      f"ms, measured {seconds / steps * 1e3:.3f} ms a step")
    rc.log(f"{rc.metric['name']}: " + "; ".join(bounds))
    return 100.0 * least / measured
