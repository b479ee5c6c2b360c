"""``step_host_cpu_ms_per_step``: CPU time of the dispatching thread inside
the step unit's ``workflow.step`` spans, per pass (the ``cpu_us`` the
program puts on that span: two ``time.thread_time()`` reads).  Where
``step_host_ms_per_step`` is the span's wall, which counts the runtime's
back-pressure as host time, this counts only what the thread computed.  A
program whose spans carry no ``cpu_us`` reads as nothing."""


def read(rc):
    s = rc.samples
    if s.get("kind") != "train":
        return None
    cpu = [e["args"]["cpu_us"] for e in s["program_spans"]
           if e["name"] == "workflow.step" and
           (e.get("args") or {}).get("unit") == s["step_unit"] and
           "cpu_us" in e["args"]]
    return sum(cpu) / len(cpu) / 1e3 if cpu else None
