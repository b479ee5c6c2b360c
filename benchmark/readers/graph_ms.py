"""``graph_ms_per_step``: host wall of one pass round the unit graph
outside the train-step unit and outside the benchmark's tap (whose fence is
the time blocked on the device): the program's own ``workflow.step`` spans
of every other unit, summed, over the passes made."""


def read(rc):
    s = rc.samples
    if s.get("kind") != "train":
        return None
    skip = {s["step_unit"], "BenchTap"}
    other, passes = 0.0, 0
    for e in s["program_spans"]:
        if e["name"] != "workflow.step":
            continue
        unit = (e.get("args") or {}).get("unit")
        if unit == s["step_unit"]:
            passes += 1
        elif unit not in skip:
            other += e["dur"]
    return None if not passes else other / passes / 1e3
