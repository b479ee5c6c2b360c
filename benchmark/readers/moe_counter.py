"""A counter of the routed expert layers, as the step unit published it
for its last whole class pass (``samples["moe"][params.key]``; the
builder copies ``TransformerLMStep.moe_counters``).  A program or a cell
without such layers reads as nothing."""


def read(rc):
    value = (rc.samples.get("moe") or {}).get(rc.metric["params"]["key"])
    return None if value is None else float(value)
