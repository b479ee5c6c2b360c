"""A reading of the delta-rule linear-attention layers, as the step unit
published it for its last whole class pass (``samples["kda"][params.key]``;
builder ``lm_train_kda`` copies ``TransformerLMStep.kda_counters``).  A
program or a cell without such a layer reads as nothing."""


def read(rc):
    value = (rc.samples.get("kda") or {}).get(rc.metric["params"]["key"])
    return None if value is None else float(value)
