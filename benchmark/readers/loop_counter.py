"""A counter of a looped stack, as the step unit published it for its last
whole class pass (``samples["loop"][params.key]``; builder ``lm_train_loop``
copies ``TransformerLMStep.loop_counters``).  A program or a cell without a
looped stack reads as nothing."""


def read(rc):
    value = (rc.samples.get("loop") or {}).get(rc.metric["params"]["key"])
    return None if value is None else float(value)
