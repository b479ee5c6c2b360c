"""A reading of the attention layers that have a window on their scores, as
the step unit published it for its last whole class pass
(``samples["attn"][params.key]``; builder ``lm_train_swa`` copies
``TransformerLMStep.attn_counters``).  A program or a cell without such
layers reads as nothing."""


def read(rc):
    value = (rc.samples.get("attn") or {}).get(rc.metric["params"]["key"])
    return None if value is None else float(value)
