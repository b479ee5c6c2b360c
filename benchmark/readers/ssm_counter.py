"""A reading of the state-space layers, as the step unit published it for
its last whole class pass (``samples["ssm"][params.key]``; builder
``lm_train_ssm`` copies ``TransformerLMStep.ssm_counters``).  A program or a
cell without a state-space layer reads as nothing."""


def read(rc):
    value = (rc.samples.get("ssm") or {}).get(rc.metric["params"]["key"])
    return None if value is None else float(value)
