"""A reading of the indexers' selections (learned sparse attention), as the
step unit published it for its last whole class pass
(``samples["dsa"][params.key]``; builder ``lm_train_dsa`` copies
``TransformerLMStep.dsa_counters``).  A program or a cell without an
indexer reads as nothing."""


def read(rc):
    value = (rc.samples.get("dsa") or {}).get(rc.metric["params"]["key"])
    return None if value is None else float(value)
