"""Operations and bytes of causal multi-head attention in a looped stack,
as the algorithm needs them for one call whatever implements it:
``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads of ``head_dim``, ``(batch, t, head)`` each, bfloat16; one call a layer
application, ``num_hidden_layers x total_ut_steps`` of them a step (the
layers run ``total_ut_steps`` times over the same weights).

Products as ``flash_attention_mla.py`` counts them: two forward (QK^T, PV)
and five backward (the recomputed QK^T, dV, dP, dK, dQ), each ``2 * t * t *
head_dim`` operations a query head, of which causality needs half.  Bytes:
q, k, v, o, do, dq, dk, dv once each, and the float32 rows (the log-sum-exp
written and read, delta read).  The program's key/value-blocked kernels
split the backward pass in two: the pass that gives dk and dv is charged the
four products it cannot do without and every read, the pass that gives dq
the fifth product and its write.  A forward kernel that an implementation
runs again to recompute what it did not keep is the implementation's, not
the algorithm's, and is not counted (its time is in the measured sum).
"""


def calls_per_step(cfg: dict, traffic: dict) -> list:
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg.get("num_key_value_heads", heads))
    hd = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // heads)
    calls = int(cfg["num_hidden_layers"]) * int(cfg.get("total_ut_steps", 1))
    product = 2.0 * b * heads * t * t * hd / 2.0                # causal half
    q_bytes = 2.0 * b * t * heads * hd                          # bf16
    kv_bytes = 2.0 * b * t * kv * hd
    row = 4.0 * b * heads * t
    return [
        # QK^T, PV; reads q, k, v; writes o and the log-sum-exp row
        {"pattern": "flash_attention_kvb_fwd", "count": calls,
         "flops": 2 * product, "bytes": 2 * q_bytes + 2 * kv_bytes + row},
        # QK^T again, dV, dP, dK; reads q, k, v, do and both rows; writes dk,
        # dv
        {"pattern": "flash_attention_kvb_dkv", "count": calls,
         "flops": 4 * product,
         "bytes": 2 * q_bytes + 4 * kv_bytes + 2 * row},
        # dQ; writes dq
        {"pattern": "flash_attention_kvb_dq", "count": calls,
         "flops": product, "bytes": q_bytes},
    ]
