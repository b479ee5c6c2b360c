"""Operations and bytes of attention in a stack that mixes window and full
layers (``ops/pallas/attention.py``'s key/value-blocked kernels: under a
window named ``flash_attention_kvb_swa_fwd`` / ``_swa_dkv`` / ``_swa_dq`` in
the trace, on a full layer ``flash_attention_kvb_fwd`` / ``_dkv`` / ``_dq``),
as the algorithm needs them for one call whatever implements it:
``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads of ``head_dim``, ``(batch, t, head)`` each, bfloat16; one call a layer
by the layer's kind (``layer_types``).

Products as ``flash_attention_dsa.py`` counts them: two forward (QK^T, PV)
and five backward (the recomputed QK^T, dV, dP, dK, dQ), each ``2 * pairs *
head_dim`` operations a query head, over the (query, key) pairs the layer's
mask leaves: on a ``sliding_attention`` layer the band's exactly (query ``i``
sees key ``j`` iff ``0 <= i - j < sliding_window``: 25,167,872 of a row of
8,192 under a window of 4,096), on a ``full_attention`` layer the causal
triangle's ``t (t + 1) / 2``; so what a kernel computes of the masked part of
a tile it visits counts against it, and the share reads the same work
whichever tiles it visits.  Bytes: q, o, do, dq once a query head, k, v, dk,
dv once a KEY/VALUE head (the kernels read and write them once a query head,
the group repeated: the implementation's) and the float32 rows (the
log-sum-exp written and read, delta read).  The pass that gives dk and dv is
charged the four products it cannot do without and every read, the pass that
gives dq the fifth product and its write.
"""


def attended_pairs(t: int, window) -> int:
    w = t if not window else min(int(window), t)
    return w * (w + 1) // 2 + (t - w) * w


def calls_per_step(cfg: dict, traffic: dict) -> list:
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg.get("num_key_value_heads", heads))
    hd = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // heads)
    kinds = list(cfg["layer_types"])
    q_bytes = 2.0 * b * t * heads * hd                          # bf16
    kv_bytes = 2.0 * b * t * kv * hd
    row = 4.0 * b * heads * t
    calls = []
    for kind, infix, window in (
            ("sliding_attention", "kvb_swa_", cfg.get("sliding_window")),
            ("full_attention", "kvb_", None)):
        count = kinds.count(kind)
        if not count:
            continue
        product = 2.0 * b * heads * attended_pairs(t, window) * hd
        calls += [
            # QK^T, PV; reads q, k, v; writes o and the log-sum-exp row
            {"pattern": f"flash_attention_{infix}fwd", "count": count,
             "flops": 2 * product,
             "bytes": 2 * q_bytes + 2 * kv_bytes + row},
            # QK^T again, dV, dP, dK; reads q, k, v, do and both rows;
            # writes dk, dv
            {"pattern": f"flash_attention_{infix}dkv", "count": count,
             "flops": 4 * product,
             "bytes": 2 * q_bytes + 4 * kv_bytes + 2 * row},
            # dQ; writes dq
            {"pattern": f"flash_attention_{infix}dq", "count": count,
             "flops": product, "bytes": q_bytes},
        ]
    return calls
