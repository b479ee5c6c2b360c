"""Operations and bytes of attention over a learned selection of keys
(``ops/pallas/attention.py``'s key/value-blocked kernels with a selection
operand, named ``flash_attention_kvb_sel_fwd`` / ``_sel_dkv`` / ``_sel_dq``
in the trace), as the algorithm needs them for one call whatever implements
it: ``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads of ``head_dim``, ``(batch, t, head)`` each, bfloat16; a query attends
to its ``sa_config.topk`` selected keys (every earlier key while it has
fewer), so a row has ``sum_t min(t + 1, topk)`` (query, key) pairs; one call
a layer.

Products as ``flash_attention_loop.py`` counts them: two forward (QK^T, PV)
and five backward (the recomputed QK^T, dV, dP, dK, dQ), each ``2 * pairs *
head_dim`` operations a query head: over the SELECTED pairs only, so what
the kernels compute of the masked part of a visited tile counts against
them.  Bytes: q, o, do, dq once a query head, k, v, dk, dv once a key/value
head (the kernels read and write them once a QUERY head, the group repeated:
the implementation's), the float32 rows (the log-sum-exp written and read,
delta read) and the selection itself, one byte a (query, key) pair of the
whole square, read once by each pass (the kernels read a tile once a head).
The pass that gives dk and dv is charged the four products it cannot do
without and every read, the pass that gives dq the fifth product, the
selection's second read and its write.
"""


def selected_pairs(t: int, topk: int) -> int:
    head = min(t, topk)
    return head * (head + 1) // 2 + max(t - topk, 0) * topk


def calls_per_step(cfg: dict, traffic: dict) -> list:
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg.get("num_key_value_heads", heads))
    hd = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // heads)
    calls = int(cfg["num_hidden_layers"])
    pairs = selected_pairs(t, int(cfg["sa_config"]["topk"]))
    product = 2.0 * b * heads * pairs * hd
    q_bytes = 2.0 * b * t * heads * hd                          # bf16
    kv_bytes = 2.0 * b * t * kv * hd
    row = 4.0 * b * heads * t
    sel = 1.0 * b * t * t                                       # int8
    return [
        # QK^T, PV; reads q, k, v and the selection; writes o and the
        # log-sum-exp row
        {"pattern": "flash_attention_kvb_sel_fwd", "count": calls,
         "flops": 2 * product,
         "bytes": 2 * q_bytes + 2 * kv_bytes + row + sel},
        # QK^T again, dV, dP, dK; reads q, k, v, do, both rows and the
        # selection; writes dk, dv
        {"pattern": "flash_attention_kvb_sel_dkv", "count": calls,
         "flops": 4 * product,
         "bytes": 2 * q_bytes + 4 * kv_bytes + 2 * row + sel},
        # dQ; reads the selection again; writes dq
        {"pattern": "flash_attention_kvb_sel_dq", "count": calls,
         "flops": product, "bytes": q_bytes + sel},
    ]
