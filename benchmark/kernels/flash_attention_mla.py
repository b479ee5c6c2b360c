"""Operations and bytes of causal attention over latent attention's expanded
heads, as the algorithm needs them for one call whatever implements it:
``heads`` heads whose query/key width is ``qk_nope_head_dim +
qk_rope_head_dim`` and whose value width is ``v_head_dim`` (the same here),
``(batch, t, head)`` each, bfloat16; one call a layer, the MTP module's
layer among them.

Products as ``flash_attention.py`` counts them: two forward (QK^T, PV) and
five backward (the recomputed QK^T, dV, dP, dK, dQ), each ``2 * t * t *
width`` operations a head, of which causality needs half.  Bytes: q, k, v,
o, do, dq, dk, dv once each, and the float32 rows (the log-sum-exp written
and read, delta read).  The program's key/value-blocked kernels
(``ops/pallas/attention.py``) split the backward pass in two: the pass that
gives dk and dv is charged the four products it cannot do without and every
read, the pass that gives dq the fifth product and its write: what the
second pass computes and reads again is the implementation's, not the
algorithm's.
"""


def calls_per_step(cfg: dict, traffic: dict) -> list:
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    heads = int(cfg["num_attention_heads"])
    qk = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    vd = int(cfg["v_head_dim"])
    layers = int(cfg["num_hidden_layers"]) + \
        int(cfg.get("num_nextn_predict_layers", 0))
    qk_product = 2.0 * b * heads * t * t * qk / 2.0            # causal half
    v_product = 2.0 * b * heads * t * t * vd / 2.0
    qk_bytes = 2.0 * b * t * heads * qk                         # bf16
    v_bytes = 2.0 * b * t * heads * vd
    row = 4.0 * b * heads * t
    return [
        # QK^T, PV; reads q, k, v; writes o and the log-sum-exp row
        {"pattern": "flash_attention_kvb_fwd", "count": layers,
         "flops": qk_product + v_product,
         "bytes": 2 * qk_bytes + 2 * v_bytes + row},
        # QK^T again, dV, dP, dK; reads q, k, v, do and both rows; writes
        # dk, dv
        {"pattern": "flash_attention_kvb_dkv", "count": layers,
         "flops": 2 * qk_product + 2 * v_product,
         "bytes": 3 * qk_bytes + 3 * v_bytes + 2 * row},
        # dQ; writes dq
        {"pattern": "flash_attention_kvb_dq", "count": layers,
         "flops": qk_product, "bytes": qk_bytes},
    ]
