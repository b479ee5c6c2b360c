"""Operations and bytes of the flash-attention kernels
(``ops/pallas/attention.py``) under grouped-query attention, as the
algorithm needs them for one call: causal attention of ``heads`` query
heads over ``kv`` key/value heads, ``(batch, t, head)`` each, bfloat16.

Products as ``flash_attention.py`` counts them: two forward (QK^T, PV) and
five backward (the recomputed QK^T, dV, dP, dQ, dK), each ``2 * t * t *
head`` operations a *query* head, of which causality needs half.  Bytes:
every operand read once and every result written once, and a key/value
tensor counts once a key/value head: the kernels' index maps fetch it once
for its whole group.  Forward reads q, k, v and writes o and the float32
log-sum-exp row; backward reads q, k, v, do, that row and the float32 delta
row, and writes dq (bfloat16) and dk, dv (float32, a key/value head each).
"""


def calls_per_step(cfg: dict, traffic: dict) -> list:
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    head = int(cfg["hidden_size"]) // heads
    layers = sum(1 for kind in cfg["layer_types"] if kind == "full_attention")
    product = 2.0 * b * heads * t * t * head / 2.0             # causal half
    q_bytes = 2.0 * b * t * heads * head                        # bf16
    kv_bytes = 2.0 * b * t * kv * head
    row = 4.0 * b * heads * t
    return [
        {"pattern": "flash_attention_fwd", "count": layers,
         "flops": 2 * product, "bytes": 2 * q_bytes + 2 * kv_bytes + row},
        {"pattern": "flash_attention_bwd", "count": layers,
         "flops": 5 * product,
         "bytes": 3 * q_bytes + 2 * kv_bytes + 2 * row + 2 * 2 * kv_bytes},
    ]
