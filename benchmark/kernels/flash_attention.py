"""Operations and bytes of the flash-attention kernels
(``ops/pallas/attention.py``), as the algorithm needs them for one call:
causal attention over ``(batch, heads, t, head)`` in bfloat16.

Forward: two products (QK^T, PV), each ``2 * t * t * head`` operations a
head, of which causality needs half.  Backward: five (the recomputed QK^T,
dV, dP, dQ, dK): the recomputation is the algorithm's own, so it counts
here, though not in ``train_mfu``.  Bytes: every operand read once and
every result written once, bfloat16, plus the float32 log-sum-exp row.
"""


def calls_per_step(cfg: dict, traffic: dict) -> list:
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    heads, d = int(cfg["n_head"]), int(cfg["n_embd"])
    layers = int(cfg["n_layer"])
    product = 2.0 * b * heads * t * t * (d // heads) / 2.0     # causal half
    tensor = 2.0 * b * t * d                                    # bf16 bytes
    lse = 4.0 * b * heads * t
    return [
        {"pattern": "flash_attention_fwd", "count": layers,
         "flops": 2 * product, "bytes": 4 * tensor + lse},
        {"pattern": "flash_attention_bwd", "count": layers,
         "flops": 5 * product, "bytes": 8 * tensor + 2 * lse},
    ]
