"""Operations and bytes of the routed expert layer's grouped products
(``parallel/moe.py::moe_routed_ffn``: ``lax.ragged_dot``, which the TPU
compiler turns into kernels named ``ragged-dot-none*`` in the trace).

A sparse layer makes nine a step: ``x w1``, ``x w3``, ``h w2`` forward, and
for each the gradient of its left operand (a grouped product again) and of
its weights (the products of each group's rows, transposed).  Every one is
``2 * pairs * d * f`` operations over the ``pairs`` (token, choice) pairs
routed to held experts, which the step counts (``moe_pairs_held_per_step``
in the configuration as run: the builder writes the counter's mean over the
last class pass there, summed over the sparse layers).  Bytes, bfloat16: the
rows of both activations (``pairs * (d + f)``) and the held experts'
weights (``held * d * f``), each read or written once by each product.
"""


def calls_per_step(cfg: dict, traffic: dict) -> list:
    pairs = cfg.get("moe_pairs_held_per_step")
    if pairs is None:
        return [{"pattern": "ragged-dot-none", "count": 0, "flops": 0.0,
                 "bytes": 0.0}]
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    held = int(cfg["experts_held"]["count"])
    sparse = int(cfg["num_hidden_layers"]) - int(cfg["num_dense_layers"])
    per_layer = float(pairs) / sparse
    return [{"pattern": "ragged-dot-none", "count": 9 * sparse,
             "flops": 2.0 * per_layer * d * f,
             "bytes": 2.0 * (per_layer * (d + f) + held * d * f)}]
