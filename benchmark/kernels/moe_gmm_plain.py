"""Operations and bytes of the grouped products of a routed expert layer
whose experts are the plain two-weight unit ``W_down relu(W_up x)^2``
(``parallel/moe.py::moe_routed_ffn`` with ``w3`` None; the Pallas kernels
``moe_gmm_*.ragged-dot-none`` of ``ops/pallas/grouped.py``, or
``lax.ragged_dot``, which the TPU compiler turns into kernels named
``ragged-dot-none*``: one pattern finds either form, so the share reads the
same work whichever ran).

Such a layer makes SIX a step: ``x w1`` and ``h w2`` forward, and for each
the gradient of its left operand (a grouped product again) and of its
weights (the products of each group's rows, transposed).  Every one is ``2 *
pairs * d * f`` operations over the ``pairs`` (token, choice) pairs routed
to held experts, which the step counts (``moe_pairs_held_per_step`` in the
configuration as run: the builder writes the counter's mean over the last
class pass there, summed over the ``E`` layers, which are counted from
``hybrid_override_pattern`` as run).  Bytes, bfloat16: the rows of both
activations (``pairs * (d + f)``) and the held experts' weights (``held * d
* f``), each read or written once by each product.  A checkpointed layer
that made a product again would add its time and no operation: the share
would fall, never pass 100 %.
"""


def calls_per_step(cfg: dict, traffic: dict) -> list:
    pairs = cfg.get("moe_pairs_held_per_step")
    sparse = str(cfg.get("hybrid_override_pattern", "")).count("E")
    if pairs is None or not sparse:
        return [{"pattern": "ragged-dot-none", "count": 0, "flops": 0.0,
                 "bytes": 0.0}]
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    held = int(cfg["experts_held"]["count"])
    per_layer = float(pairs) / sparse
    return [{"pattern": "ragged-dot-none", "count": 6 * sparse,
             "flops": 2.0 * per_layer * d * f,
             "bytes": 2.0 * (per_layer * (d + f) + held * d * f)}]
