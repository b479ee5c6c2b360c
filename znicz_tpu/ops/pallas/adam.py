"""Fused AdamW update as one Pallas kernel — the single-HBM-pass version
of znicz_tpu.ops.adam.update, completing the optimizer kernel family next
to the fused SGD kernel (ops/pallas/sgd.py; SURVEY.md §3.2 "fused
SGD-update" parity deliverable, extended to the AdamW path).

Weights/grad/moments stream HBM -> VMEM tile by tile; hyperparameters
(including the post-increment step count ``t``) ride SMEM as scalars;
outputs alias the weight/moment inputs (true in-place update).  Shapes
whose rows cannot tile into VMEM take the jnp implementation, with a
warning naming the leaf (_elementwise.tiled_update)."""

from __future__ import annotations

import jax.numpy as jnp

from znicz_tpu.ops import adam as adam_ops
from znicz_tpu.ops.pallas._elementwise import tiled_update


def _kernel(h_ref, w_ref, g_ref, m_ref, v_ref, w_out, m_out, v_out):
    # bias corrections c1 = 1-b1^t, c2 = 1-b2^t arrive precomputed: they
    # are per-step scalars, and a pow on SMEM operands is not something
    # the scalar core should be asked to lower
    lr, wd, b1, b2, eps, c1, c2, bs = (
        h_ref[0], h_ref[1], h_ref[2], h_ref[3], h_ref[4], h_ref[5],
        h_ref[6], h_ref[7])
    w = w_ref[:]
    g = g_ref[:] / bs
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * (g * g)
    mhat = m / c1
    vhat = v / c2
    w_out[:] = w - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * w)
    m_out[:] = m
    v_out[:] = v


def fused_adam_update(w, grad, m, v, t, learning_rate, weight_decay,
                     beta1, beta2, eps, batch_size, *,
                     interpret: bool = False):
    """(w, m, v) -> (w', m', v') with ops.adam.update semantics, one
    pass.  ``t`` is the POST-increment step count (caller advances it).
    Arrays of any rank; scalars may be traced."""
    tf = jnp.asarray(t, jnp.float32)
    c1 = 1.0 - jnp.asarray(beta1, jnp.float32) ** tf
    c2 = 1.0 - jnp.asarray(beta2, jnp.float32) ** tf
    result = tiled_update(
        _kernel,
        [learning_rate, weight_decay, beta1, beta2, eps, c1, c2,
         batch_size],
        (w, grad, m, v), aliases={1: 0, 3: 1, 4: 2}, n_out=3,
        name="adam", interpret=interpret)
    if result is None:
        return adam_ops.update(jnp, w, grad, m, v, t, learning_rate,
                               weight_decay, beta1, beta2, eps,
                               batch_size)
    return result
