"""Fused SGD update as one Pallas kernel — the explicit single-HBM-pass
version of znicz_tpu.ops.sgd.update (reference: the weights_update /
bias_update kernels fused normalization + decay + momentum + apply in one
launch, gradient_descent.{cl,cu} — SURVEY.md §3.2).

Weights/grad/velocity stream HBM -> VMEM tile by tile; hyperparameters
ride SMEM as scalars; outputs alias the weight/velocity inputs (true
in-place update, no extra HBM traffic).  Shapes whose rows cannot tile
into VMEM take the jnp implementation, with a warning naming the leaf
(_elementwise.tiled_update)."""

from __future__ import annotations

import jax.numpy as jnp

from znicz_tpu.ops import sgd as sgd_ops
from znicz_tpu.ops.pallas._elementwise import tiled_update


def _kernel(h_ref, w_ref, g_ref, v_ref, w_out, v_out):
    lr, wd, l1, mom, bs = (h_ref[0], h_ref[1], h_ref[2], h_ref[3], h_ref[4])
    w = w_ref[:]
    g = g_ref[:] / bs
    g = g + wd * ((1.0 - l1) * w + l1 * jnp.sign(w))
    # velocity may be stored narrow (state_dtype bf16): f32 math inside
    # the tile, one narrow store — the single HBM pass is the point
    vel = mom * v_ref[:].astype(w.dtype) + lr * g
    w_out[:] = w - vel
    v_out[:] = vel.astype(v_out.dtype)


def fused_sgd_update(w, grad, vel, learning_rate, weights_decay, l1_vs_l2,
                     gradient_moment, batch_size, *, interpret: bool = False):
    """(w, vel) -> (w', vel') with ops.sgd.update semantics, one pass.

    Arrays of any rank (tiled over a 2-D view); hyperparams may be traced
    scalars.  ``interpret=True`` runs the Mosaic interpreter (CPU tests).
    """
    result = tiled_update(
        _kernel,
        [learning_rate, weights_decay, l1_vs_l2, gradient_moment,
         batch_size],
        (w, grad, vel), aliases={1: 0, 3: 1}, n_out=2,
        name="sgd", interpret=interpret)
    if result is None:
        # ops.sgd.update preserves vel's storage dtype itself
        return sgd_ops.update(jnp, w, grad, vel, learning_rate,
                              weights_decay, l1_vs_l2, gradient_moment,
                              batch_size)
    return result
