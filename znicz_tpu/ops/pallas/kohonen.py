"""Kohonen SOM batch step as one Pallas kernel — distance compute, argmin
reduction and neighborhood-weighted update fused in a single VMEM pass
(SURVEY.md §3.2 names the kohonen.{cl,cu} triple a Pallas deliverable).

Everything stays in VMEM for the whole step: squared distances ride one
MXU GEMM (|x|^2 - 2 x·Wᵀ + |w|^2), the winner one-hot is built by
comparing against the row minimum (no gather), winner grid-coordinates
come from ``onehot @ coords`` (MXU again), and the update's two matmuls
(Hᵀ·X and Hᵀ·1) produce the same batch-stable rule as ops.kohonen.update.
The reference needs three kernel launches with HBM round-trips between
them; here weights are read once and written once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(s_ref, x_ref, w_ref, c_ref, wout_ref, idx_ref):
    alpha, sigma, bs = s_ref[0], s_ref[1], s_ref[2]
    x = x_ref[:]                                     # (B, D)
    w = w_ref[:]                                     # (N, D)
    coords = c_ref[:]                                # (N, 2)
    B, N = x.shape[0], w.shape[0]
    # every dot runs at HIGHEST precision: the winner one-hot compares
    # d2 against its row min EXACTLY, and default-precision MXU bf16
    # passes flip winners vs the f32 oracle (measured on chip: 40% of
    # weight elements diverged). The SOM step is dispatch-latency-bound
    # (a 16 KB weight table), so the extra passes are free.
    hi = jax.lax.Precision.HIGHEST
    x2 = (x * x).sum(axis=1, keepdims=True)          # (B, 1)
    w2 = (w * w).sum(axis=1)                         # (N,)
    d2 = x2 - 2.0 * jnp.dot(x, w.T, precision=hi,
                            preferred_element_type=jnp.float32) + w2
    dmin = d2.min(axis=1, keepdims=True)
    # winner one-hot WITHOUT gather: smallest column index attaining the
    # row min — argmin's first-tie semantics
    col = jax.lax.broadcasted_iota(jnp.int32, (B, N), 1)
    idx = jnp.where(d2 == dmin, col, N).min(axis=1, keepdims=True)
    onehot = (col == idx).astype(jnp.float32)        # (B, N)
    idx_ref[:] = idx
    # neighborhood of each sample's winner over the grid
    wc = jnp.dot(onehot, coords, precision=hi,
                 preferred_element_type=jnp.float32)  # (B, 2)
    wc2 = (wc * wc).sum(axis=1, keepdims=True)
    c2 = (coords * coords).sum(axis=1)
    g2 = wc2 - 2.0 * jnp.dot(wc, coords.T, precision=hi,
                             preferred_element_type=jnp.float32) + c2
    h = jnp.exp(-g2 / (2.0 * sigma * sigma))         # (B, N)
    row = jax.lax.broadcasted_iota(jnp.int32, (B, N), 0).astype(jnp.float32)
    h = jnp.where(row < bs, h, 0.0)                  # mask padded samples
    num = jnp.dot(h.T, x, precision=hi,
                  preferred_element_type=jnp.float32)  # (N, D)
    den = h.sum(axis=0)[:, None]                     # (N, 1)
    wout_ref[:] = w + alpha * (num - den * w) / (den + 1.0)


def som_step(x, weights, coords, alpha, sigma, batch_size, *,
             interpret: bool = False):
    """-> (new_weights, winner_idx): one fused SOM batch step with
    ops.kohonen.update semantics; ``batch_size`` masks padded rows
    (rows >= batch_size contribute nothing)."""
    B = x.shape[0]
    scal = jnp.stack([jnp.asarray(alpha, jnp.float32),
                      jnp.asarray(sigma, jnp.float32),
                      jnp.asarray(batch_size, jnp.float32)])
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    new_w, idx = pl.pallas_call(
        _kernel,
        in_specs=[smem, vmem, vmem, vmem],
        out_specs=(vmem, vmem),
        out_shape=(jax.ShapeDtypeStruct(weights.shape, weights.dtype),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32)),
        name="kohonen_train", interpret=interpret,
    )(scal, x, weights, coords)
    return new_w, idx[:, 0]
