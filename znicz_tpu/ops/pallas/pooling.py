"""Stochastic pooling with in-kernel PRNG — Pallas rebuild of the
reference's stochastic pooling kernels, whose defining feature is the
device-resident xorshift draw per output cell (SURVEY.md §3.2 names this
a Pallas deliverable precisely because the PRNG semantics are the point).

The window-patch tensor (built by the caller, same layout as
ops.pooling.patches) streams through VMEM; the kernel draws one uniform
per (output cell, channel) from the TPU core PRNG, builds the in-window
CDF with a static tap loop, and selects the winner by comparison — no
gather.  Inverse-CDF semantics are identical to
ops.pooling.stochastic_forward: strict ``cdf < u * total`` compare, so a
zero-mass window selects tap 0 (always in bounds).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _select(patch, valid, u, use_abs):
    """patch (M, K, C), valid (M, K, 1), u (M, C) in [0,1) ->
    (y, idx) each (M, C)."""
    K = patch.shape[1]
    p = jnp.abs(patch) if use_abs else jnp.maximum(patch, 0.0)
    p = p * valid
    total = p.sum(axis=1)                       # (M, C)
    target = u * total
    # static tap loop: running cdf + strict-compare rank = inverse CDF
    cdf = jnp.zeros_like(total)
    idx = jnp.zeros(total.shape, jnp.int32)
    for k in range(K):
        cdf = cdf + p[:, k, :]
        idx = idx + (cdf < target).astype(jnp.int32)
    idx = jnp.minimum(idx, K - 1)
    y = jnp.zeros_like(total)
    for k in range(K):
        y = y + jnp.where(idx == k, patch[:, k, :], 0.0)
    return y, idx


def _uniform(bits):
    """uint32 -> f32 uniform in [0, 1) via the top 24 bits (Mosaic has no
    uint32->f32 cast; the shifted value fits int32, whose cast exists)."""
    return (bits >> 8).astype(jnp.int32).astype(jnp.float32) * (2.0 ** -24)


def _kernel_prng(seed_ref, patch_ref, valid_ref, y_ref, idx_ref, *,
                 use_abs):
    pltpu.prng_seed(seed_ref[0])
    bits = pltpu.bitcast(
        pltpu.prng_random_bits((patch_ref.shape[0], patch_ref.shape[2])),
        jnp.uint32)
    y_ref[:], idx_ref[:] = _select(patch_ref[:], valid_ref[:],
                                   _uniform(bits), use_abs)


def _kernel_bits(patch_ref, valid_ref, bits_ref, y_ref, idx_ref, *,
                 use_abs):
    y_ref[:], idx_ref[:] = _select(patch_ref[:], valid_ref[:],
                                   _uniform(bits_ref[:]), use_abs)


def stochastic_pool(patch, valid, seed, use_abs: bool = False, *,
                    bits=None, interpret: bool = False):
    """-> (y, winner_tap): patch ``(M, K, C)`` (M = n*oh*ow flattened
    output cells, K = ky*kx taps), valid ``(M, K)`` per-cell in-bounds
    mask (border windows clip per position).

    ``seed`` is an int32 scalar (counter-PRNG determinism contract as
    pallas/dropout.py); ``bits`` injects uint32 randoms of shape (M, C)
    for the CPU interpreter, whose emulated TPU PRNG yields zeros."""
    M, K, C = patch.shape
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    valid3 = valid.reshape(M, K, 1).astype(patch.dtype)
    out_shape = (jax.ShapeDtypeStruct((M, C), patch.dtype),
                 jax.ShapeDtypeStruct((M, C), jnp.int32))
    if bits is None:
        return pl.pallas_call(
            partial(_kernel_prng, use_abs=use_abs),
            in_specs=[smem, vmem, vmem], out_specs=(vmem, vmem),
            out_shape=out_shape, name="stochastic_pool",
            interpret=interpret,
        )(jnp.asarray([seed], jnp.int32), patch, valid3)
    return pl.pallas_call(
        partial(_kernel_bits, use_abs=use_abs),
        in_specs=[vmem, vmem, vmem], out_specs=(vmem, vmem),
        out_shape=out_shape, name="stochastic_pool",
        interpret=interpret,
    )(patch, valid3, bits)
