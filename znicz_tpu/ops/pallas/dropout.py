"""Dropout with in-kernel PRNG — Pallas rebuild of the reference's
dropout.{cl,cu} xorshift mask kernel (SURVEY.md §3.2).

The mask is generated from the TPU core PRNG (``pltpu.prng_random_bits``)
and applied in the same VMEM pass — no mask round-trip through HBM on the
generate side (the mask is still emitted for the backward, reference
semantics: backward multiplies by the same mask).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mask_apply(bits, thresh, scale, x):
    keep = bits > thresh                  # P(keep) = 1 - ratio
    mask = jnp.where(keep, scale, 0.0).astype(x.dtype)
    return x * mask, mask


def _kernel_prng(seed_ref, thresh_ref, scale_ref, x_ref, y_ref, mask_ref):
    pltpu.prng_seed(seed_ref[0])
    bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
    y_ref[:], mask_ref[:] = _mask_apply(bits, thresh_ref[0], scale_ref[0],
                                        x_ref[:])


def _kernel_bits(thresh_ref, scale_ref, bits_ref, x_ref, y_ref, mask_ref):
    y_ref[:], mask_ref[:] = _mask_apply(bits_ref[:], thresh_ref[0],
                                        scale_ref[0], x_ref[:])


def dropout_forward(x, seed, ratio: float, *, bits=None,
                    interpret: bool = False):
    """-> (y, mask): inverted-dropout (kept entries scaled by 1/(1-ratio)),
    mask reusable by the backward.  ``seed`` is an int32 scalar; the same
    (seed, shape) pair reproduces the same mask (counter-PRNG semantics,
    matching znicz_tpu.core.prng's determinism contract).

    ``bits``: optional precomputed uint32 randoms of x.shape — the CPU
    test path (the interpreter's emulated TPU PRNG yields zeros); on TPU
    leave None for in-kernel generation."""
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1]) if x.ndim != 2 else x
    thresh = jnp.asarray(
        [jnp.uint32(min(max(ratio, 0.0), 1.0 - 1e-9) * (2 ** 32 - 1))])
    scale = jnp.asarray([1.0 / (1.0 - ratio)], jnp.float32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out_shape = (jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                 jax.ShapeDtypeStruct(x2.shape, x2.dtype))
    if bits is None:
        y, mask = pl.pallas_call(
            _kernel_prng, in_specs=[smem, smem, smem, vmem],
            out_specs=(vmem, vmem), out_shape=out_shape,
            name="dropout", interpret=interpret,
        )(jnp.asarray([seed], jnp.int32), thresh, scale, x2)
    else:
        y, mask = pl.pallas_call(
            _kernel_bits, in_specs=[smem, smem, vmem, vmem],
            out_specs=(vmem, vmem), out_shape=out_shape,
            name="dropout", interpret=interpret,
        )(thresh, scale, bits.reshape(x2.shape), x2)
    return y.reshape(orig_shape), mask.reshape(orig_shape)
