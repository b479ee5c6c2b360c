"""LRN forward/backward as Pallas kernels — rebuild of the reference's
normalization.{cl,cu} (SURVEY.md §3.2: "cross-map sliding sums fwd;
exact-derivative bwd").

One VMEM pass each: the channel window sum is a static unrolled
shift-accumulate over the lane dimension (n is small — 5 in AlexNet), so
forward fuses square + window + pow + multiply without touching HBM
between, and backward likewise fuses the adjoint window.
Semantics identical to znicz_tpu.ops.lrn (the jnp oracle the tests pin).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _window(x, n: int, adjoint: bool):
    """Sliding channel-window sum via static shifts (lane-dim rolls)."""
    half = n // 2
    lo = (n - 1 - half) if adjoint else half
    c = x.shape[-1]
    acc = x
    for off in range(1, lo + 1):          # contributions from the left
        shifted = jnp.pad(x, ((0, 0), (off, 0)))[:, :c]
        acc = acc + shifted
    for off in range(1, n - lo):          # contributions from the right
        shifted = jnp.pad(x, ((0, 0), (0, off)))[:, off:]
        acc = acc + shifted
    return acc


def _fwd_kernel(n: int, alpha: float, beta: float, k: float,
                x_ref, y_ref):
    from znicz_tpu.ops.lrn import _pow_neg_beta

    x = x_ref[:]
    d = k + alpha * _window(x * x, n, adjoint=False)
    y_ref[:] = x * _pow_neg_beta(jnp, d, beta)


def _bwd_kernel(n: int, alpha: float, beta: float, k: float,
                x_ref, e_ref, out_ref):
    from znicz_tpu.ops.lrn import _pow_neg_beta

    x = x_ref[:]
    e = e_ref[:]
    d = k + alpha * _window(x * x, n, adjoint=False)
    dnb = _pow_neg_beta(jnp, d, beta)
    t = e * x * (dnb / d)
    out_ref[:] = e * dnb - 2.0 * alpha * beta * x * _window(
        t, n, adjoint=True)


def _flat2(x):
    return x.reshape(-1, x.shape[-1])


def lrn_forward(x, alpha: float, beta: float, k: float, n: int, *,
                interpret: bool = False):
    x2 = _flat2(x)
    from functools import partial
    y = pl.pallas_call(
        partial(_fwd_kernel, n, alpha, beta, k),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        name="lrn_fwd", interpret=interpret,
    )(x2)
    return y.reshape(x.shape)


def lrn_backward(x, err_output, alpha: float, beta: float, k: float, n: int,
                 *, interpret: bool = False):
    x2, e2 = _flat2(x), _flat2(err_output)
    from functools import partial
    out = pl.pallas_call(
        partial(_bwd_kernel, n, alpha, beta, k),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        name="lrn_bwd", interpret=interpret,
    )(x2, e2)
    return out.reshape(x.shape)
