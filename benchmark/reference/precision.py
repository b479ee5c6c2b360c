"""The precisions a reference can compute in.

``f32``: float32 operands, ``jax.default_matmul_precision("highest")`` set
by the caller: the reference proper.  ``fp8``: the control.  The
configurations of this benchmark state bfloat16 compute over float32
master weights, so the nearest precision below is 8 bits, in the usual
recipe of an fp8 path: every operand of a matrix product or convolution is
rounded to ``float8_e4m3fn`` on the way in, and the gradient that flows
back into the product is rounded to ``float8_e5m2``; both scaled per tensor
to the format's range, and the product itself taken in float32.

A reference wraps each product as ``out(dot(q(a), q(b)))`` with
``q, out = operand(p), product(p)``: ``q`` rounds an operand (gradients
pass straight through it), ``out`` leaves the result alone and rounds the
gradient arriving at it, so that both backward products see 8-bit
operands as the forward one does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "fp8")


def _rounded(x, dtype, top: float):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(x.dtype) / scale


@jax.custom_vjp
def _operand_fp8(x):
    return _rounded(x, jnp.float8_e4m3fn, 448.0)


_operand_fp8.defvjp(lambda x: (_operand_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _product_fp8(y):
    return y


_product_fp8.defvjp(lambda y: (y, None),
                    lambda _, g: (_rounded(g, jnp.float8_e5m2, 57344.0),))


def _check(precision: str) -> None:
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}: one of {PRECISIONS}")


def operand(precision: str):
    """-> the function applied to each operand of a product."""
    _check(precision)
    return _operand_fp8 if precision == "fp8" else (lambda x: x)


def product(precision: str):
    """-> the function applied to each product's result."""
    _check(precision)
    return _product_fp8 if precision == "fp8" else (lambda y: y)
