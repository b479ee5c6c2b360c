"""Rotary embedding of the LAST columns of every head, in place, on whole
rows of heads: the kernel that lets latent attention's queries stay in the
layout their projection wrote.

A latent-attention head is ``[nope | rope]`` columns and only the ``rope``
tail is rotated.  Written with array operations (slice the tail, rotate,
concatenate) the head's row is cut at a column that is no multiple of the
128 lanes, XLA lays the ``(b, t, heads, head_dim)`` array out time-minor
to make the cut cheap, and the flash kernels, which read rows, get it
through a copy (and hand their gradient back through two).  Here the
array stays ``(rows, heads * head_dim)`` as the product left it: the grid
walks ``(row block, head)``, a block is the head's last 128 lanes, the
tail's two halves swap places by one lane rotation, and the result is
written over the input (``input_output_aliases``), so only a head's last
128 lanes move through VMEM and the ``nope`` columns before them are never
touched.

The tail is in HALVES order: its first ``rope / 2`` columns are the pairs'
first members, the rest their second members (a projection whose pairs
are neighbours is brought to it by permuting its weight's columns, which
moves no activation).  Linear in ``x``, so the gradient is the same kernel
at the negated angle.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct

#: the kernel's name in the lowered program and in device traces
KERNEL_NAME = "rope_tail"
_LANES = 128
#: rows to a block, the largest that divides the time axis
_ROW_BLOCKS = (1024, 512, 256, 128, 64, 32, 16)


def unsupported_reason(t: int, dh: int, rope: int) -> str | None:
    """Why the kernel cannot rotate the last ``rope`` columns of heads of
    ``dh`` over ``t`` positions, or ``None``: the tail has to lie inside
    the head's last 128 lanes, and the time axis has to be cut in row
    blocks that hold one table block each."""
    if dh % _LANES:
        return f"head_dim={dh} is not a multiple of {_LANES}"
    if rope % 2 or not 0 < rope <= _LANES:
        return f"a rotated tail of {rope} columns does not lie in one " \
               f"{_LANES}-lane block"
    if t % _ROW_BLOCKS[-1]:
        return f"t={t} is not a multiple of the {_ROW_BLOCKS[-1]}-row block"
    return None


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, rope: int):
    x = x_ref[...].astype(jnp.float32)                     # (rows, 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    half = rope // 2
    # a column of the tail's first half meets the one ``half`` lanes up,
    # a column of the second half the one ``half`` lanes down
    partner = jnp.where(lane < _LANES - half,
                        pltpu.roll(x, _LANES - half, 1),
                        pltpu.roll(x, half, 1))
    o_ref[...] = (x * cos_ref[...] + partner * sin_ref[...]).astype(
        o_ref.dtype)


def _tables(cos, sin, rope: int):
    """``(t, 128)`` float32 factors of a head's last 128 lanes: 1 and 0 on
    the lanes before the tail, ``cos | cos`` and ``-sin | sin`` on it."""
    t = cos.shape[0]
    keep = _LANES - rope
    return (jnp.concatenate([jnp.ones((t, keep), jnp.float32), cos, cos], 1),
            jnp.concatenate([jnp.zeros((t, keep), jnp.float32), -sin, sin],
                            1))


# Jitted, so that a program's layers share one trace and one lowering
@partial(jax.jit, static_argnames=("heads", "interpret"))
def _call(x, cos, sin, heads: int, interpret: bool):
    b, t, width = x.shape
    dh, rope = width // heads, 2 * cos.shape[1]
    rows = next(r for r in _ROW_BLOCKS if t % r == 0)
    per_t, last = t // rows, dh // _LANES - 1
    cos_t, sin_t = _tables(cos, sin, rope)
    tail = pl.BlockSpec((rows, _LANES),
                        lambda i, h: (i, h * (last + 1) + last),
                        memory_space=pltpu.VMEM)
    table = pl.BlockSpec((rows, _LANES), lambda i, h: (i % per_t, 0),
                         memory_space=pltpu.VMEM)
    x2 = x.reshape(b * t, width)
    out = pl.pallas_call(
        partial(_kernel, rope=rope),
        grid=(b * per_t, heads),
        in_specs=[tail, table, table], out_specs=tail,
        out_shape=_out_struct(x2.shape, x.dtype, x),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=KERNEL_NAME, interpret=interpret,
    )(x2, cos_t, sin_t)
    return out.reshape(x.shape)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rope_tail(x, cos, sin, heads: int, interpret: bool = False):
    """``x (b, t, heads * dh)`` with the last ``2 * cos.shape[1]`` columns
    of every head rotated by the angles whose ``cos`` and ``sin`` are
    ``(t, rope / 2)`` float32 (halves order, see the module's docstring):
    ``[x1 | x2] -> [x1 cos - x2 sin | x2 cos + x1 sin]`` in float32,
    every other column as it was.  Shapes: :func:`unsupported_reason`."""
    return _call(x, cos, sin, heads, interpret)


def _rope_fwd(x, cos, sin, heads, interpret):
    return _call(x, cos, sin, heads, interpret), (cos, sin)


def _rope_bwd(heads, interpret, res, g):
    cos, sin = res
    # the rotation's transpose is the rotation back; the angles are
    # positions' constants and take no gradient
    return (_call(g, cos, -sin, heads, interpret), jnp.zeros_like(cos),
            jnp.zeros_like(sin))


rope_tail.defvjp(_rope_fwd, _rope_bwd)
