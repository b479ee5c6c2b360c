"""The Mamba-2 layer's gate, its gated group norm and the gain behind it as
two Pallas kernels, with the output product they feed (``parallel/ssm.py``
has the layer and the ``jax.numpy`` form these stand in for, the closing
lines of ``ssm.mixer``).  In that form the statistic is taken a group on a
``(tokens x groups, inner / groups)`` view of ``(b, t, inner)`` arrays,
which is no bitcast on a TPU's tiles beside kernels that read and write
rows: the float32 product, its view, the normed rows and, under
differentiation, their cotangents are moved through HBM a group at a time.
Here a visit holds a tile of rows of ONE group's lanes (whole lane tiles), so
a group's statistic is a sum over the lanes the visit already holds and
nothing float32 or ``(tokens, groups)``-shaped leaves VMEM in either pass.

Both kernels read the scan's result ``y (b, t, inner)`` as its kernel wrote
it and ``z`` from the lanes ``[start, start + inner)`` of the layer's input
projection ITSELF, cut by the block spec (as ``ops/pallas/ssm_conv.py`` cuts
``x | B | C`` from it: no copy of the cut is prepared).

**Forward** (:data:`FWD_KERNEL_NAME`): grid ``(row of the batch, group, tile
of time)``.  In VMEM: both operands in float32, ``a = y silu(z)``, the mean
square over the group's lanes, ``rsqrt``, one cast to the operands' dtype,
the product with the gain in that dtype.  Writes ``(b, t, inner)`` row-major
in the operands' dtype: the very operand of the output product.

**Backward** (:data:`BWD_KERNEL_NAME`): the same visits.  It makes the
product, the statistic and the sigmoid again, takes the second sum over the
group's lanes (``dn a``), and writes ``dy`` and ``dz`` in the operands'
dtype, the gated rows once more (the output product's weights want them for
their gradient: the forward kernel's result is kept by nobody; they take
the cotangent's own buffer, a tile as it has been read) and, float32, the
gain's gradient summed over a row's tiles in a block that stays in VMEM
(eight partial sums a lane, one a sublane: the rows of the batch and the
sublanes are summed outside, a few hundred KB).

Inside a visit the work runs :data:`PASS_ROWS` rows at a time, a lane tile
after another, so that a piece's float32 values live in registers or in
VMEM beside them between the load and the store, never in HBM; a sum over a
group's lanes is a sum of its lane tiles (whole registers added) and ONE sum
over 128 lanes a piece.

Precision is the ``jax.numpy`` form's: operands in the compute dtype;
product, ``silu``, statistic and ``rsqrt`` float32; one cast; then the gain
in the compute dtype (a product of two 16-bit values is exact in float32,
so rounding it once is that dtype's own multiply).  The gain's gradient
accumulates in float32.
"""

from __future__ import annotations

import contextlib
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.observe import probe as _probe
from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct
from znicz_tpu.ops.pallas.ssm_conv import _folded

#: the kernels' names in the lowered program and in device traces
FWD_KERNEL_NAME = "ssm_gate_fwd"
BWD_KERNEL_NAME = "ssm_gate_bwd"
LANES = 128
#: rows a tile of time is whole multiples of: one tile of a 16-bit array
ROW_TILE = 16
#: rows of a piece of a visit (alone at the Nemotron shape, forward /
#: backward ms a layer: 16 rows 1.22 / 1.76, 32 rows 0.75 / 1.66, 64 rows
#: 0.68 / 1.66, 128 rows 0.66 / 1.66; my chip run, PR 49)
PASS_ROWS = 128
#: the longest tile of a visit, and the bytes of one operand's tile (a
#: group of 512 lanes in 16 bits takes 1,024 rows, one of 4,096 takes 128)
_MAX_ROWS, _TILE_BYTES = 1024, 2 ** 20
_VMEM_LIMIT = 32 * 1024 * 1024


def tile_rows(t: int, width: int, itemsize: int) -> int:
    """Rows of a visit's tile for rows of ``t`` positions and a group of
    ``width`` lanes: the largest power of two up to 1,024 that divides ``t``
    and keeps an operand's tile within a MiB, :data:`ROW_TILE` at least."""
    rows = math.gcd(t, _MAX_ROWS)
    while rows > ROW_TILE and rows * width * itemsize > _TILE_BYTES:
        rows //= 2
    return rows


def unsupported_reason(t: int, inner: int, groups: int, start: int,
                       itemsize: int) -> str | None:
    """Why the kernels cannot take rows of ``t`` positions, ``inner``
    entries of ``itemsize`` bytes in ``groups`` groups and a ``z`` that
    starts at lane ``start`` of the projection, or ``None``: a group of
    whole lane tiles, a cut that starts at a whole group's width, rows of
    whole 16-row tiles, the backward kernel's seven tiles (each fetched
    while the one before it is worked on) inside the kernels' VMEM."""
    width = inner // max(groups, 1)
    if groups < 1 or width * groups != inner or width % LANES:
        return (f"a group of {inner} / {groups} entries is not whole tiles "
                f"of {LANES} lanes")
    if start % width:
        return (f"z starts at lane {start} of the projection, no multiple "
                f"of a group's {width} lanes")
    if t % ROW_TILE:
        return f"rows of {t} positions are no multiple of {ROW_TILE}"
    if 2 * 7 * ROW_TILE * width * itemsize > _VMEM_LIMIT:
        return (f"{ROW_TILE} rows of a group of {width} lanes do not fit "
                f"the kernels' {_VMEM_LIMIT >> 20} MiB of VMEM seven times "
                f"over")
    return None


def _pieces(rows: int, width: int):
    """-> ``(rows a piece, pieces a tile, the lane tiles' slices)``."""
    r = math.gcd(PASS_ROWS, rows)
    return r, rows // r, [slice(a, a + LANES) for a in range(0, width, LANES)]


def _over_lanes(parts: list):
    """The sum over a group's lanes of ``parts``, its lane tiles ``(r,
    128)`` float32 -> ``(r, 1)``: the tiles added, then one sum over 128
    lanes."""
    acc = parts[0]
    for v in parts[1:]:
        acc = acc + v
    return jnp.sum(acc, axis=-1, keepdims=True)


def _gated(y_ref, z_ref, here, at):
    """-> ``(y, z, sigmoid(z), y z sigmoid(z))`` of the piece ``here`` of
    the lane tile ``at``, float32."""
    y = y_ref[0, here, at].astype(jnp.float32)
    z = z_ref[0, here, at].astype(jnp.float32)
    sig = jax.nn.sigmoid(z)
    return y, z, sig, y * (z * sig)


def _rounded(v, dtype):
    """``v`` float32 as ``dtype`` holds it, in float32 again: the one cast
    of the ``jax.numpy`` form, and a product IN that dtype (of two such
    values the float32 product is exact, so rounding it is the dtype's own
    multiply)."""
    return v.astype(dtype).astype(jnp.float32)


def _fwd_kernel(y_ref, z_ref, g_ref, o_ref, *, eps: float):
    rows, width = y_ref.shape[1:]
    r, count, ats = _pieces(rows, width)
    gain = [g_ref[:, at].astype(jnp.float32) for at in ats]

    def piece(k, _):
        here = pl.ds(pl.multiple_of(k * r, r), r)
        a = [_gated(y_ref, z_ref, here, at)[3] for at in ats]
        rs = lax.rsqrt(_over_lanes([v * v for v in a]) * (1.0 / width) + eps)
        for at, v, g in zip(ats, a, gain):
            o_ref[0, here, at] = (_rounded(v * rs, o_ref.dtype) * g
                                  ).astype(o_ref.dtype)
        return _

    lax.fori_loop(0, count, piece, 0)


def _bwd_kernel(y_ref, z_ref, g_ref, do_ref, dy_ref, dz_ref, o_ref, sums_ref,
                *, eps: float):
    rows, width = y_ref.shape[1:]
    r, count, ats = _pieces(rows, width)
    dtype = o_ref.dtype
    gain = [g_ref[:, at].astype(jnp.float32) for at in ats]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)

    def piece(k, _):
        here = pl.ds(pl.multiple_of(k * r, r), r)
        ops = [_gated(y_ref, z_ref, here, at) for at in ats]
        do = [do_ref[0, here, at].astype(jnp.float32) for at in ats]
        # the cotangent of the normed rows: the gain's product in the
        # operands' dtype, as the forward pass made its own
        dn = [_rounded(d * g, dtype) for d, g in zip(do, gain)]
        rs = lax.rsqrt(_over_lanes([op[3] * op[3] for op in ops]) *
                       (1.0 / width) + eps)
        # da = rs (dn - n mean(dn n)), n = a rs
        pull = _over_lanes([d * op[3] for d, op in zip(dn, ops)]) * \
            (rs * rs * rs * (1.0 / width))
        for at, (y, z, sig, a), d, d_o, g in zip(ats, ops, dn, do, gain):
            n = _rounded(a * rs, dtype)
            o_ref[0, here, at] = (n * g).astype(dtype)
            sums_ref[0, :, at] += _folded(d_o * n)
            da = d * rs - a * pull
            dy_ref[0, here, at] = (da * (z * sig)).astype(dtype)
            dz_ref[0, here, at] = (da * y * (sig * (1.0 + z * (1.0 - sig)))
                                   ).astype(dtype)
        return _

    lax.fori_loop(0, count, piece, 0)


def _specs(t: int, inner: int, groups: int, start: int, itemsize: int):
    """The block specs both kernels share, for grid point ``(row, group,
    tile of time)``: a tile of a group's lanes of a ``(b, t, inner)``
    array, the same tile of ``z`` cut from the projection's lanes, the
    group's gain."""
    width = inner // groups
    rows = tile_rows(t, width, itemsize)
    vm, first = pltpu.VMEM, start // width
    tile = pl.BlockSpec((1, rows, width), lambda i, j, c: (i, c, j),
                        memory_space=vm)
    cut = pl.BlockSpec((1, rows, width), lambda i, j, c: (i, c, first + j),
                       memory_space=vm)
    gain = pl.BlockSpec((1, width), lambda i, j, c: (0, j), memory_space=vm)
    return t // rows, tile, cut, gain


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)

_STATIC = ("start", "groups", "eps", "interpret")


@partial(jax.jit, static_argnames=_STATIC)
def gate_fwd(y, proj, g, *, start: int, groups: int, eps: float,
             interpret: bool):
    """-> ``RMSNorm(y silu(proj[..., start:start + inner]); g)`` over each
    of the ``groups`` groups' ``inner / groups`` entries, ``(b, t, inner)``
    in ``y``'s dtype.  ``y (b, t, inner)``; ``proj (b, t, any width)`` in
    the same dtype; ``g (1, inner)``."""
    b, t, inner = y.shape
    steps, tile, cut, gain = _specs(t, inner, groups, start,
                                    y.dtype.itemsize)
    return pl.pallas_call(
        partial(_fwd_kernel, eps=eps),
        grid=(b, groups, steps),
        in_specs=[tile, cut, gain],
        out_specs=tile,
        out_shape=_out_struct(y.shape, y.dtype, y),
        compiler_params=_PARAMS,
        name=FWD_KERNEL_NAME,
        interpret=interpret,
    )(y, proj, g)


@partial(jax.jit, static_argnames=_STATIC)
def gate_bwd(y, proj, g, do, *, start: int, groups: int, eps: float,
             interpret: bool):
    """-> ``(dy, dz, the gated rows again, dg (1, inner) float32)``: the
    gradients of ``sum(gate_fwd(y, proj, g) * do)`` to ``y``, to the cut of
    ``proj`` and to the gain, and ``gate_fwd``'s own result."""
    b, t, inner = y.shape
    steps, tile, cut, gain = _specs(t, inner, groups, start,
                                    y.dtype.itemsize)
    like = _out_struct(y.shape, y.dtype, y)
    dy, dz, out, sums = pl.pallas_call(
        partial(_bwd_kernel, eps=eps),
        grid=(b, groups, steps),
        in_specs=[tile, cut, gain, tile],
        # a row's partial sums stay in VMEM over the row's tiles
        out_specs=[tile, tile, tile,
                   pl.BlockSpec((1, 8, inner // groups),
                                lambda i, j, c: (i, 0, j),
                                memory_space=pltpu.VMEM)],
        out_shape=[like, like, like,
                   _out_struct((b, 8, inner), jnp.float32, y)],
        # the gated rows take the cotangent's place, a tile as it is read
        input_output_aliases={3: 2},
        compiler_params=_PARAMS,
        name=BWD_KERNEL_NAME,
        interpret=interpret,
    )(y, proj, g, do)
    return dy, dz, out, sums.sum(axis=(0, 1))[None]


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def gate_out(y, proj, g, w_out, start: int, groups: int, eps: float,
             interpret: bool, scopes: tuple | None = None):
    """The layer's closing lines by the two kernels, differentiable in the
    scan's result ``y (b, t, inner)``, the projection ``proj (b, t, any
    width)`` whose lanes ``[start, start + inner)`` are ``z``, the gain ``g
    (1, inner)`` and the output product's weight ``w_out (inner, d)`` ->
    ``RMSNorm(y silu(z); g) @ w_out``, ``(b, t, d)``.  The product stands
    inside so that the backward pass keeps NOTHING of the forward kernel's:
    its rule reads the operands alone, and the weight's gradient takes the
    gated rows the backward kernel writes again.  ``scopes``: the names of
    the scopes (``observe.probe.scope``) the gate and the product open,
    in both passes; metadata only."""
    with _scope(scopes, 0):
        gated = gate_fwd(y, proj, g, start=start, groups=groups, eps=eps,
                         interpret=interpret)
    with _scope(scopes, 1):
        return gated @ w_out


def _scope(scopes, part: int, bwd: bool = False):
    if scopes is None:
        return contextlib.nullcontext()
    return (_probe.scope_bwd if bwd else _probe.scope)(scopes[part])


def _gate_out_fwd(y, proj, g, w_out, start, groups, eps, interpret, scopes):
    return (gate_out(y, proj, g, w_out, start, groups, eps, interpret,
                     scopes), (y, proj, g, w_out))


def _gate_out_bwd(start, groups, eps, interpret, scopes, kept, d_out):
    y, proj, g, w_out = kept
    # the product's two transposes, as autodiff writes them
    with _scope(scopes, 1, bwd=True):
        do = jnp.einsum("btd,id->bti", d_out, w_out).astype(y.dtype)
    with _scope(scopes, 0, bwd=True):
        dy, dz, gated, dg = gate_bwd(y, proj, g, do, start=start,
                                     groups=groups, eps=eps,
                                     interpret=interpret)
    with _scope(scopes, 1, bwd=True):
        dw = jnp.einsum("bti,btd->id", gated, d_out)
    # the cotangent's way back beside those of the projection's other lanes
    with _scope(scopes, 0, bwd=True):
        behind = proj.shape[2] - start - dz.shape[2]
        return (dy, jnp.pad(dz, ((0, 0), (0, 0), (start, behind))),
                dg.astype(g.dtype), dw)


gate_out.defvjp(_gate_out_fwd, _gate_out_bwd)
