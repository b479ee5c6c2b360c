"""The Mamba-2 layer's depthwise causal convolution with its bias, its
``silu`` and the cast behind it as two Pallas kernels (``parallel/ssm.py``
has the layer and the ``jax.numpy`` form these stand in for, ``ssm._conv``).
In that form the float32 copy of the operand, its padded copy, the ``taps``
shifted products (a shift of one row is no bitcast on ``(8, 128)`` tiles),
their sum and, under differentiation, as many shifted cotangents and two
float32 arrays a tap's gradient go through HBM; here nothing float32 and
token-long leaves VMEM in either pass.

Both kernels read the layer's input projection ITSELF, ``proj (b, t,
ssm_in_width)``: the lanes ``[start, start + width)`` that hold ``x | B |
C`` are cut by the block spec (whole lane tiles, as ``ops/pallas/ssd.py``
cuts ``x``, ``B`` and ``C`` from one array), so no copy of the cut is
prepared.  A visit holds a tile of ``rows x lanes`` (:func:`tiles`) and,
by a second block spec on the same array, the :data:`HALO` rows in front of
it (zeros in front of the sequence), of which the convolution reads the
last ``taps - 1``.

**Forward** (:data:`FWD_KERNEL_NAME`): grid ``(row of the batch, block of
lanes, tile of time)``.  In VMEM: the operand in float32, the ``taps``
shifted products against the float32 taps (a sublane rotation of the tile
with its halo), the bias, ``silu``, one cast.  Writes ``(b, t, width)`` in
the operand's dtype: the very array the scan's kernels cut their blocks
from.

**Backward** (:data:`BWD_KERNEL_NAME`): the same visits with the tiles of
time in reverse.  It makes the sum and the sigmoid again, ``dc = dy *
silu'(c)``, and returns ``dv_t = sum_j k_j dc_{t + taps - 1 - j}`` in the
operand's dtype (the ``taps - 1`` rows of ``dc`` behind the tile are the
first rows of the tile visited before: carried in VMEM) and, float32, the
taps' and the bias's gradients summed over a row's tiles in a block that
stays in VMEM (eight partial sums a tap, one a sublane: the rows of the
batch and the sublanes are summed outside, a few hundred KB).

Inside a visit the work runs a :data:`PASS_ROWS` ``x`` :data:`LANES` piece at
a time, so that a piece's float32 values live in registers between the
load and the store.

Precision is ``ssm._conv``'s: the operand in the compute dtype; taps, bias,
sum and ``silu`` float32; one rounding at the end; the gradients of taps
and bias accumulate in float32.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct

#: the kernels' names in the lowered program and in device traces
FWD_KERNEL_NAME = "ssm_conv_fwd"
BWD_KERNEL_NAME = "ssm_conv_bwd"
LANES = 128
#: rows in front of a tile that a visit fetches beside it: one whole tile
#: of a 16-bit array; the taps reach ``taps - 1`` of them
HALO = 16
#: rows of a piece of a visit, whose values live in registers
PASS_ROWS = 64
#: the widest and the longest tile of a visit
_MAX_LANES, _MAX_ROWS = 512, 1024
_VMEM_LIMIT = 32 * 1024 * 1024


def tiles(t: int, start: int, width: int) -> tuple:
    """``(rows, lanes)`` of a visit's tile for rows of ``t`` positions and
    the cut ``[start, start + width)``: the largest power of two up to 1,024
    rows that divides ``t``, and up to 512 lanes that divides ``start`` and
    ``width`` (256 of 4,352 lanes behind 4,096, 512 of 6,144)."""
    return (math.gcd(t, _MAX_ROWS),
            math.gcd(math.gcd(start, width), _MAX_LANES))


def unsupported_reason(t: int, start: int, width: int,
                       taps: int) -> str | None:
    """Why the kernels cannot take rows of ``t`` positions, the cut
    ``[start, start + width)`` of the projection's lanes and ``taps`` taps,
    or ``None``: a cut that starts and ends at whole lane tiles, rows of
    whole halo tiles, taps within the halo."""
    if start % LANES or width % LANES or width <= 0:
        return (f"the cut of {width} lanes behind {start} is not whole "
                f"tiles of {LANES} lanes")
    if t % HALO:
        return f"rows of {t} positions are no multiple of {HALO}"
    if not 1 <= taps <= HALO + 1:
        return (f"{taps} taps reach further back than the {HALO} rows "
                f"fetched in front of a tile")
    return None


def _pieces(rows: int, lanes: int):
    """-> ``(rows a piece, pieces a tile, the pieces' lane slices)``."""
    r = min(PASS_ROWS, rows)
    return r, rows // r, [slice(a, a + LANES) for a in range(0, lanes, LANES)]


def _before(x_ref, halo, k, r: int, at):
    """The :data:`HALO` rows in front of piece ``k`` (traced) of the tile,
    float32: the tile's own, or ``halo`` in front of the first piece."""
    own = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(k * r - HALO, 0), HALO),
                         HALO), at]
    return jnp.where(k == 0, halo, own.astype(jnp.float32))


def _shifted(before, cur, taps: int) -> list:
    """``[cur moved s rows down, for s in 0 .. taps - 1]``, the rows that
    enter at the top ``before``'s last: ``(r, 128)`` float32 each."""
    full = jnp.concatenate([before, cur], axis=0)
    return [cur] + [pltpu.roll(full, s, 0)[HALO:] for s in range(1, taps)]


def _rows_of(coef_ref, at) -> list:
    """The taps and, last, the bias of the lanes ``at``: ``(1, 128)``
    float32 each."""
    return [coef_ref[j:j + 1, at] for j in range(coef_ref.shape[0])]


def _sum(coef: list, moved: list):
    """``bias + sum_s k_{taps - 1 - s} moved[s]``."""
    n = len(moved)
    acc = coef[n] + coef[n - 1] * moved[0]
    for s in range(1, n):
        acc = acc + coef[n - 1 - s] * moved[s]
    return acc


def _halo_of(halo_ref, first, at):
    """The fetched rows in front of the tile, zeros in front of the row's
    first."""
    return jnp.where(first, 0.0, halo_ref[0, :, at].astype(jnp.float32))


def _fwd_kernel(x_ref, halo_ref, coef_ref, y_ref, *, taps: int):
    rows, lanes = x_ref.shape[1:]
    r, count, ats = _pieces(rows, lanes)
    first = pl.program_id(2) == 0
    for at in ats:
        coef = _rows_of(coef_ref, at)
        halo = _halo_of(halo_ref, first, at)

        def piece(k, _):
            here = pl.ds(pl.multiple_of(k * r, r), r)
            cur = x_ref[0, here, at].astype(jnp.float32)
            c = _sum(coef, _shifted(_before(x_ref, halo, k, r, at), cur,
                                    taps))
            y_ref[0, here, at] = (c * jax.nn.sigmoid(c)).astype(y_ref.dtype)
            return _

        lax.fori_loop(0, count, piece, 0)


def _folded(v):
    """``(r, 128)`` float32 -> ``(8, 128)``: the sum of its 8-row slices
    (whole registers added; the sublanes are summed outside)."""
    return sum(v[a:a + 8] for a in range(8, v.shape[0], 8)) + v[:8]


def _bwd_kernel(x_ref, halo_ref, coef_ref, dy_ref, dv_ref, sums_ref, next_sc,
                *, taps: int):
    rows, lanes = x_ref.shape[1:]
    r, count, ats = _pieces(rows, lanes)
    c_id = pl.program_id(2)
    # tiles come last first: the row's first tile is the last visited
    first = c_id == pl.num_programs(2) - 1

    @pl.when(c_id == 0)
    def _init():
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)
        next_sc[...] = jnp.zeros(next_sc.shape, jnp.float32)

    for at in ats:
        coef = _rows_of(coef_ref, at)
        halo = _halo_of(halo_ref, first, at)

        def piece(i, carry):
            behind, sums = carry
            k = count - 1 - i
            here = pl.ds(pl.multiple_of(k * r, r), r)
            cur = x_ref[0, here, at].astype(jnp.float32)
            moved = _shifted(_before(x_ref, halo, k, r, at), cur, taps)
            c = _sum(coef, moved)
            sig = jax.nn.sigmoid(c)
            dc = dy_ref[0, here, at].astype(jnp.float32) * \
                (sig * (1.0 + c * (1.0 - sig)))
            # dv_t = sum_s k_{taps - 1 - s} dc_{t + s}: dc moved s rows up,
            # the rows that enter at the bottom the piece's behind it
            full = jnp.concatenate([dc, behind], axis=0)
            dv = coef[taps - 1] * dc
            for s in range(1, taps):
                dv = dv + coef[taps - 1 - s] * \
                    pltpu.roll(full, r + HALO - s, 0)[:r]
            dv_ref[0, here, at] = dv.astype(dv_ref.dtype)
            sums = tuple(
                [acc + _folded(dc * moved[taps - 1 - j])
                 for j, acc in enumerate(sums[:taps])] +
                [sums[taps] + _folded(dc)])
            return dc[:HALO], sums

        zero = jnp.zeros((8, LANES), jnp.float32)
        behind, sums = lax.fori_loop(
            0, count, piece, (next_sc[:, at], (zero,) * (taps + 1)))
        next_sc[:, at] = behind
        for j, acc in enumerate(sums):
            sums_ref[0, 8 * j:8 * j + 8, at] += acc


def _specs(t: int, start: int, width: int, coef_rows: int, tile_of):
    """The block specs both kernels share, for grid point ``(row, block of
    lanes, step)`` at tile of time ``tile_of(step)``: the tile cut from the
    projection's lanes, the rows in front of it (the first tile's are
    fetched from the row's start and zeroed in the kernel), the taps and
    bias, and a tile of a ``(b, t, width)`` array."""
    rows, lanes = tiles(t, start, width)
    vm, first, halos = pltpu.VMEM, start // lanes, rows // HALO
    cut = pl.BlockSpec((1, rows, lanes), lambda i, j, c:
                       (i, tile_of(c), first + j), memory_space=vm)
    halo = pl.BlockSpec((1, HALO, lanes), lambda i, j, c:
                        (i, jnp.maximum(tile_of(c) * halos - 1, 0),
                         first + j), memory_space=vm)
    coef = pl.BlockSpec((coef_rows, lanes), lambda i, j, c: (0, j),
                        memory_space=vm)
    tile = pl.BlockSpec((1, rows, lanes), lambda i, j, c:
                        (i, tile_of(c), j), memory_space=vm)
    return (t // rows, width // lanes), cut, halo, coef, tile


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


@partial(jax.jit, static_argnames=("start", "interpret"))
def conv_fwd(proj, coef, *, start: int, interpret: bool):
    """-> ``silu(conv(proj[..., start:start + width]) + bias)`` ``(b, t,
    width)`` in ``proj``'s dtype.  ``proj (b, t, any width)``; ``coef
    (taps + 1, width)`` float32: the taps (``c_t = sum_j k_j v_{t - taps +
    1 + j}``, zeros before the sequence), then the bias."""
    b, t, _ = proj.shape
    taps, width = coef.shape[0] - 1, coef.shape[1]
    (steps, blocks), cut, halo, coef_spec, tile = _specs(
        t, start, width, taps + 1, lambda c: c)
    return pl.pallas_call(
        partial(_fwd_kernel, taps=taps),
        grid=(b, blocks, steps),
        in_specs=[cut, halo, coef_spec],
        out_specs=tile,
        out_shape=_out_struct((b, t, width), proj.dtype, proj),
        compiler_params=_PARAMS,
        name=FWD_KERNEL_NAME,
        interpret=interpret,
    )(proj, proj, coef)


@partial(jax.jit, static_argnames=("start", "interpret"))
def conv_bwd(proj, coef, dy, *, start: int, interpret: bool):
    """-> ``(dv (b, t, width) in proj's dtype, d coef (taps + 1, width)
    float32)``: the gradients of ``sum(conv_fwd(proj, coef) * dy)`` to the
    cut of ``proj`` and to the taps and the bias."""
    b, t, _ = proj.shape
    taps, width = coef.shape[0] - 1, coef.shape[1]
    (steps, blocks), cut, halo, coef_spec, tile = _specs(
        t, start, width, taps + 1, lambda c: steps - 1 - c)
    lanes = tile.block_shape[2]
    dv, sums = pl.pallas_call(
        partial(_bwd_kernel, taps=taps),
        grid=(b, blocks, steps),
        in_specs=[cut, halo, coef_spec, tile],
        # a row's partial sums stay in VMEM over the row's tiles
        out_specs=[tile, pl.BlockSpec((1, 8 * (taps + 1), lanes),
                                      lambda i, j, c: (i, 0, j),
                                      memory_space=pltpu.VMEM)],
        out_shape=[_out_struct((b, t, width), proj.dtype, proj),
                   _out_struct((b, 8 * (taps + 1), width), jnp.float32,
                               proj)],
        scratch_shapes=[pltpu.VMEM((HALO, lanes), jnp.float32)],
        compiler_params=_PARAMS,
        name=BWD_KERNEL_NAME,
        interpret=interpret,
    )(proj, proj, coef, dy)
    return dv, sums.reshape(b, taps + 1, 8, width).sum(axis=(0, 2))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv(proj, coef, start: int, interpret: bool):
    """The convolution of the lanes ``[start, start + width)`` of ``proj``
    with its bias and ``silu`` by the two kernels, differentiable in
    ``proj (b, t, any width)`` and ``coef (taps + 1, width)`` float32 (the
    taps, then the bias) -> ``(b, t, width)`` in ``proj``'s dtype."""
    return conv_fwd(proj, coef, start=start, interpret=interpret)


def _conv_fwd(proj, coef, start, interpret):
    # a kernel's result leaving a custom_vjp: named for the layer's
    # checkpoint policy (``plan.py::_KEPT_ALWAYS``), or the kernel runs twice
    y = checkpoint_name(conv_fwd(proj, coef, start=start,
                                 interpret=interpret), "ssm_conv")
    return y, (proj, coef)


def _conv_bwd(start, interpret, kept, dy):
    proj, coef = kept
    dv, dcoef = conv_bwd(proj, coef, dy, start=start, interpret=interpret)
    # the cotangent's way back beside those of the projection's other lanes
    behind = proj.shape[2] - start - dv.shape[2]
    return jnp.pad(dv, ((0, 0), (0, 0), (start, behind))), dcoef


conv.defvjp(_conv_fwd, _conv_bwd)
