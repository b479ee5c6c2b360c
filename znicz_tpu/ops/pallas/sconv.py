"""The gated short convolution's gates and taps as two Pallas kernels
(``parallel/blocks.py::_block_sconv`` has the layer and the ``jax.numpy``
form these stand in for, ``blocks._sconv_gate``): ``y = C * conv(B * X)``
of the three cuts ``B | C | X`` of the layer's input projection.  In that
form the float32 copy of ``B * X``, its padded copy, the ``taps`` shifted
products, their sum and, under differentiation, the shifted cotangents, the
gates' gradients and the concatenation of ``dB | dC | dX`` go through HBM;
here the projection is read once a pass and nothing float32 and token-long
leaves VMEM.  The tiles, the halo and the sublane rotation are
``ops/pallas/ssm_conv.py``'s (its kernels' bodies are untouched: the two
layers differ in signature, not in a parameter).

**Forward** (:data:`FWD_KERNEL_NAME`): grid ``(row of the batch, block of
lanes, tile of time)``.  It reads ``proj (b, t, 3 d)`` ITSELF by three
block specs on the one array (``B`` at lanes ``[0, d)``, ``C`` at ``[d,
2 d)``, ``X`` at ``[2 d, 3 d)``: ``jnp.split``'s order; a tile of
``ssm_conv.tiles`` a visit) and by two more the :data:`HALO` rows of ``B``
and ``X`` in front of the tile (zeros in front of the sequence).  In VMEM:
``z = B * X`` in float32; ``c_t = sum_j k_j z_{t - taps + 1 + j}`` with
float32 taps; ``y = C * c``; one cast.  Writes ``y (b, t, d)`` in the
operands' dtype: the output product's operand.

**Backward** (:data:`BWD_KERNEL_NAME`): grid ``(row of the batch, tile of
time)``, the tiles in reverse.  It writes the projection's WHOLE cotangent,
``(b, t, 3 d)``, as one array (no concatenate, no pad), and one array has
one block spec: so a visit holds whole rows, all ``3 d`` lanes of
:func:`bwd_rows` positions of ``proj`` and of the cotangent and ``d`` of
``dy``, and walks them a lane tile at a time.  It makes ``z`` and ``c``
again, ``dC = dy * c``, ``dc = dy * C``, ``dz_t = sum_j k_j dc_{t + taps -
1 - j}`` (the ``taps - 1`` rows of ``dc`` behind the tile are the first
rows of the tile visited before: carried in VMEM), ``dB = dz * X``, ``dX =
dz * B``; the taps' gradient ``dk_j = sum_t dc_t z_{t - taps + 1 + j}``
accumulates in float32 in a block that stays in VMEM over a row's tiles
(eight partial sums a tap, one a sublane, summed outside with the rows of
the batch).

Residuals: ``proj`` and the taps, nothing else.

Precision is the ``jax.numpy`` form's or better: operands in the compute
dtype, everything between them float32, so ``y``, ``dB``, ``dC`` and ``dX``
are rounded ONCE each and the taps' gradient not at all.  That form, as
written, rounds ``z``, ``c``, ``dc`` and ``dz`` to the compute dtype on the
way; as XLA compiles it for the TPU it does not round ``z`` and ``c`` (a
16-bit product fused into a float32 consumer keeps its excess precision),
and kernels that did stood further from the float32 reference than the
parent's step in two of the benchmark's four gaps (``PERF.md`` section 6,
PR 51).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct
from znicz_tpu.ops.pallas.ssm_conv import (HALO, LANES, _MAX_ROWS, _PARAMS,
                                           _VMEM_LIMIT, _folded, _pieces,
                                           _shifted, tiles)

#: the kernels' names in the lowered program and in device traces
FWD_KERNEL_NAME = "sconv_gate_fwd"
BWD_KERNEL_NAME = "sconv_gate_bwd"
#: bytes the backward kernel's blocks may take of VMEM, double-buffered
#: (under ``ssm_conv``'s limit of 32 MiB, which both kernels run with)
_BWD_BLOCK_BYTES = 16 * 1024 * 1024


def unsupported_reason(t: int, d: int, taps: int, bias: bool) -> str | None:
    """Why the kernels cannot take rows of ``t`` positions of a layer ``d``
    wide with ``taps`` taps, or ``None``: three cuts of whole lane tiles,
    rows of whole halo tiles, taps within the halo, no convolution
    bias."""
    if d % LANES or d <= 0:
        return (f"the cuts of {d} lanes are not whole tiles of {LANES} "
                f"lanes")
    if t % HALO:
        return f"rows of {t} positions are no multiple of {HALO}"
    if not 1 <= taps <= HALO + 1:
        return (f"{taps} taps reach further back than the {HALO} rows "
                f"fetched in front of a tile")
    if bias:
        return "the convolution has a bias, and the kernels add none"
    return None


def bwd_rows(t: int, d: int, itemsize: int) -> int:
    """Rows of the backward kernel's tile: the largest power of two up to
    1,024 that divides ``t`` whose blocks (``3 d`` lanes of ``proj``, ``d``
    of ``dy``, ``3 d`` of the cotangent, each twice) fit
    :data:`_BWD_BLOCK_BYTES`, and no fewer than the halo's (256 of 4,096 at
    ``d`` 2,048 in 16 bits)."""
    rows = math.gcd(t, _MAX_ROWS)
    while rows > HALO and 14 * d * itemsize * rows > _BWD_BLOCK_BYTES:
        rows //= 2
    return rows


def _z(gate_b, xin):
    """``B * X`` in float32: the product of two 16-bit values is exact
    there, and the compiled ``jax.numpy`` form does not round it either
    (XLA keeps the excess precision of a 16-bit product it fuses into a
    float32 consumer; my chip run, PR 51)."""
    return gate_b.astype(jnp.float32) * xin.astype(jnp.float32)


def _halo_of(b_ref, x_ref, first, at_b, at_x):
    """``z`` of the fetched rows in front of the tile, zeros in front of
    the row's first."""
    return jnp.where(first, 0.0,
                     _z(b_ref[0, :, at_b], x_ref[0, :, at_x]))


def _before(b_ref, x_ref, halo, k, r: int, at_b, at_x):
    """``z`` of the :data:`HALO` rows in front of piece ``k`` (traced) of
    the tile: the tile's own, or ``halo`` in front of the first piece."""
    rows = pl.ds(pl.multiple_of(jnp.maximum(k * r - HALO, 0), HALO), HALO)
    return jnp.where(k == 0, halo,
                     _z(b_ref[0, rows, at_b], x_ref[0, rows, at_x]))


def _taps_of(k_ref, at) -> list:
    """The taps of the lanes ``at``: ``(1, 128)`` float32 each."""
    return [k_ref[j:j + 1, at] for j in range(k_ref.shape[0])]


def _sum(coef: list, moved: list):
    """``sum_s k_{taps - 1 - s} moved[s]``."""
    n = len(moved)
    acc = coef[n - 1] * moved[0]
    for s in range(1, n):
        acc = acc + coef[n - 1 - s] * moved[s]
    return acc


def _fwd_kernel(b_ref, c_ref, x_ref, bh_ref, xh_ref, k_ref, y_ref):
    rows, lanes = b_ref.shape[1:]
    r, count, ats = _pieces(rows, lanes)
    taps = k_ref.shape[0]
    first = pl.program_id(2) == 0
    for at in ats:
        coef = _taps_of(k_ref, at)
        halo = _halo_of(bh_ref, xh_ref, first, at, at)

        def piece(k, _):
            here = pl.ds(pl.multiple_of(k * r, r), r)
            z = _z(b_ref[0, here, at], x_ref[0, here, at])
            c = _sum(coef, _shifted(
                _before(b_ref, x_ref, halo, k, r, at, at), z, taps))
            y_ref[0, here, at] = (c_ref[0, here, at].astype(jnp.float32) *
                                  c).astype(y_ref.dtype)
            return _

        lax.fori_loop(0, count, piece, 0)


def _bwd_kernel(proj_ref, halo_ref, k_ref, dy_ref, dproj_ref, sums_ref,
                next_sc):
    rows, d = dy_ref.shape[1:]
    r, count, ats = _pieces(rows, d)
    taps, dtype = k_ref.shape[0], dproj_ref.dtype
    c_id = pl.program_id(1)
    # tiles come last first: the row's first tile is the last visited
    first = c_id == pl.num_programs(1) - 1

    @pl.when(c_id == 0)
    def _init():
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)
        next_sc[...] = jnp.zeros(next_sc.shape, jnp.float32)

    for at in ats:
        # B | C | X: the same lane tile of each cut
        at_b, at_c, at_x = (slice(at.start + s * d, at.stop + s * d)
                            for s in range(3))
        coef = _taps_of(k_ref, at)
        halo = _halo_of(halo_ref, halo_ref, first, at_b, at_x)

        def piece(i, carry):
            behind, sums = carry
            k = count - 1 - i
            here = pl.ds(pl.multiple_of(k * r, r), r)
            gate_b = proj_ref[0, here, at_b].astype(jnp.float32)
            xin = proj_ref[0, here, at_x].astype(jnp.float32)
            moved = _shifted(
                _before(proj_ref, proj_ref, halo, k, r, at_b, at_x),
                gate_b * xin, taps)
            dy = dy_ref[0, here, at].astype(jnp.float32)
            dproj_ref[0, here, at_c] = (dy * _sum(coef, moved)).astype(dtype)
            dc = dy * proj_ref[0, here, at_c].astype(jnp.float32)
            # dz_t = sum_s k_{taps - 1 - s} dc_{t + s}: dc moved s rows up,
            # the rows that enter at the bottom the piece's behind it
            full = jnp.concatenate([dc, behind], axis=0)
            dz = coef[taps - 1] * dc
            for s in range(1, taps):
                dz = dz + coef[taps - 1 - s] * \
                    pltpu.roll(full, r + HALO - s, 0)[:r]
            dproj_ref[0, here, at_b] = (dz * xin).astype(dtype)
            dproj_ref[0, here, at_x] = (dz * gate_b).astype(dtype)
            sums = tuple(acc + _folded(dc * moved[taps - 1 - j])
                         for j, acc in enumerate(sums))
            return dc[:HALO], sums

        zero = jnp.zeros((8, LANES), jnp.float32)
        behind, sums = lax.fori_loop(
            0, count, piece, (next_sc[:, at], (zero,) * taps))
        next_sc[:, at] = behind
        for j, acc in enumerate(sums):
            sums_ref[0, 8 * j:8 * j + 8, at] += acc


@partial(jax.jit, static_argnames=("interpret",))
def gate_fwd(proj, taps, *, interpret: bool):
    """-> ``C * conv(B * X)`` ``(b, t, d)`` in ``proj``'s dtype.  ``proj (b,
    t, 3 d)``, the cuts ``B | C | X``; ``taps (taps, d)`` float32 (``c_t =
    sum_j k_j z_{t - taps + 1 + j}``, zeros before the sequence)."""
    b, t, _ = proj.shape
    d = taps.shape[1]
    rows, lanes = tiles(t, d, d)
    vm, blocks, halos = pltpu.VMEM, d // lanes, rows // HALO

    def cut(s):
        return pl.BlockSpec((1, rows, lanes), lambda i, j, c:
                            (i, c, s * blocks + j), memory_space=vm)

    def halo(s):
        # the first tile's are fetched from the row's start and zeroed in
        # the kernel
        return pl.BlockSpec((1, HALO, lanes), lambda i, j, c:
                            (i, jnp.maximum(c * halos - 1, 0),
                             s * blocks + j), memory_space=vm)

    return pl.pallas_call(
        _fwd_kernel,
        grid=(b, blocks, t // rows),
        in_specs=[cut(0), cut(1), cut(2), halo(0), halo(2),
                  pl.BlockSpec((taps.shape[0], lanes), lambda i, j, c: (0, j),
                               memory_space=vm)],
        out_specs=cut(0),
        out_shape=_out_struct((b, t, d), proj.dtype, proj),
        compiler_params=_PARAMS,
        name=FWD_KERNEL_NAME,
        interpret=interpret,
    )(proj, proj, proj, proj, proj, taps)


@partial(jax.jit, static_argnames=("interpret",))
def gate_bwd(proj, taps, dy, *, interpret: bool):
    """-> ``(d proj (b, t, 3 d) in proj's dtype, d taps (taps, d)
    float32)``: the gradients of ``sum(gate_fwd(proj, taps) * dy)``."""
    b, t, wide = proj.shape
    n, d = taps.shape
    rows = bwd_rows(t, d, proj.dtype.itemsize)
    vm, steps, halos = pltpu.VMEM, t // rows, rows // HALO

    def tile(width):
        return pl.BlockSpec((1, rows, width), lambda i, c:
                            (i, steps - 1 - c, 0), memory_space=vm)

    dproj, sums = pl.pallas_call(
        _bwd_kernel,
        grid=(b, steps),
        in_specs=[tile(wide),
                  pl.BlockSpec((1, HALO, wide), lambda i, c: (
                      i, jnp.maximum((steps - 1 - c) * halos - 1, 0), 0),
                      memory_space=vm),
                  pl.BlockSpec((n, d), lambda i, c: (0, 0), memory_space=vm),
                  tile(d)],
        # a row's partial sums stay in VMEM over the row's tiles
        out_specs=[tile(wide),
                   pl.BlockSpec((1, 8 * n, d), lambda i, c: (i, 0, 0),
                                memory_space=vm)],
        out_shape=[_out_struct((b, t, wide), proj.dtype, proj),
                   _out_struct((b, 8 * n, d), jnp.float32, proj)],
        scratch_shapes=[pltpu.VMEM((HALO, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=BWD_KERNEL_NAME,
        interpret=interpret,
    )(proj, proj, taps, dy)
    return dproj, sums.reshape(b, n, 8, d).sum(axis=(0, 2))


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def gate(proj, taps, interpret: bool):
    """``C * conv(B * X)`` of the cuts ``B | C | X`` of ``proj (b, t, 3
    d)`` by the two kernels, differentiable in ``proj`` and ``taps (taps,
    d)`` float32 -> ``(b, t, d)`` in ``proj``'s dtype."""
    return gate_fwd(proj, taps, interpret=interpret)


def _gate_fwd(proj, taps, interpret):
    return gate_fwd(proj, taps, interpret=interpret), (proj, taps)


def _gate_bwd(interpret, kept, dy):
    return gate_bwd(*kept, dy, interpret=interpret)


gate.defvjp(_gate_fwd, _gate_bwd)
