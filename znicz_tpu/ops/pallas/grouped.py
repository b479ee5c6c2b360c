"""Grouped products as Pallas kernels: the routed expert layer's hot op
(``parallel/moe.py::moe_routed_ffn``), in ``lax.ragged_dot``'s place on
a TPU.

Rows lie sorted by group, ``sizes (held,)`` rows to each group's weights,
and whatever follows ``sizes.sum()`` is nobody's (the buffer's tail).
Three kernels, one walk:

- :func:`gmm_rows` — rows times their group's weights,
  ``(rows, k) x (held, k, n) -> (rows, n)``;
- :func:`gmm_rows_t` — the same with the weights contracted on their
  LAST axis, ``(rows, n) x (held, k, n) -> (rows, k)``: the gradient to
  the rows, read from the weights as they are stored (no transposed
  copy);
- :func:`gmm_weights` — the gradient to the weights: each group's rows,
  transposed, times its rows of the cotangent, ``-> (held, k, n)``
  float32.

The walk (:func:`plan`) is a list of VISITS, one for each (row tile,
group) pair that shares a row, in row order; an empty group has one
visit, so that its weights' gradient is written (as zeros).  XLA's own
kernel walks rows by 512 and fetches a weight tile for every row tile,
so it needs the wide tile to hide the fetch; here a group's whole
``(k, n)`` matrix (or as wide a slab of it as fits) stays in VMEM over
the group's consecutive visits (the row kernels fetch it themselves, a
whole group ahead of its use), so each weight is fetched once a product
whatever the row tile, and the tile can be narrow: with ``held`` groups
over ``rows`` rows the walk has at most ``rows / ROW_TILE + held - 1``
visits and a boundary inside a tile costs 128 row-slots, not 512
(:func:`tile_fill`).

The grid is static (``rows / ROW_TILE + held - 1`` steps); visits past
the last one fetch nothing (their block indices repeat the last visit's)
and the row kernels spend them on the tail: every row tile past the last
live row is written as zeros, as are a live tile's rows past the last
group's end, so the result is defined everywhere and needs no cut before
it meets another factor.  Operands in the rows' dtype (bfloat16 in
production), float32 accumulation.

``jax.experimental.pallas.ops.tpu.megablox`` is the published form of
these three (``gmm``, ``gmm(transpose_rhs=True)``, ``tgmm``); what it
lacks is the resident weight block (its tiling is 128 x 128 x 128) and a
defined tail.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct


#: the kernels' names in the lowered program and in device traces: one
#: substring, ``moe_gmm``, finds the three.  They end in the name the TPU
#: compiler gives ``lax.ragged_dot``'s kernel because they are the same
#: nine products a layer, and ``benchmark/kernels/moe_gmm.py`` finds the
#: grouped products by it: ``moe_gmm_roofline`` goes on reading their
#: least time over the time of whichever form ran
_XLA_NAME = "ragged-dot-none"
ROWS_KERNEL_NAME = f"moe_gmm_rows.{_XLA_NAME}"
ROWS_T_KERNEL_NAME = f"moe_gmm_rows_t.{_XLA_NAME}"
WEIGHTS_KERNEL_NAME = f"moe_gmm_weights.{_XLA_NAME}"

#: rows to a visit.  On a v5e at the benchmark's shapes (12,288 rows of
#: which 8,190 live, 16 groups, the fullest 3.9 x the mean, 2,048 x 1,536
#: weights, bfloat16; each kernel alone, my chip runs, PR 31, PERF.md
#: section 6): row kernels 0.426 / 0.443 / 0.573 ms at 128 / 256 / 512,
#: the weights' gradient 0.633 / 0.636 / 0.670: what a narrow tile gains in
#: fill (0.82 / 0.70 / 0.52) a visit of 128 rows loses in the MXU (a weight
#: tile is loaded for 128 rows where 256 amortise it), so 128 and 256 tie
#: here and 128 wins as the groups grow more ragged
ROW_TILE = 128
#: bytes of one weight block (row kernels: operand dtype) or one block of
#: the weights' gradient (float32), of which two are held at a time: the
#: largest the benchmark's widths ask for (2,048 x 1,536 float32); at 6 MiB
#: the weights' gradient ran in two slabs, 0.67 ms against 0.63
_BLOCK_BYTES = 12 * 1024 * 1024
#: scoped VMEM asked of the compiler (a v5e core has 128 MiB; the default
#: scope of 16 would not hold two such blocks and the row tiles)
_VMEM_LIMIT = 64 * 1024 * 1024


@partial(jax.jit, static_argnames=("rows", "tile"))
def plan(sizes, rows: int, tile: int = ROW_TILE):
    """The walk over ``rows`` rows in tiles of ``tile`` for groups of
    ``sizes`` rows -> the kernels' scalar-prefetch arguments ``(offsets
    (held + 1,), group (V,), tile_of (V,), out_tile (V,), n_active
    (1,))`` over ``V = rows / tile + held - 1`` grid steps, int32.

    Visit ``v < n_active`` works on rows ``offsets[group[v]] ..
    offsets[group[v] + 1]`` inside row tile ``tile_of[v]``.  A later
    step repeats the last visit's ``group`` and ``tile_of`` (nothing to
    fetch) and its ``out_tile`` walks the tiles past the last live row,
    which the row kernels write as zeros; ``tile_of == out_tile`` on
    every visit."""
    held = sizes.shape[0]
    tiles = rows // tile
    starts, ends, span = _spans(sizes, tile)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = jnp.minimum(starts // tile, tiles - 1)
    span = jnp.maximum(span, 1)                # an empty group: one visit
    visit0 = jnp.cumsum(span) - span           # each group's first visit
    n_active = span.sum()
    v = jnp.arange(tiles + held - 1, dtype=jnp.int32)
    group = (v[:, None] >= visit0[None, :]).sum(1, dtype=jnp.int32) - 1
    last = jnp.minimum(first[-1] + span[-1], tiles) - 1
    tile_of = jnp.minimum(first[group] + v - visit0[group], last)
    # the tail's tiles: past the live rows, and past a last visit that an
    # empty group made beyond them (it wrote that tile as zeros)
    tail = jnp.maximum(-(-ends[-1] // tile), last + 1)
    out_tile = jnp.where(v < n_active, tile_of,
                         jnp.minimum(tail + v - n_active, tiles - 1))
    return offsets, group, tile_of, out_tile, n_active[None]


def _spans(sizes, tile: int):
    """-> ``(starts, ends, span)`` of the groups' rows, int32: ``span``
    the row tiles of ``tile`` a group shares a row with (0 when empty)."""
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    return starts, ends, jnp.where(
        sizes > 0, (ends - 1) // tile - starts // tile + 1, 0)


@partial(jax.jit, static_argnames=("tile",))
def tile_fill(sizes, tile: int):
    """Live rows over the row-slots a grouped product visits when it
    walks these groups in row tiles of ``tile``: a (tile, group) pair
    that shares a row costs the whole tile.  Float32 scalar, 1.0 for no
    rows at all."""
    _, ends, span = _spans(sizes, tile)
    slots = (tile * span.sum()).astype(jnp.float32)
    return jnp.where(slots > 0, ends[-1] / jnp.maximum(slots, 1.0), 1.0)


def _rows_kernel(off_ref, grp_ref, til_ref, dst_ref, n_ref, a_ref, w_any,
                 o_ref, *fetched, tile: int, contract_last: bool):
    j, v = pl.program_id(0), pl.program_id(1)
    at, g = dst_ref[v], grp_ref[v]
    if fetched:
        _fetch_groups(w_any, *fetched, j, v, g, grp_ref, o_ref.shape[1],
                      contract_last)

    def weights():
        # the slot this kernel fetched the group's slab into, or the block
        # the pipeline brought
        return fetched[0][g % 2] if fetched else w_any[...]

    # the first step on this output tile: what the block holds is
    # whatever the memory held, so nothing of it is kept
    fresh = (v == 0) | (at != dst_ref[jnp.maximum(v - 1, 0)])
    active = v < n_ref[0]

    @pl.when(active)
    def _visit():
        dims = (((1,), (1 if contract_last else 0,)), ((), ()))
        acc = jax.lax.dot_general(a_ref[...], weights(), dims,
                                  preferred_element_type=jnp.float32)
        row = at * tile + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        # rows of other groups in this tile: kept as earlier visits wrote
        # them, zeros on a fresh tile (later visits write theirs over)
        kept = row < jnp.where(fresh, 0, (at + 1) * tile)
        prev = jnp.where(kept, o_ref[...].astype(jnp.float32), 0.0)
        o_ref[...] = jnp.where(mine, acc, prev).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(active) & fresh)
    def _tail():
        # every live tile has had its visit: a fresh one is past them
        o_ref[...] = jnp.zeros_like(o_ref)


def _fetch_groups(w_hbm, w_ref, sem, j, v, g, grp_ref, slab: int,
                  contract_last: bool):
    """The row kernels' own fetch of a group's slab of weights from HBM
    into its slot of the two in ``w_ref``, a whole group ahead of its use."""
    held = w_hbm.shape[0]

    def fetch(group):
        src = w_hbm.at[group, pl.ds(j * slab, slab), :] if contract_last \
            else w_hbm.at[group, :, pl.ds(j * slab, slab)]
        return pltpu.make_async_copy(src, w_ref.at[group % 2],
                                     sem.at[group % 2])

    @pl.when(v == 0)
    def _prime():
        fetch(g).start()

    # every group has a visit, in order (plan): on a group's first, its
    # weights arrive and the next group's set out, a whole group ahead of
    # their use (the pipeline's own fetch, one step ahead, left the first
    # visit of each group waiting: 0.466 -> 0.429 ms a product, PERF.md)
    @pl.when((v == 0) | (g != grp_ref[jnp.maximum(v - 1, 0)]))
    def _turn():
        fetch(g).wait()

        @pl.when(g + 1 < held)
        def _ahead():
            fetch(g + 1).start()


def _weights_kernel(off_ref, grp_ref, til_ref, dst_ref, n_ref, a_ref, g_ref,
                    o_ref, *, tile: int):
    v = pl.program_id(1)
    g = grp_ref[v]
    lo, hi = off_ref[g], off_ref[g + 1]

    @pl.when((v == 0) | (g != grp_ref[jnp.maximum(v - 1, 0)]))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    start = til_ref[v] * tile
    active = (v < n_ref[0]) & (hi > lo)
    whole = (start >= lo) & (start + tile <= hi)

    def accumulate(cut):
        o_ref[...] += jax.lax.dot_general(
            cut(a_ref[...]), cut(g_ref[...]), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(active & whole)
    def _inside():
        accumulate(lambda x: x)

    @pl.when(active & jnp.logical_not(whole))
    def _boundary():
        def cut(x):
            # other groups' rows (and the tail's, whatever they hold)
            # leave both factors as zeros; selected in float32, which
            # every TPU generation's vector unit has
            row = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            mine = (row >= lo) & (row < hi)
            return jnp.where(mine, x.astype(jnp.float32), 0.0
                             ).astype(x.dtype)
        accumulate(cut)


def _slab(other: int, dim: int, itemsize: int) -> int:
    """The widest slab of ``dim`` (all of it, whatever its width: a block as
    wide as the array needs no lane alignment; or a divisor that is a
    multiple of 128) whose ``other x slab`` block stays within
    ``_BLOCK_BYTES``; 0 when none does (not even 128 columns, or a width
    128 does not divide that is too wide to take whole)."""
    for parts in range(1, max(dim // 128, 1) + 1):
        if dim % parts == 0 and (parts == 1 or (dim // parts) % 128 == 0) \
                and other * (dim // parts) * itemsize <= _BLOCK_BYTES:
            return dim // parts
    return 0


def _weights_slabs(k: int, n: int) -> tuple:
    """``(rows, columns)`` of a block of the weights' float32 gradient:
    ``k`` whole, so that the cotangent is read once, and as wide a slab of
    ``n``; where ``n`` cannot be cut (128 does not divide it) and is too
    wide whole, ``n`` whole and a slab of ``k``.  ``(0, 0)``: neither."""
    slab = _slab(k, n, 4)
    if slab:
        return k, slab
    slab = _slab(n, k, 4)
    return (slab, n) if slab else (0, 0)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _rows_call(a, w, sizes, contract_last: bool, interpret: bool):
    rows, c = a.shape
    tile = ROW_TILE
    held = w.shape[0]
    out = w.shape[1] if contract_last else w.shape[2]
    slab = _slab(c, out, w.dtype.itemsize)
    block = (slab, c) if contract_last else (c, slab)
    # the kernel fetches the weights itself (they stay in HBM) wherever it
    # can cut its slab from them: Mosaic slices a memory reference on its
    # last axis by multiples of 128 lanes only, even for the whole of it
    # ("Slice shape along dimension 2 must be aligned to tiling (128), but
    # is 1856"); a last axis 128 does not divide comes by the pipeline, a
    # whole group's block at a time
    own_fetch = w.shape[2] % 128 == 0
    w_spec = pl.BlockSpec(memory_space=pl.ANY) if own_fetch else \
        pl.BlockSpec((None, *block), lambda j, v, off, grp, *_:
                     (grp[v], j, 0) if contract_last else (grp[v], 0, j))
    return pl.pallas_call(
        partial(_rows_kernel, tile=tile, contract_last=contract_last),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # the slabs outermost: a group's weights then stay put over
            # its consecutive visits
            grid=(out // slab, rows // tile + held - 1),
            in_specs=[
                pl.BlockSpec((tile, c),
                             lambda j, v, off, grp, til, *_: (til[v], 0)),
                w_spec],
            out_specs=pl.BlockSpec(
                (tile, slab),
                lambda j, v, off, grp, til, out_tile, n: (out_tile[v], j)),
            scratch_shapes=[
                pltpu.VMEM((2, *block), w.dtype),
                pltpu.SemaphoreType.DMA((2,))] if own_fetch else []),
        out_shape=_out_struct((rows, out), a.dtype, a),
        compiler_params=_params("parallel", "arbitrary"),
        name=ROWS_T_KERNEL_NAME if contract_last else ROWS_KERNEL_NAME,
        interpret=interpret,
    )(*plan(sizes, rows, tile), a, w)


# The three are jitted (as the walk and the counter are) so that a
# program's calls at one shape share one trace of the kernel and one
# lowering to Mosaic: a routed layer makes 21 (9 in its compact branch, 12
# in its full one), the benchmark's step 84, and lowering each by itself
# put 13 s on a 31 s start of that program from a warm compile cache (my
# chip runs, PR 31).
@partial(jax.jit, static_argnames=("interpret",))
def gmm_rows(a, w, sizes, *, interpret: bool = False):
    """``a (rows, k)`` times its group's ``w (held, k, n)`` -> ``(rows,
    n)`` in ``a``'s dtype; zeros past ``sizes.sum()`` rows."""
    _check(a.shape[0], *w.shape[1:], w.shape[0], a.dtype,
           a.shape[1] == w.shape[1] and w.dtype == a.dtype)
    return _rows_call(a, w, sizes, False, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def gmm_rows_t(g, w, sizes, *, interpret: bool = False):
    """``g (rows, n)`` times its group's ``w (held, k, n)`` transposed ->
    ``(rows, k)``: :func:`gmm_rows`'s gradient to its rows, the weights
    read as stored; zeros past ``sizes.sum()`` rows."""
    _check(g.shape[0], *w.shape[1:], w.shape[0], g.dtype,
           g.shape[1] == w.shape[2] and w.dtype == g.dtype)
    return _rows_call(g, w, sizes, True, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def gmm_weights(a, g, sizes, *, interpret: bool = False):
    """Each group's rows of ``a (rows, k)``, transposed, times its rows
    of ``g (rows, n)`` -> ``(held, k, n)`` float32:
    :func:`gmm_rows`'s gradient to its weights, as the products
    accumulate it (an empty group's is zeros; rows past ``sizes.sum()``
    count for nothing, whatever they hold)."""
    rows, k = a.shape
    n, held, tile = g.shape[1], sizes.shape[0], ROW_TILE
    _check(rows, k, n, held, a.dtype,
           g.shape[0] == rows and g.dtype == a.dtype)
    bk, bn = _weights_slabs(k, n)
    by_k = bk < k                  # the slabs cut ``k``; else they cut ``n``
    return pl.pallas_call(
        partial(_weights_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # the visits innermost: a group's block of the result stays
            # put, and sums, over its consecutive visits
            grid=(k // bk if by_k else n // bn, rows // tile + held - 1),
            in_specs=[
                pl.BlockSpec((tile, bk), lambda j, v, off, grp, til, *_:
                             (til[v], j if by_k else 0)),
                pl.BlockSpec((tile, bn), lambda j, v, off, grp, til, *_:
                             (til[v], 0 if by_k else j))],
            out_specs=pl.BlockSpec(
                (None, bk, bn), lambda j, v, off, grp, *_:
                (grp[v], j, 0) if by_k else (grp[v], 0, j))),
        out_shape=_out_struct((held, k, n), jnp.float32, a),
        compiler_params=_params("parallel", "arbitrary"),
        name=WEIGHTS_KERNEL_NAME,
        interpret=interpret,
    )(*plan(sizes, rows, tile), a, g)


def _check(rows, k, n, held, dtype, agree: bool):
    """Refuse by name what the kernels cannot take (call sites gate on
    :func:`unsupported_reason`)."""
    why = unsupported_reason(rows, k, n, held, dtype)
    if why is None and not agree:
        why = "the operands' shapes or dtypes do not agree"
    if why:
        raise ValueError(f"grouped product: {why}")


def unsupported_reason(rows: int, k: int, n: int, held: int,
                       dtype) -> str | None:
    """Why the three kernels cannot take a product of ``rows`` rows with
    ``(held, k, n)`` weights in ``dtype``, or ``None`` when they can."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"operands of {dtype}: bfloat16 or float32"
    if held < 1:
        return "no group"
    if rows < ROW_TILE or rows % ROW_TILE:
        return f"rows={rows} is not a multiple of the {ROW_TILE}-row tile"
    if any(width % 128 and (width < 128 or width % 8) for width in (k, n)):
        return (f"k={k}, n={n}: both must be multiples of 128 lanes, or over "
                f"128, multiples of 8 and taken whole (the last lane tile "
                f"masked)")
    if max(k, n) * 128 * 4 > _BLOCK_BYTES:
        return (f"a float32 block of {max(k, n)} x 128 is over the "
                f"{_BLOCK_BYTES >> 20} MiB a block may take of VMEM")
    if not (_slab(k, n, dtype.itemsize) and _slab(n, k, dtype.itemsize) and
            _weights_slabs(k, n)[0]):
        return (f"k={k}, n={n}: a width 128 lanes do not divide is taken "
                f"whole, and no such block of the weights stays within the "
                f"{_BLOCK_BYTES >> 20} MiB a block may take of VMEM")
    return None


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(a, w, sizes, interpret: bool = False):
    """:func:`gmm_rows`, differentiable: its rules are the two other
    kernels (the weights' gradient rounded to ``w``'s dtype, as a
    cotangent must be; a caller that keeps float32 masters calls
    :func:`gmm_weights` itself)."""
    return gmm_rows(a, w, sizes, interpret=interpret)


def _gmm_fwd(a, w, sizes, interpret):
    return gmm_rows(a, w, sizes, interpret=interpret), (a, w, sizes)


def _gmm_bwd(interpret, res, g):
    a, w, sizes = res
    return (gmm_rows_t(g, w, sizes, interpret=interpret),
            gmm_weights(a, g, sizes, interpret=interpret).astype(w.dtype),
            None)


gmm.defvjp(_gmm_fwd, _gmm_bwd)
