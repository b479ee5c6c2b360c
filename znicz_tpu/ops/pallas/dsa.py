"""Learned sparse attention's indexer as Pallas kernels, a block of queries
at a time (``parallel/dsa.py`` has the loss, the selection and the scan that
calls these): the target of the alignment loss, the index scores, and the
index scores' gradients.  In the blocked ``jax.numpy`` forms a block's
``(heads, block, keys)`` float32 scores go through HBM several times; here
they never leave VMEM.

**The target** (:data:`ALIGN_KERNEL_NAME`): the mean over the attention
heads of each head's softmax over the query's SELECTED keys.  One kernel
walks the key tiles twice, as a grid of ``(2 sweeps, key tiles)``:

1. **rows**: for each key/value head ``s = q_g k_g^T * scale`` on the MXU
   (operands as they come, float32 accumulation), masked by the selection's
   ``(block, tile)`` int8 tile, which serves every head, and a running
   maximum and sum of ``exp`` a (head, query) row, as the blocked flash
   forward kernel keeps them (``ops/pallas/attention.py``): after the last
   tile the row's log-sum-exp over its selected keys, kept in VMEM;
2. **target**: the same product again, ``exp(s - lse)`` (0 where masked),
   summed over the heads into the ``(block, tile)`` float32 output tile and
   divided by their number.

The scores stay float32 from the MXU to the ``exp`` (the ``jax.numpy`` form
rounds them to the compute dtype on their way to HBM).

**The index scores** (:data:`INDEX_SCORES_KERNEL_NAME`): ``idx[i, s] = sum_j
w[i, j] relu(qI[i, j] . kI[s])``.  A grid of key tiles; in a tile ``s = qI
kI^T`` on the MXU, :data:`_INDEX_CHUNK` index heads a product (operands as
they come, float32 accumulation), ``relu``, the weight of the (head, query)
row and the sum over the heads, all float32, into the ``(block, tile)``
output tile.  **Their gradients** (:data:`INDEX_GRADS_KERNEL_NAME`), from
``d_idx = dL/didx`` a tile at a time: ``s`` again in the tile, ``g = d_idx *
w * (s > 0)`` rounded to the operands' dtype, then ``dqI += g kI`` (float32,
resident over the tiles), ``dkI^T[tile] = qI^T g`` (float32, keys-minor, a
tile a step) and ``dw += sum over keys of d_idx * relu(s)``.  Each product
contracts or produces only ``di`` = 64 of the MXU's 128 columns, so the four
run at about half its peak at best.

A key tile wholly above the block's last query holds no selected pair, and
``d_idx`` is 0 there: the number of tiles at or under the diagonal comes as
a prefetched scalar, the tiles past it are neither fetched nor computed
(their block indices stay where they were) and their output is written as
zeros.

Layouts.  ``q`` comes head-major, ``(kv, group x block, dh)`` (row ``h x
block + i`` of a key/value head's slab is query ``i`` of its ``h``-th query
head), so a key/value head's scores are one product with 8 x 128 rows and
the selection's tile masks them as ``(group, block, tile)`` by broadcast;
``k`` comes as the layer has it, ``(keys, kv x dh)``, and a key/value head is
a slice of ``dh`` lanes: hence a head width that is a multiple of 128.  The
index kernels take everything as the layer has it: the index queries
``(block, hi x di)`` and ``dqI`` likewise, a head ``di`` lanes of a row (cut
from the lanes in the kernel, at any offset: a head-major copy of ``qI``
outside the scan bought nothing); the one index key head ``(keys, di)``,
its gradient keys-minor ``(di, keys)``; the weights and their gradient
``(block, hi)``, a head's column cut from the lanes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct
from znicz_tpu.ops.pallas.attention import _MASKED, _nt

#: the kernel's name in the lowered program and in device traces
ALIGN_KERNEL_NAME = "dsa_align_target"
#: the index kernels' names there (the scores; their three gradients)
INDEX_SCORES_KERNEL_NAME = "dsa_index_scores"
INDEX_GRADS_KERNEL_NAME = "dsa_index_grads"
#: keys a tile may hold, largest first (:func:`align_tile` chooses)
_TILES = (1024, 512, 256, 128)
#: index heads whose scores one product of the index kernels makes
_INDEX_CHUNK = 4
#: scoped VMEM asked of the compiler (the blocked flash kernels' limit)
_VMEM_LIMIT = 32 * 1024 * 1024


def _align_vmem(tile: int, block: int, heads: int, kv: int, dh: int) -> int:
    """Bytes of VMEM the kernel's working set takes: ``q`` whole and the
    key tile of every key/value head, 16-bit and double-buffered; the
    selection's int8 tile double-buffered and once widened; the float32
    output tile double-buffered; the two row statistics, a ``(block, 1)``
    column a head padded to the 128 lanes; a key/value head's float32
    scores three times (the product, masked, ``exp``).  21.8 MiB at 1,024
    keys, 128 queries, 32 heads of 128 on 4."""
    return (2 * 2 * heads * block * dh + 2 * 2 * tile * kv * dh +
            6 * block * tile + 2 * 4 * block * tile +
            2 * 4 * 128 * heads * block +
            3 * 4 * (heads // kv) * block * tile)


def _largest_tile(keys: int, vmem) -> int:
    """The largest of :data:`_TILES` that divides ``keys`` and whose working
    set, ``vmem(tile)`` bytes, fits :data:`_VMEM_LIMIT`; 0 when none does."""
    return next((t for t in _TILES if keys % t == 0 and
                 vmem(t) <= _VMEM_LIMIT), 0)


def _tile_refusal(keys: int, what: str, vmem) -> str | None:
    """Why no tile serves key extents that are multiples of ``keys`` for
    ``what`` (the shape, in words), or ``None``."""
    least = _TILES[-1]
    if keys % least:
        return f"key extents of {keys} are no multiple of the {least}-key tile"
    if not _largest_tile(keys, vmem):
        return (f"{what}: a {least}-key tile needs {vmem(least) >> 20} MiB "
                f"of the kernel's {_VMEM_LIMIT >> 20} MiB of VMEM")
    return None


def align_tile(keys: int, block: int, heads: int, kv: int, dh: int) -> int:
    """Keys a tile holds: the largest of :data:`_TILES` that divides
    ``keys`` and whose working set fits :data:`_VMEM_LIMIT`; 0 when none
    does."""
    return _largest_tile(keys, lambda t: _align_vmem(t, block, heads, kv, dh))


def unsupported_reason(block: int, keys: int, heads: int, kv: int,
                       dh: int) -> str | None:
    """Why the kernel cannot take blocks of ``block`` queries of ``heads``
    heads ``dh`` wide on ``kv`` key/value heads against key extents that
    are multiples of ``keys``, or ``None``: a block of whole int8 tiles
    (32 rows), a head that is a multiple of the 128 lanes (a key/value
    head is cut from ``(keys, kv x dh)`` by lanes), and a tile that
    divides the extents with its working set inside the limit."""
    if block % 32:
        return f"a block of {block} queries is no multiple of 32 rows"
    if dh % 128:
        return f"head_dim={dh} is not a multiple of 128"
    if heads % kv:
        return f"{heads} heads do not divide over {kv} key/value heads"
    return _tile_refusal(keys, f"{heads} heads of {dh}", lambda t:
                         _align_vmem(t, block, heads, kv, dh))


def _align_kernel(n_ref, q_ref, k_ref, sel_ref, p_ref, m_sc, l_sc, *,
                  sm_scale: float):
    kv, group, block, _ = m_sc.shape
    dh = q_ref.shape[-1]
    tile = sel_ref.shape[-1]
    sweep, j = pl.program_id(0), pl.program_id(1)
    live = j < n_ref[0]

    def scores(g: int, picked):
        """Key/value head ``g``'s masked scores ``(group, block, tile)``."""
        s = _nt(q_ref[g], k_ref[:, g * dh:(g + 1) * dh]) * sm_scale
        return jnp.where(picked[None], s.reshape(group, block, tile),
                         jnp.float32(_MASKED))

    @pl.when((sweep == 0) & (j == 0))
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _MASKED)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when((sweep == 0) & live)
    def _rows():
        picked = sel_ref[...].astype(jnp.int32) != 0
        for g in range(kv):
            s = scores(g, picked)
            m_prev = m_sc[g]
            # a row whose first tiles hold none of its keys gathers ones
            # there (exp(0)); its first selected key wipes them (alpha 0),
            # and every query has one, in a live tile
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            l_sc[g] = jnp.exp(m_prev - m_new) * l_sc[g] + \
                jnp.exp(s - m_new).sum(axis=-1, keepdims=True)
            m_sc[g] = m_new

    @pl.when((sweep == 1) & (j == 0))
    def _lse():
        m_sc[...] = m_sc[...] + jnp.log(l_sc[...])

    @pl.when((sweep == 1) & live)
    def _target():
        picked = sel_ref[...].astype(jnp.int32) != 0
        acc = jnp.zeros((block, tile), jnp.float32)
        for g in range(kv):
            acc = acc + jnp.exp(scores(g, picked) - m_sc[g]).sum(axis=0)
        p_ref[...] = acc * jnp.float32(1.0 / (kv * group))

    @pl.when((sweep == 1) & jnp.logical_not(live))
    def _empty():
        p_ref[...] = jnp.zeros_like(p_ref)


# Jitted, so that a layer's scans share one lowering of each key extent
@partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def align_target(q, k, sel, last, *, sm_scale: float, interpret: bool):
    """-> ``p`` float32 ``(block, keys)``: the mean over the heads of each
    head's softmax over the block's selected keys, 0 elsewhere.

    ``q`` ``(kv, group x block, dh)`` head-major (module docstring) and ``k``
    ``(keys, kv, dh)`` in the compute dtype; ``sel`` int8 ``(block, keys)``,
    nonzero where the query attends to the key, every query with a key of
    its own and none past ``last``, the position (an int32 scalar, traced)
    of the block's last query among the keys."""
    kv, rows, dh = q.shape
    block, keys = sel.shape
    group = rows // block
    tile = align_tile(keys, block, kv * group, kv, dh)
    n_live = (last // tile + 1).astype(jnp.int32).reshape(1)
    # a tile past the last live one stays on it: nothing is fetched
    at = lambda j, n: jnp.minimum(j, n[0] - 1)             # noqa: E731
    vm = pltpu.VMEM
    return pl.pallas_call(
        partial(_align_kernel, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(2, keys // tile),
            in_specs=[
                pl.BlockSpec((kv, rows, dh), lambda s, j, n: (0, 0, 0),
                             memory_space=vm),
                pl.BlockSpec((tile, kv * dh), lambda s, j, n:
                             (at(j, n), 0), memory_space=vm),
                pl.BlockSpec((block, tile), lambda s, j, n:
                             (0, at(j, n)), memory_space=vm)],
            # the first sweep writes nothing: its steps stay on the tile
            # the second sweep writes first
            out_specs=pl.BlockSpec((block, tile), lambda s, j, n:
                                   (0, j * s), memory_space=vm),
            scratch_shapes=[pltpu.VMEM((kv, group, block, 1), jnp.float32),
                            pltpu.VMEM((kv, group, block, 1), jnp.float32)]),
        out_shape=_out_struct((block, keys), jnp.float32, q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=ALIGN_KERNEL_NAME,
        interpret=interpret,
    )(n_live, q, k.reshape(keys, kv * dh), sel)


# -- the index scores and their gradients ------------------------------------

def _index_vmem(tile: int, block: int, hi: int, di: int) -> int:
    """Bytes of VMEM the gradients' kernel takes at most (the scores' kernel
    takes less): the queries whole and the key tile, 16-bit, and ``d_idx``'s
    float32 tile, double-buffered; the float32 ``dqI`` whole and ``dkI``'s
    keys-minor tile, double-buffered; the weights and their gradient,
    ``(block, 128)`` float32 each; a chunk of :data:`_INDEX_CHUNK` heads'
    float32 scores four times (the product, ``d_idx`` where positive, times
    the weight, times the score) and ``g`` in 16 bits.  12.8 MiB at 1,024
    keys, 128 queries, 16 heads of 64."""
    pad = lambda n, m: -(-n // m) * m                       # noqa: E731
    wide, rows = pad(hi * di, 128), min(hi, _INDEX_CHUNK) * block
    return (2 * 2 * block * wide + 2 * 2 * tile * pad(di, 128) +
            2 * 4 * block * tile +
            2 * 4 * block * wide + 2 * 4 * pad(di, 8) * tile +
            4 * 4 * block * pad(hi, 128) +
            (4 * 4 + 2) * rows * tile)


def index_tile(keys: int, block: int, hi: int, di: int) -> int:
    """Keys a tile of the index kernels holds: as :func:`align_tile`."""
    return _largest_tile(keys, lambda t: _index_vmem(t, block, hi, di))


def index_unsupported_reason(block: int, keys: int, hi: int,
                             di: int) -> str | None:
    """Why the index kernels cannot take blocks of ``block`` queries of
    ``hi`` index heads ``di`` wide against key extents that are multiples
    of ``keys``, or ``None``: a block's 16-bit rows are whole tiles (16
    rows), and a tile divides the extents with its working set inside the
    limit (any ``di``: a head is cut from the lanes wherever it starts)."""
    if block % 16:
        return f"a block of {block} queries is no multiple of 16 rows"
    return _tile_refusal(keys, f"{hi} index heads of {di}", lambda t:
                         _index_vmem(t, block, hi, di))


def _chunks(hi: int):
    """The index heads :data:`_INDEX_CHUNK` at a time: ``(first, count)``."""
    return [(h, min(_INDEX_CHUNK, hi - h)) for h in range(0, hi, _INDEX_CHUNK)]


def _columns(w, first: int, count: int):
    """Heads ``first`` to ``first + count`` of ``w`` ``(block, hi)`` as
    ``(count, block, 1)``: a column a head, to broadcast over the keys."""
    return jnp.stack([w[:, h:h + 1] for h in range(first, first + count)])


def _heads(q_ref, first: int, count: int, di: int):
    """Heads ``first`` to ``first + count`` of ``q_ref`` ``(block, hi x
    di)`` one under the other, ``(count x block, di)``: a product's rows."""
    return jnp.concatenate([q_ref[:, h * di:(h + 1) * di]
                            for h in range(first, first + count)])


def _index_scores_kernel(n_ref, q_ref, k_ref, w_ref, idx_ref):
    block, tile = idx_ref.shape
    hi, di = w_ref.shape[1], k_ref.shape[1]
    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _scores():
        k, w = k_ref[...], w_ref[...]
        acc = jnp.zeros((block, tile), jnp.float32)
        for first, count in _chunks(hi):
            s = _nt(_heads(q_ref, first, count, di), k)
            r = jnp.maximum(s, 0.0).reshape(count, block, tile)
            acc = acc + (r * _columns(w, first, count)).sum(axis=0)
        idx_ref[...] = acc

    @pl.when(jnp.logical_not(live))
    def _empty():
        idx_ref[...] = jnp.zeros_like(idx_ref)


def _index_grads_kernel(n_ref, q_ref, k_ref, w_ref, d_ref, dq_ref, dk_ref,
                        dw_ref):
    block, tile = d_ref.shape
    hi, di = w_ref.shape[1], k_ref.shape[1]
    j = pl.program_id(0)
    live = j < n_ref[0]

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(live)
    def _grads():
        k, w, d = k_ref[...], w_ref[...], d_ref[...]
        head = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        dk = jnp.zeros(dk_ref.shape, jnp.float32)
        dw = jnp.zeros(w.shape, jnp.float32)
        for first, count in _chunks(hi):
            q = _heads(q_ref, first, count, di)
            s = _nt(q, k).reshape(count, block, tile)
            # d_idx where the score passed the relu: times the weight it is
            # the score's gradient, times the score the weight's
            kept = jnp.where(s > 0, d[None], 0.0)
            g = (kept * _columns(w, first, count)).astype(q.dtype).reshape(
                count * block, tile)
            dq = jnp.dot(g, k, preferred_element_type=jnp.float32)
            for c in range(count):
                dq_ref[:, (first + c) * di:(first + c + 1) * di] += \
                    dq[c * block:(c + 1) * block]
            dk = dk + jax.lax.dot_general(
                q, g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            sums = (kept * s).sum(axis=-1, keepdims=True)   # (count, block, 1)
            for c in range(count):
                dw = dw + jnp.where(head == first + c, sums[c], 0.0)
        dk_ref[...] = dk
        dw_ref[...] += dw

    @pl.when(jnp.logical_not(live))
    def _empty():
        dk_ref[...] = jnp.zeros_like(dk_ref)


def _index_call(kernel, name: str, q, k, w, more, outs, last, interpret):
    """One of the two index kernels over the key tiles of ``k``: ``q``, the
    key tile and ``w`` as operands, then ``more`` ``(block, keys)`` arrays
    a tile at a time; ``outs``: ``(shape, tiled)`` float32 results, whole
    and resident over the tiles, or ``(rows, keys)`` and a tile a step."""
    block, hi = w.shape
    keys, di = k.shape
    tile = index_tile(keys, block, hi, di)
    n_live = (last // tile + 1).astype(jnp.int32).reshape(1)
    # a tile past the last live one stays on it: nothing is fetched
    at = lambda j, n: jnp.minimum(j, n[0] - 1)             # noqa: E731
    vm = pltpu.VMEM

    def out_spec(shape, tiled):
        if tiled:
            return pl.BlockSpec((shape[0], tile), lambda j, n: (0, j),
                                memory_space=vm)
        return pl.BlockSpec(shape, lambda j, n: (0, 0), memory_space=vm)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(keys // tile,),
            in_specs=[
                pl.BlockSpec(q.shape, lambda j, n: (0, 0), memory_space=vm),
                pl.BlockSpec((tile, di), lambda j, n: (at(j, n), 0),
                             memory_space=vm),
                pl.BlockSpec(w.shape, lambda j, n: (0, 0), memory_space=vm),
                *(pl.BlockSpec((block, tile), lambda j, n: (0, at(j, n)),
                               memory_space=vm) for _ in more)],
            out_specs=[out_spec(*o) for o in outs]),
        out_shape=[_out_struct(shape, jnp.float32, q) for shape, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=name,
        interpret=interpret,
    )(n_live, q, k, w, *more)


@partial(jax.jit, static_argnames=("interpret",))
def index_scores(q, k, w, last, *, interpret: bool):
    """-> ``idx`` float32 ``(block, keys)``: ``sum_j w[i, j] relu(q[i, j] .
    k[s])``, 0 in the tiles wholly past ``last``.

    ``q`` ``(block, hi x di)`` (head ``j`` lanes ``j x di`` onward of a row)
    and ``k`` ``(keys, di)`` in the compute dtype; ``w`` float32 ``(block,
    hi)``; ``last``: the position (an int32 scalar, traced) of the block's
    last query among the keys."""
    block, _ = w.shape
    idx, = _index_call(_index_scores_kernel, INDEX_SCORES_KERNEL_NAME, q, k,
                       w, (), [((block, k.shape[0]), True)], last,
                       interpret)
    return idx


@partial(jax.jit, static_argnames=("interpret",))
def index_grads(q, k, w, d_idx, last, *, interpret: bool):
    """-> ``(dq (block, hi x di), dk^T (di, keys), dw (block, hi))`` float32:
    the gradients of ``sum(idx * d_idx)`` (:func:`index_scores`) to its three
    operands, from the scores made again in the tile; ``g = d_idx * w * (s >
    0)`` is rounded to ``q``'s dtype before its two products.  ``dk`` leaves
    keys-minor, 0 in the tiles wholly past ``last``: the layout XLA gives the
    scan's carry that sums it (``(keys, di)`` cost a transposing copy of the
    carry's size a block, 2 ms a layer), and ``q^T g`` transposes the small
    operand where ``g^T q`` transposed the scores' size.  ``d_idx`` float32
    ``(block, keys)``, 0 past ``last``."""
    return tuple(_index_call(
        _index_grads_kernel, INDEX_GRADS_KERNEL_NAME, q, k, w, (d_idx,),
        [(q.shape, False), (k.shape[::-1], True), (w.shape, False)], last,
        interpret))
