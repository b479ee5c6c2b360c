"""The target of learned sparse attention's alignment loss as a Pallas
kernel: for one block of queries, the mean over the attention heads of each
head's softmax over the query's SELECTED keys (``parallel/dsa.py`` has the
loss, the selection and the scan that calls this a block at a time).

The blocked ``jax.numpy`` form makes a key/value head's ``(block x group,
keys)`` scores as one product and XLA sends them through HBM three times
(row maximum, ``exp`` and row sum, the normalised sum over the group).
Here they never leave VMEM.  One kernel, :data:`ALIGN_KERNEL_NAME`, walks the
key tiles twice, as a grid of ``(2 sweeps, key tiles)``:

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
rounds them to the compute dtype on their way to HBM).  A key tile wholly
above the block's last query holds no selected pair: the number of tiles at
or under the diagonal comes as a prefetched scalar, the tiles past it are
neither fetched nor computed (their block indices stay where they were) and
their output is written as zeros.

Layouts.  ``q`` comes head-major, ``(kv, group x block, dh)`` (row ``h x
block + i`` of a key/value head's slab is query ``i`` of its ``h``-th query
head), so a key/value head's scores are one product with 8 x 128 rows and
the selection's tile masks them as ``(group, block, tile)`` by broadcast;
``k`` comes as the layer has it, ``(keys, kv x dh)``, and a key/value head is
a slice of ``dh`` lanes: hence a head width that is a multiple of 128.
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
#: keys a tile may hold, largest first (:func:`align_tile` chooses)
_TILES = (1024, 512, 256, 128)
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


def align_tile(keys: int, block: int, heads: int, kv: int, dh: int) -> int:
    """Keys a tile holds: the largest of :data:`_TILES` that divides
    ``keys`` and whose working set fits :data:`_VMEM_LIMIT`; 0 when none
    does."""
    return next((t for t in _TILES if keys % t == 0 and
                 _align_vmem(t, block, heads, kv, dh) <= _VMEM_LIMIT), 0)


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
    least = _TILES[-1]
    if keys % least:
        return f"key extents of {keys} are no multiple of the {least}-key tile"
    if not align_tile(keys, block, heads, kv, dh):
        need = _align_vmem(least, block, heads, kv, dh)
        return (f"{heads} heads of {dh}: a {least}-key tile needs "
                f"{need >> 20} MiB of the kernel's {_VMEM_LIMIT >> 20} MiB "
                f"of VMEM")
    return None


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
