"""Shared scaffolding for fused elementwise optimizer kernels (SGD,
AdamW): 2-D view, row tiling under a VMEM budget, SMEM hyperparameter
pack, vma-aware out specs, and in-place aliasing.

Returns ``None`` when no legal tile fits VMEM (a flat-sharded ZeRO leaf
is one long row) — the caller falls back to its jnp implementation, and
:func:`tiled_update` names the leaf shape in a warning so the hand-over
is never silent."""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_log = logging.getLogger("znicz_tpu.ops.pallas")

#: VMEM working-set budget (bytes) for the in+out tiles, DOUBLE-buffered
#: as the Pallas pipeline allocates them — under Mosaic's 16 MiB default
#: scoped-VMEM limit on v5e
VMEM_BUDGET = 12 * 1024 * 1024


def out_struct(shape, dtype, like):
    """ShapeDtypeStruct inheriting ``like``'s varying-mesh-axes: under
    shard_map with vma checking, pallas_call outputs must declare which
    mesh axes they vary over — same set as the operands (an EMPTY
    frozenset means replicated and is still required).  THE one copy of
    this policy (used by the optimizer kernels here and the
    flash-attention kernel)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _pick_tile(rows: int, cols: int, n_buffers: int,
               min_tile: int = 8) -> int:
    """Largest workable row tile: whole-array when it fits (one grid
    step), else the biggest power-of-two divisor of ``rows`` that fits,
    else 0 (= no tile fits; caller must fall back).  ``min_tile`` is
    Mosaic's sublane tiling: a block that does not span the whole array
    needs (8, 128)-divisible rows for 32-bit refs, (16, 128) for
    16-bit."""
    def fits(t: int) -> bool:
        return 2 * t * cols * 4 * n_buffers <= VMEM_BUDGET

    if fits(rows):
        return rows
    for t in (512, 256, 128, 64, 32, 16, 8):
        if t >= min_tile and rows % t == 0 and fits(t):
            return t
    return 0


def tiled_update(kernel, hyper_scalars, arrays, aliases: dict,
                 n_out: int, *, name: str, interpret: bool = False):
    """Run ``kernel(h_ref, *in_refs, *out_refs)`` tiled over same-shaped
    ``arrays`` (first array defines shape/dtype).  ``aliases`` maps
    operand index (1-based: 0 is the SMEM hyper pack) -> output index for
    in-place updates.  Returns a tuple of ``n_out`` arrays reshaped to
    the input shape, or ``None`` if no tile fits VMEM — after a warning
    that names ``name`` and the leaf shape (once per trace)."""
    orig_shape = arrays[0].shape
    a2 = [a.reshape(-1, orig_shape[-1]) if a.ndim != 2 else a
          for a in arrays]
    rows, cols = a2[0].shape
    # 16-bit buffers (narrow optimizer state) tile at (16, 128) sublanes
    min_tile = 16 if any(jnp.dtype(a.dtype).itemsize < 4 for a in a2) \
        else 8
    tile = _pick_tile(rows, cols, len(arrays) + n_out, min_tile)
    if tile == 0:
        _log.warning(
            "engine.pallas: no VMEM tile fits the fused %s update of a "
            "%s leaf (2-D view %dx%d); this leaf takes the XLA reference "
            "update", name, tuple(orig_shape), rows, cols)
        return None
    hyper = jnp.stack([jnp.asarray(h, jnp.float32)
                       for h in hyper_scalars])
    spec = pl.BlockSpec((tile, cols), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    # each output inherits shape/dtype/vma from the operand it aliases
    # (narrow velocity stays narrow); non-aliased outputs mirror arrays[0]
    src = {out_i: a2[in_i - 1] for in_i, out_i in aliases.items()}
    outs = tuple(
        out_struct(a2[0].shape, src.get(i, a2[0]).dtype,
                   src.get(i, a2[0]))
        for i in range(n_out))
    results = pl.pallas_call(
        kernel,
        grid=(rows // tile,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] +
                 [spec] * len(arrays),
        out_specs=(spec,) * n_out,
        out_shape=outs,
        input_output_aliases=dict(aliases),
        name=f"fused_{name}_update",
        interpret=interpret,
    )(hyper, *a2)
    if n_out == 1:
        results = (results,)
    return tuple(r.reshape(orig_shape) for r in results)
