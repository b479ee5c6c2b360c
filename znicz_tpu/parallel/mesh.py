"""Device-mesh construction.

Axis conventions (used across the framework; SURVEY.md §3.4 table):

- ``data``  — batch (DP): gradients psum over it;
- ``model`` — weight output-dim (TP): FC layers shard their (in, out)
  weights on out; collectives are all-gathers XLA inserts;
- ``seq``   — sequence/context (SP, ring attention extension).

Multi-host: on a pod slice ``jax.devices()`` already spans hosts after
``jax.distributed.initialize``; the same mesh code covers single-chip,
one-host-8-chip, and multi-host — XLA routes collectives over ICI/DCN from
the mesh topology.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax import lax
from jax.sharding import Mesh


def varying(x, axis_name):
    """Mark ``x`` as varying over ``axis_name`` (shard_map vma typing for
    scan carries)."""
    return lax.pcast(x, axis_name, to="varying")


def make_mesh(axis_sizes: dict[str, int],
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh with the given ``{axis: size}`` (insertion-ordered).
    Total size must equal the device count used."""
    devs = list(devices) if devices is not None else jax.devices()
    n = int(np.prod(list(axis_sizes.values())))
    if n > len(devs):
        raise ValueError(f"mesh wants {n} devices, have {len(devs)}")
    shape = tuple(axis_sizes.values())
    arr = np.array(devs[:n]).reshape(shape)
    return Mesh(arr, tuple(axis_sizes.keys()))


def data_parallel_mesh(n: Optional[int] = None,
                       devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-axis ("data",) mesh over ``n`` devices (default: all)."""
    devs = list(devices) if devices is not None else jax.devices()
    n = n if n is not None else len(devs)
    return make_mesh({"data": n}, devs)


def make_hybrid_mesh(axis_sizes: dict[str, int],
                     dcn_axis_sizes: Optional[dict[str, int]] = None,
                     devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """DCN-aware mesh for multi-slice pods (SURVEY.md §6.8: "DCN-aware
    mesh axes for multi-slice").

    ``axis_sizes`` is the TOTAL per-axis size; ``dcn_axis_sizes`` says how
    much of each axis spans slices over the data-center network (default
    1 per axis = everything intra-slice).  Bandwidth rule: only axes whose
    collectives are one gradient psum per step (``data``, or ``pipe``'s
    point-to-point transfers) should span DCN; keep ``model``/``seq``
    (per-layer all-gathers) on ICI.

    On a runtime that reports slice topology (``device.slice_index``,
    real multi-slice pods) the assignment delegates to
    ``jax.experimental.mesh_utils.create_hybrid_device_mesh`` so
    inner-axis neighbors are ICI neighbors; single-slice/CPU platforms
    degrade to the plain ordered mesh (same axis names and sizes, so the
    sharded program is identical — only the physical routing differs).
    """
    dcn = {k: 1 for k in axis_sizes}
    dcn.update(dcn_axis_sizes or {})
    unknown = set(dcn) - set(axis_sizes)
    if unknown:
        raise ValueError(f"dcn axes {sorted(unknown)} not in axis_sizes")
    for name, total in axis_sizes.items():
        if total % dcn[name]:
            raise ValueError(f"axis {name!r}: dcn size {dcn[name]} must "
                             f"divide total {total}")
    devs = list(devices) if devices is not None else jax.devices()
    n_slices = len({getattr(d, "slice_index", 0) for d in devs})
    n_dcn = int(np.prod(list(dcn.values())))
    if n_slices > 1 and n_dcn > 1:
        if n_dcn > n_slices:
            raise ValueError(f"dcn axes span {n_dcn} slices, runtime "
                             f"reports only {n_slices}")
        from jax.experimental import mesh_utils
        ici_shape = tuple(axis_sizes[k] // dcn[k] for k in axis_sizes)
        ici_n = int(np.prod(ici_shape))
        # surplus tolerance mirroring the single-slice make_mesh path:
        # the first n_dcn slices, and the first ici_n devices OF EACH
        # (create_hybrid_device_mesh demands exact per-granule counts)
        by_slice: dict[int, list] = {}
        for d in devs:
            by_slice.setdefault(getattr(d, "slice_index", 0), []).append(d)
        trimmed = []
        for sid in sorted(by_slice)[:n_dcn]:
            if len(by_slice[sid]) < ici_n:
                raise ValueError(
                    f"slice {sid} has {len(by_slice[sid])} devices, mesh "
                    f"wants {ici_n} per slice")
            trimmed += by_slice[sid][:ici_n]
        arr = mesh_utils.create_hybrid_device_mesh(
            ici_shape, tuple(dcn[k] for k in axis_sizes), devices=trimmed)
        return Mesh(arr, tuple(axis_sizes.keys()))
    if n_slices > 1:
        # n_dcn == 1 means "everything intra-slice": honor it by building
        # from one slice when it holds enough devices (devs[:n] could
        # otherwise silently straddle the DCN boundary)
        total = int(np.prod(list(axis_sizes.values())))
        by_slice = {}
        for d in devs:
            by_slice.setdefault(getattr(d, "slice_index", 0), []).append(d)
        for sid in sorted(by_slice):
            if len(by_slice[sid]) >= total:
                return make_mesh(axis_sizes, by_slice[sid])
        raise ValueError(
            f"no single slice holds the {total} devices this mesh wants "
            f"(largest has {max(len(v) for v in by_slice.values())}); "
            f"give the slice-spanning axis a dcn_axis_sizes entry")
    return make_mesh(axis_sizes, devs)
