"""Implicit-im2col convolution as a Pallas kernel — the hand-written GEMM
conv of SURVEY.md §8 step 3 ("hand-written kernel parity"), rebuilding the
reference's conv/forward.{cl,cu} shared-memory im2col GEMM.

One grid step per image: the padded input tile sits in VMEM and the
kernel-window loop issues one MXU GEMM per (ky, kx) tap —
``y[p, :] += x[p*s + tap, :] @ w[tap]`` — accumulating in f32.  The patch
tensor the reference materializes in shared memory never exists: the
window taps are stride-1 VMEM slices (implicit im2col).

Strides are handled by PHASE DECOMPOSITION outside the kernel: Mosaic
cannot lower strided vector extracts (`vector.extract_strided_slice`
verification error on hardware), so the padded input is split into
``sy*sx`` stride-1 phase planes (one XLA reshape+transpose,
space-to-depth style) and the tap for window offset ``(iy, ix)`` reads
phase ``(iy%sy, ix%sx)`` at stride-1 offset ``(iy//sy, ix//sx)`` — same
bytes, same FLOPs, and the kernel only ever slices with unit stride.
At stride 1 the decomposition is the identity.

Policy note (ops/pallas/__init__.py): XLA's native conv is the default
everywhere; this kernel is the selectable parity path
(``root.common.engine.pallas``) and the tier-1 cross-check target.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.conv import normalize_geometry, out_size


def phase_split(xpad, sy: int, sx: int):
    """``(n, hp, wp, c) -> (n, sy, sx, hq, wq, c)`` stride-1 phase planes
    (``hq = ceil(hp/sy)``, zero-padded): plane ``(py, px)`` holds rows
    ``py::sy`` and cols ``px::sx``.  Conv geometry guarantees in-kernel
    taps never reach the ceil padding."""
    n, hp, wp, c = xpad.shape
    hq, wq = -(-hp // sy), -(-wp // sx)
    xpad = jnp.pad(xpad, ((0, 0), (0, hq * sy - hp),
                          (0, wq * sx - wp), (0, 0)))
    return (xpad.reshape(n, hq, sy, wq, sx, c)
            .transpose(0, 2, 4, 1, 3, 5))


def load_planes(xph_ref, sy: int, sx: int):
    """Load each ``(hq, wq, cin)`` phase plane from the block ref ONCE
    (the tap loop would otherwise re-issue a whole-plane load per tap)."""
    return [[xph_ref[0, py, px] for px in range(sx)] for py in range(sy)]


def tap_slice(planes, iy: int, ix: int, sy: int, sx: int,
              oh: int, ow: int):
    """Stride-1 tap for window offset ``(iy, ix)`` from loaded phase
    planes -> ``(oh, ow, cin)``."""
    plane = planes[iy % sy][ix % sx]                # (hq, wq, cin)
    cin = plane.shape[-1]
    return jax.lax.slice(plane, (iy // sy, ix // sx, 0),
                         (iy // sy + oh, ix // sx + ow, cin))


def _kernel(xph_ref, w_ref, b_ref, y_ref, *, ky, kx, sy, sx, oh, ow):
    cin = xph_ref.shape[-1]
    cout = w_ref.shape[-1]
    planes = load_planes(xph_ref, sy, sx)
    acc = jnp.zeros((oh * ow, cout), jnp.float32)
    for iy in range(ky):
        for ix in range(kx):
            tap = tap_slice(planes, iy, ix, sy, sx, oh, ow)
            acc += jnp.dot(tap.reshape(oh * ow, cin), w_ref[iy, ix],
                           preferred_element_type=jnp.float32)
    acc += b_ref[:]
    y_ref[0] = acc.reshape(oh, ow, cout).astype(y_ref.dtype)


def conv2d_im2col(x, weights, bias, sliding=(1, 1), padding=(0, 0, 0, 0),
                  *, interpret: bool = False):
    """NHWC x * HWIO weights (+ bias) — pre-activation conv, identical
    geometry semantics to ops.conv.forward_linear."""
    ky, kx = weights.shape[0], weights.shape[1]
    ky, kx, sy, sx, pt, pb, pl_, pr = normalize_geometry(
        kx, ky, sliding, padding)
    n, h, w, cin = x.shape
    oh = out_size(h, ky, sy, pt, pb)
    ow = out_size(w, kx, sx, pl_, pr)
    cout = weights.shape[3]
    xpad = jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    xph = phase_split(xpad, sy, sx)
    _, _, _, hq, wq, _ = xph.shape
    if bias is None:
        bias = jnp.zeros((cout,), x.dtype)
    kern = partial(_kernel, ky=ky, kx=kx, sy=sy, sx=sx, oh=oh, ow=ow)
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, sy, sx, hq, wq, cin),
                         lambda i: (i, 0, 0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, cout), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, cout), x.dtype),
        name="conv_fwd", interpret=interpret,
    )(xph, weights, bias)
