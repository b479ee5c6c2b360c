"""Hand-written conv backward + deconv Pallas kernels — the col2im
overlap-scatter family SURVEY.md §3.2 calls "the trickiest kernels in the
repo" (reference: gradient_descent_conv/*.{cl,cu}, deconv.{cl,cu},
gradient_descent_deconv/*.{cl,cu}).

TPU-first design: the reference's atomic scatter col2im does not map to
the MXU, so the adjoint is re-expressed as a *gather* — the cotangent is
interior-dilated by the stride and framed by ``k-1`` zeros (one
``lax.pad`` outside the kernel, exactly like the forward kernel's
``jnp.pad``), after which every input-gradient pixel is a stride-1 tap
correlation: ``ei[p, :] += dp[p + tap, :] @ w_flip[tap]`` — one MXU GEMM
per kernel-window tap, f32 accumulation, no atomics, no scatter.  The
weight gradient reuses the forward's strided-tap trick with the GEMM
transposed (``gw[tap] += x[tap-slice]ᵀ @ e``), accumulated across the
batch grid via the revisited-output pattern.

The same two kernels serve the deconv pair: deconv *forward* is the conv
input-gradient with data in place of the cotangent; deconv err_input is
the plain forward conv (ops.pallas.conv); deconv grad_w is the grad
kernel with input/error roles swapped (reference: gd_deconv.py).

Policy note (ops/pallas/__init__.py): XLA's fused vjp conv pair is the
default everywhere; these are the selectable parity path
(``root.common.engine.pallas``) and the tier-1 cross-check target.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.conv import normalize_geometry, out_size
from znicz_tpu.ops.pallas.conv import (load_planes, phase_split,
                                       tap_slice)


def _adjoint_kernel(dp_ref, wf_ref, out_ref, *, ky, kx, hp, wp):
    """Stride-1 tap correlation over the dilated+framed cotangent:
    ``out[p, :] = sum_tap dp[p + tap, :] @ wf[tap]``."""
    dp = dp_ref[0]                                 # (hp+ky-1, wp+kx-1, B)
    nb = dp.shape[-1]
    na = wf_ref.shape[-1]
    acc = jnp.zeros((hp * wp, na), jnp.float32)
    for jy in range(ky):
        for jx in range(kx):
            tap = lax.slice(dp, (jy, jx, 0), (jy + hp, jx + wp, nb))
            acc += jnp.dot(tap.reshape(hp * wp, nb), wf_ref[jy, jx],
                           preferred_element_type=jnp.float32)
    out_ref[0] = acc.reshape(hp, wp, na).astype(out_ref.dtype)


def _grad_kernel(xph_ref, e_ref, gw_ref, gb_ref, *,
                 ky, kx, sy, sx, oh, ow):
    """Per-tap transposed GEMM ``gw[tap] += xtapᵀ @ e``, f32-accumulated
    across the batch grid (outputs are revisited every step).  Taps come
    from the phase-split input (see ops.pallas.conv) — Mosaic cannot
    lower strided in-kernel slices."""
    i = pl.program_id(0)
    cin = xph_ref.shape[-1]
    cout = e_ref.shape[-1]
    e = e_ref[0].reshape(oh * ow, cout)

    @pl.when(i == 0)
    def _init():
        gw_ref[...] = jnp.zeros_like(gw_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    planes = load_planes(xph_ref, sy, sx)
    for iy in range(ky):
        for ix in range(kx):
            tap = tap_slice(planes, iy, ix, sy, sx, oh, ow)
            gw_ref[iy, ix] += lax.dot_general(
                tap.reshape(oh * ow, cin), e, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    gb_ref[0, :] += e.astype(jnp.float32).sum(axis=0)


def _dilate_and_frame(e, ky, kx, sy, sx, hp, wp):
    """lax.pad with interior = stride-1 dilation + ``k-1`` frame (+ slack
    rows the window never covered; negative when out_shape crops)."""
    n, oh, ow, c = e.shape
    ry = hp - ((oh - 1) * sy + ky)
    rx = wp - ((ow - 1) * sx + kx)
    return lax.pad(e, jnp.zeros((), e.dtype),
                   ((0, 0, 0), (ky - 1, ky - 1 + ry, sy - 1),
                    (kx - 1, kx - 1 + rx, sx - 1), (0, 0, 0)))


def _adjoint_call(dp, wf, hp, wp, ky, kx, out_dtype, interpret):
    n = dp.shape[0]
    na = wf.shape[-1]
    kern = partial(_adjoint_kernel, ky=ky, kx=kx, hp=hp, wp=wp)
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1,) + dp.shape[1:], lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, hp, wp, na), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, hp, wp, na), out_dtype),
        name="conv_bwd_input", interpret=interpret,
    )(dp, wf)


def _grad_call(xpad, e, ky, kx, sy, sx, oh, ow, interpret):
    xph = phase_split(xpad, sy, sx)
    n, _, _, hq, wq, cin = xph.shape
    cout = e.shape[-1]
    kern = partial(_grad_kernel, ky=ky, kx=kx, sy=sy, sx=sx, oh=oh, ow=ow)
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, sy, sx, hq, wq, cin),
                         lambda i: (i, 0, 0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, oh, ow, cout), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((ky, kx, cin, cout), lambda i: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ky, kx, cin, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        name="conv_bwd_weights", interpret=interpret,
    )(xph, e)


def conv2d_backward(x, weights, err_v, sliding=(1, 1),
                    padding=(0, 0, 0, 0), *, interpret: bool = False):
    """Linear-conv backward: ``(err_input, grad_w, grad_b)`` for NHWC x,
    HWIO weights and the activation-corrected cotangent ``err_v`` —
    identical semantics to the linear part of ops.conv.backward."""
    ky, kx = weights.shape[0], weights.shape[1]
    ky, kx, sy, sx, pt, pb, pl_, pr = normalize_geometry(
        kx, ky, sliding, padding)
    n, h, w, cin = x.shape
    oh = out_size(h, ky, sy, pt, pb)
    ow = out_size(w, kx, sx, pl_, pr)
    hp, wp = h + pt + pb, w + pl_ + pr
    dp = _dilate_and_frame(err_v, ky, kx, sy, sx, hp, wp)
    wf = weights[::-1, ::-1].transpose(0, 1, 3, 2)  # (ky, kx, cout, cin)
    ei_pad = _adjoint_call(dp, wf, hp, wp, ky, kx, x.dtype, interpret)
    err_input = ei_pad[:, pt:pt + h, pl_:pl_ + w, :]
    xpad = jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    gw, gb = _grad_call(xpad, err_v, ky, kx, sy, sx, oh, ow, interpret)
    return (err_input, gw.astype(weights.dtype),
            gb.reshape(-1).astype(err_v.dtype))


def deconv2d(x, weights, sliding, padding, out_shape, *,
             interpret: bool = False):
    """Transposed conv: ``(n, oh, ow, nk)`` x, HWIO ``(ky, kx, c, nk)``
    weights -> ``out_shape`` ``(n, h, w, c)`` — semantics of
    ops.deconv.forward (the conv input-gradient with data as cotangent)."""
    ky, kx, c, nk = weights.shape
    ky, kx, sy, sx, pt, pb, pl_, pr = normalize_geometry(
        kx, ky, sliding, padding)
    h, w_out = out_shape[1], out_shape[2]
    hp, wp = h + pt + pb, w_out + pl_ + pr
    dp = _dilate_and_frame(x, ky, kx, sy, sx, hp, wp)
    wf = weights[::-1, ::-1].transpose(0, 1, 3, 2)  # (ky, kx, nk, c)
    out_pad = _adjoint_call(dp, wf, hp, wp, ky, kx, x.dtype, interpret)
    return out_pad[:, pt:pt + h, pl_:pl_ + w_out, :]


def deconv2d_backward(x, weights, err_output, sliding=(1, 1),
                      padding=(0, 0, 0, 0), *, interpret: bool = False):
    """``(err_input, grad_w)`` for the deconv pair: err_input is the
    plain forward conv of err_output (adjoint of the adjoint — reuses the
    forward im2col kernel); grad_w is the grad kernel with input/error
    roles swapped (ops.deconv.backward semantics)."""
    from znicz_tpu.ops.pallas.conv import conv2d_im2col

    ky, kx, c, nk = weights.shape
    ky, kx, sy, sx, pt, pb, pl_, pr = normalize_geometry(
        kx, ky, sliding, padding)
    err_input = conv2d_im2col(err_output, weights, None, (sy, sx),
                              (pt, pb, pl_, pr), interpret=interpret)
    n, oh, ow, _ = x.shape
    epad = jnp.pad(err_output, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    gw, _ = _grad_call(epad, x, ky, kx, sy, sx, oh, ow, interpret)
    return err_input, gw.astype(weights.dtype)
