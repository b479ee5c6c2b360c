"""Blocked MXU GEMM with fused bias + activation — the rebuild of the
reference's shared tiled-GEMM include (matrix_multiplication.{cl,cu},
SURVEY.md §3.2: "#include'd by all2all + gd + conv kernels") and the FC
forward/backward kernels built on it (all2all/forward.*,
gradient_descent/err_h_update + weights_update + bias_update).

Classic revisited-accumulator blocking: grid (m, n, k) with the
contraction innermost, one f32 VMEM accumulator per (m, n) tile, bias
add + activation fused into the final k step (the reference fuses them
into the same kernel).  Inputs are zero-padded to block multiples
outside the kernel (the forward conv kernel's jnp.pad discipline) and
the output sliced back.

Policy note (ops/pallas/__init__.py): XLA's native dot is the default
everywhere; these are the selectable parity path
(``root.common.engine.pallas``) and the tier-1 cross-check target.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops import activations

#: activations the fused kernel applies in-block (the reference macro
#: set; the exotic standalone-unit extras stay on the XLA path)
FUSED_ACTIVATIONS = (activations.LINEAR, activations.TANH,
                     activations.RELU, activations.STRICT_RELU,
                     activations.SIGMOID)


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m


def _matmul_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *,
                   n_k: int, activation: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _emit():
        acc = acc_ref[...] + b_ref[...]
        o_ref[...] = activations.forward(
            jnp, activation, acc).astype(o_ref.dtype)


def matmul(x, w, bias=None, activation: str = activations.LINEAR, *,
           interpret: bool = False):
    """``act(x @ w + bias)`` on (M, K) x (K, N) operands."""
    if activation not in FUSED_ACTIVATIONS:
        raise ValueError(f"activation {activation!r} is not in the fused "
                         f"kernel set {FUSED_ACTIVATIONS}")
    M, K = x.shape
    _, N = w.shape
    bm = min(512, _rup(M, 8))
    bn = min(512, _rup(N, 128))
    bk = min(512, _rup(K, 128))
    Mp, Np, Kp = _rup(M, bm), _rup(N, bn), _rup(K, bk)
    xp_ = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    b = jnp.zeros((N,), x.dtype) if bias is None else bias
    bp = jnp.pad(b, (0, Np - N)).reshape(1, Np)
    n_k = Kp // bk
    out = pl.pallas_call(
        partial(_matmul_kernel, n_k=n_k, activation=activation),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="fc_fwd", interpret=interpret,
    )(xp_, wp, bp)
    return out[:M, :N]


def _act_bwd_kernel(y_ref, e_ref, o_ref, *, activation: str):
    o_ref[...] = activations.backward(jnp, activation, y_ref[...],
                                      e_ref[...]).astype(o_ref.dtype)


def _act_backward(y, err, activation: str, *, interpret: bool):
    """err_v = err * act'(y), one elementwise pass (the start of the
    reference's err_h_update kernel), row-tiled so wide layers stay
    inside VMEM (a whole-array block would ask for M*N*4 bytes x 3
    buffers at once)."""
    if activation == activations.LINEAR:
        return err
    M, N = y.shape
    Mp, Np = _rup(M, 8), _rup(N, 128)
    bm = Mp
    while bm > 8 and bm * Np * 4 * 3 > 12 * 1024 * 1024:
        bm //= 2
    bm = _rup(bm, 8)
    Mp = _rup(Mp, bm)
    yp = jnp.pad(y, ((0, Mp - M), (0, Np - N)))
    ep = jnp.pad(err, ((0, Mp - M), (0, Np - N)))
    spec = pl.BlockSpec((bm, Np), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        partial(_act_bwd_kernel, activation=activation),
        grid=(Mp // bm,),
        in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), err.dtype),
        name="activation_bwd", interpret=interpret,
    )(yp, ep)
    return out[:M, :N]


def fc_forward(x, w, bias=None, activation: str = activations.LINEAR, *,
               interpret: bool = False):
    """All2All forward: flatten-batch GEMM + fused bias/activation
    (semantics of ops.linear.forward)."""
    return matmul(x.reshape(x.shape[0], -1), w, bias, activation,
                  interpret=interpret)


def fc_backward(x, y, w, err_output,
                activation: str = activations.LINEAR,
                activation_applied: bool = True, *,
                interpret: bool = False):
    """All2All backward: ``(err_input, grad_w, grad_b)`` with gradients
    summed over the batch (semantics of ops.linear.backward) — the
    reference's err_h_update / weights_update / bias_update trio as
    three blocked GEMMs over the same kernel."""
    x_flat = x.reshape(x.shape[0], -1)
    if activation_applied:
        err_v = _act_backward(y.reshape(y.shape[0], -1),
                              err_output.reshape(err_output.shape[0], -1),
                              activation, interpret=interpret)
    else:
        err_v = err_output.reshape(err_output.shape[0], -1)
    err_input = matmul(err_v, w.T, interpret=interpret).reshape(x.shape)
    grad_w = matmul(x_flat.T, err_v, interpret=interpret)
    grad_b = err_v.sum(axis=0)
    return err_input, grad_w, grad_b
