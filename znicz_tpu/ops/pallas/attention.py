"""Flash attention as a Pallas kernel — the hot-op kernel for the
long-context stack (TPU-native extension; the reference predates
transformers, SURVEY.md §6.7, but its identity — a hand-written kernel
for every op family's hot path — is matched here for attention).

Row-block formulation: the grid walks ``(batch*heads, q_blocks)``; each
step holds one q block plus the full K/V for that head in VMEM and
computes its softmax row exactly — the ``(t, t)`` score matrix never
touches HBM (XLA's dense path materializes it twice per layer per step:
~1 GB/layer at b=8, h=8, t=2048, f32).  The saved residual is the
logsumexp row ``lse`` (one f32 per query), from which the backward kernel
reconstructs the probabilities: ``p = exp(s·scale - lse)``.

VMEM budget per grid step is O(block_q·t + t·dh) ≈ 1.5 MB at t=2048 —
fine through t≈8k.  Beyond that the sequence axis should be sharded (ring
attention, znicz_tpu/parallel/ring_attention.py); the two compose: the
ring rotates K/V blocks over ICI while each local block uses dense math,
so per-shard t stays in this kernel's range.

Backward follows the standard flash recipe in one grid pass: dq per
q block; dk/dv accumulated across q blocks into a revisited output block
(Pallas TPU grids execute sequentially, so accumulation over the minor
grid axis is sound).

Grouped-query attention rides the index maps: with ``group`` query heads
to each key/value head the folded q is ``(b*h, t, dh)`` and k, v are
``(b*h/group, t, dh)``; grid row ``i`` reads key/value row ``i // group``
(the query heads of one group are consecutive rows), so a key/value head
is fetched once for its whole group, and the backward kernel's dk/dv
block stays resident over the group's ``group * t/block_q`` consecutive
steps and sums over them.  ``group == 1`` is the program it always was.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: the kernels' names in the lowered program and in device traces — what
#: chip_smoke.py looks for to prove the step compiled them
FWD_KERNEL_NAME = "flash_attention_fwd"
BWD_KERNEL_NAME = "flash_attention_bwd"


def _mask_scores(s, causal: bool, iq: int, block_q: int):
    """Apply the causal mask to one q block's score rows ``(bq, t)``."""
    if not causal:
        return s
    bq, t = s.shape
    qpos = jax.lax.broadcasted_iota(jnp.int32, (bq, t), 0) + iq * block_q
    kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, t), 1)
    return jnp.where(kpos > qpos, jnp.float32(-1e30), s)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                sm_scale: float, block_q: int):
    iq = pl.program_id(1)
    q = q_ref[0]                                       # (bq, dh)
    k = k_ref[0]                                       # (t, dh)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = _mask_scores(s, causal, iq, block_q)
    m = s.max(axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=1, keepdims=True)
    # p rides the MXU at the input dtype (bf16 in production); the
    # accumulator and the 1/l normalization stay f32
    o = jnp.dot(p.astype(v.dtype), v,
                preferred_element_type=jnp.float32) / l
    o_ref[0] = o.astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                        # (bq, 1)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *, causal: bool, sm_scale: float,
                block_q: int, group: int = 1):
    iq = pl.program_id(1)
    first = iq == 0
    if group > 1:
        # dk/dv sum over the group's query heads as well as over q blocks
        first = first & (pl.program_id(0) % group == 0)

    @pl.when(first)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    q = q_ref[0]                                       # (bq, dh)
    k = k_ref[0]                                       # (t, dh)
    v = v_ref[0]
    lse = lse_ref[0]                                   # (bq, 1)
    delta = delta_ref[0]                               # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = _mask_scores(s, causal, iq, block_q)
    p = jnp.exp(s - lse)                               # (bq, t)
    # dv += pᵀ @ do (p cast to the MXU input dtype; accumulate f32)
    dv_ref[0] += jax.lax.dot_general(
        p.astype(v.dtype), do_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    # ds = p ⊙ (do @ vᵀ − Δ), already includes the softmax jacobian
    dp = jax.lax.dot_general(do_ref[0], v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * sm_scale
    dsc = ds.astype(q.dtype)
    dq_ref[0] = jnp.dot(dsc, k,
                        preferred_element_type=jnp.float32
                        ).astype(dq_ref.dtype)
    # dk += dsᵀ @ q
    dk_ref[0] += jax.lax.dot_general(
        dsc, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _pick_block_q(t: int) -> int:
    # 128 rows already fill the MXU's systolic dimension; larger q blocks
    # only grow the (block_q, t) score temporaries that dominate the
    # BACKWARD kernel's VMEM working set
    return 128 if t % 128 == 0 else 0


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal: bool, interpret: bool):
    o, _ = _flash_fwd(q, k, v, causal, interpret)
    return o


from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct


def _group_of(q, k) -> int:
    """Query heads to each key/value head of folded ``q`` and ``k``."""
    if q.shape[0] % k.shape[0]:
        raise ValueError(f"{q.shape[0]} folded query heads do not divide "
                         f"over {k.shape[0]} key/value heads")
    return q.shape[0] // k.shape[0]


def _kv_block(t: int, dh: int, group: int):
    """BlockSpec of one whole key/value head, row ``i // group``."""
    index = (lambda i, j: (i, 0, 0)) if group == 1 else \
        (lambda i, j: (i // group, 0, 0))
    return pl.BlockSpec((1, t, dh), index, memory_space=pltpu.VMEM)


def _call_fwd(q, k, v, causal, interpret):
    bh, t, dh = q.shape
    block_q = _pick_block_q(t)
    group = _group_of(q, k)
    kern = partial(_fwd_kernel, causal=causal,
                   sm_scale=1.0 / float(np.sqrt(dh)), block_q=block_q)
    kv = partial(_kv_block, t, dh, group)
    qspec = pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        grid=(bh, t // block_q),
        in_specs=[qspec, kv(), kv()],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            # lse rides as (bh, t, 1): a 2-D (1, block_q) block is not a
            # legal Mosaic tile (penultimate dim 1 is neither 8-divisible
            # nor the full bh axis) — the trailing singleton makes the
            # last-two block dims (block_q, 1) == (8k-divisible, full dim)
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct((bh, t, dh), q.dtype, q),
            _out_struct((bh, t, 1), jnp.float32, q),
        ],
        name=FWD_KERNEL_NAME,
        interpret=interpret,
    )(q, k, v)


def _flash_fwd(q, k, v, causal, interpret):
    o, lse = _call_fwd(q, k, v, causal, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, interpret, res, do, dlse=None):
    q, k, v, o, lse = res
    bh, t, dh = q.shape
    block_q = _pick_block_q(t)
    group = _group_of(q, k)
    # Δ = rowsum(do ⊙ o) — the lse-side term of the softmax jacobian;
    # shaped (bh, t, 1) like lse for the same Mosaic-tiling reason.
    # When the caller also differentiates through lse (the ring×flash
    # merge), its cotangent folds into the SAME kernel:
    #   ds = p·(dp − Δ)·scale  and  ∂lse/∂s = p·scale
    #   ⇒ ds_total = p·(dp − (Δ − dlse))·scale
    # so Δ' = Δ − dlse and the backward kernel is reused unchanged.
    delta = (do.astype(jnp.float32) *
             o.astype(jnp.float32)).sum(-1, keepdims=True)
    if dlse is not None:
        delta = delta - dlse
    kern = partial(_bwd_kernel, causal=causal,
                   sm_scale=1.0 / float(np.sqrt(dh)), block_q=block_q,
                   group=group)
    kv = partial(_kv_block, t, dh, group)
    qblk3 = lambda: pl.BlockSpec((1, block_q, dh),     # noqa: E731
                                 lambda i, j: (i, j, 0),
                                 memory_space=pltpu.VMEM)
    qblk2 = lambda: pl.BlockSpec((1, block_q, 1),      # noqa: E731
                                 lambda i, j: (i, j, 0),
                                 memory_space=pltpu.VMEM)
    dq, dk, dv = pl.pallas_call(
        kern,
        grid=(bh, t // block_q),
        in_specs=[qblk3(), kv(), kv(), qblk3(), qblk2(), qblk2()],
        # dk/dv revisit the same (bh)-indexed block across the q axis —
        # sequential grid makes the += accumulation exact
        out_specs=[qblk3(), kv(), kv()],
        out_shape=[
            _out_struct((bh, t, dh), q.dtype, q),
            _out_struct(k.shape, jnp.float32, q),
            _out_struct(k.shape, jnp.float32, q),
        ],
        name=BWD_KERNEL_NAME,
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_lse(q, k, v, causal: bool = False,
                        interpret: bool = False):
    """Flash attention over FOLDED per-head tensors ``(b·h, t, dh)``
    returning ``(o, lse)`` with BOTH outputs differentiable — the
    building block for blockwise composition (ring attention merges
    per-block results by lse weight, so lse carries real cotangents).
    Same kernels as :func:`flash_attention`; the lse cotangent folds
    into the backward's Δ term (see :func:`_flash_bwd`)."""
    return _call_fwd(q, k, v, causal, interpret)


def _flash_lse_fwd(q, k, v, causal, interpret):
    o, lse = _call_fwd(q, k, v, causal, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, interpret, res, cts):
    do, dlse = cts
    return _flash_bwd(causal, interpret, res, do, dlse)


flash_attention_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def unsupported_reason(t: int, dh: int) -> str | None:
    """Why this kernel cannot take the shape, or ``None`` when it can:
    q-blockable time axis, lane-sized head dim, and a VMEM budget that
    must cover the BACKWARD kernel (the one actually run under
    value_and_grad): full K/V plus f32 dk/dv accumulator blocks plus the
    three (block_q, t) f32 score temporaries (p, dp, ds)."""
    bq = _pick_block_q(t)
    if bq == 0:
        return f"t={t} is not a multiple of the 128-row q block"
    if dh % 64 != 0:
        return f"head_dim={dh} is not a multiple of 64"
    vmem = 4 * t * dh * 4 + 3 * bq * t * 4
    if vmem > 10 * 1024 * 1024:
        return (f"full K/V at t={t}, head_dim={dh} needs {vmem >> 20} MiB "
                f"of VMEM in the backward kernel (budget 10 MiB); shard "
                f"the seq axis")
    return None


def supported(t: int, dh: int) -> bool:
    """Shapes this kernel handles (see :func:`unsupported_reason`)."""
    return unsupported_reason(t, dh) is None


def flash_attention(q, k, v, causal: bool = False, *,
                    interpret: bool = False):
    """Fused attention over per-head tensors ``(b, t, h, dh)`` — same
    contract as ops.attention.attention (``softmax(q·kᵀ/√dh)·v``),
    differentiable via the flash backward kernels.  ``k`` and ``v`` may
    carry fewer heads (``h`` a multiple of theirs): grouped-query
    attention, query head ``j`` reading key/value head ``j // group``."""
    b, t, h, dh = q.shape
    why = unsupported_reason(t, dh)
    if why:
        raise ValueError(
            f"flash_attention cannot take this shape: {why} — gate call "
            f"sites on ops.pallas.attention.supported() or use the dense "
            f"path")
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        b * x.shape[2], t, -1)
    o = _flash(fold(q), fold(k), fold(v), causal, interpret)
    return o.reshape(b, h, t, dh).transpose(0, 2, 1, 3)
