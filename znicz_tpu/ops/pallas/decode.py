"""Paged flash-decode as a Pallas kernel — single-query attention over
the block-paged KV arena (ISSUE 12), beside the training-side flash
(attention.py) and ring kernels.

Decode attention is one query row per slot against every cached row the
slot has written: memory-bound, gather-heavy, and the only attention
shape the generative plane dispatches in steady state.  The XLA path
(``PagedKVDecoder._paged_attend``) first materializes the gathered
``(B, T_view, H, Dh)`` K/V copies in HBM and then reads them again for
the scores; this kernel fuses the two — the grid walks ``(slot, page)``
and each step DMAs ONE arena page, every head of it, straight into VMEM
via the page table (a *scalar-prefetch* operand: block index maps read
it before the kernel body runs, the Pallas paged-attention idiom),
scoring it against the slot's resident query rows with an f32
online-softmax accumulator (m/l/acc scratch, carried across the
sequential page axis — the same recipe ``ring_attention`` and the
contiguous decoder use: f32 statistics, probabilities rounded to the
value dtype before they meet V, so numerics agree with the jnp
reference to rounding).

Masking: key row ``r`` (global position ``p·page + r``) participates
iff ``p·page + r < length`` for the slot — rows past the slot's write
frontier, scratch-page padding entries, and empty batch slots
(``length == 0`` never happens live; admission guarantees ``>= 1``) all
fall out of the same comparison, with the serve plane's shared -1e30
mask constant.

Interpret-mode fallback: like every kernel in this package the
``interpret=True`` flag runs the identical kernel on the Pallas
interpreter, so the CPU test suite executes the real kernel logic
(tests/test_paged.py pins it against the jnp reference within the
established 2e-5 band).  Compiled TPU dispatch is proven at lane-sized
heads — gate call sites on :func:`supported` (or pass interpret) exactly
like ``ops.pallas.attention``.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: the kernel's name in the lowered program and in device traces
KERNEL_NAME = "paged_flash_decode"


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # every head of the slot rides one grid step in the arena's own
    # (page, H, Dh) layout: heads on sublanes, head_dim on lanes, page
    # rows on the major axis.  One query row is VPU work (a (1, Dh) MXU
    # operand would waste the array), and reductions over the major axis
    # are plain vreg adds — no relayout anywhere.
    q = q_ref[0].astype(jnp.float32)                 # (H, Dh)
    k = k_ref[0].astype(jnp.float32)                 # (page, H, Dh)
    v = v_ref[0].astype(jnp.float32)
    s = (k * q[None]).sum(axis=-1, keepdims=True) / scale   # (page, H, 1)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + p * page
    s = jnp.where(kpos >= len_ref[b], jnp.float32(-1e30), s)
    m_prev = m_ref[...]                              # (H, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=0))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new[None])                  # (page, H, 1)
    l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=0)
    # p meets V at V's dtype (bf16 on the chip), accumulated in f32 —
    # the rounding KVDecoder._attend applies
    pv = pexp.astype(v_ref.dtype).astype(jnp.float32) * v
    acc_ref[...] = acc_ref[...] * alpha + pv.sum(axis=0)
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _emit():
        o_ref[0] = acc_ref[...] / l_ref[...]


def supported(page: int, head_dim: int) -> bool:
    """Shapes the COMPILED kernel is proven on: sublane-multiple pages
    and lane-sized head dims.  Interpret mode has no such constraint —
    the paged decoder picks interpret automatically off-TPU."""
    return page % 8 == 0 and head_dim % 128 == 0


def paged_flash_decode(q, k_pages, v_pages, page_table, lengths, *,
                       interpret: bool = False):
    """Fused single-query paged attention.

    ``q (B, H, Dh)``; ``k_pages/v_pages (N, page, H, Dh)`` — one arena
    layer; ``page_table (B, P)`` int32 arena page ids (padding entries
    point at the scratch page and are masked by ``lengths``);
    ``lengths (B,)`` int32 valid rows per slot (``pos + 1`` at decode
    time).  Returns ``o (B, H, Dh)`` float32.
    """
    B, H, Dh = q.shape
    N, page = k_pages.shape[0], k_pages.shape[1]
    P = page_table.shape[1]
    if not interpret and not supported(page, Dh):
        raise ValueError(
            f"compiled paged_flash_decode needs page % 8 == 0 and "
            f"head_dim % 128 == 0; got page={page}, head_dim={Dh} — "
            f"gate call sites on ops.pallas.decode.supported() or run "
            f"interpret")
    kern = partial(_kernel, page=page, scale=float(np.sqrt(Dh)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        # every block's last two dims are the arrays' own (H, Dh), the
        # one block shape Mosaic takes at any head count
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, p, pt, ln: (b, 0, 0)),
            # THE paged gather: the block index rides the prefetched
            # page table, so each grid step DMAs exactly the page the
            # slot mapped at view position p
            pl.BlockSpec((1, page, H, Dh),
                         lambda b, p, pt, ln: (pt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page, H, Dh),
                         lambda b, p, pt, ln: (pt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dh), lambda b, p, pt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),         # running max
            pltpu.VMEM((H, 1), jnp.float32),         # running denom
            pltpu.VMEM((H, Dh), jnp.float32),        # o accumulator
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), jnp.float32),
        name=KERNEL_NAME,
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q, k_pages, v_pages)


def reference(q, k_pages, v_pages, page_table, lengths):
    """The jnp oracle the kernel is pinned against: gather the page
    view, mask rows past each slot's length, dense softmax in f32."""
    B, H, Dh = q.shape
    page = k_pages.shape[1]
    t_view = page_table.shape[1] * page
    kc = k_pages[page_table].reshape(B, t_view, H, Dh)
    vc = v_pages[page_table].reshape(B, t_view, H, Dh)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / np.sqrt(Dh)
    dead = jnp.arange(t_view)[None, :] >= \
        jnp.asarray(lengths, jnp.int32)[:, None]
    s = jnp.where(dead[:, None, :], jnp.float32(-1e30), s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, vc.astype(jnp.float32))
