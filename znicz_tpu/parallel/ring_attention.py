"""Ring attention — sequence-parallel exact attention over the ``seq``
mesh axis (long-context support; Liu et al. 2023 blockwise ring attention
pattern, re-derived for shard_map + lax.ppermute).

Each device holds a sequence block of Q/K/V ``(b, t_local, h, dh)``.  K/V
blocks rotate around the ring (one ``lax.ppermute`` per step — ICI
neighbor traffic only) while a numerically-stable online softmax
accumulates the local Q block's output:

    m' = max(m, rowmax(s));  l' = l*e^(m-m') + rowsum(e^(s-m'))
    o' = o*e^(m-m') + e^(s-m') @ V_blk

After ``seq`` steps every Q block has attended to the full sequence and
``o / l`` equals dense attention exactly (pinned by
tests/test_parallel_axes.py::test_ring_attention_matches_dense).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   window: int | None = None):
    """Sequence-sharded exact attention; call inside shard_map with the
    time dimension sharded over ``axis_name``.  ``window``: the band as a
    mask on each block's scores (``ops.attention.masked_scores``: every
    block is still visited, so it is what a window layer falls back to on
    an unsharded axis where no kernel takes its shape, not a way to run
    one over a ring)."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t_loc, h, dh = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    from znicz_tpu.ops.attention import masked_scores

    def scores(k_blk, blk_idx):
        # masked_scores accumulates f32 over bf16 matmul inputs (MXU
        # fast path); K/V rotate in their input dtype so ICI traffic
        # stays bf16-sized
        return masked_scores(jnp, q, k_blk, causal,
                             q_offset=my_idx * t_loc,
                             k_offset=blk_idx * t_loc, window=window)

    def step(carry, _):
        o, m, l, k_blk, v_blk, blk_idx = carry
        # online-softmax state (o, m, l) accumulates in f32 even when
        # q/k/v are bf16 — the exp/rescale chain loses digits fast in
        # half precision (standard flash-attention accumulator rule)
        s = scores(k_blk, blk_idx).astype(jnp.float32)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        # p rides the MXU at the value dtype; accumulation stays f32
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        # rotate: after this step we hold the block of (blk_idx - 1) % n
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        blk_idx = (blk_idx - 1) % axis_size
        return (o, m_new, l, k_blk, v_blk, blk_idx), None

    # initial accumulators must carry the same varying-axis type as the
    # loop-updated values (shard_map scan vma rule); deriving them from q
    # inherits whatever axes q varies over (seq here, plus data/model when
    # composed with dp/tp)
    zeros_q = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32) * 0.0
    o0 = zeros_q                                       # (b, h, t_loc, dh)
    m0 = zeros_q[..., 0] - jnp.inf
    l0 = zeros_q[..., 0]
    (o, m, l, _, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v, my_idx), None, length=axis_size)
    out = (o / l[..., None]).astype(q.dtype)
    return jnp.transpose(out, (0, 2, 1, 3))  # (b, t_loc, h, dh)


def _merge_blocks(o, lse, o_s, lse_s, include):
    """Numerically-stable lse-weighted merge of two NORMALIZED attention
    results over the same queries but disjoint key blocks:
    ``softmax``-combining ``(o, lse)`` with ``(o_s, lse_s)``;
    ``include=False`` leaves the accumulator unchanged (a causally
    excluded future block).  All f32; shapes ``o`` (bh, t, dh), ``lse``
    (bh, t, 1)."""
    m = jnp.maximum(lse, lse_s)
    w_old = jnp.exp(lse - m)
    w_new = jnp.exp(lse_s - m)
    tot = w_old + w_new
    o_out = (o * w_old + o_s.astype(jnp.float32) * w_new) / tot
    lse_out = m + jnp.log(tot)
    # excluded blocks leave the accumulator BIT-EXACT (a select, not a
    # zero-weight pass through the merge arithmetic)
    return (jnp.where(include, o_out, o),
            jnp.where(include, lse_out, lse))


def ring_flash_attention(q, k, v, axis_name: str, causal: bool = False,
                         interpret: bool = False):
    """Ring attention whose LOCAL block math is the Pallas flash kernel
    (ops/pallas/attention.py) — the long-context composition: K/V blocks
    rotate over ICI exactly as in :func:`ring_attention`, but each ring
    step computes its (q-block × k-block) attention without ever
    materializing the score matrix, and per-block results combine by the
    lse merge rule (:func:`_merge_blocks`).

    Block-aligned causality needs NO kernel offsets: the diagonal step
    (own k block) runs the kernel's causal mask as-is (q/k positions
    aligned), fully-past blocks run unmasked, fully-future blocks are
    excluded from the merge.  Gradients flow through the merge into both
    o and lse — :func:`flash_attention_lse` carries the lse cotangent
    into the shared backward kernel.

    Same signature/semantics as :func:`ring_attention` (``(b, t_loc, h,
    dh)`` sequence-sharded, called inside shard_map)."""
    from znicz_tpu.ops.pallas.attention import flash_attention_lse

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t_loc, h, dh = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t_loc, dh)

    qf = fold(q)

    def step(carry, _):
        o, lse, k_blk, v_blk, blk_idx = carry
        kf, vf = fold(k_blk), fold(v_blk)
        if causal:
            # the first ring step holds the own (diagonal) block, so the
            # cond's causal branch runs at least once per device
            o_s, lse_s = lax.cond(
                blk_idx == my_idx,
                lambda: flash_attention_lse(qf, kf, vf, True, interpret),
                lambda: flash_attention_lse(qf, kf, vf, False, interpret))
            include = blk_idx <= my_idx
        else:
            o_s, lse_s = flash_attention_lse(qf, kf, vf, False, interpret)
            include = True
        o, lse = _merge_blocks(o, lse, o_s, lse_s, include)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        blk_idx = (blk_idx - 1) % axis_size
        return (o, lse, k_blk, v_blk, blk_idx), None

    # accumulator init mirrors ring_attention: derive from q so the
    # varying-axis type matches the loop-updated values
    o0 = fold(q).astype(jnp.float32) * 0.0             # (bh, t_loc, dh)
    lse0 = o0[..., :1] - jnp.inf                       # (bh, t_loc, 1)
    (o, _, _, _, _), _ = lax.scan(
        step, (o0, lse0, k, v, my_idx), None, length=axis_size)
    out = o.reshape(b, h, t_loc, dh).astype(q.dtype)
    return jnp.transpose(out, (0, 2, 1, 3))            # (b, t_loc, h, dh)


def ring_mha_forward(x, params: dict, n_heads: int, axis_name: str,
                     causal: bool = False):
    """MHA with ring attention: x ``(b, t_local, d)`` sequence-sharded;
    projection weights replicated (or tp-sharded by the caller).  Same
    projection/param convention as the dense op — only the core differs."""
    from znicz_tpu.ops.attention import mha_forward

    def core(q, k, v, causal):
        return ring_attention(q, k, v, axis_name, causal=causal)

    return mha_forward(jnp, x, params, n_heads, causal=causal,
                       attention_fn=core)
