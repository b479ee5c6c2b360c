"""Multi-head attention ops (TPU-native extension; no reference
counterpart — veles.znicz predates transformers, SURVEY.md §6.7 — but the
rebuild treats long-context as first-class).

Dense reference implementation here; the sequence-parallel ring variant
(identical math, K/V blocks rotated over the ``seq`` mesh axis) lives in
znicz_tpu.parallel.ring_attention and is pinned equal to this one by
tests/test_parallel_axes.py.

Layouts: activations ``(batch, time, d_model)``; heads split last dim.
"""

from __future__ import annotations

import numpy as np


def split_heads(xp, x, n_heads: int):
    b, t, d = x.shape
    return x.reshape(b, t, n_heads, d // n_heads)


def merge_heads(xp, x):
    b, t, h, dh = x.shape
    return x.reshape(b, t, h * dh)


def softmax(xp, x, axis=-1):
    m = x.max(axis=axis, keepdims=True)
    e = xp.exp(x - m)
    return e / e.sum(axis=axis, keepdims=True)


def masked_scores(xp, q, k, causal: bool, q_offset=0, k_offset=0,
                  window: int | None = None):
    """Scaled q·kᵀ scores ``(b, h, tq, tk)`` with optional causal masking;
    ``*_offset`` give global positions when q/k are sequence blocks — the
    ONE definition of the mask convention, shared by dense attention and
    the ring variant (znicz_tpu.parallel.ring_attention).  ``window`` (with
    ``causal``): query ``i`` sees key ``j`` iff ``0 <= i - j < window``, the
    band as a second mask on the same scores.

    The product accumulates in f32 even for bf16 inputs (matmul inputs
    stay bf16 on the MXU; only the accumulator widens — the same rule as
    the Pallas flash kernel, so the auto-selected paths agree)."""
    dh = q.shape[-1]
    try:
        s = xp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=xp.float32)
    except TypeError:      # numpy has no accumulator-dtype control
        s = xp.einsum("bqhd,bkhd->bhqk", q, k)
    s = s / np.sqrt(dh).astype(s.dtype)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qpos = xp.arange(tq)[:, None] + q_offset
        kpos = xp.arange(tk)[None, :] + k_offset
        s = xp.where((kpos > qpos)[None, None, :, :],
                     xp.asarray(-1e30, dtype=s.dtype), s)
        if window is not None:
            s = xp.where((qpos - kpos >= window)[None, None, :, :],
                         xp.asarray(-1e30, dtype=s.dtype), s)
    elif window is not None:
        raise ValueError("a window on the scores needs causal=True")
    return s


def attention(xp, q, k, v, causal: bool = False, window: int | None = None):
    """Scaled-dot-product attention over per-head tensors
    ``(b, t, h, dh)``."""
    p = softmax(xp, masked_scores(xp, q, k, causal, window=window))
    # probabilities ride the MXU at the value dtype (flash-kernel rule)
    return xp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def mha_forward(xp, x, params: dict, n_heads: int, causal: bool = False,
                attention_fn=None):
    """Full MHA block: qkv projections -> attention -> output projection.
    ``params``: wq/wk/wv/wo ``(d, d)`` (+ optional bq/bk/bv/bo).
    ``attention_fn(q, k, v, causal)`` overrides the core (the ring variant
    passes its sequence-parallel kernel) — ONE definition of the
    projection/param convention for all MHA assemblies."""
    def proj(w_key, b_key):
        y = x @ params[w_key]
        if params.get(b_key) is not None:
            y = y + params[b_key]
        return split_heads(xp, y, n_heads)

    q = proj("wq", "bq")
    k = proj("wk", "bk")
    v = proj("wv", "bv")
    if attention_fn is None:
        o = attention(xp, q, k, v, causal=causal)
    else:
        o = attention_fn(q, k, v, causal=causal)
    y = merge_heads(xp, o) @ params["wo"]
    if params.get("bo") is not None:
        y = y + params["bo"]
    return y
