"""The index scores' two Pallas kernels (``ops/pallas/dsa.py``:
``dsa_index_scores``, ``dsa_index_grads``) against the blocked ``jax.numpy``
einsums they stand in for (``parallel/dsa.py::_one_block``), interpreted on
the CPU: the scores and their three gradients a block at a time, then the
selection, the loss and the gradients of ``index_select_align``; and the
function that says which shapes the kernels take."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.ops.pallas import dsa as pdsa
from znicz_tpu.parallel import dsa

BLOCK = dsa.Q_BLOCK


def _einsums(qb, k, w, d_idx):
    """``_one_block``'s index scores and their gradients, from the kernels'
    ``qb`` ``(block, hi x di)``."""
    f32 = jnp.float32
    block, hi = w.shape
    qi = qb.reshape(block, hi, -1)                           # (bq, hi, di)
    s = jnp.einsum("qjd,kd->qjk", qi, k, preferred_element_type=f32)
    r = jnp.maximum(s, 0.0)
    idx = (r * w[:, :, None]).sum(1)
    g = (d_idx[:, None, :] * w[:, :, None] * (s > 0)).astype(qi.dtype)
    dqi = jnp.einsum("qjk,kd->qjd", g, k, preferred_element_type=f32)
    dki = jnp.einsum("qjk,qjd->kd", g, qi, preferred_element_type=f32)
    dw = (d_idx[:, None, :] * r).sum(-1)
    return idx, dqi.reshape(qb.shape), dki.T, dw        # dkI keys-minor


@pytest.mark.parametrize("hi,di,keys,last,dtype", [
    (16, 64, 1024, 1023, jnp.bfloat16),   # the benchmark's indexer, one tile
    (16, 8, 512, 511, jnp.float32),       # the last block, one tile
    (16, 8, 384, 383, jnp.float32),       # three tiles of 128, all live
    (16, 8, 640, 255, jnp.float32),       # a middle block: 2 of 5 tiles live
    (6, 24, 1536, 700, jnp.float32),      # heads no multiple of the chunk
    (3, 16, 2048, 2047, jnp.bfloat16),    # fewer heads than a chunk, 2 tiles
], ids=["keye-bf16", "last-block", "three-tiles", "middle-block",
        "six-heads", "three-heads-bf16"])
def test_the_kernels_give_the_einsums_scores_and_gradients(hi, di, keys, last,
                                                           dtype):
    ks = jax.random.split(jax.random.PRNGKey(hi * keys + last), 4)
    qb = jax.random.normal(ks[0], (BLOCK, hi * di)).astype(dtype)
    k = jax.random.normal(ks[1], (keys, di)).astype(dtype)
    w = jax.random.normal(ks[2], (BLOCK, hi))             # of either sign
    assert float(w.min()) < 0 < float(w.max())
    tile = pdsa.index_tile(keys, BLOCK, hi, di)
    live = (last // tile + 1) * tile
    # the loss's gradient is zero past the block's last query, and here on
    # the whole first tile where another follows
    d_idx = jax.random.normal(ks[3], (BLOCK, keys))
    d_idx = d_idx * (jnp.arange(keys)[None, :] <= last)
    if live > tile:
        d_idx = d_idx.at[:, :tile].set(0.0)
    idx = pdsa.index_scores(qb, k, w, jnp.int32(last), interpret=True)
    got = pdsa.index_grads(qb, k, w, d_idx, jnp.int32(last), interpret=True)
    want_idx, *want = _einsums(qb, k, w, d_idx)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(idx[:, :live], want_idx[:, :live],
                               atol=1e-5 * float(jnp.abs(want_idx).max()))
    # a tile wholly past the block's last query is written as zeros
    assert float(jnp.abs(idx[:, live:]).max(initial=0.0)) == 0.0
    for name, a, b in zip(("dqI", "dkI^T", "dw"), got, want):
        assert a.dtype == jnp.float32 and a.shape == b.shape
        # in 16 bits a score within rounding of 0 may pass the relu on one
        # side only: its g is a whole entry off
        np.testing.assert_allclose(a, b, atol=tol * float(jnp.abs(b).max()),
                                   err_msg=name)
    assert float(jnp.abs(got[1][:, live:]).max(initial=0.0)) == 0.0
    if live > tile:
        # keys that no gradient reaches get none
        assert float(jnp.abs(got[1][:, :tile]).max()) == 0.0


def test_g_is_rounded_to_the_operands_dtype_before_its_products():
    """``g = d_idx * w * (s > 0)`` leaves for its two products in the
    operands' dtype, as ``_one_block`` rounds it: with one key and one head
    ``dqI = g * kI`` shows the rounding."""
    qb = jnp.ones((BLOCK, 16), jnp.bfloat16)
    k = jnp.zeros((128, 16), jnp.bfloat16).at[0].set(1.0)
    w = jnp.full((BLOCK, 1), 1.0 + 2.0 ** -10)            # no bfloat16
    d_idx = jnp.zeros((BLOCK, 128)).at[:, 0].set(1.0)
    dq, dk, dw = pdsa.index_grads(qb, k, w, d_idx, jnp.int32(127),
                                  interpret=True)
    assert float(dq[0, 0]) == 1.0                      # not 1.0009765625
    assert float(dk[0, 0]) == BLOCK
    assert float(dw[0, 0]) == 16.0                     # d_idx * relu(s)


@pytest.mark.parametrize("t,hi,di,top_k,distinct_keys", [
    (256, 16, 8, 48, 0),       # one group of rows
    (512, 16, 8, 100, 0),      # four groups, extents of 1-4 blocks
    (1536, 16, 16, 200, 0),    # four groups, extents of 3, 3, 9, 3 tiles
    (384, 16, 8, 500, 0),      # no row has 500 keys: every causal pair
    (256, 16, 8, 32, 8),       # ties at the threshold
], ids=["one-group", "four-groups", "many-tiles", "all-causal", "ties"])
def test_the_selection_the_loss_and_the_gradients_follow_the_einsums(
        t, hi, di, top_k, distinct_keys):
    assert dsa.index_kernel_refusal(t, hi, di, True) is None
    from test_dsa_align_kernel import _operands

    # a head the alignment kernel refuses: the index kernels alone differ
    qi, ki, w, q, k = _operands(t, 2, 1, 16, distinct_keys, t + hi, hi, di)
    assert "head_dim" in dsa.align_kernel_refusal(t, 2, 1, 16, True)
    got, want = ({}, {})
    for out, interpret in ((want, False), (got, True)):
        f = lambda *a: dsa.index_select_align(                # noqa: E731
            *a, q, k, top_k, "t", interpret)[::-1]
        text = str(jax.make_jaxpr(jax.grad(f, has_aux=True))(qi, ki, w))
        # off the TPU the kernels run only interpreted
        for name in (pdsa.INDEX_SCORES_KERNEL_NAME,
                     pdsa.INDEX_GRADS_KERNEL_NAME):
            assert (name in text) == interpret
        assert pdsa.ALIGN_KERNEL_NAME not in text
        (out["loss"], out["sel"]), out["grads"] = jax.value_and_grad(
            f, (0, 1, 2), has_aux=True)(qi, ki, w)
    np.testing.assert_array_equal(got["sel"], want["sel"])
    picked = np.asarray(want["sel"][0] != 0)
    per_row = np.minimum(np.arange(t) + 1, top_k)
    if distinct_keys:
        assert (picked.sum(-1) > per_row).any()
    else:
        np.testing.assert_array_equal(picked.sum(-1), per_row)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=1e-5)
    for a, b in zip(got["grads"], want["grads"]):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("t,hi,di,why", [
    (16384, 16, 64, None),                # the benchmark's shape
    (4096, 64, 128, None),
    (256, 16, 8, None),                   # any index width: lanes are padded
    (200, 16, 64, "t=200"),               # no whole blocks of queries
    (32, 16, 64, "t=32"),
    (16384, 256, 128, "VMEM"),            # the queries and dqI alone
], ids=lambda v: str(v).replace(" ", "_"))
def test_which_shapes_the_index_kernels_take(t, hi, di, why):
    got = dsa.index_kernel_refusal(t, hi, di, True)
    if why is None:
        assert got is None
    else:
        assert why in got
    # only where the step's kernels run: not on this backend unless
    # interpreted
    assert "backend is cpu" in dsa.index_kernel_refusal(t, hi, di, False)


def test_the_index_tile_follows_the_key_extent_and_the_working_set():
    """1,024 keys a tile at the benchmark's shape, the largest tile that
    divides a shorter extent, a smaller one where more heads fill the
    limit; and what the kernels' own reasons say of a block or an extent
    that no tile divides."""
    assert pdsa.index_tile(4096, 128, 16, 64) == 1024
    assert pdsa._index_vmem(1024, 128, 16, 64) < pdsa._VMEM_LIMIT // 2
    assert pdsa.index_tile(384, 128, 16, 64) == 128
    assert pdsa.index_tile(1536, 128, 16, 64) == 512
    assert pdsa.index_tile(4096, 128, 128, 128) == 512
    assert pdsa.index_tile(100, 128, 16, 64) == 0
    assert "16 rows" in pdsa.index_unsupported_reason(72, 4096, 16, 64)
    assert "128-key tile" in pdsa.index_unsupported_reason(128, 100, 16, 64)
    assert pdsa.index_unsupported_reason(128, 4096, 16, 64) is None
