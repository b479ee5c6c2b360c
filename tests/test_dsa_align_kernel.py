"""The alignment target's Pallas kernel (``ops/pallas/dsa.py``) against the
blocked ``jax.numpy`` form it stands in for (``parallel/dsa.py``),
interpreted on the CPU: the target itself, the loss and the three gradients
of ``index_select_align``; and the function that says which shapes the
kernel takes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.ops.pallas import dsa as pdsa
from znicz_tpu.parallel import dsa


def _operands(t, heads, kv, dh, distinct_keys, seed, hi=16, di=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    qi = jax.random.normal(ks[0], (1, t, hi, di))
    ki = jax.random.normal(ks[1], (1, t, di))
    if distinct_keys:
        # index keys drawn from a few vectors: every row's scores tie, bit
        # for bit, in groups, at its threshold too
        ki = ki[:, jax.random.randint(ks[5], (t,), 0, distinct_keys)]
    w = jax.random.normal(ks[2], (1, t, hi))
    q = jax.random.normal(ks[3], (1, t, heads, dh))
    k = jax.random.normal(ks[4], (1, t, kv, dh))
    return qi, ki, w, q, k


def _dense_target(q, k, sel):
    """The mean over the heads of each head's softmax over the selection,
    with the ``(heads, t, t)`` scores whole."""
    heads, kv = q.shape[1], k.shape[1]
    a = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, heads // kv, 1))
    a = jnp.where(sel[None], a / np.sqrt(q.shape[-1]), -jnp.inf)
    return jax.nn.softmax(a, -1).mean(0)


@pytest.mark.parametrize("t,heads,kv,top_k,distinct_keys", [
    (256, 4, 2, 48, 0),       # one group of rows, two tiles' worth in one
    (512, 8, 2, 100, 0),      # four groups, extents of 1-4 tiles, groups of 4
    (512, 32, 4, 40, 0),      # the benchmark's heads: 4 key/value, groups of 8
    (384, 4, 4, 500, 0),      # no row has 500 keys: every causal pair; 3 tiles
    (256, 8, 4, 32, 8),       # ties at the threshold: rows hold more than 32
], ids=["one-group", "four-groups", "kv4-group8", "all-causal", "ties"])
def test_the_kernel_gives_the_blocked_forms_target_loss_and_gradients(
        t, heads, kv, top_k, distinct_keys, monkeypatch):
    dh = 128
    assert dsa.align_kernel_refusal(t, heads, kv, dh, True) is None
    # this kernel alone: the index scores by the einsums on both sides
    # (theirs against their kernels: tests/test_dsa_index_kernel.py)
    monkeypatch.setattr(dsa, "index_kernel_refusal",
                        lambda *a: "left to the einsums in this comparison")
    qi, ki, w, q, k = _operands(t, heads, kv, dh, distinct_keys, t + heads)
    got, want = ({}, {})
    for out, interpret in ((want, False), (got, True)):
        f = lambda *a: dsa.index_select_align(                # noqa: E731
            *a, q, k, top_k, "t", interpret)[::-1]
        text = str(jax.make_jaxpr(f)(qi, ki, w))
        # off the TPU the kernel runs only interpreted
        assert (pdsa.ALIGN_KERNEL_NAME in text) == interpret
        (out["loss"], out["sel"]), out["grads"] = jax.value_and_grad(
            f, (0, 1, 2), has_aux=True)(qi, ki, w)
    np.testing.assert_array_equal(got["sel"], want["sel"])
    picked = np.asarray(want["sel"][0] != 0)
    per_row = np.minimum(np.arange(t) + 1, top_k)
    if distinct_keys:
        assert (picked.sum(-1) > per_row).any()
    else:
        np.testing.assert_array_equal(picked.sum(-1), per_row)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=2e-6)
    for a, b in zip(got["grads"], want["grads"]):
        np.testing.assert_allclose(a, b, atol=2e-6 * float(jnp.abs(b).max()))
    # the target itself, the last block of queries against all the keys
    # and against the dense arithmetic; a block in the middle, whose last
    # tiles lie above it, comes out zero there
    block = dsa.Q_BLOCK
    for lo in (t - block, t // 2 - block):
        rows = slice(lo, lo + block)
        qh = q[0, rows].reshape(block, kv, heads // kv, dh).transpose(
            1, 2, 0, 3).reshape(kv, -1, dh)
        p = pdsa.align_target(qh, k[0], want["sel"][0, rows],
                              jnp.int32(lo + block - 1),
                              sm_scale=float(dh ** -0.5), interpret=True)
        dense = _dense_target(q[0, rows], k[0], jnp.asarray(picked[rows]))
        np.testing.assert_allclose(p, dense, atol=2e-6)
        assert float(jnp.abs(p[:, lo + block:]).max(initial=0.0)) == 0.0
        np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-5)


@pytest.mark.parametrize("t,heads,kv,dh,why", [
    (16384, 32, 4, 128, None),            # the benchmark's shape
    (4096, 16, 16, 256, None),
    (512, 8, 2, 128, None),
    (256, 2, 1, 64, "head_dim=64"),       # lanes cut a key/value head
    (256, 2, 1, 192, "head_dim=192"),
    (200, 4, 2, 128, "t=200"),            # no whole blocks of queries
    (32, 4, 2, 128, "t=32"),
    (256, 6, 4, 128, "6 heads"),
    (16384, 256, 4, 128, "VMEM"),         # the rows' statistics alone
], ids=lambda v: str(v).replace(" ", "_"))
def test_which_shapes_the_kernel_takes(t, heads, kv, dh, why):
    got = dsa.align_kernel_refusal(t, heads, kv, dh, True)
    if why is None:
        assert got is None
    else:
        assert why in got
    # only where the step's kernels run: not on this backend unless
    # interpreted
    assert "backend is cpu" in dsa.align_kernel_refusal(t, heads, kv, dh,
                                                        False)


def test_the_tile_follows_the_key_extent_and_the_working_set():
    """1,024 keys a tile at the benchmark's shape (every group's extent is
    a multiple of 4,096), the largest tile that divides a shorter extent,
    and a smaller one where more heads fill the limit."""
    assert pdsa.align_tile(4096, 128, 32, 4, 128) == 1024
    assert pdsa._align_vmem(1024, 128, 32, 4, 128) < pdsa._VMEM_LIMIT
    assert pdsa.align_tile(384, 128, 32, 4, 128) == 128
    assert pdsa.align_tile(1536, 128, 32, 4, 128) == 512
    assert pdsa.align_tile(4096, 128, 64, 4, 128) == 512
    assert pdsa.align_tile(100, 128, 32, 4, 128) == 0
