"""Pallas kernel parity tests (SURVEY.md §5 tier-1: "Pallas-vs-XLA
cross-check, the analog of ocl-vs-numpy") — interpreter mode on the CPU
mesh; the same calls lower to Mosaic on real TPU."""

import numpy as np
import pytest

import jax.numpy as jnp

from znicz_tpu.ops import lrn as lrn_ops, sgd as sgd_ops
from znicz_tpu.ops.pallas import (dropout_forward, fused_sgd_update,
                                  lrn_backward, lrn_forward)


def test_fused_sgd_matches_oracle():
    rng = np.random.default_rng(0)
    for shape in ((64, 128), (7, 33), (3, 5, 16)):
        w = rng.normal(size=shape).astype(np.float32)
        g = rng.normal(size=shape).astype(np.float32)
        v = rng.normal(size=shape).astype(np.float32) * 0.1
        args = (0.05, 1e-3, 0.3, 0.9, 32.0)
        w_ref, v_ref = sgd_ops.update(jnp, jnp.asarray(w), jnp.asarray(g),
                                      jnp.asarray(v), *args)
        w_pl, v_pl = fused_sgd_update(jnp.asarray(w), jnp.asarray(g),
                                      jnp.asarray(v), *args, interpret=True)
        np.testing.assert_allclose(np.asarray(w_pl), np.asarray(w_ref),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(v_pl), np.asarray(v_ref),
                                   rtol=1e-6, atol=1e-7)


def test_fused_sgd_traced_hyperparams():
    """Hyperparams as traced scalars (the LR-schedule path)."""
    import jax
    rng = np.random.default_rng(1)
    w = rng.normal(size=(16, 32)).astype(np.float32)
    g = rng.normal(size=(16, 32)).astype(np.float32)
    v = np.zeros((16, 32), np.float32)

    def step(lr):
        return fused_sgd_update(jnp.asarray(w), jnp.asarray(g),
                                jnp.asarray(v), lr, 0.0, 0.0, 0.9, 8.0,
                                interpret=True)

    w1, _ = jax.jit(step)(jnp.float32(0.1))
    w_ref, _ = sgd_ops.update(jnp, jnp.asarray(w), jnp.asarray(g),
                              jnp.asarray(v), 0.1, 0.0, 0.0, 0.9, 8.0)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w_ref), rtol=1e-6)


def test_dropout_kernel_semantics():
    """Masking math via injected bits (the CPU interpreter's emulated TPU
    PRNG yields zeros, so in-kernel bit generation is TPU-only)."""
    rng = np.random.default_rng(2)
    x = rng.normal(size=(32, 128)).astype(np.float32)
    bits = rng.integers(0, 2 ** 32, size=x.shape, dtype=np.uint32)
    ratio = 0.4
    y, mask = dropout_forward(jnp.asarray(x), seed=7, ratio=ratio,
                              bits=jnp.asarray(bits), interpret=True)
    y, mask = np.asarray(y), np.asarray(mask)
    scale = 1.0 / (1.0 - ratio)
    assert set(np.unique(mask)).issubset({0.0, np.float32(scale)})
    np.testing.assert_allclose(y, x * mask, rtol=1e-6)
    # drop rate within statistical tolerance of the threshold
    drop_rate = (mask == 0).mean()
    assert abs(drop_rate - ratio) < 0.06, drop_rate
    # bit-exact vs the threshold rule
    np.testing.assert_array_equal(
        mask != 0, bits > np.uint32(ratio * (2 ** 32 - 1)))


def test_pallas_sgd_in_fused_workflow():
    """End-to-end: the fused training step with the Pallas SGD backend
    reproduces the default XLA-fused run bit-for-bit."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.models import wine

    def run():
        prng.seed_all(17)
        w = wine.build(max_epochs=2, n_train=60, n_valid=30,
                       minibatch_size=10)
        w.initialize(device=XLADevice())
        w.run()
        w.stop()
        return w

    base = run()
    root.common.engine.pallas = True
    root.common.engine.pallas_interpret = True
    try:
        pallas = run()
    finally:
        root.common.engine.pallas = False
        root.common.engine.pallas_interpret = False
    assert base.decision.metrics_history == pallas.decision.metrics_history
    np.testing.assert_allclose(
        base.forwards[0].weights.map_read(),
        pallas.forwards[0].weights.map_read(), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("n", [4, 5])
def test_lrn_kernels_match_oracle(n):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 3, 3, 16)).astype(np.float32)
    err = rng.normal(size=x.shape).astype(np.float32)
    args = (1e-4, 0.75, 2.0, n)
    y_ref = lrn_ops.forward(np, x, *args)
    y_pl = lrn_forward(jnp.asarray(x), *args, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pl), y_ref, rtol=1e-5,
                               atol=1e-6)
    e_ref = lrn_ops.backward(np, x, err, *args)
    e_pl = lrn_backward(jnp.asarray(x), jnp.asarray(err), *args,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(e_pl), e_ref, rtol=1e-4,
                               atol=1e-5)


# -- round-3 parity tail: conv, stochastic pooling, kohonen ------------------

from znicz_tpu.ops import conv as conv_ops, kohonen as k_ops
from znicz_tpu.ops import pooling as pool_ops
from znicz_tpu.ops.pallas import conv2d_im2col, som_step, stochastic_pool

CONV_GEOMS = [
    # (h, w, cin, cout, k, sliding, padding)
    (8, 8, 3, 16, 3, (1, 1), (0, 0, 0, 0)),
    (9, 7, 4, 8, 3, (2, 2), (1, 1, 1, 1)),
    (12, 12, 2, 8, 5, (2, 2), (2, 1, 0, 2)),   # asymmetric 4-tuple pad
    (6, 6, 8, 32, 1, (1, 1), (0, 0, 0, 0)),    # 1x1
]


@pytest.mark.parametrize("geom", CONV_GEOMS)
def test_pallas_conv_matches_oracle(geom):
    h, w, cin, cout, k, sliding, padding = geom
    rng = np.random.default_rng(7)
    x = rng.normal(size=(3, h, w, cin)).astype(np.float32)
    wts = rng.normal(size=(k, k, cin, cout)).astype(np.float32) * 0.1
    b = rng.normal(size=(cout,)).astype(np.float32)
    ref = conv_ops.forward_linear(np, x, wts, b, sliding, padding)
    out = conv2d_im2col(jnp.asarray(x), jnp.asarray(wts), jnp.asarray(b),
                        sliding, padding, interpret=True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
    # and without bias
    ref0 = conv_ops.forward_linear(np, x, wts, None, sliding, padding)
    out0 = conv2d_im2col(jnp.asarray(x), jnp.asarray(wts), None,
                         sliding, padding, interpret=True)
    np.testing.assert_allclose(np.asarray(out0), ref0, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("use_abs", [False, True])
def test_pallas_stochastic_pool_matches_oracle(use_abs):
    """Injected-bits path vs ops.pooling.stochastic_forward with the SAME
    uniforms: identical winners and values (inverse-CDF strict-compare
    semantics)."""
    rng = np.random.default_rng(8)
    x = rng.normal(size=(2, 7, 5, 4)).astype(np.float32)
    ky = kx = 3
    sy = sx = 2
    patch, valid, _ = pool_ops.patches(np, x, ky, kx, sy, sx, pad_value=0.0)
    n, oh, ow, K, c = patch.shape
    bits = rng.integers(0, 2 ** 32, size=(n * oh * ow, c), dtype=np.uint32)
    # the kernel's 24-bit uniform mapping (Mosaic-compatible cast path)
    u = ((bits >> 8).astype(np.float32) * 2.0 ** -24)
    y_ref, off_ref = pool_ops.stochastic_forward(
        np, x, ky, kx, sy, sx, u.reshape(n, oh, ow, c), use_abs, train=True)
    vtile = np.broadcast_to(valid.reshape(1, oh * ow, K), (n, oh * ow, K))
    y_pl, tap = stochastic_pool(
        jnp.asarray(patch.reshape(n * oh * ow, K, c)),
        jnp.asarray(vtile.reshape(n * oh * ow, K)), seed=0,
        use_abs=use_abs, bits=jnp.asarray(bits), interpret=True)
    np.testing.assert_allclose(np.asarray(y_pl).reshape(n, oh, ow, c),
                               y_ref, rtol=1e-6)
    off_pl = pool_ops.offsets_of(
        np, np.asarray(tap).reshape(n, oh, ow, c), x.shape, ky, kx, sy, sx)
    np.testing.assert_array_equal(off_pl, off_ref)


def test_pallas_stochastic_pool_prng_branch_plumbing():
    """Exercise the bits=None in-kernel-PRNG branch end to end under the
    interpreter: the emulated TPU PRNG yields zero bits, so u == 0 and
    the strict-compare inverse CDF must select tap 0 everywhere — which
    pins the seed/SMEM spec, prng_seed/bitcast plumbing and the zero-mass
    fallback in one go (real-hardware randomness is covered by the
    selection test on TPU runs)."""
    rng = np.random.default_rng(10)
    x = rng.normal(size=(2, 6, 6, 4)).astype(np.float32)
    patch, valid, _ = pool_ops.patches(np, x, 2, 2, 2, 2, pad_value=0.0)
    n, oh, ow, K, c = patch.shape
    vtile = np.broadcast_to(valid.reshape(1, oh * ow, K), (n, oh * ow, K))
    from jax.experimental.pallas import tpu as pltpu

    interp = pltpu.InterpretParams()
    y, tap = stochastic_pool(
        jnp.asarray(patch.reshape(n * oh * ow, K, c)),
        jnp.asarray(vtile.reshape(n * oh * ow, K)), seed=3,
        interpret=interp)
    np.testing.assert_array_equal(np.asarray(tap), 0)
    np.testing.assert_allclose(np.asarray(y),
                               patch.reshape(n * oh * ow, K, c)[:, 0, :],
                               rtol=1e-6)


def test_pallas_som_step_matches_oracle():
    rng = np.random.default_rng(9)
    B, D, sy, sx = 32, 6, 5, 4
    x = rng.normal(size=(B, D)).astype(np.float32)
    w = rng.normal(size=(sy * sx, D)).astype(np.float32)
    coords = np.asarray(k_ops.grid_coords(np, sy, sx))
    for bs in (B, 20):   # full batch + padded tail
        mask = (np.arange(B) < bs) if bs < B else None
        w_ref, idx_ref = k_ops.update(np, x, w, coords, 0.3, 1.5, mask)
        w_pl, idx_pl = som_step(jnp.asarray(x), jnp.asarray(w),
                                jnp.asarray(coords), 0.3, 1.5, bs,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(w_pl), w_ref, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(idx_pl), idx_ref)


def test_pallas_conv_unit_selection():
    """root.common.engine.pallas routes Conv.xla_run through the im2col
    kernel with identical outputs."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.core.workflow import Workflow
    from znicz_tpu.units.conv import Conv

    def run_once():
        prng.seed_all(12)
        w = Workflow(name="c")
        conv = Conv(w, n_kernels=8, kx=3, ky=3, sliding=(2, 2),
                    padding=(1, 1, 1, 1))
        from znicz_tpu.core.memory import Array
        conv.input = Array()
        conv.input.mem = np.random.default_rng(5).normal(
            size=(4, 9, 9, 3)).astype(np.float32)
        conv.initialize(device=XLADevice())
        conv.xla_run()
        return np.asarray(conv.output.map_read())

    base = run_once()
    root.common.engine.pallas = True
    root.common.engine.pallas_interpret = True
    try:
        pallas = run_once()
    finally:
        root.common.engine.pallas = False
        root.common.engine.pallas_interpret = False
    np.testing.assert_allclose(pallas, base, rtol=1e-5, atol=1e-6)


def test_pallas_kohonen_trainer_selection():
    """SOM demo trains identically through the fused Pallas step."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.models import kohonen as km

    def run_once():
        prng.seed_all(21)
        w = km.build(max_epochs=2, shape=(5, 5), n_train=200)
        w.initialize(device=XLADevice())
        w.run()
        return np.asarray(w.trainer.weights.map_read())

    base = run_once()
    root.common.engine.pallas = True
    root.common.engine.pallas_interpret = True
    try:
        pallas = run_once()
    finally:
        root.common.engine.pallas = False
        root.common.engine.pallas_interpret = False
    np.testing.assert_allclose(pallas, base, rtol=1e-4, atol=1e-5)


def test_pallas_stochastic_pooling_unit_selection():
    """The stochastic pooling unit's Pallas path emits values from the
    right windows with offsets consistent with the emitted values."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.core.memory import Array
    from znicz_tpu.core.workflow import Workflow
    from znicz_tpu.units.pooling import StochasticPooling

    prng.seed_all(33)
    root.common.engine.pallas = True
    root.common.engine.pallas_interpret = True
    try:
        w = Workflow(name="sp")
        unit = StochasticPooling(w, kx=2, ky=2, sliding=(2, 2))
        unit.input = Array()
        x = np.random.default_rng(6).normal(
            size=(3, 6, 6, 4)).astype(np.float32)
        unit.input.mem = x
        unit.initialize(device=XLADevice())
        unit.xla_run()
    finally:
        root.common.engine.pallas = False
        root.common.engine.pallas_interpret = False
    y = np.asarray(unit.output.map_read())
    off = np.asarray(unit.input_offset.map_read())
    flat = x.reshape(3, -1, 4)
    n, oh, ow, c = y.shape
    for ni in range(n):
        for ci in range(c):
            picked = flat[ni, off[ni, :, :, ci].ravel(), ci]
            np.testing.assert_allclose(picked, y[ni, :, :, ci].ravel(),
                                       rtol=1e-6)


def test_flash_attention_matches_dense():
    """Flash forward == dense-softmax oracle (causal and full), and the
    custom-VJP gradients match autograd-through-the-oracle."""
    import jax

    from znicz_tpu.ops import attention as att
    from znicz_tpu.ops.pallas import flash_attention

    rng = np.random.default_rng(4)
    b, t, h, dh = 2, 256, 2, 64
    q = rng.normal(size=(b, t, h, dh)).astype(np.float32)
    k = rng.normal(size=(b, t, h, dh)).astype(np.float32)
    v = rng.normal(size=(b, t, h, dh)).astype(np.float32)

    for causal in (False, True):
        def oracle(q, k, v):
            return att.attention(jnp, q, k, v, causal=causal).sum()

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=causal,
                                   interpret=True).sum()

        o_ref = att.attention(jnp, jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal)
        o_pl = flash_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=causal,
                               interpret=True)
        np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
        g_ref = jax.grad(oracle, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g_pl = jax.grad(flash, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b_ in zip(g_pl, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4)


def test_flash_attention_supported_gate():
    from znicz_tpu.ops.pallas.attention import supported

    assert supported(2048, 64)
    assert supported(256, 128)
    assert not supported(100, 64)      # t not q-blockable
    assert not supported(256, 48)      # head dim not lane-aligned
    assert not supported(1 << 20, 64)  # VMEM budget


@pytest.mark.parametrize("t,dh,causal,h", [
    (384, 256, True, 2), (384, 64, True, 2), (640, 64, True, 2),
    (256, 128, False, 2), (384, 128, True, 16)],
    ids=["3x128_dh256", "3x128_dh64", "5x128_dh64", "full_dh128",
         "3x128_16_heads_of_128_direct"])
def test_blocked_flash_attention_matches_dense(t, dh, causal, h):
    """The key/value-blocked kernels (interpreted), forward and the three
    gradients, against dense attention: heads of 256 and 64, a time axis
    that is 3 and 5 blocks (no power of two times the block), causal (only
    the tiles under the diagonal are visited) and full; and 16 heads of
    128 read from the layer's own ``(b, t, h, dh)``, the shape a looped
    dense stack brings (the other cases fold their operands head-major)."""
    import jax

    from znicz_tpu.ops import attention as att
    from znicz_tpu.ops.pallas import attention as pattn

    b, direct = 1, h == 16
    ks = jax.random.split(jax.random.PRNGKey(t + dh), 4)
    q, k, v, ct = (jax.random.normal(kk, (b, t, h, dh)) for kk in ks)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, dh)  # noqa

    def blocked(q, k, v):
        if direct:
            return pattn._flash_kvb(q, k, v, causal, True)
        o = pattn._flash_kvb(fold(q), fold(k), fold(v), causal, True)
        return o.reshape(b, h, t, dh).transpose(0, 2, 1, 3)

    def dense(q, k, v):
        return att.attention(jnp, q, k, v, causal=causal)

    block = pattn._kvb_block(t, dh, "fwd")
    assert block == (128 if t % 256 else 256)
    n = t // block
    qi, ki, flags = pattn._visits(t, block, causal, False)
    assert len(qi) == (n * (n + 1) // 2 if causal else n * n)
    assert not causal or all(ki <= qi)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(blocked(q, k, v), dense(q, k, v),
                                   atol=2e-5)
        got = jax.grad(lambda *a: (blocked(*a) * ct).sum(), (0, 1, 2))(
            q, k, v)
        want = jax.grad(lambda *a: (dense(*a) * ct).sum(), (0, 1, 2))(
            q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


def _random_selection(key, b, t, density=0.3, dead=()):
    """int8 ``(b, t, t)``: a random subset of the causal pairs, the
    diagonal always in it, and the ``(rows, columns)`` slices of ``dead``
    emptied below the diagonal."""
    import jax

    causal = jnp.tril(jnp.ones((t, t), bool))
    sel = (jax.random.uniform(key, (b, t, t)) < density) & causal
    for rows, cols in dead:
        sel = sel.at[:, rows, cols].set(False)
    return (sel | jnp.eye(t, dtype=bool)).astype(jnp.int8)


def _dense_selected(q, k, v, sel):
    import jax

    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    a = jax.nn.softmax(jnp.where(sel[:, None] != 0, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", a, v)


@pytest.mark.parametrize("b,t,h,kv,dh", [
    (2, 256, 4, 2, 64), (1, 384, 2, 2, 128), (1, 256, 4, 1, 128),
    (1, 640, 2, 2, 64)],
    ids=["folded_grouped_dh64", "direct_3x128_dh128",
         "direct_grouped_dh128", "folded_5x128_dh64"])
def test_blocked_flash_attention_with_a_selection_matches_dense(b, t, h, kv,
                                                                dh):
    """The blocked kernels with a selection operand (interpreted), forward
    and the three gradients, against dense attention masked by the same
    selection: folded and the layer's own layout, grouped key/value heads
    in both (in the layer's layout the group is repeated inside the rule
    and dk, dv come back a key/value head each), a time axis of 3 and 5
    blocks, rows whose first tiles hold none of their keys, and a tile
    below the diagonal that holds no selected pair at all."""
    import jax

    from znicz_tpu.ops.pallas import attention as pattn

    ks = jax.random.split(jax.random.PRNGKey(t + dh + kv), 5)
    q = jax.random.normal(ks[0], (b, t, h, dh))
    k, v = (jax.random.normal(kk, (b, t, kv, dh)) for kk in ks[1:3])
    ct = jax.random.normal(ks[3], (b, t, h, dh))
    # rows from 200 on see nothing of the first 128 keys; rows 128-255 see
    # nothing of keys 0-127 at all: tile (1, 0) is empty
    sel = _random_selection(ks[4], b, t, dead=(
        (slice(200, None), slice(0, 128)), (slice(128, 256), slice(0, 128))))
    assert pattn.blocked_unsupported_reason(t, dh) is None
    block = pattn._kvb_block(t, dh, "fwd", True)
    assert block == (128 if t % 256 else 256)

    def blocked(q, k, v):
        return pattn.flash_attention(q, k, v, True, interpret=True, sel=sel)

    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: blocked(*a).sum(), (0, 1, 2)))(q, k, v))
    for name in pattn.KVB_SEL_KERNEL_NAMES.values():
        assert name in text
    assert f"{pattn.KVB_FWD_KERNEL_NAME} " not in text
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(blocked(q, k, v),
                                   _dense_selected(q, k, v, sel), atol=2e-5)
        got = jax.grad(lambda *a: (blocked(*a) * ct).sum(), (0, 1, 2))(
            q, k, v)
        want = jax.grad(lambda *a: (_dense_selected(*a, sel) * ct).sum(),
                        (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_a_causal_selection_is_the_causal_kernels_result():
    """With every causal pair selected the kernels with a selection give
    what the causal kernels give, forward and backward."""
    from unittest import mock

    import jax

    from znicz_tpu.ops.pallas import attention as pattn

    b, t, h, dh = 1, 256, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q, k, v, ct = (jax.random.normal(kk, (b, t, h, dh)) for kk in ks)
    sel = jnp.tril(jnp.ones((b, t, t), jnp.int8))
    with mock.patch.object(pattn, "unsupported_reason",
                           lambda t, dh: "refused for the test"):
        plain = lambda *a: pattn.flash_attention(            # noqa: E731
            *a, True, interpret=True)
        picked = lambda *a: pattn.flash_attention(           # noqa: E731
            *a, True, interpret=True, sel=sel)
        np.testing.assert_allclose(picked(q, k, v), plain(q, k, v),
                                   atol=1e-6)
        got = jax.grad(lambda *a: (picked(*a) * ct).sum(), (0, 1, 2))(
            q, k, v)
        want = jax.grad(lambda *a: (plain(*a) * ct).sum(), (0, 1, 2))(
            q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


def test_a_selection_needs_the_blocked_form_and_a_causal_call():
    from znicz_tpu.ops.pallas import attention as pattn

    q = jnp.zeros((1, 192, 2, 64))
    with pytest.raises(ValueError, match="selection.*128-row"):
        pattn.flash_attention(q, q, q, True, interpret=True,
                              sel=jnp.ones((1, 192, 192), jnp.int8))
    q = jnp.zeros((1, 256, 2, 64))
    with pytest.raises(ValueError, match="causal=True"):
        pattn.flash_attention(q, q, q, False, interpret=True,
                              sel=jnp.ones((1, 256, 256), jnp.int8))
    # the selection's tile is in the working set the tile is chosen by:
    # 6 bytes an entry, and 1,024 rows still fit at a head of 128
    for pass_ in ("fwd", "dkv", "dq"):
        assert pattn._kvb_vmem(pass_, 1024, 128, True) - \
            pattn._kvb_vmem(pass_, 1024, 128) == 6 * 1024 * 1024
        assert pattn._kvb_block(16384, 128, pass_, True) == 1024
    assert pattn.kvb_block_rows(4096, 64) == {"fwd": 0, "dkv": 0, "dq": 0}
    assert pattn.kvb_block_rows(4096, 64, True) == {
        "fwd": 1024, "dkv": 1024, "dq": 1024}


@pytest.mark.parametrize("pass_", ["fwd", "dkv", "dq"])
@pytest.mark.parametrize("dh", [64, 128, 256, 512])
@pytest.mark.parametrize("t", [256, 384, 640, 768, 1024, 3072, 4096])
def test_blocked_tile_follows_the_time_axis_the_head_and_the_pass(t, dh,
                                                                  pass_):
    """The chooser: a block that divides ``t`` and whose working set (the
    formula beside the limit) is within ``_KVB_VMEM_LIMIT``; a time axis
    that 1,024 does not divide gets what it got (the largest of 512, 256,
    128 that divides it: 128 at 384 and 640, 256 at 256 and 768), one that
    it divides 1,024 rows, the largest that divides and fits (dk/dv at
    head 512 is the formula's 32 MiB, the limit), in every pass but dk/dv
    from head 256 on, which the sweep showed no faster there and keeps at
    512."""
    from znicz_tpu.ops.pallas import attention as pattn

    block = pattn._kvb_block(t, dh, pass_)
    assert block and t % block == 0
    assert pattn._kvb_vmem(pass_, block, dh) <= pattn._KVB_VMEM_LIMIT
    before = next(b for b in (512, 256, 128) if t % b == 0)
    kept = pass_ == "dkv" and dh >= 256
    assert block == (1024 if t % 1024 == 0 and not kept else before)
    # no larger candidate both divides and fits, but where 512 is kept
    larger = [b for b in pattn._KVB_BLOCKS if b > block and t % b == 0 and
              pattn._kvb_vmem(pass_, b, dh) <= pattn._KVB_VMEM_LIMIT]
    assert larger == ([1024] if kept and t % 1024 == 0 else [])
    # the formula grows with every argument it has: twice the rows of the
    # largest tile would not fit in any pass at any head
    assert pattn._kvb_vmem(pass_, 2048, dh) > pattn._KVB_VMEM_LIMIT
    assert pattn._kvb_vmem(pass_, block, dh) < \
        pattn._kvb_vmem(pass_, block, dh + 64)


def test_blocked_tile_rows_are_zero_where_the_form_is_not_blocked():
    from znicz_tpu.ops.pallas import attention as pattn

    assert pattn.kvb_block_rows(4096, 128) == {
        "fwd": 1024, "dkv": 1024, "dq": 1024}
    assert pattn.kvb_block_rows(4096, 256) == {
        "fwd": 1024, "dkv": 512, "dq": 1024}
    assert pattn.kvb_block_rows(8192 + 512, 64) == {
        "fwd": 512, "dkv": 512, "dq": 512}
    # the whole-row form's shapes, and a refused one
    assert pattn.kvb_block_rows(4096, 64) == {"fwd": 0, "dkv": 0, "dq": 0}
    assert pattn.kvb_block_rows(4096, 576) == {"fwd": 0, "dkv": 0, "dq": 0}


@pytest.mark.parametrize("t,dh,causal,direct,blocks", [
    (1024, 128, True, True, None), (2048, 128, True, True, None),
    (2048, 64, True, False, None), (1024, 64, False, False, None),
    (2048, 256, False, True, None),
    (2048, 128, True, True, {"fwd": 1024, "dkv": 512, "dq": 256})],
    ids=["one_cut_tile_direct", "three_tiles_two_cut_direct",
         "three_tiles_two_cut_folded", "one_tile_full_folded",
         "four_tiles_full_direct_dh256", "each_pass_its_own_block"])
def test_blocked_flash_attention_at_1024_row_tiles_matches_dense(
        t, dh, causal, direct, blocks):
    """Forward and the three gradients (interpreted) against the dense
    core at shapes whose tile is 1,024 rows: one tile that the diagonal
    cuts; three of which two are cut; full attention over one and four
    (at head 256, where dk/dv runs 16 tiles of 512 beside them); the
    layer's own layout and the folded one; and the three passes each on a
    block of its own (the chooser patched)."""
    import contextlib
    import jax
    from unittest import mock

    from znicz_tpu.ops import attention as att
    from znicz_tpu.ops.pallas import attention as pattn

    b, h = 1, 3 if blocks else 2
    ks = jax.random.split(jax.random.PRNGKey(t + dh + causal), 4)
    q, k, v, ct = (jax.random.normal(kk, (b, t, h, dh)) for kk in ks)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, dh)  # noqa

    def blocked(q, k, v):
        if direct:
            return pattn._flash_kvb(q, k, v, causal, True)
        o = pattn._flash_kvb(fold(q), fold(k), fold(v), causal, True)
        return o.reshape(b, h, t, dh).transpose(0, 2, 1, 3)

    def dense(q, k, v):
        return att.attention(jnp, q, k, v, causal=causal)

    patched = mock.patch.object(
        pattn, "_kvb_block", lambda t, dh, pass_, sel=False: blocks[pass_]) \
        if blocks else contextlib.nullcontext()
    if not blocks:
        assert pattn.kvb_block_rows(8192 + t, dh) == {
            "fwd": 1024, "dkv": 512 if dh == 256 else 1024, "dq": 1024}
        n = t // 1024
        flags = pattn._visits(t, 1024, causal, False)[2]
        assert len(flags) == (n * (n + 1) // 2 if causal else n * n)
        assert sum((flags & pattn._CUT) != 0) == (n if causal else 0)
    with patched, jax.default_matmul_precision("highest"):
        if blocks:                      # this shape is the test's alone
            text = str(jax.make_jaxpr(jax.grad(
                lambda *a: blocked(*a).sum(), (0, 1, 2)))(q, k, v))
            for rows in blocks.values():
                assert f"f32[{rows},{dh}]" in text     # its accumulator
        np.testing.assert_allclose(blocked(q, k, v), dense(q, k, v),
                                   atol=2e-5)
        got = jax.grad(lambda *a: (blocked(*a) * ct).sum(), (0, 1, 2))(
            q, k, v)
        want = jax.grad(lambda *a: (dense(*a) * ct).sum(), (0, 1, 2))(
            q, k, v)
    if blocks:                  # no later test meets the patched programs
        pattn._kvb_call_fwd.clear_cache()
        pattn._kvb_call_bwd.clear_cache()
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_blocked_flash_attention_repeats_grouped_key_value_heads():
    """``flash_attention`` at a shape only the blocked form takes, with
    fewer key/value heads: the heads are repeated for the kernels and the
    gradients sum back over each group."""
    import jax
    from unittest import mock

    from znicz_tpu.ops.pallas import attention as pattn

    b, t, kv, group, dh = 1, 256, 1, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, t, kv * group, dh))
    k = jax.random.normal(ks[1], (b, t, kv, dh))
    v = jax.random.normal(ks[2], (b, t, kv, dh))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v, causal=True,
                                   interpret=True) ** 2).sum()

    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(pattn.flash_attention), (0, 1, 2))(q, k, v)
        with mock.patch.object(pattn, "unsupported_reason",
                               lambda t, dh: "refused for the test"):
            assert pattn.form_of(t, dh) == ("blocked", None)
            text = str(jax.make_jaxpr(pattn.flash_attention)(q, k, v))
            got = jax.grad(loss(pattn.flash_attention), (0, 1, 2))(q, k, v)
    assert pattn.KVB_FWD_KERNEL_NAME in text
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4)


def _refuse_rows():
    """``form_of`` gives a small test shape the blocked form."""
    from unittest import mock

    from znicz_tpu.ops.pallas import attention as pattn

    return mock.patch.object(pattn, "unsupported_reason",
                             lambda t, dh: "refused for the test")


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("dh", [128, 256])
def test_blocked_direct_layout_is_the_folded_layout_to_the_bit(dh, causal):
    """The blocked kernels over the layer's own ``(b, t, h, dh)`` (blocks
    cut by head-indexed maps from ``(b, t, h * dh)``) against the same
    kernels over operands folded head-major: the output and the three
    gradients bit for bit, at 2 batches x 3 heads (``i // heads``, ``i %
    heads`` both move) and a time axis of 3 blocks."""
    import jax

    from znicz_tpu.ops.pallas import attention as pattn

    b, t, h = 2, 384, 3
    ks = jax.random.split(jax.random.PRNGKey(dh + causal), 4)
    q, k, v, ct = (jax.random.normal(kk, (b, t, h, dh)).astype(jnp.bfloat16)
                   for kk in ks)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, dh)  # noqa

    def direct(q, k, v):
        return pattn._flash_kvb(q, k, v, causal, True)

    def folded(q, k, v):
        o = pattn._flash_kvb(fold(q), fold(k), fold(v), causal, True)
        return o.reshape(b, h, t, dh).transpose(0, 2, 1, 3)

    assert pattn._kvb_block(t, dh, "dkv") == 128
    with _refuse_rows():
        assert pattn.direct_layout(t, dh)
    np.testing.assert_array_equal(direct(q, k, v), folded(q, k, v))
    grads = [jax.grad(lambda *a: (f(*a).astype(jnp.float32) *
                                  ct.astype(jnp.float32)).sum(),
                      (0, 1, 2))(q, k, v) for f in (direct, folded)]
    for got, want in zip(*grads):
        assert got.shape == (b, t, h, dh)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dh", [64, 192])
def test_heads_that_are_no_multiple_of_128_still_fold(dh):
    """A ``(1, block, 64)`` or ``(1, block, 192)`` block of ``(b, t, h *
    dh)`` is no legal TPU block: such heads reach the blocked kernels
    folded head-major, as before."""
    import jax

    from znicz_tpu.ops.pallas import attention as pattn

    b, t, h = 1, 256, 2
    q = jax.random.normal(jax.random.PRNGKey(dh), (b, t, h, dh))
    with _refuse_rows():
        assert pattn.form_of(t, dh) == ("blocked", None)
        assert not pattn.direct_layout(t, dh)
        text = str(jax.make_jaxpr(lambda q: pattn.flash_attention(
            q, q, q, causal=True, interpret=True))(q))
    assert pattn.KVB_FWD_KERNEL_NAME in text
    assert f"[{b * h},{t},{dh}]" in text and "transpose" in text
    # and the whole-row form's shapes are not asked: they fold as ever
    assert not pattn.direct_layout(2048, 128)
    assert pattn.direct_layout(4096, 256) and pattn.direct_layout(8192, 128)
    assert not pattn.direct_layout(8192, 64)


def test_blocked_direct_layout_repeats_grouped_key_value_heads():
    """Fewer key/value heads in the direct layout (repeated on axis 2
    before the view) equal the dense result over repeated heads, and the
    gradients sum back over each group."""
    import jax

    from znicz_tpu.ops import attention as att
    from znicz_tpu.ops.pallas import attention as pattn

    b, t, kv, group, dh = 2, 256, 2, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, ct = (jax.random.normal(kk, (b, t, kv * group, dh)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (b, t, kv, dh)) for kk in ks[2:])

    def dense(q, k, v):
        k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
        return att.attention(jnp, q, k, v, causal=True)

    def flash(q, k, v):
        return pattn.flash_attention(q, k, v, causal=True, interpret=True)

    with jax.default_matmul_precision("highest"), _refuse_rows():
        assert pattn.direct_layout(t, dh)
        np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
        got, want = (jax.grad(lambda *a: (f(*a) * ct).sum(), (0, 1, 2))(
            q, k, v) for f in (flash, dense))
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=5e-5)


@pytest.mark.parametrize("under_grad", [False, True], ids=["fwd", "grad"])
def test_direct_layout_program_transposes_no_operand(under_grad):
    """The jaxpr of ``flash_attention`` at head 256, forward and under
    ``jax.grad``: no ``transpose`` of a ``(b, t, heads, head_dim)``
    operand (the one array turned head-major is ``delta``'s float32
    ``(b, heads, t)``, stacked head by head), and the three kernels under
    the names ``benchmark/kernels/flash_attention_mla.py`` looks for."""
    import jax

    from znicz_tpu.ops.pallas import attention as pattn

    b, t, h, dh = 2, 256, 3, 256
    q = jax.random.normal(jax.random.PRNGKey(0), (b, t, h, dh),
                          jnp.bfloat16)

    def fn(q, k, v):
        return pattn.flash_attention(q, k, v, causal=True, interpret=True)

    if under_grad:
        fn = jax.grad(lambda *a, f=fn: f(*a).astype(jnp.float32).sum(),
                      (0, 1, 2))
    with _refuse_rows():
        jaxpr = jax.make_jaxpr(fn)(q, q, q)

    def eqns(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    names = {e.params["name"] for e in eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"}
    want = {"flash_attention_kvb_fwd"}
    if under_grad:
        want |= {"flash_attention_kvb_dkv", "flash_attention_kvb_dq"}
    assert names == want
    assert want <= {pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
                    pattn.KVB_DQ_KERNEL_NAME}
    moved = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "transpose"]
    assert all(e.invars[0].aval.ndim < 4 and
               e.invars[0].aval.size <= b * t * h for e in moved), moved


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("dh,rope", [(256, 64), (128, 32), (128, 128)])
def test_rope_tail_rotates_each_heads_tail_and_nothing_else(dh, rope, dtype):
    """The in-place row kernel (interpreted) against cutting every head,
    rotating its tail in halves order and concatenating: the same array,
    the columns before the tail untouched to the bit, and the gradient
    the rotation back."""
    import jax

    from znicz_tpu.ops.pallas import rope as prope
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.blocks import _rope_angles, _rotate

    b, t, h = 2, 48, 3
    ks = jax.random.split(jax.random.PRNGKey(dh + rope), 2)
    x, ct = (jax.random.normal(k, (b, t, h * dh)).astype(dtype) for k in ks)
    cos, sin = _rope_angles(t, rope, 10000.0)
    assert prope.unsupported_reason(t, dh, rope) is None

    def cut(x):
        x4 = x.reshape(b, t, h, dh)
        return jnp.concatenate([x4[..., :dh - rope], _rotate(
            x4[..., dh - rope:], 10000.0)], axis=-1).reshape(x.shape)

    def rows(x):
        return prope.rope_tail(x, cos, sin, h, True)

    # (a fused multiply-add here or there: the last bit may differ)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(rows(x).astype(jnp.float32),
                               cut(x).astype(jnp.float32), atol=tol)
    np.testing.assert_array_equal(
        rows(x).reshape(b, t, h, dh)[..., :dh - rope],
        x.reshape(b, t, h, dh)[..., :dh - rope])
    got, want = (jax.grad(lambda x, f=f: (f(x).astype(jnp.float32) *
                                          ct.astype(jnp.float32)).sum())(x)
                 for f in (rows, cut))
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=tol)


@pytest.mark.parametrize("t,dh,rope,word", [
    (4096, 192, 64, "head_dim=192"), (4096, 256, 192, "192 columns"),
    (4096, 256, 7, "7 columns"), (100, 256, 64, "t=100")])
def test_rope_tail_unsupported_reason_names_the_refused_shape(t, dh, rope,
                                                              word):
    from znicz_tpu.ops.pallas import rope as prope

    assert word in prope.unsupported_reason(t, dh, rope)
    assert prope.unsupported_reason(4096, 256, 64) is None


def test_flash_attention_keeps_the_whole_row_form_wherever_it_accepted():
    """At every shape the whole-row kernels took (the benchmark's GQA
    layer: head 64 at 4,096) ``flash_attention`` is the program it was,
    to the bit: the same two kernels and none of the blocked ones."""
    import jax

    from znicz_tpu.ops.pallas import attention as pattn

    assert pattn.form_of(4096, 64) == ("rows", None)
    assert pattn.unsupported_reason(4096, 64) is None
    assert pattn.unsupported_reason(4096, 256) is not None
    assert pattn.blocked_unsupported_reason(4096, 256) is None
    b, t, h, kv, dh = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (b, t, h, dh))
    k, v = (jax.random.normal(kk, (b, t, kv, dh)) for kk in ks[1:])
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(   # noqa: E731
        b * x.shape[2], t, dh)
    grad = jax.grad(lambda *a: pattn.flash_attention(
        *a, causal=True, interpret=True).sum(), (0, 1, 2))
    text = str(jax.make_jaxpr(grad)(q, k, v))
    assert pattn.FWD_KERNEL_NAME in text and pattn.BWD_KERNEL_NAME in text
    assert "kvb" not in text
    direct = pattn._flash(fold(q), fold(k), fold(v), True, True)
    np.testing.assert_array_equal(
        pattn.flash_attention(q, k, v, causal=True, interpret=True),
        direct.reshape(b, h, t, dh).transpose(0, 2, 1, 3))


@pytest.mark.parametrize("t,dh,word", [
    (100, 64, "t=100"), (4096, 48, "head_dim=48"),
    (4096, 1024, "head_dim=1024"), (4096, 576, "needs 34 MiB")])
def test_blocked_unsupported_reason_names_the_refused_shape(t, dh, word):
    """The refusal of a wide head is the chooser's own formula: head 512
    is the widest of which every pass holds a 1,024-row block (dk/dv: 32
    MiB, the limit to the byte), as it was the widest before."""
    from znicz_tpu.ops.pallas import attention as pattn

    assert word in pattn.blocked_unsupported_reason(t, dh)
    assert [dh for dh in range(64, 1088, 64)
            if pattn.blocked_unsupported_reason(4096, dh) is None] == \
        list(range(64, 576, 64))
    assert pattn._kvb_vmem("dkv", 1024, 512) == pattn._KVB_VMEM_LIMIT
    assert pattn.form_of(t, dh)[0] is None
    with pytest.raises(ValueError, match=word):
        pattn.flash_attention(jnp.zeros((1, t, 1, dh)),
                              jnp.zeros((1, t, 1, dh)),
                              jnp.zeros((1, t, 1, dh)))


def test_fused_adam_matches_oracle():
    from znicz_tpu.ops import adam as adam_ops
    from znicz_tpu.ops.pallas import fused_adam_update

    rng = np.random.default_rng(9)
    for shape in ((64, 128), (7, 33), (3, 5, 16)):
        w = rng.normal(size=shape).astype(np.float32)
        g = rng.normal(size=shape).astype(np.float32)
        m = rng.normal(size=shape).astype(np.float32) * 0.1
        v = np.abs(rng.normal(size=shape)).astype(np.float32) * 0.01
        args = (3.0, 0.01, 0.001, 0.9, 0.999, 1e-8, 32.0)
        w_ref, m_ref, v_ref = adam_ops.update(
            jnp, jnp.asarray(w), jnp.asarray(g), jnp.asarray(m),
            jnp.asarray(v), *args)
        w_pl, m_pl, v_pl = fused_adam_update(
            jnp.asarray(w), jnp.asarray(g), jnp.asarray(m),
            jnp.asarray(v), *args, interpret=True)
        for got, want in ((w_pl, w_ref), (m_pl, m_ref), (v_pl, v_ref)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-6)


def test_pallas_adam_workflow_matches_xla():
    """optimizer=adam + engine.pallas: the fused step runs the Pallas
    adam kernel (interpret mode) and matches the XLA path's training."""
    from znicz_tpu.core.config import root
    from znicz_tpu.core import prng as prng_mod
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.standard_workflow import StandardWorkflow

    def run(pallas: bool):
        prng_mod.seed_all(66)
        root.common.engine.pallas = pallas
        root.common.engine.pallas_interpret = pallas
        try:
            w = StandardWorkflow(
                name="PAdam", loss_function="softmax", layers=[
                    {"type": "all2all_tanh",
                     "->": {"output_sample_shape": 8}},
                    {"type": "softmax", "->": {"output_sample_shape": 3}}],
                loader_name="synthetic_classifier",
                loader_config={"n_classes": 3, "sample_shape": (4,),
                               "n_train": 30, "n_valid": 0,
                               "minibatch_size": 30},
                decision_config={"max_epochs": 3}, optimizer="adam")
            w.initialize(device=XLADevice())
            w.run()
            w.step.sync_to_units()
            return np.asarray(w.forwards[0].weights.map_read()).copy()
        finally:
            root.common.engine.pallas = False
            root.common.engine.pallas_interpret = False

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5,
                               atol=1e-6)


# -- round-4 parity tail: conv backward (col2im-as-gather) + deconv pair -----

from znicz_tpu.ops import activations, deconv as deconv_ops
from znicz_tpu.ops.pallas import (conv2d_backward, deconv2d,
                                  deconv2d_backward)


@pytest.mark.parametrize("geom", CONV_GEOMS)
def test_pallas_conv_backward_matches_oracle(geom):
    """err_input/grad_w/grad_b vs the XLA vjp oracle (the linear part of
    ops.conv.backward) across strides and asymmetric padding."""
    h, w, cin, cout, k, sliding, padding = geom
    rng = np.random.default_rng(11)
    x = rng.normal(size=(3, h, w, cin)).astype(np.float32)
    wts = rng.normal(size=(k, k, cin, cout)).astype(np.float32) * 0.1
    out_shape = conv_ops.forward_linear(np, x, wts, None, sliding,
                                        padding).shape
    err = rng.normal(size=out_shape).astype(np.float32)
    ei_ref, gw_ref, gb_ref = conv_ops.backward(
        jnp, jnp.asarray(x), None, jnp.asarray(wts), jnp.asarray(err),
        sliding, padding, activations.LINEAR, activation_applied=False)
    ei_pl, gw_pl, gb_pl = conv2d_backward(
        jnp.asarray(x), jnp.asarray(wts), jnp.asarray(err), sliding,
        padding, interpret=True)
    np.testing.assert_allclose(np.asarray(ei_pl), np.asarray(ei_ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_pl), np.asarray(gw_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb_pl), np.asarray(gb_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("geom", CONV_GEOMS)
def test_pallas_deconv_matches_oracle(geom):
    """deconv2d forward == ops.deconv.forward; deconv2d_backward ==
    ops.deconv.backward (err_input + grad_w), same geometries."""
    h, w, cin, cout, k, sliding, padding = geom
    rng = np.random.default_rng(12)
    wts = rng.normal(size=(k, k, cin, cout)).astype(np.float32) * 0.1
    oh = conv_ops.out_size(h, k, sliding[0], *(padding[0], padding[1]))
    ow = conv_ops.out_size(w, k, sliding[1], *(padding[2], padding[3]))
    x = rng.normal(size=(3, oh, ow, cout)).astype(np.float32)
    out_shape = deconv_ops.output_shape_for(x.shape, wts.shape, sliding,
                                            padding)
    y_ref = deconv_ops.forward(jnp, jnp.asarray(x), jnp.asarray(wts),
                               sliding, padding, out_shape)
    y_pl = deconv2d(jnp.asarray(x), jnp.asarray(wts), sliding, padding,
                    out_shape, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)
    err = rng.normal(size=out_shape).astype(np.float32)
    ei_ref, gw_ref = deconv_ops.backward(
        jnp, jnp.asarray(x), jnp.asarray(wts), jnp.asarray(err), sliding,
        padding)
    ei_pl, gw_pl = deconv2d_backward(
        jnp.asarray(x), jnp.asarray(wts), jnp.asarray(err), sliding,
        padding, interpret=True)
    np.testing.assert_allclose(np.asarray(ei_pl), np.asarray(ei_ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_pl), np.asarray(gw_ref),
                               rtol=1e-4, atol=1e-4)


def test_pallas_gd_conv_unit_selection():
    """root.common.engine.pallas routes GradientDescentConv (incl. the
    tanh activation correction) through the hand-written backward with
    identical training effect."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.core.memory import Array
    from znicz_tpu.core.workflow import Workflow
    from znicz_tpu.units.conv import ConvTanh
    from znicz_tpu.units.gd_conv import GDTanhConv

    def run_once():
        prng.seed_all(14)
        rng = np.random.default_rng(2)
        w = Workflow(name="g")
        fwd = ConvTanh(w, n_kernels=6, kx=3, ky=3, sliding=(2, 2),
                       padding=(1, 1, 1, 1))
        fwd.input = Array(rng.normal(size=(3, 8, 8, 2)).astype(np.float32))
        fwd.initialize(device=XLADevice())
        fwd.run()
        gd = GDTanhConv(w, learning_rate=0.1, weights_decay=0.01,
                        gradient_moment=0.9)
        gd.link_from_forward(fwd)
        gd.err_output = Array(rng.normal(size=fwd.output.shape)
                              .astype(np.float32))
        gd.batch_size = 3
        gd.initialize(device=XLADevice())
        gd.run()
        return {a: np.asarray(getattr(gd, a).map_read()).copy()
                for a in ("err_input", "weights", "bias",
                          "gradient_weights", "gradient_bias")}

    base = run_once()
    root.common.engine.pallas = True
    root.common.engine.pallas_interpret = True
    try:
        pallas = run_once()
    finally:
        root.common.engine.pallas = False
        root.common.engine.pallas_interpret = False
    for attr, want in base.items():
        np.testing.assert_allclose(pallas[attr], want, rtol=1e-4,
                                   atol=1e-5, err_msg=attr)


def test_pallas_deconv_unit_selection():
    """root.common.engine.pallas routes Deconv + GDDeconv through the
    hand-written transposed-conv pair with identical results."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.core.memory import Array
    from znicz_tpu.core.workflow import Workflow
    from znicz_tpu.units.deconv import Deconv
    from znicz_tpu.units.gd_deconv import GDDeconv

    def run_once():
        prng.seed_all(15)
        rng = np.random.default_rng(4)
        w = Workflow(name="d")
        fwd = Deconv(w, n_kernels=6, kx=3, ky=3, n_channels=2,
                     sliding=(2, 2), padding=(1, 1, 1, 1))
        fwd.input = Array(rng.normal(size=(2, 4, 4, 6)).astype(np.float32))
        fwd.initialize(device=XLADevice())
        fwd.run()
        gd = GDDeconv(w, learning_rate=0.1, gradient_moment=0.9)
        gd.link_from_forward(fwd)
        gd.err_output = Array(rng.normal(size=fwd.output.shape)
                              .astype(np.float32))
        gd.batch_size = 2
        gd.initialize(device=XLADevice())
        gd.run()
        return {"out": np.asarray(fwd.output.map_read()).copy(),
                "err_input": np.asarray(gd.err_input.map_read()).copy(),
                "weights": np.asarray(gd.weights.map_read()).copy(),
                "vel": np.asarray(gd.gradient_weights.map_read()).copy()}

    base = run_once()
    root.common.engine.pallas = True
    root.common.engine.pallas_interpret = True
    try:
        pallas = run_once()
    finally:
        root.common.engine.pallas = False
        root.common.engine.pallas_interpret = False
    for attr, want in base.items():
        np.testing.assert_allclose(pallas[attr], want, rtol=1e-4,
                                   atol=1e-5, err_msg=attr)


# -- round-4 parity tail 2: the blocked FC GEMM (matrix_multiplication) ------

from znicz_tpu.ops import linear as lin_ops
from znicz_tpu.ops.pallas import fc_backward, fc_forward

FC_GEOMS = [(32, 784, 100), (7, 13, 3), (129, 200, 257), (8, 128, 128)]


@pytest.mark.parametrize("geom", FC_GEOMS)
@pytest.mark.parametrize("act", ["linear", "tanh", "relu", "strict_relu",
                                 "sigmoid"])
def test_pallas_fc_gemm_matches_oracle(geom, act):
    """Blocked-GEMM fc forward/backward vs ops.linear across padded and
    exact-block geometries and every fused activation."""
    B, F, O = geom
    rng = np.random.default_rng(13)
    x = rng.normal(size=(B, F)).astype(np.float32)
    w = (rng.normal(size=(F, O)) * 0.05).astype(np.float32)
    b = rng.normal(size=(O,)).astype(np.float32)
    y_ref = lin_ops.forward(np, x, w, b, act)
    y_pl = fc_forward(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), act,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(y_pl), y_ref, rtol=1e-4,
                               atol=1e-4)
    e = rng.normal(size=(B, O)).astype(np.float32)
    refs = lin_ops.backward(np, x, y_ref, w, e, act)
    outs = fc_backward(jnp.asarray(x), jnp.asarray(y_ref), jnp.asarray(w),
                       jnp.asarray(e), act, interpret=True)
    for name, got, want in zip(("err_input", "grad_w", "grad_b"), outs,
                               refs):
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                                   atol=2e-3, err_msg=name)


def test_pallas_fc_unit_selection():
    """engine.pallas routes All2AllTanh + GDTanh through the blocked
    GEMM kernels with identical training effect."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.core.memory import Array
    from znicz_tpu.core.workflow import Workflow
    from znicz_tpu.units.all2all import All2AllTanh
    from znicz_tpu.units.gd import GDTanh

    def run_once():
        prng.seed_all(19)
        rng = np.random.default_rng(7)
        w = Workflow(name="fc")
        fwd = All2AllTanh(w, output_sample_shape=24)
        fwd.input = Array(rng.normal(size=(16, 33)).astype(np.float32))
        fwd.initialize(device=XLADevice())
        fwd.run()
        gd = GDTanh(w, learning_rate=0.1, weights_decay=0.01,
                    gradient_moment=0.9)
        gd.link_from_forward(fwd)
        gd.err_output = Array(rng.normal(size=fwd.output.shape)
                              .astype(np.float32))
        gd.batch_size = 16
        gd.initialize(device=XLADevice())
        gd.run()
        return {a: np.asarray(getattr(gd, a).map_read()).copy()
                for a in ("err_input", "weights", "bias",
                          "gradient_weights", "gradient_bias")}

    base = run_once()
    root.common.engine.pallas = True
    root.common.engine.pallas_interpret = True
    try:
        pallas = run_once()
    finally:
        root.common.engine.pallas = False
        root.common.engine.pallas_interpret = False
    for attr, want in base.items():
        np.testing.assert_allclose(pallas[attr], want, rtol=2e-4,
                                   atol=2e-5, err_msg=attr)


def test_pallas_gd_override_cleared_on_numpy_reinit():
    """A gd unit initialized under engine.pallas on XLA, then
    re-initialized onto the numpy backend, must run the numpy oracle —
    not the stale Pallas closure (GradientDescentBase.numpy_init)."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import NumpyDevice, XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.core.memory import Array
    from znicz_tpu.core.workflow import Workflow
    from znicz_tpu.units.all2all import All2AllTanh
    from znicz_tpu.units.gd import GDTanh

    prng.seed_all(23)
    rng = np.random.default_rng(9)
    w = Workflow(name="t")
    fwd = All2AllTanh(w, output_sample_shape=8)
    fwd.input = Array(rng.normal(size=(4, 12)).astype(np.float32))
    root.common.engine.pallas = True
    root.common.engine.pallas_interpret = True
    try:
        fwd.initialize(device=XLADevice())
        fwd.run()
        gd = GDTanh(w, learning_rate=0.1)
        gd.link_from_forward(fwd)
        gd.err_output = Array(rng.normal(size=fwd.output.shape)
                              .astype(np.float32))
        gd.batch_size = 4
        gd.initialize(device=XLADevice())
        gd.run()
        assert "_backward" in gd.__dict__      # override installed
    finally:
        root.common.engine.pallas = False
        root.common.engine.pallas_interpret = False
    gd.initialize(device=NumpyDevice())
    assert "_backward" not in gd.__dict__      # override dropped
    gd.run()                                   # numpy oracle, no jax
    assert isinstance(gd.err_input.mem, np.ndarray)


def test_fused_sgd_narrow_state():
    """bf16 velocity storage through the kernel: f32 math in-tile, one
    narrow store, velocity dtype preserved (both tiled and fallback
    shapes)."""
    rng = np.random.default_rng(5)
    for shape in ((64, 128), (3, 5, 16)):
        w = jnp.asarray(rng.normal(size=shape), jnp.float32)
        g = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=shape) * 0.1, jnp.bfloat16)
        args = (0.05, 1e-3, 0.3, 0.9, 32.0)
        w_ref, v_ref = sgd_ops.update(jnp, w, g, v.astype(jnp.float32),
                                      *args)
        w_pl, v_pl = fused_sgd_update(w, g, v, *args, interpret=True)
        assert v_pl.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(w_pl), np.asarray(w_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(v_pl, dtype=np.float32),
            np.asarray(v_ref.astype(jnp.bfloat16), dtype=np.float32),
            rtol=1e-5, atol=1e-6)


# -- the grouped products (ops/pallas/grouped.py) -----------------------------
#: group sizes over a buffer of rows (tile 128): even; an empty group in
#: the middle and at the end; one group 3.9 x the mean; a boundary on a
#: tile's edge; 511 / 512 / 513 live rows against 512; nothing live
GROUPED_CASES = {
    "even": ([128, 128, 128, 128], 512),
    "empty_groups": ([100, 0, 300, 50, 0], 512),
    "one_of_3.9_means": ([499, 20, 20, 20, 190, 19, 1, 255], 1280),
    "boundary_on_a_tile_edge": ([128, 256, 3, 125], 640),
    "live_511_of_512": ([0, 511], 512),
    "live_512_of_512": ([200, 312], 512),
    "live_513_over_512": ([200, 313], 640),
    "nothing_live": ([0, 0, 0], 256),
}


def _grouped_operands(case, k=128, n=256):
    """-> ``(sizes, live, a, g, w)``: float32 operands whose rows past
    the last live one hold NaN."""
    sizes, rows = GROUPED_CASES[case]
    rng = np.random.default_rng(len(case))
    sizes = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(rows) < sizes.sum())[:, None]
    a = jnp.where(live, rng.normal(size=(rows, k)).astype(np.float32),
                  jnp.nan)
    g = jnp.where(live, rng.normal(size=(rows, n)).astype(np.float32),
                  jnp.nan)
    w = jnp.asarray(rng.normal(size=(sizes.shape[0], k, n)), jnp.float32)
    return sizes, live, a, g, w


@pytest.mark.parametrize("case", list(GROUPED_CASES))
@pytest.mark.parametrize("kernel", ["rows", "rows_t", "weights"])
def test_grouped_product_kernels_match_ragged_dot(kernel, case):
    """Each kernel against ``lax.ragged_dot`` / ``ragged_dot_general`` in
    float32; the buffer's tail holds NaN going in and comes out as zeros
    (and counts for nothing in the weights' gradient)."""
    import jax
    from jax import lax
    from znicz_tpu.ops.pallas import grouped
    from znicz_tpu.parallel.moe import _TO_WEIGHTS

    sizes, live, a, g, w = _grouped_operands(case)
    a0, g0 = jnp.where(live, a, 0), jnp.where(live, g, 0)
    with jax.default_matmul_precision("highest"):
        if kernel == "rows":
            got = grouped.gmm_rows(a, w, sizes, interpret=True)
            want = jnp.where(live, lax.ragged_dot(a0, w, sizes), 0)
        elif kernel == "rows_t":
            got = grouped.gmm_rows_t(g, w, sizes, interpret=True)
            want = jnp.where(live, jax.linear_transpose(
                lambda x: lax.ragged_dot(x, w, sizes), a0)(g0)[0], 0)
        else:
            got = grouped.gmm_weights(a, g, sizes, interpret=True)
            want = lax.ragged_dot_general(a0, g0, sizes, _TO_WEIGHTS)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-4)
    if kernel != "weights":
        assert not np.asarray(got)[int(sizes.sum()):].any()   # the tail


#: widths 128 lanes do not divide (over one lane tile, multiples of 8): the
#: block takes the width whole.  (k, n) as the weights lie: the last axis
#: unaligned (the pipeline brings the weights), the middle one (the kernel
#: fetches them itself), both
UNALIGNED_WIDTHS = {"n_192": (128, 192), "k_192": (192, 128),
                    "both_136_200": (136, 200)}


@pytest.mark.parametrize("widths", list(UNALIGNED_WIDTHS))
@pytest.mark.parametrize("kernel", ["rows", "rows_t", "weights"])
def test_grouped_product_kernels_take_a_width_128_does_not_divide(kernel,
                                                                  widths):
    """The three kernels at a width taken whole (Nemotron 3's experts are
    1,856 = 14.5 x 128 wide), against ``lax.ragged_dot`` in float32, the
    tail NaN going in and zeros coming out; no operand is padded."""
    import jax
    from jax import lax
    from znicz_tpu.ops.pallas import grouped
    from znicz_tpu.parallel.moe import _TO_WEIGHTS

    k, n = UNALIGNED_WIDTHS[widths]
    assert grouped.unsupported_reason(1280, k, n, 8, jnp.float32) is None
    sizes, live, a, g, w = _grouped_operands("one_of_3.9_means", k, n)
    a0, g0 = jnp.where(live, a, 0), jnp.where(live, g, 0)
    with jax.default_matmul_precision("highest"):
        if kernel == "rows":
            got = grouped.gmm_rows(a, w, sizes, interpret=True)
            want = jnp.where(live, lax.ragged_dot(a0, w, sizes), 0)
        elif kernel == "rows_t":
            got = grouped.gmm_rows_t(g, w, sizes, interpret=True)
            want = jnp.where(live, jax.linear_transpose(
                lambda x: lax.ragged_dot(x, w, sizes), a0)(g0)[0], 0)
        else:
            got = grouped.gmm_weights(a, g, sizes, interpret=True)
            want = lax.ragged_dot_general(a0, g0, sizes, _TO_WEIGHTS)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-4)
    if kernel != "weights":
        assert not np.asarray(got)[int(sizes.sum()):].any()   # the tail


def test_grouped_weights_gradient_cuts_k_where_n_cannot_be_cut(monkeypatch):
    """The weights' gradient takes ``k`` whole and slabs of ``n``; where 128
    does not divide ``n`` and the whole block is over the budget it takes
    ``n`` whole and slabs of ``k`` (the Nemotron cell's ``(2,688, 1,856)``
    float32 block is 19 MiB against 12), to the same values."""
    import jax
    from jax import lax
    from znicz_tpu.ops.pallas import grouped
    from znicz_tpu.parallel.moe import _TO_WEIGHTS

    assert grouped._weights_slabs(2688, 1856) == (896, 1856)
    assert grouped._weights_slabs(1856, 2688) == (1856, 896)
    assert grouped._weights_slabs(2048, 1536) == (2048, 1536)
    assert grouped.unsupported_reason(18432, 2688, 1856, 16,
                                      jnp.bfloat16) is None
    sizes, live, a, g, w = _grouped_operands("one_of_3.9_means", 256, 192)
    a, g = (jnp.where(live, v, 0).astype(jnp.bfloat16) for v in (a, g))
    monkeypatch.setattr(grouped, "_BLOCK_BYTES", 256 * 128 * 4)
    assert grouped._weights_slabs(256, 192) == (128, 192)
    got = grouped.gmm_weights(a, g, sizes, interpret=True)
    want = lax.ragged_dot_general(a, g, sizes, _TO_WEIGHTS,
                                  preferred_element_type=jnp.float32)
    # the same exact products of bfloat16 operands, summed in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("slabs", [1, 2], ids=["whole_block", "two_slabs"])
def test_grouped_product_is_differentiable_and_splits_wide_weights(
        slabs, monkeypatch):
    """``gmm``'s rules are the two other kernels; with a block budget of
    half a weight the same products run over two slabs."""
    import jax
    from jax import lax
    from znicz_tpu.ops.pallas import grouped

    sizes, live, a, g, w = _grouped_operands("one_of_3.9_means", 256, 512)
    a = jnp.where(live, a, 0)
    monkeypatch.setattr(grouped, "_BLOCK_BYTES", 256 * 512 * 4 // slabs)
    assert grouped._slab(256, 512, 4) == 512 // slabs
    assert grouped._slab(512, 256, 4) == 256 // slabs

    def loss(f):
        return lambda a, w: (f(a, w) * jnp.where(live, g, 0)).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda a, w: grouped.gmm(a, w, sizes, True)),
                       argnums=(0, 1))(a, w)
        want = jax.grad(loss(lambda a, w: jnp.where(
            live, lax.ragged_dot(a, w, sizes), 0)), argnums=(0, 1))(a, w)
    for name, x, y in zip(("rows", "weights"), got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=2e-4, err_msg=name)


@pytest.mark.parametrize("sizes,tile,fill", [
    ([128, 128, 128, 128], 128, 1.0), ([128, 128, 128, 128], 512, 0.25),
    ([100, 0, 300, 50], 128, 450 / 768), ([0, 0], 128, 1.0),
    ([511, 1], 512, 0.5)])
def test_grouped_tile_fill_counts_a_shared_tile_twice(sizes, tile, fill):
    from znicz_tpu.ops.pallas import grouped

    got = float(grouped.tile_fill(jnp.asarray(sizes, jnp.int32), tile))
    assert got == pytest.approx(fill)
    # and the walk the kernels take has that many visits of live groups
    rows = -(-max(sum(sizes), 1) // tile) * tile
    offsets, group, tile_of, out_tile, n = grouped.plan(
        jnp.asarray(sizes, jnp.int32), rows, tile)
    visits = int(n[0]) - sum(1 for s in sizes if s == 0)
    assert sum(sizes) == pytest.approx(fill * tile * max(visits, 1)) or \
        not sum(sizes)
    assert list(np.asarray(offsets)) == list(np.cumsum([0] + sizes))
    assert (np.diff(np.asarray(out_tile)) >= 0).all()
    assert (np.asarray(tile_of)[:int(n[0])] ==
            np.asarray(out_tile)[:int(n[0])]).all()


@pytest.mark.parametrize("shape,dtype,word", [
    ((500, 128, 256, 4), jnp.float32, "128-row tile"),
    ((512, 100, 256, 4), jnp.float32, "128 lanes"),
    ((512, 128, 256, 4), jnp.float16, "bfloat16 or float32"),
    ((512, 128, 32768, 4), jnp.bfloat16, "MiB"),
    ((512, 260, 128, 4), jnp.float32, "multiples of 8"),
    ((512, 20000, 200, 4), jnp.bfloat16, "taken whole"),
    ((512, 128, 256, 0), jnp.bfloat16, "no group")])
def test_grouped_unsupported_reason_names_the_refused_shape(shape, dtype,
                                                            word):
    from znicz_tpu.ops.pallas import grouped

    assert grouped.unsupported_reason(12288, 2048, 1536, 16,
                                      jnp.bfloat16) is None
    why = grouped.unsupported_reason(*shape, dtype)
    assert why and word in why
    rows, k, n, held = shape
    if held:
        with pytest.raises(ValueError, match="grouped product"):
            grouped.gmm_rows(jnp.zeros((rows, k), dtype),
                             jnp.zeros((held, k, n), dtype),
                             jnp.zeros((held,), jnp.int32), interpret=True)


@pytest.mark.parametrize("kernel", ["ROWS", "ROWS_T", "WEIGHTS"])
def test_grouped_kernel_names_are_found_by_the_benchmarks_pattern(kernel):
    """``moe_gmm_roofline`` divides nine products' least time by the time
    of whatever its pattern finds: all three names match it, or the share
    is of a part (over 100 %) or of nothing (a traced line without it)."""
    import importlib.util
    import os

    from znicz_tpu.ops.pallas import grouped

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "kernels", "moe_gmm.py")
    spec = importlib.util.spec_from_file_location("_kernels_moe_gmm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    (call,) = mod.calls_per_step({}, {})
    name = getattr(grouped, kernel + "_KERNEL_NAME")
    assert call["pattern"] in name and name.startswith("moe_gmm_")


# -- the blocked flash kernels under a window ---------------------------------

def _dense_windowed(q, k, v, window):
    from znicz_tpu.ops import attention as att

    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    return att.attention(jnp, q, k, v, causal=True, window=window)


@pytest.mark.parametrize("dh,h,kv,window,t,block", [
    (64, 6, 1, 256, 512, 128), (128, 6, 1, 100, 256, 128),
    (128, 2, 2, 300, 512, 128), (64, 2, 2, 129, 384, 128),
    (128, 2, 1, 384, 512, 256), (64, 2, 2, 200, 512, None)],
    ids=["dh64_group6_window_2_blocks", "dh128_group6_window_under_a_block",
         "dh128_group1_window_off_the_block", "dh64_group1_window_129",
         "dh128_group2_blocks_of_256", "dh64_one_tile_both_cuts"])
def test_windowed_blocked_kernels_match_the_dense_masked_form(
        dh, h, kv, window, t, block):
    """``flash_attention(..., causal=True, window=W)`` (interpreted),
    forward and the three gradients, against dense attention with the band
    as a mask: heads of 64 (folded operands) and 128 (the layer's own
    layout), a group of 1, 2 and 6 query heads a key/value head, windows
    that are and are not a multiple of the block, one shorter than a block
    (the diagonal's tile then takes both cuts), and the chooser's own tile
    (one tile a row: both cuts in it)."""
    import contextlib
    import jax
    from unittest import mock

    from znicz_tpu.ops.pallas import attention as pattn

    ks = jax.random.split(jax.random.PRNGKey(t + dh + window), 4)
    q, ct = (jax.random.normal(kk, (1, t, h, dh)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (1, t, kv, dh)) for kk in ks[2:])

    def blocked(q, k, v):
        return pattn.flash_attention(q, k, v, causal=True, interpret=True,
                                     window=window)

    patched = mock.patch.object(
        pattn, "_kvb_block", lambda t, dh, pass_, sel=False: block) \
        if block else contextlib.nullcontext()
    with patched, jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: blocked(*a).sum(), (0, 1, 2)))(q, k, v))
        for name in pattn.KVB_SWA_KERNEL_NAMES.values():
            assert name in text
        assert pattn.KVB_FWD_KERNEL_NAME + " " not in text
        np.testing.assert_allclose(blocked(q, k, v),
                                   _dense_windowed(q, k, v, window),
                                   atol=2e-5)
        got = jax.grad(lambda *a: (blocked(*a) * ct).sum(), (0, 1, 2))(
            q, k, v)
        want = jax.grad(lambda *a: (_dense_windowed(*a, window) * ct).sum(),
                        (0, 1, 2))(q, k, v)
    if block:                   # no later test meets the patched programs
        pattn._kvb_call_fwd.clear_cache()
        pattn._kvb_call_bwd.clear_cache()
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


def _parents_visits(t, block, causal, by_kv):
    """``_visits`` as the parent commit (PR 47) wrote it, flags 1, 2, 4."""
    n = t // block
    pairs = [(i, j) for i in range(n) for j in range(n)
             if not causal or j <= i]
    outer = 1 if by_kv else 0
    pairs.sort(key=lambda ij: (ij[outer], ij[1 - outer]))
    flags = []
    for v, (i, j) in enumerate(pairs):
        first = v == 0 or pairs[v - 1][outer] != pairs[v][outer]
        last = v + 1 == len(pairs) or pairs[v + 1][outer] != pairs[v][outer]
        flags.append(1 * first + 2 * last + 4 * (causal and i == j))
    return (np.asarray([i for i, _ in pairs], np.int32),
            np.asarray([j for _, j in pairs], np.int32),
            np.asarray(flags, np.int32))


@pytest.mark.parametrize("t,block", [(8192, 1024), (4096, 1024),
                                     (16384, 1024), (4096, 512), (640, 128)])
def test_visit_tables_without_a_window_are_the_parents(t, block):
    """``_visits(..., window=None)`` (and with the argument left out) equals
    the parent's tables array for array, dtype for dtype, in both orders,
    causal and full: a call without a window walks the grid it walked."""
    from znicz_tpu.ops.pallas import attention as pattn

    for causal in (True, False):
        for by_kv in (False, True):
            want = _parents_visits(t, block, causal, by_kv)
            for got in (pattn._visits(t, block, causal, by_kv),
                        pattn._visits(t, block, causal, by_kv, None)):
                for a, b in zip(got, want):
                    assert a.dtype == b.dtype
                    np.testing.assert_array_equal(a, b)
    assert pattn._LOW not in (pattn._FIRST, pattn._LAST, pattn._CUT)


@pytest.mark.parametrize("t,block,window", [
    (8192, 1024, 4096), (8192, 1024, 4095), (8192, 1024, 4097),
    (2048, 256, 300), (1024, 128, 100), (1024, 128, 1), (1024, 256, 5000)])
def test_band_tables_list_every_tile_with_a_live_entry_and_no_other(
        t, block, window):
    """Under a window the tables list exactly the tiles in which some query
    sees some key (``0 <= i - j < window``), in both orders, and flag
    ``_LOW`` exactly those in which some pair lies ``window`` or more apart
    (the second mask is computed there alone) and ``_CUT`` the diagonal's;
    at the cell's shape 30 of the triangle's 36 tiles, four of them cut by
    the band's edge."""
    from znicz_tpu.ops.pallas import attention as pattn

    pos = np.arange(t)
    apart = pos[:, None] - pos[None, :]
    live = (apart >= 0) & (apart < window)
    n = t // block
    tiles = lambda m: m.reshape(n, block, n, block).any(axis=(1, 3))  # noqa
    want_live, want_low = tiles(live), tiles(apart >= window)
    for by_kv in (False, True):
        qi, ki, flags = pattn._visits(t, block, True, by_kv, window)
        listed = np.zeros((n, n), bool)
        listed[qi, ki] = True
        np.testing.assert_array_equal(listed, want_live)
        assert len(qi) == want_live.sum()            # none listed twice
        np.testing.assert_array_equal((flags & pattn._LOW) != 0,
                                      want_low[qi, ki])
        np.testing.assert_array_equal((flags & pattn._CUT) != 0, qi == ki)
        outer = ki if by_kv else qi
        assert all(np.diff(outer) >= 0)
        first = np.r_[True, np.diff(outer) != 0]
        last = np.r_[np.diff(outer) != 0, True]
        np.testing.assert_array_equal((flags & pattn._FIRST) != 0, first)
        np.testing.assert_array_equal((flags & pattn._LAST) != 0, last)
    if (t, block, window) == (8192, 1024, 4096):
        assert len(qi) == 30 and len(pattn._visits(t, block, True, False)[0]) \
            == 36
        assert int(((flags & pattn._LOW) != 0).sum()) == 4
    if window >= t:                    # a window longer than the row: causal
        for a, b in zip(pattn._visits(t, block, True, False, window),
                        pattn._visits(t, block, True, False)):
            np.testing.assert_array_equal(a, b)


def test_a_window_needs_the_blocked_form_a_causal_call_and_no_selection():
    from znicz_tpu.ops.pallas import attention as pattn

    q = jnp.zeros((1, 256, 2, 64))
    with pytest.raises(ValueError, match="causal"):
        pattn.flash_attention(q, q, q, interpret=True, window=64)
    with pytest.raises(ValueError, match="selection"):
        pattn.flash_attention(q, q, q, causal=True, interpret=True, window=64,
                              sel=jnp.ones((1, 256, 256), jnp.int8))
    with pytest.raises(ValueError, match="at least one"):
        pattn.flash_attention(q, q, q, causal=True, interpret=True, window=0)
    with pytest.raises(ValueError, match="multiple of the 128"):
        pattn.flash_attention(q[:, :200], q[:, :200], q[:, :200],
                              causal=True, interpret=True, window=64)
    # a shape the whole-row form would take runs blocked under a window,
    # in the layer's layout at a head of 128
    assert pattn.form_of(256, 128)[0] == "rows"
    assert not pattn.direct_layout(256, 128)
    assert pattn.direct_layout(256, 128, True)
    assert not pattn.direct_layout(256, 64, True)
    assert pattn.kvb_block_rows(256, 64) == dict.fromkeys(
        ("fwd", "dkv", "dq"), 0)
    assert pattn.kvb_block_rows(256, 64, window=64) == dict.fromkeys(
        ("fwd", "dkv", "dq"), 256)
