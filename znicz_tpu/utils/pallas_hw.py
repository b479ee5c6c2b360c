"""Compiled-mode Pallas parity sweep — every hand-written kernel family
executed against its oracle in ONE callable, so a chip window can verify
the whole kernel layer end to end (VERDICT r3: "implemented" for a kernel
means it runs on the target chip at least once; a lowering failure is a
FAIL, never a silent fallback).

``run_parity(interpret=False)`` returns ``{family: "ok" | "FAIL: ..."}``.
``chip_smoke.py`` runs it compiled on the chip and fails on anything but
``ok``; with ``interpret=True`` the same sweep doubles as a CPU smoke test
of the harness itself (tests/test_pallas_kernels.py pins the per-kernel
math — this module only cares that the compiled kernel agrees with the
oracle).

Shapes are TPU-native (lane-aligned 128 channels, 8-row tiles) so the
sweep exercises the real Mosaic tiling, not degenerate padding paths.
"""

from __future__ import annotations

import numpy as np


def _check(name, fn, results):
    try:
        fn()
        results[name] = "ok"
    except Exception as exc:  # noqa: BLE001 — a sweep must finish
        results[name] = f"FAIL: {exc!r}"[:400]


def run_parity(interpret: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from znicz_tpu.ops import (adam as adam_ops, attention as att,
                               conv as conv_ops, deconv as deconv_ops,
                               kohonen as k_ops, lrn as lrn_ops,
                               pooling as pool_ops, sgd as sgd_ops)
    from znicz_tpu.ops import pallas as pk

    rng = np.random.default_rng(0)
    results: dict = {}

    def sgd():
        w = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
        g = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
        v = jnp.zeros((256, 256), jnp.float32)
        args = (0.05, 1e-3, 0.3, 0.9, 32.0)
        w_ref, v_ref = sgd_ops.update(jnp, w, g, v, *args)
        w_pl, v_pl = pk.fused_sgd_update(w, g, v, *args,
                                         interpret=interpret)
        np.testing.assert_allclose(np.asarray(w_pl), np.asarray(w_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(v_pl), np.asarray(v_ref),
                                   rtol=1e-5, atol=1e-6)

    def adam():
        w = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
        g = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
        m = jnp.zeros((256, 256), jnp.float32)
        v = jnp.zeros((256, 256), jnp.float32)
        args = (3.0, 0.01, 0.001, 0.9, 0.999, 1e-8, 32.0)
        refs = adam_ops.update(jnp, w, g, m, v, *args)
        outs = pk.fused_adam_update(w, g, m, v, *args, interpret=interpret)
        for got, want in zip(outs, refs):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-6)

    # kernels that draw in-kernel PRNG bits need the TPU-emulating
    # interpreter off-chip (plain interpret=True has no prng_seed rule)
    prng_interp = pltpu.InterpretParams() if interpret else False

    def dropout():
        x = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
        ratio = 0.4
        y, mask = pk.dropout_forward(x, seed=7, ratio=ratio,
                                     interpret=prng_interp)
        y, mask = np.asarray(y), np.asarray(mask)
        scale = np.float32(1.0 / (1.0 - ratio))
        assert set(np.unique(mask)).issubset({np.float32(0.0), scale})
        np.testing.assert_allclose(y, np.asarray(x) * mask, rtol=1e-6)
        if not interpret:   # in-kernel PRNG is real only on hardware
            rate = float((mask == 0).mean())
            assert abs(rate - ratio) < 0.05, f"drop rate {rate}"

    def lrn():
        x = rng.normal(size=(4, 8, 8, 128)).astype(np.float32)
        err = rng.normal(size=x.shape).astype(np.float32)
        args = (1e-4, 0.75, 2.0, 5)
        y_ref = lrn_ops.forward(np, x, *args)
        y_pl = pk.lrn_forward(jnp.asarray(x), *args, interpret=interpret)
        np.testing.assert_allclose(np.asarray(y_pl), y_ref, rtol=1e-4,
                                   atol=1e-5)
        e_ref = lrn_ops.backward(np, x, err, *args)
        e_pl = pk.lrn_backward(jnp.asarray(x), jnp.asarray(err), *args,
                               interpret=interpret)
        np.testing.assert_allclose(np.asarray(e_pl), e_ref, rtol=1e-3,
                                   atol=1e-4)

    def conv_fwd(dtype=None, rtol=1e-4, atol=1e-4):
        # one body serves both precisions: the policy feeds bf16
        # activations to the kernels on hardware, and the compiled sweep
        # must prove that lowering too
        dtype = dtype or jnp.float32
        x = jnp.asarray(rng.normal(size=(8, 16, 16, 64)), dtype)
        w = jnp.asarray(rng.normal(size=(3, 3, 64, 128)) * 0.1, dtype)
        b = jnp.asarray(rng.normal(size=(128,)), dtype)
        ref = conv_ops.forward_linear(jnp, x, w, b, (1, 1), (1, 1, 1, 1))
        out = pk.conv2d_im2col(x, w, b, (1, 1), (1, 1, 1, 1),
                               interpret=interpret)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=rtol, atol=atol)

    def conv_bwd():
        from znicz_tpu.ops.activations import LINEAR
        x = jnp.asarray(rng.normal(size=(8, 16, 16, 64)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(3, 3, 64, 128)) * 0.1,
                        jnp.float32)
        err = jnp.asarray(rng.normal(size=(8, 8, 8, 128)), jnp.float32)
        refs = conv_ops.backward(jnp, x, None, w, err, (2, 2),
                                 (1, 1, 1, 1), LINEAR,
                                 activation_applied=False)
        outs = pk.conv2d_backward(x, w, err, (2, 2), (1, 1, 1, 1),
                                  interpret=interpret)
        for got, want in zip(outs, refs):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-3)

    def deconv():
        x = jnp.asarray(rng.normal(size=(8, 8, 8, 128)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(4, 4, 64, 128)) * 0.1,
                        jnp.float32)
        out_shape = deconv_ops.output_shape_for(
            x.shape, w.shape, (2, 2), (1, 1, 1, 1))
        y_ref = deconv_ops.forward(jnp, x, w, (2, 2), (1, 1, 1, 1),
                                   out_shape)
        y_pl = pk.deconv2d(x, w, (2, 2), (1, 1, 1, 1), out_shape,
                           interpret=interpret)
        np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-3)
        err = jnp.asarray(rng.normal(size=out_shape), jnp.float32)
        refs = deconv_ops.backward(jnp, x, w, err, (2, 2), (1, 1, 1, 1))
        outs = pk.deconv2d_backward(x, w, err, (2, 2), (1, 1, 1, 1),
                                    interpret=interpret)
        for got, want in zip(outs, refs):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-3)

    def stochastic_pool():
        x = rng.normal(size=(4, 16, 16, 128)).astype(np.float32)
        patch, valid, _ = pool_ops.patches(np, x, 2, 2, 2, 2,
                                           pad_value=0.0)
        n, oh, ow, K, c = patch.shape
        vtile = np.broadcast_to(valid.reshape(1, oh * ow, K),
                                (n, oh * ow, K))
        y, tap = pk.stochastic_pool(
            jnp.asarray(patch.reshape(n * oh * ow, K, c)),
            jnp.asarray(vtile.reshape(n * oh * ow, K)), seed=5,
            interpret=prng_interp)
        y, tap = np.asarray(y), np.asarray(tap)
        assert tap.min() >= 0 and tap.max() < K
        picked = np.take_along_axis(patch.reshape(n * oh * ow, K, c),
                                    tap[:, None, :], axis=1)[:, 0, :]
        np.testing.assert_allclose(y, picked, rtol=1e-6)

    def kohonen():
        x = rng.normal(size=(64, 128)).astype(np.float32)
        w = rng.normal(size=(256, 128)).astype(np.float32)
        coords = np.asarray(k_ops.grid_coords(np, 16, 16))
        w_ref, idx_ref = k_ops.update(np, x, w, coords, 0.3, 1.5, None)
        w_pl, idx_pl = pk.som_step(jnp.asarray(x), jnp.asarray(w),
                                   jnp.asarray(coords), 0.3, 1.5, 64,
                                   interpret=interpret)
        np.testing.assert_allclose(np.asarray(w_pl), w_ref, rtol=1e-3,
                                   atol=1e-4)
        np.testing.assert_array_equal(np.asarray(idx_pl), idx_ref)

    def flash_attention(dtype=None, rtol=2e-4, atol=2e-4,
                        grad_rtol=2e-3, grad_atol=2e-3):
        # one body serves both precisions, forward AND backward — the
        # bf16 backward (ds/dq emitted in q.dtype, bf16 MXU operands) is
        # what production training runs and must prove its own lowering
        dtype = dtype or jnp.float32
        if dtype == jnp.float32 and jax.default_backend() != "cpu":
            # accelerator backends run f32 matmuls at reduced default
            # precision (TPU MXU: bf16 passes — measured on-chip, the
            # two ORACLE precisions differ by ~1.2e-2 max abs with the
            # kernel within 5e-3 of the default oracle; GPU: tf32) —
            # only exact-f32 CPU keeps the tight band
            rtol, atol = 2e-2, 2e-2
            grad_rtol, grad_atol = 5e-2, 1e-1
        b, t, h, dh = 2, 512, 2, 128
        q = jnp.asarray(rng.normal(size=(b, t, h, dh)), dtype)
        k = jnp.asarray(rng.normal(size=(b, t, h, dh)), dtype)
        v = jnp.asarray(rng.normal(size=(b, t, h, dh)), dtype)
        for causal in (False, True):
            o_ref = att.attention(jnp, q, k, v, causal=causal)
            o_pl = pk.flash_attention(q, k, v, causal=causal,
                                      interpret=interpret)
            np.testing.assert_allclose(
                np.asarray(o_pl, np.float32),
                np.asarray(o_ref, np.float32), rtol=rtol, atol=atol)

        def oracle(q, k, v):
            return att.attention(jnp, q, k, v, causal=True).sum()

        def flash(q, k, v):
            return pk.flash_attention(q, k, v, causal=True,
                                      interpret=interpret).sum()

        g_ref = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
        g_pl = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_pl, g_ref):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b_, np.float32),
                rtol=grad_rtol, atol=grad_atol)

    def fc_gemm():
        from znicz_tpu.ops import linear as lin_ops
        x = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(256, 128)) * 0.05, jnp.float32)
        b = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
        y_ref = lin_ops.forward(jnp, x, w, b, "tanh")
        y_pl = pk.fc_forward(x, w, b, "tanh", interpret=interpret)
        np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        e = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
        refs = lin_ops.backward(jnp, x, y_ref, w, e, "tanh")
        outs = pk.fc_backward(x, y_ref, w, e, "tanh",
                              interpret=interpret)
        for got, want in zip(outs, refs):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-3)

    def conv_fwd_bf16():
        conv_fwd(dtype=jnp.bfloat16, rtol=5e-2, atol=5e-1)

    def flash_attention_bf16():
        flash_attention(dtype=jnp.bfloat16, rtol=5e-2, atol=5e-2,
                        grad_rtol=1e-1, grad_atol=5e-1)

    def paged_decode():
        # the serve plane's kernel (--pallas-decode): f32 and the bf16
        # arena the chip actually holds, page ids shuffled so the
        # scalar-prefetched gather is exercised
        from znicz_tpu.ops.pallas import decode as pdk
        B, H, Dh, page, n_pages, P = 8, 4, 128, 16, 40, 4
        pt = jnp.asarray(rng.integers(0, n_pages, size=(B, P)), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, P * page + 1, size=(B,)),
                              jnp.int32)
        for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
            q = jnp.asarray(rng.normal(size=(B, H, Dh)), dtype)
            k = jnp.asarray(rng.normal(size=(n_pages, page, H, Dh)), dtype)
            v = jnp.asarray(rng.normal(size=(n_pages, page, H, Dh)), dtype)
            o = pdk.paged_flash_decode(q, k, v, pt, lengths,
                                       interpret=interpret)
            # the kernel's products are exact f32 on the VPU; hold the
            # einsum oracle to the same (the MXU's default is bf16
            # passes)
            with jax.default_matmul_precision("highest"):
                want = pdk.reference(q, k, v, pt, lengths)
            np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                                       rtol=tol, atol=tol)

    def sgd_bf16state():
        # narrow optimizer state: velocity stored bf16, f32 math in-tile
        w = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
        g = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(256, 256)) * 0.1, jnp.bfloat16)
        args = (0.05, 1e-3, 0.3, 0.9, 32.0)
        w_ref, v_ref = sgd_ops.update(jnp, w, g, v.astype(jnp.float32),
                                      *args)
        w_pl, v_pl = pk.fused_sgd_update(w, g, v, *args,
                                         interpret=interpret)
        assert v_pl.dtype == jnp.bfloat16, v_pl.dtype
        np.testing.assert_allclose(np.asarray(w_pl), np.asarray(w_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(v_pl, dtype=np.float32),
            np.asarray(v_ref.astype(jnp.bfloat16), dtype=np.float32),
            rtol=1e-5, atol=1e-6)

    for name, fn in (("sgd", sgd), ("adam", adam), ("dropout", dropout),
                     ("lrn", lrn), ("fc_gemm", fc_gemm),
                     ("conv_fwd", conv_fwd),
                     ("conv_bwd", conv_bwd), ("deconv", deconv),
                     ("stochastic_pool", stochastic_pool),
                     ("kohonen", kohonen),
                     ("flash_attention", flash_attention),
                     ("conv_fwd_bf16", conv_fwd_bf16),
                     ("flash_attention_bf16", flash_attention_bf16),
                     ("paged_decode", paged_decode),
                     ("sgd_bf16state", sgd_bf16state)):
        _check(name, fn, results)
    return results
