"""Structural (jaxpr-level) regression guards for the compiled-mode bug
classes the first on-chip Pallas parity sweep exposed (2026-07-31 01:01
UTC; ROADMAP Queue 1 item 3) — defects invisible to interpret-mode parity
because they live in Mosaic lowering or MXU default-precision semantics,
not in the math.  These tests pin the *structural property each fix
relies on*, so a refactor cannot silently reintroduce the bug class
between chip windows (suite-level compiled regression protection is
otherwise chip-gated; VERDICT r4 weak #7).

Bug classes covered:
1. Kohonen winner flips: default-precision MXU bf16 passes break exact
   ``d2 == dmin`` comparisons (40.8% of weights diverged on chip).
   Guard: every dot inside the SOM kernel runs Precision.HIGHEST.
2. Adam remote-compile crash: a scalar ``pow`` on SMEM operands crashes
   the Mosaic scalar-core compiler.  Guard: no pow of a traced scalar
   inside the kernel jaxpr (bias corrections precomputed outside).
3. Conv/deconv Mosaic strided-slice failure: stride>1 slices inside a
   kernel fail to lower.  Guard: no strided slice/dynamic-slice ops in
   any conv-family kernel jaxpr (the phase-split decomposition makes
   every in-kernel tap stride-1).
4. Flash-attention lse tiling: a 2-D ``(1, block_q)`` lse block is not
   a legal Mosaic tile.  Guard: lse/delta ride as rank-3 blocks with a
   trailing singleton.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _pallas_eqns(closed_jaxpr):
    """All equations inside every pallas_call kernel jaxpr, recursively
    (scan/cond bodies included so kernels under lax control flow are
    still found)."""
    found = []

    def walk(jaxpr, inside_kernel):
        for eqn in jaxpr.eqns:
            if inside_kernel:
                found.append(eqn)
            here = inside_kernel or eqn.primitive.name == "pallas_call"
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    walk(sub, here)

    def _sub_jaxprs(val):
        import jax.extend.core as jex_core
        if isinstance(val, jex_core.ClosedJaxpr):
            return [val.jaxpr]
        if isinstance(val, jex_core.Jaxpr):
            return [val]
        if isinstance(val, (tuple, list)):
            out = []
            for v in val:
                out.extend(_sub_jaxprs(v))
            return out
        return []

    walk(closed_jaxpr.jaxpr, False)
    assert found, "no pallas_call found in the traced function"
    return found


def test_kohonen_kernel_dots_run_highest_precision():
    from znicz_tpu.ops.pallas.kohonen import som_step

    x = jnp.zeros((8, 6), jnp.float32)
    w = jnp.zeros((16, 6), jnp.float32)
    coords = jnp.zeros((16, 2), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x, w, c: som_step(x, w, c, 0.1, 1.0, 8))(x, w, coords)
    dots = [e for e in _pallas_eqns(jaxpr)
            if e.primitive.name == "dot_general"]
    assert dots, "SOM kernel lost its MXU dots?"
    for eqn in dots:
        prec = eqn.params.get("precision")
        assert prec is not None and all(
            p == jax.lax.Precision.HIGHEST for p in np.ravel(prec)), (
            f"SOM kernel dot at default precision would flip winners on "
            f"the MXU (chip-measured 40.8% divergence): {eqn}")


def test_adam_kernel_has_no_scalar_pow():
    from znicz_tpu.ops.pallas.adam import fused_adam_update

    w = jnp.zeros((128, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda w, g, m, v, t: fused_adam_update(
            w, g, m, v, t, 1e-3, 0.01, 0.9, 0.999, 1e-8, 32))(
        w, w, w, w, jnp.int32(3))
    banned = {"pow", "integer_pow"}
    inside = [e for e in _pallas_eqns(jaxpr)
              if e.primitive.name in banned]
    assert not inside, (
        f"pow inside the adam kernel crashes the Mosaic scalar-core "
        f"compiler (remote-compile HTTP 500) — precompute bias "
        f"corrections outside: {inside}")


@pytest.mark.parametrize("case", ["fwd", "bwd", "deconv"])
def test_conv_kernels_have_no_strided_slices(case):
    from znicz_tpu.ops import conv as conv_ops
    from znicz_tpu.ops import deconv as deconv_ops
    from znicz_tpu.ops.pallas import conv, conv_bwd

    sliding, padding = (2, 2), (1, 2, 1, 2)     # the Mosaic-hostile case
    x = jnp.zeros((3, 13, 13, 3), jnp.float32)
    w = jnp.zeros((5, 5, 3, 8), jnp.float32)
    b = jnp.zeros((8,), jnp.float32)
    out_shape = conv_ops.forward_linear(
        np, np.zeros(x.shape, np.float32), np.zeros(w.shape, np.float32),
        None, sliding, padding).shape
    if case == "fwd":
        fn = lambda x, w, b: conv.conv2d_im2col(      # noqa: E731
            x, w, b, sliding, padding)
        jaxpr = jax.make_jaxpr(fn)(x, w, b)
    elif case == "bwd":
        err = jnp.zeros(out_shape, jnp.float32)
        fn = lambda x, w, e: conv_bwd.conv2d_backward(  # noqa: E731
            x, w, e, sliding, padding)
        jaxpr = jax.make_jaxpr(fn)(x, w, err)
    else:
        xd = jnp.zeros(out_shape, jnp.float32)
        dec_shape = deconv_ops.output_shape_for(
            out_shape, w.shape, sliding, padding)
        fn = lambda x, w: conv_bwd.deconv2d(          # noqa: E731
            x, w, sliding, padding, dec_shape)
        jaxpr = jax.make_jaxpr(fn)(xd, w)
    for eqn in _pallas_eqns(jaxpr):
        if eqn.primitive.name == "slice":
            strides = eqn.params.get("strides")
            assert strides is None or all(s == 1 for s in strides), (
                f"stride>1 slice inside a conv kernel fails Mosaic "
                f"lowering — use the phase-split decomposition "
                f"(ops/pallas/conv.py::phase_split): {eqn}")
        # the current kernels index only via BlockSpecs and static
        # stride-1 taps; dynamic slicing inside the kernel is the other
        # Mosaic-hostile addressing mode, so its appearance at all is a
        # red flag
        assert eqn.primitive.name != "dynamic_slice", str(eqn)


def test_flash_lse_rides_rank3_with_trailing_singleton():
    from znicz_tpu.ops.pallas.attention import _call_fwd

    bh, t, dh = 2, 256, 64
    q = jnp.zeros((bh, t, dh), jnp.float32)
    o, lse = _call_fwd(q, q, q, False, True)
    assert o.shape == (bh, t, dh)
    assert lse.ndim == 3 and lse.shape == (bh, t, 1), (
        "lse must keep its trailing singleton: a 2-D (1, block_q) block "
        "is not a legal Mosaic tile (docs/TUNING.md)")
    # and the backward (which consumes lse and builds the same-shaped
    # delta) runs through the public custom-VJP entry
    from znicz_tpu.ops.pallas.attention import flash_attention
    q4 = jnp.zeros((1, t, 2, dh), jnp.float32)
    grads = jax.grad(lambda q: flash_attention(
        q, q, q, interpret=True).sum())(q4)
    assert grads.shape == q4.shape
