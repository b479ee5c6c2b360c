"""Tier-1 tests for the All2All / GD unit pairs: numpy-vs-xla backend parity
(the rebuild of the reference's ocl-vs-numpy cross-backend tests,
SURVEY.md §5) and wiring semantics."""

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.core.memory import Array
from znicz_tpu.core.workflow import Workflow
from znicz_tpu.units.all2all import (All2All, All2AllSoftmax, All2AllTanh,
                                     All2AllRELU)
from znicz_tpu.units.gd import GradientDescent, GDSoftmax, GDTanh
from znicz_tpu.units.nn_units import MatchingObject


def make_forward(cls, device, x, **kwargs):
    prng.seed_all(42)
    w = Workflow(name="t")
    unit = cls(w, **kwargs)
    unit.input = Array(x)
    unit.initialize(device=device)
    unit.run()
    return unit


@pytest.mark.parametrize("cls", [All2All, All2AllTanh, All2AllRELU,
                                 All2AllSoftmax])
def test_forward_backend_parity(cls):
    rng = np.random.default_rng(5)
    x = rng.normal(size=(8, 12)).astype(np.float32)
    u_np = make_forward(cls, NumpyDevice(), x, output_sample_shape=7)
    u_xla = make_forward(cls, XLADevice(), x, output_sample_shape=7)
    np.testing.assert_allclose(u_xla.output.map_read(),
                               u_np.output.map_read(), rtol=1e-5, atol=1e-5)
    # same seed => identical weight init across backends
    np.testing.assert_array_equal(u_np.weights.map_read(),
                                  u_xla.weights.map_read())
    if cls is All2AllSoftmax:
        np.testing.assert_array_equal(u_np.max_idx.map_read(),
                                      u_xla.max_idx.map_read())


def make_gd_pair(fwd_cls, gd_cls, device, x, err, **gd_kwargs):
    prng.seed_all(43)
    w = Workflow(name="t")
    fwd = fwd_cls(w, output_sample_shape=err.shape[1])
    fwd.input = Array(x)
    fwd.initialize(device=device)
    fwd.run()
    gd = gd_cls(w, **gd_kwargs)
    gd.link_from_forward(fwd)
    gd.err_output = Array(err)
    gd.batch_size = x.shape[0]
    gd.initialize(device=device)
    gd.run()
    return fwd, gd


@pytest.mark.parametrize("fwd_cls,gd_cls", [
    (All2All, GradientDescent),
    (All2AllTanh, GDTanh),
    (All2AllSoftmax, GDSoftmax),
])
def test_gd_backend_parity(fwd_cls, gd_cls):
    rng = np.random.default_rng(6)
    x = rng.normal(size=(6, 10)).astype(np.float32)
    err = rng.normal(size=(6, 4)).astype(np.float32)
    kwargs = dict(learning_rate=0.1, weights_decay=0.01, gradient_moment=0.9)
    _, gd_np = make_gd_pair(fwd_cls, gd_cls, NumpyDevice(), x, err, **kwargs)
    _, gd_xla = make_gd_pair(fwd_cls, gd_cls, XLADevice(), x, err, **kwargs)
    for attr in ("err_input", "weights", "bias", "gradient_weights",
                 "gradient_bias"):
        np.testing.assert_allclose(
            getattr(gd_xla, attr).map_read(), getattr(gd_np, attr).map_read(),
            rtol=1e-4, atol=1e-5, err_msg=attr)


def test_gd_matches_autograd():
    """Hand-written backward vs jax.grad of the composed forward loss —
    the TPU-native correctness oracle the reference never had."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import linear as linops

    rng = np.random.default_rng(7)
    x = rng.normal(size=(5, 8)).astype(np.float32)
    err = rng.normal(size=(5, 3)).astype(np.float32)  # dL/dy for L = sum(y*err)
    # lr=1, no momentum/decay: gradient_weights == grad/batch after one step
    fwd, gd = make_gd_pair(All2AllTanh, GDTanh, NumpyDevice(), x, err,
                           learning_rate=1.0, gradient_moment=0.0,
                           weights_decay=0.0)
    w0 = gd.weights.map_read() + gd.gradient_weights.map_read()  # pre-update
    b0 = gd.bias.map_read() + gd.gradient_bias.map_read()

    def loss(x_, w_, b_):
        return (linops.forward(jnp, x_, w_, b_, "tanh") *
                jnp.asarray(err)).sum()

    gx, gw, gb = jax.grad(loss, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(w0), jnp.asarray(b0))
    batch = x.shape[0]
    np.testing.assert_allclose(gd.err_input.map_read(), np.asarray(gx),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gd.gradient_weights.map_read() * batch,
                               np.asarray(gw), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gd.gradient_bias.map_read() * batch,
                               np.asarray(gb), rtol=1e-4, atol=1e-4)


def test_weights_transposed_gd_matches_natural():
    """A transposed-layout layer must compute and train identically to the
    natural layout (the reference's weights_transposed flag)."""
    rng = np.random.default_rng(8)
    x = rng.normal(size=(4, 6)).astype(np.float32)
    err = rng.normal(size=(4, 3)).astype(np.float32)
    w_init = rng.normal(size=(6, 3)).astype(np.float32)
    b_init = rng.normal(size=(3,)).astype(np.float32)

    def build(transposed):
        wf = Workflow(name="t")
        fwd = All2AllTanh(wf, output_sample_shape=3,
                          weights_transposed=transposed)
        fwd.input = Array(x)
        fwd.weights.mem = w_init.T.copy() if transposed else w_init.copy()
        fwd.bias.mem = b_init.copy()
        fwd.initialize(device=NumpyDevice())
        fwd.run()
        gd = GDTanh(wf, learning_rate=0.1, gradient_moment=0.5)
        gd.link_from_forward(fwd)
        gd.err_output = Array(err)
        gd.batch_size = x.shape[0]
        gd.initialize(device=NumpyDevice())
        gd.run()
        return fwd, gd

    fwd_n, gd_n = build(False)
    fwd_t, gd_t = build(True)
    np.testing.assert_allclose(fwd_t.output.map_read(),
                               fwd_n.output.map_read(), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gd_t.err_input.map_read(),
                               gd_n.err_input.map_read(), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gd_t.weights.map_read().T,
                               gd_n.weights.map_read(), rtol=1e-5, atol=1e-6)


def test_matching_registry_pairs_fwd_and_gd():
    assert MatchingObject.gd_for(
        All2AllTanh.__new__(All2AllTanh)) is GDTanh
    assert MatchingObject.forwards["softmax"] is All2AllSoftmax
    assert MatchingObject.gds["softmax"] is GDSoftmax
