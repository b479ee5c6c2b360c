"""Deconv/GDDeconv tests (SURVEY.md §3.1-§3.2 deconv rows): adjoint
identity vs the conv ops, numpy-vs-xla parity, gradient numeric check, and
the tier-2 conv autoencoder workflow."""

import numpy as np
import pytest

import jax.numpy as jnp

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.core.memory import Array
from znicz_tpu.core.workflow import Workflow
from znicz_tpu.ops import conv as conv_ops, deconv as deconv_ops
from znicz_tpu.standard_workflow import StandardWorkflow
from znicz_tpu.units.conv import Conv
from znicz_tpu.units.deconv import Deconv
from znicz_tpu.units.gd_deconv import GDDeconv


GEOM = dict(sliding=(2, 2), padding=(1, 1, 1, 1))


def test_deconv_is_conv_adjoint():
    """<conv(x), e> == <x, deconv(e)> for every geometry — the defining
    property of the transposed conv."""
    rng = np.random.default_rng(0)
    for sliding, padding in [((1, 1), (0, 0, 0, 0)), ((2, 2), (1, 1, 1, 1)),
                             ((2, 1), (1, 0, 2, 1))]:
        x = rng.normal(size=(2, 9, 8, 3)).astype(np.float64)
        w = rng.normal(size=(3, 3, 3, 5)).astype(np.float64)
        y = conv_ops.forward_linear(np, x, w, None, sliding, padding)
        e = rng.normal(size=y.shape)
        back = deconv_ops.forward(np, e, w, sliding, padding, x.shape)
        np.testing.assert_allclose((y * e).sum(), (x * back).sum(), rtol=1e-10)


def test_deconv_op_backend_parity():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 4, 4, 5)).astype(np.float32)
    w = rng.normal(size=(3, 3, 2, 5)).astype(np.float32)
    out_shape = deconv_ops.output_shape_for(x.shape, w.shape, **GEOM)
    y_np = deconv_ops.forward(np, x, w, GEOM["sliding"], GEOM["padding"],
                              out_shape)
    y_x = deconv_ops.forward(jnp, jnp.asarray(x), jnp.asarray(w),
                             GEOM["sliding"], GEOM["padding"], out_shape)
    np.testing.assert_allclose(np.asarray(y_x), y_np, rtol=1e-4, atol=1e-5)
    err = rng.normal(size=out_shape).astype(np.float32)
    ein_np, gw_np = deconv_ops.backward(np, x, w, err, **GEOM)
    ein_x, gw_x = deconv_ops.backward(jnp, jnp.asarray(x), jnp.asarray(w),
                                      jnp.asarray(err), **GEOM)
    np.testing.assert_allclose(np.asarray(ein_x), ein_np, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw_x), gw_np, rtol=1e-4, atol=1e-4)


def test_deconv_backward_numeric():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(1, 3, 3, 2)).astype(np.float64)
    w = rng.normal(size=(3, 3, 1, 2)).astype(np.float64)
    out_shape = deconv_ops.output_shape_for(x.shape, w.shape, (1, 1),
                                            (0, 0, 0, 0))
    err = rng.normal(size=out_shape)
    ein, gw = deconv_ops.backward(np, x, w, err, (1, 1), (0, 0, 0, 0))
    eps = 1e-6
    for arr, grad in ((x, ein), (w, gw)):
        flat = arr.ravel()
        for i in rng.choice(flat.size, 6, replace=False):
            old = flat[i]
            flat[i] = old + eps
            up = (deconv_ops.forward(np, x, w, (1, 1), (0, 0, 0, 0),
                                     out_shape) * err).sum()
            flat[i] = old - eps
            down = (deconv_ops.forward(np, x, w, (1, 1), (0, 0, 0, 0),
                                       out_shape) * err).sum()
            flat[i] = old
            np.testing.assert_allclose(grad.ravel()[i],
                                       (up - down) / (2 * eps), rtol=1e-6)


@pytest.mark.parametrize("device_cls", [NumpyDevice, XLADevice])
def test_deconv_unit_standalone_and_gd(device_cls):
    prng.seed_all(5)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 4, 4, 6)).astype(np.float32)
    w = Workflow(name="t")
    fwd = Deconv(w, n_kernels=6, kx=3, ky=3, n_channels=2, **GEOM)
    fwd.input = Array(x)
    fwd.initialize(device=device_cls())
    fwd.run()
    assert fwd.output.shape == (2, 7, 7, 2)
    gd = GDDeconv(w, learning_rate=0.1, gradient_moment=0.9)
    gd.link_from_forward(fwd)
    gd.err_output = Array(rng.normal(size=fwd.output.shape)
                          .astype(np.float32))
    gd.batch_size = 2
    gd.initialize(device=device_cls())
    w_before = fwd.weights.map_read().copy()
    gd.run()
    assert gd.err_input.shape == x.shape
    assert not np.allclose(fwd.weights.map_read(), w_before)


def test_deconv_tied_weights_follow_conv():
    prng.seed_all(6)
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1, 8, 8, 1)).astype(np.float32)
    w = Workflow(name="t")
    conv = Conv(w, n_kernels=3, kx=3, ky=3)
    conv.input = Array(x)
    conv.initialize(device=NumpyDevice())
    conv.run()
    de = Deconv(w, n_kernels=3, kx=3, ky=3)
    de.link_conv_attrs(conv)
    de.input = Array(conv.output.map_read().copy())
    de.initialize(device=NumpyDevice())
    de.run()
    assert de.output.shape == x.shape
    assert de.weights.map_read() is not None
    with pytest.raises(RuntimeError):
        de.param_arrays()


@pytest.mark.parametrize("fused", [True, False])
def test_conv_autoencoder_workflow(fused):
    """Tier-2: conv -> deconv autoencoder on identity targets (reference:
    Deconv autoencoder workflow, BASELINE config 4)."""
    prng.seed_all(17)
    w = StandardWorkflow(
        name="ConvAE",
        layers=[
            {"type": "conv", "->": {"n_kernels": 4, "kx": 3, "ky": 3},
             "<-": {"learning_rate": 0.01, "gradient_moment": 0.9}},
            {"type": "deconv", "->": {"n_kernels": 4, "kx": 3, "ky": 3,
                                      "n_channels": 1},
             "<-": {"learning_rate": 0.01, "gradient_moment": 0.9}},
        ],
        loss_function="mse", loader_name="synthetic_regression",
        loader_config={"sample_shape": (8, 8, 1), "identity": True,
                       "n_train": 128, "n_valid": 64, "minibatch_size": 32},
        decision_config={"max_epochs": 5}, fused=fused)
    w.initialize(device=XLADevice())
    w.run()
    dec = w.decision
    assert bool(dec.complete)
    first = dec.metrics_history[0]["metric_validation"]
    last = dec.metrics_history[-1]["metric_validation"]
    assert last < first * 0.7, dec.metrics_history
