"""Tier-1 tests for the conv-stack units: numpy-vs-xla backend parity,
fwd/gd pairing, dropout/stochastic determinism (SURVEY.md §5 tier-1)."""

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.core.memory import Array
from znicz_tpu.core.workflow import Workflow
from znicz_tpu.units.activation import (ForwardTanh, BackwardTanh,
                                        ForwardLog, BackwardLog,
                                        ForwardSinCos, BackwardSinCos,
                                        ForwardTanhLog, BackwardTanhLog)
from znicz_tpu.units.conv import Conv, ConvTanh, ConvRELU, gabor_bank
from znicz_tpu.units.dropout import DropoutForward, DropoutBackward
from znicz_tpu.units.gd_conv import GradientDescentConv, GDTanhConv
from znicz_tpu.units.gd_pooling import (GDAvgPooling, GDMaxPooling)
from znicz_tpu.units.normalization import (LRNormalizerForward,
                                           LRNormalizerBackward)
from znicz_tpu.units.nn_units import MatchingObject
from znicz_tpu.units.pooling import (AvgPooling, MaxPooling, MaxAbsPooling,
                                     StochasticPooling)


def run_unit(cls, device, x, seed=42, init_attrs=(), **kwargs):
    prng.seed_all(seed)
    w = Workflow(name="t")
    unit = cls(w, **kwargs)
    unit.input = Array(x)
    for name, val in init_attrs:
        setattr(unit, name, Array(val))
    unit.initialize(device=device)
    unit.run()
    return unit


@pytest.mark.parametrize("cls", [Conv, ConvTanh, ConvRELU])
def test_conv_backend_parity(cls):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 8, 8, 3)).astype(np.float32)
    kw = dict(n_kernels=5, kx=3, ky=3, sliding=(2, 2), padding=(1, 1, 1, 1))
    u_np = run_unit(cls, NumpyDevice(), x, **kw)
    u_x = run_unit(cls, XLADevice(), x, **kw)
    np.testing.assert_array_equal(u_np.weights.map_read(),
                                  u_x.weights.map_read())
    np.testing.assert_allclose(u_x.output.map_read(), u_np.output.map_read(),
                               rtol=1e-4, atol=1e-5)
    assert u_np.output.shape == (2, 4, 4, 5)


def test_conv_gabor_filling_deterministic():
    prng.seed_all(7)
    b1 = gabor_bank(5, 5, 3, 8)
    prng.seed_all(7)
    b2 = gabor_bank(5, 5, 3, 8)
    np.testing.assert_array_equal(b1, b2)
    assert np.abs(b1).max() <= 0.1 + 1e-6


@pytest.mark.parametrize("fwd_cls,gd_cls", [
    (Conv, GradientDescentConv),
    (ConvTanh, GDTanhConv),
])
def test_gd_conv_backend_parity(fwd_cls, gd_cls):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 6, 6, 2)).astype(np.float32)
    kw = dict(n_kernels=4, kx=3, ky=3, sliding=(1, 1), padding=(0, 0, 0, 0))

    def build(device):
        prng.seed_all(9)
        w = Workflow(name="t")
        fwd = fwd_cls(w, **kw)
        fwd.input = Array(x)
        fwd.initialize(device=device)
        fwd.run()
        gd = gd_cls(w, learning_rate=0.1, weights_decay=0.01,
                    gradient_moment=0.9)
        gd.link_from_forward(fwd)
        gd.err_output = Array(rng.normal(size=fwd.output.shape)
                              .astype(np.float32))
        gd.batch_size = x.shape[0]
        gd.initialize(device=device)
        gd.run()
        return gd

    rng = np.random.default_rng(1)          # same err stream for both
    gd_np = build(NumpyDevice())
    rng = np.random.default_rng(1)
    gd_x = build(XLADevice())
    for attr in ("err_input", "weights", "bias", "gradient_weights",
                 "gradient_bias"):
        np.testing.assert_allclose(
            getattr(gd_x, attr).map_read(), getattr(gd_np, attr).map_read(),
            rtol=2e-4, atol=1e-4, err_msg=attr)


def test_matching_registry_has_conv_pairs():
    for key in ("conv", "conv_tanh", "conv_relu", "conv_str", "max_pooling",
                "avg_pooling", "stochastic_pooling", "norm", "dropout"):
        assert key in MatchingObject.forwards, key
        assert key in MatchingObject.gds, key


@pytest.mark.parametrize("cls", [MaxPooling, MaxAbsPooling, AvgPooling])
def test_pooling_backend_parity(cls):
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 7, 7, 3)).astype(np.float32)
    u_np = run_unit(cls, NumpyDevice(), x, kx=2, ky=2)
    u_x = run_unit(cls, XLADevice(), x, kx=2, ky=2)
    np.testing.assert_allclose(u_x.output.map_read(), u_np.output.map_read(),
                               rtol=1e-5, atol=1e-6)
    if hasattr(u_np, "input_offset"):
        np.testing.assert_array_equal(u_np.input_offset.map_read(),
                                      u_x.input_offset.map_read())


def test_max_pooling_gd_scatter():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 6, 6, 2)).astype(np.float32)
    for device in (NumpyDevice(), XLADevice()):
        w = Workflow(name="t")
        fwd = MaxPooling(w, kx=2, ky=2)
        fwd.input = Array(x)
        fwd.initialize(device=device)
        fwd.run()
        gd = GDMaxPooling(w)
        gd.link_from_forward(fwd)
        err = rng.normal(size=fwd.output.shape).astype(np.float32)
        gd.err_output = Array(err)
        gd.initialize(device=device)
        gd.run()
        ein = gd.err_input.map_read()
        assert ein.shape == x.shape
        np.testing.assert_allclose(ein.sum(), err.sum(), rtol=1e-4)
        rng = np.random.default_rng(3)  # reset for second device


def test_avg_pooling_gd_spread():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1, 4, 4, 1)).astype(np.float32)
    w = Workflow(name="t")
    fwd = AvgPooling(w, kx=2, ky=2)
    fwd.input = Array(x)
    fwd.initialize(device=NumpyDevice())
    fwd.run()
    gd = GDAvgPooling(w)
    gd.link_from_forward(fwd)
    gd.err_output = Array(np.ones(fwd.output.shape, np.float32))
    gd.initialize(device=NumpyDevice())
    gd.run()
    np.testing.assert_allclose(gd.err_input.map_read(), 0.25, rtol=1e-6)


def test_stochastic_pooling_seed_reproducible():
    rng = np.random.default_rng(5)
    x = np.abs(rng.normal(size=(2, 6, 6, 2))).astype(np.float32)
    u1 = run_unit(StochasticPooling, NumpyDevice(), x, seed=11, kx=2, ky=2)
    u2 = run_unit(StochasticPooling, NumpyDevice(), x, seed=11, kx=2, ky=2)
    np.testing.assert_array_equal(u1.output.map_read(), u2.output.map_read())
    # forward_mode is deterministic expectation, backend-parity checkable
    prng.seed_all(12)
    w = Workflow(name="t")
    fwd = StochasticPooling(w, kx=2, ky=2)
    fwd.input = Array(x)
    fwd.forward_mode = True
    fwd.initialize(device=XLADevice())
    fwd.run()
    fwd_np = StochasticPooling(Workflow(name="t2"), kx=2, ky=2)
    fwd_np.input = Array(x)
    fwd_np.forward_mode = True
    fwd_np.initialize(device=NumpyDevice())
    fwd_np.run()
    np.testing.assert_allclose(fwd.output.map_read(),
                               fwd_np.output.map_read(), rtol=1e-5, atol=1e-6)


def test_lrn_units_backend_parity():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(2, 4, 4, 8)).astype(np.float32)
    u_np = run_unit(LRNormalizerForward, NumpyDevice(), x)
    u_x = run_unit(LRNormalizerForward, XLADevice(), x)
    np.testing.assert_allclose(u_x.output.map_read(), u_np.output.map_read(),
                               rtol=1e-5, atol=1e-6)
    for device in (NumpyDevice(), XLADevice()):
        w = Workflow(name="t")
        fwd = LRNormalizerForward(w)
        fwd.input = Array(x)
        fwd.initialize(device=device)
        fwd.run()
        gd = LRNormalizerBackward(w)
        gd.link_from_forward(fwd)
        gd.err_output = Array(np.ones_like(x))
        gd.initialize(device=device)
        gd.run()
        assert gd.err_input.shape == x.shape


def test_dropout_train_and_inference():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(4, 10)).astype(np.float32)
    u = run_unit(DropoutForward, NumpyDevice(), x, seed=13,
                 dropout_ratio=0.5)
    y = u.output.map_read()
    mask = u.mask.map_read()
    assert set(np.unique(mask)).issubset({0.0, 2.0})
    np.testing.assert_allclose(y, x * mask)
    # backward reuses the mask
    w = Workflow(name="t")
    gd = DropoutBackward(w)
    gd.link_from_forward(u)
    err = np.ones_like(x)
    gd.err_output = Array(err)
    gd.initialize(device=NumpyDevice())
    gd.run()
    np.testing.assert_allclose(gd.err_input.map_read(), mask)
    # inference: identity
    u.forward_mode = True
    u.run()
    np.testing.assert_allclose(u.output.map_read(), x)


@pytest.mark.parametrize("fwd_cls,bwd_cls", [
    (ForwardTanh, BackwardTanh),
    (ForwardLog, BackwardLog),
    (ForwardSinCos, BackwardSinCos),
    (ForwardTanhLog, BackwardTanhLog),
])
def test_activation_units_parity_and_numeric(fwd_cls, bwd_cls):
    rng = np.random.default_rng(8)
    x = rng.normal(size=(3, 8)).astype(np.float32) * 2.0
    u_np = run_unit(fwd_cls, NumpyDevice(), x)
    u_x = run_unit(fwd_cls, XLADevice(), x)
    np.testing.assert_allclose(u_x.output.map_read(), u_np.output.map_read(),
                               rtol=1e-5, atol=1e-6)
    # backward vs central difference on the numpy path
    w = Workflow(name="t")
    gd = bwd_cls(w)
    gd.link_from_forward(u_np)
    err = np.ones_like(x)
    gd.err_output = Array(err)
    gd.initialize(device=NumpyDevice())
    gd.run()
    from znicz_tpu.ops import activations as act_ops
    eps = 1e-3
    num = (act_ops.forward(np, fwd_cls.ACTIVATION, x + eps) -
           act_ops.forward(np, fwd_cls.ACTIVATION, x - eps)) / (2 * eps)
    # skip points near piecewise kinks (tanhlog switchover)
    safe = np.abs(np.abs(x) - 1.0) > 1e-2
    np.testing.assert_allclose(gd.err_input.map_read()[safe], num[safe],
                               rtol=2e-2, atol=1e-3)
