"""Analytic FLOPs model (znicz_tpu/utils/flops.py) — tier-1 checks
against hand-computed GEMM/conv counts."""

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice
from znicz_tpu.utils import flops


def _fc_workflow():
    from znicz_tpu.models.mnist_fc import build_fused
    prng.seed_all(3)
    w = build_fused(max_epochs=1, layers=(64,), minibatch_size=10,
                    n_train=100, n_valid=0)
    w.initialize(device=NumpyDevice())
    return w


def test_fc_forward_flops():
    w = _fc_workflow()
    batch = 32
    # 784 -> 64 -> 10
    expect = 2.0 * batch * (784 * 64 + 64 * 10)
    got = sum(flops.forward_flops(f, batch) for f in w.forwards)
    assert got == expect


def test_train_step_is_3x_forward():
    w = _fc_workflow()
    assert flops.train_step_flops(w.forwards, 8) == \
        3.0 * sum(flops.forward_flops(f, 8) for f in w.forwards)


def test_conv_forward_flops():
    from znicz_tpu.units.conv import ConvRELU
    from znicz_tpu.core.memory import Array

    prng.seed_all(3)
    conv = ConvRELU(None, n_kernels=16, kx=3, ky=3)
    conv.input = Array(np.zeros((4, 8, 8, 2), np.float32))
    conv.initialize(device=NumpyDevice())
    conv.run()
    out = conv.output.shape  # (4, Ho, Wo, 16)
    expect = 2.0 * 4 * out[1] * out[2] * 16 * (3 * 3 * 2)
    assert flops.forward_flops(conv, 4) == expect


def test_mfu_uses_peak_table(monkeypatch):
    import jax

    w = _fc_workflow()
    step = flops.train_step_flops(w.forwards, 32)
    monkeypatch.delenv(flops.PEAK_FLOPS_ENV, raising=False)
    # the CPU has no peak the table could know: no MFU, silently
    assert flops.mfu(1000.0, w.forwards, 32) is None

    class Chip:
        platform = "tpu"
        device_kind = "TPU v5 lite"     # what the v5e reports (PR 21)

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    assert flops.mfu(1000.0, w.forwards, 32) == \
        (1000.0 / 32) * step / 197e12
    # an accelerator the table does not list is an error, not a missing
    # metric
    Chip.device_kind = "TPU v9"
    with pytest.raises(ValueError, match="TPU v9"):
        flops.peak_flops()
