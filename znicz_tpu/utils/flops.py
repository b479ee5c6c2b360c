"""Analytic FLOPs model for workflow forwards (SURVEY.md §6.1 rebuild:
the reference has no FLOPs accounting at all; MFU reporting is the
TPU-native observability upgrade VERDICT r1 item 4 asks for).

Counts multiply-accumulates as 2 FLOPs.  A training step is counted as
3x the forward GEMM/conv FLOPs (1 fwd + 2 bwd passes: err_input GEMM and
weight-gradient GEMM) — the standard MFU convention.  Elementwise work
(activations, pooling, LRN) is bandwidth- not FLOPs-bound on TPU and is
deliberately excluded; MFU measures MXU utilisation.
"""

from __future__ import annotations

import os

import numpy as np


#: dense bf16 peak FLOPs/s per chip (MXU).  f32 jnp code still rides the
#: MXU at bf16 rate under the default matmul precision, so this is the
#: honest denominator for either dtype.
TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}


#: explicit per-chip peak override (FLOPs/s, float literal).  This is
#: how a CPU run gets a *nominal* denominator so MFU stays a
#: live, comparable-within-one-host number instead of silently absent —
#: an MFU computed against it is NOT comparable across machines and the
#: docs say so (OBSERVABILITY.md "Step anatomy & goodput").
PEAK_FLOPS_ENV = "ZNICZ_TPU_PEAK_FLOPS"


def peak_flops() -> float | None:
    """Per-chip peak for the live ``device_kind``.
    ``None`` on the CPU backend, whose peak the table cannot know; on
    any other backend a device the table does not list is an error, not
    a silently absent MFU.  ``$ZNICZ_TPU_PEAK_FLOPS`` wins over
    everything: the nominal-denominator escape hatch."""
    env = os.environ.get(PEAK_FLOPS_ENV, "")
    if env:
        try:
            val = float(env)
        except ValueError:
            val = 0.0
        if val > 0.0:
            return val
    import jax
    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    for pattern, g in (("v6 lite", "v6e"), ("v6e", "v6e"),
                       ("v5 lite", "v5e"), ("v5e", "v5e"),
                       ("v5p", "v5p"), ("v4", "v4")):
        if pattern in kind:
            return TPU_PEAK_FLOPS[g]
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to "
        f"utils/flops.py::TPU_PEAK_FLOPS")


def forward_flops(unit, batch: int) -> float:
    """Forward-pass MXU FLOPs of one unit for a ``batch``-row minibatch."""
    from znicz_tpu.units.all2all import All2All
    from znicz_tpu.units.conv import Conv
    from znicz_tpu.units.deconv import Deconv

    if isinstance(unit, All2All):
        n_in = int(np.prod(unit.input.shape[1:]))
        n_out = int(np.prod(unit.output.shape[1:]))
        return 2.0 * batch * n_in * n_out
    if isinstance(unit, (Conv, Deconv)):
        # gather side of the GEMM: out_positions x (kx*ky*c_in) x c_out
        out_shape = unit.output.shape  # (B, H, W, C_out)
        positions = int(np.prod(out_shape[1:3]))
        c_out = int(out_shape[3])
        c_in = int(unit.input.shape[3])
        k = int(unit.kx) * int(unit.ky) * c_in
        return 2.0 * batch * positions * k * c_out
    return 0.0


def train_step_flops(forwards, batch: int) -> float:
    """Analytic MXU FLOPs of one fused train step (fwd + bwd)."""
    return 3.0 * sum(forward_flops(f, batch) for f in forwards)


def mfu(samples_per_sec: float, forwards, batch: int) -> float | None:
    """Model FLOPs utilisation vs the chip's dense bf16 peak."""
    peak = peak_flops()
    if not peak:
        return None
    step_flops = train_step_flops(forwards, batch)
    return (samples_per_sec / batch) * step_flops / peak
