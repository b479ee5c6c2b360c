"""Device backends — rebuild of veles/backends.py.

The reference offers ``NumpyDevice`` / ``OpenCLDevice`` / ``CUDADevice``
selected by ``root.common.engine.backend``; each owns a context + queue and a
per-device BLOCK_SIZE table.  Here the accelerated backend is XLA:

- ``XLADevice`` wraps a ``jax.Device`` — whichever one it is handed, or
  this process's first local device (the CPU under ``JAX_PLATFORMS=cpu``,
  which is what the test suite runs on) — plus the compilation policy
  (matmul precision);
- ``TPUDevice`` is an ``XLADevice`` that must be a TPU: constructing one
  on a machine without a chip raises, so ``-d tpu`` can never train on
  the CPU and exit 0;
- ``NumpyDevice`` is the always-available pure-numpy oracle backend every
  accelerated unit also implements (reference parity: ``--force-numpy``).

Device selection: ``AutoDevice()`` honors ``root.common.engine.backend``
("tpu" | "numpy" | "auto") and logs the device it chose.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax

from znicz_tpu.core.config import root
from znicz_tpu.core.logger import Logger
from znicz_tpu.observe import probe


def resolve_compute_dtype(platform: str, precision: str | None = None):
    """THE precision policy: bf16 on any accelerator platform when
    ``precision`` (default ``root.common.engine.precision``) is
    "bfloat16"; f32 on CPU regardless, preserving oracle numerics.
    Shared by ``XLADevice.compute_dtype`` and the SPMD transformer
    stack."""
    import jax.numpy as jnp
    precision = precision or root.common.engine.get("precision", "bfloat16")
    return jnp.bfloat16 if (precision == "bfloat16"
                            and platform != "cpu") else jnp.float32


def first_local_device() -> jax.Device:
    """This process's first local device.  The call that starts jax's
    client (seconds on a TPU host) runs under ``setup.backend``; a
    caller that started it already (the benchmark asks for its chips
    first) pays nothing here and leaves no span."""
    from jax._src import xla_bridge

    # (a jax without the question counts as not started: bool() is False)
    if getattr(xla_bridge, "backends_are_initialized", bool)():
        return jax.local_devices()[0]
    with probe.setup_phase("backend"):
        return jax.local_devices()[0]


class Device(Logger):
    """Base device."""

    #: dispatch suffix: AcceleratedUnit calls f"{suffix}_init" / f"{suffix}_run"
    suffix = "numpy"

    def __init__(self) -> None:
        super().__init__()

    @property
    def is_accelerated(self) -> bool:
        return self.suffix != "numpy"

    def synchronize(self) -> None:
        """Barrier until queued device work completes (no-op on numpy)."""

    def __repr__(self) -> str:
        return f"<{type(self).__name__}>"


class NumpyDevice(Device):
    """Pure-numpy oracle backend (reference: veles/backends.py :: NumpyDevice)."""

    suffix = "numpy"


class XLADevice(Device):
    """XLA device on whatever platform jax gives it — same code path on
    the TPU and on the CPU the tests run on.

    Holds the jax.Device this process drives plus compile policy.
    The reference's per-device BLOCK_SIZE autotune table maps to XLA's own
    tiling — the only knob we keep is matmul precision (bfloat16 on MXU vs
    float32 oracle).
    """

    suffix = "xla"

    def __init__(self, device: Optional[jax.Device] = None,
                 precision: Optional[str] = None) -> None:
        super().__init__()
        # local_devices, not devices: after a jax.distributed join,
        # jax.devices()[0] is process 0's device — non-addressable from
        # every other rank.  Single-process they are identical.
        self.jax_device = device if device is not None \
            else first_local_device()
        self.precision = precision or root.common.engine.get("precision", "bfloat16")
        self.platform = self.jax_device.platform

    @property
    def compute_dtype(self):
        return resolve_compute_dtype(self.platform, self.precision)

    def put(self, host_array: np.ndarray) -> jax.Array:
        # device_put transfers asynchronously and reads the source buffer
        # until the transfer completes; callers (the Loader hot path) reuse
        # and mutate their host buffers per minibatch, so hand the transfer
        # a private copy — otherwise runs are nondeterministic under async
        # dispatch (observed as run-to-run weight divergence).
        return jax.device_put(np.array(host_array, copy=True),
                              self.jax_device)

    def synchronize(self) -> None:
        (jax.device_put(0.0, self.jax_device) + 0).block_until_ready()

    def __repr__(self) -> str:
        return (f"<{type(self).__name__} {self.jax_device} "
                f"precision={self.precision}>")


class TPUDevice(XLADevice):
    """An :class:`XLADevice` that is a TPU chip, or an error.  There is
    no fallback: a run that asked for the chip (``-d tpu``,
    ``engine.backend = "tpu"``, ``chip_smoke.py``) must not pass on the
    CPU."""

    def __init__(self, device: Optional[jax.Device] = None,
                 precision: Optional[str] = None) -> None:
        super().__init__(device, precision)
        if self.platform != "tpu":
            raise RuntimeError(
                f"TPUDevice needs a TPU, but jax gave {self.jax_device} "
                f"(platform {self.platform!r}); run on a machine with a "
                f"chip, or use XLADevice()/AutoDevice() to run on "
                f"whatever is there")


def AutoDevice() -> Device:
    """Select per ``root.common.engine.backend`` (reference: AutoDevice):
    "numpy" is the oracle, "tpu" insists on a chip, "auto" takes this
    process's first XLA device — and says which, so a run that landed on
    the CPU shows it."""
    backend = root.common.engine.get("backend", "auto")
    if backend == "numpy":
        return NumpyDevice()
    if backend == "tpu":
        return TPUDevice()
    device = XLADevice()
    device.info(f"AutoDevice chose {device.jax_device} "
                f"(platform {device.platform})")
    return device
