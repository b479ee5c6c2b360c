"""AcceleratedUnit — per-backend dispatch.  Rebuild of
veles/accelerated_units.py :: AcceleratedUnit.

The reference dispatches ``initialize()`` -> ``{numpy,ocl,cuda}_init`` and
``run()`` -> ``{numpy,ocl,cuda}_run`` on the selected Device, and gives units
kernel plumbing (``build_program`` with preprocessor defines, ``get_kernel``,
``execute_kernel``).  Here the accelerated backend is XLA:

- ``numpy_init``/``numpy_run`` — the pure-numpy oracle path, required;
- ``xla_init``/``xla_run`` — the TPU path.  The default ``xla_init`` jit-
  compiles the unit's pure compute function (``self.compute`` — a static
  method over jax arrays); ``xla_run`` feeds it the ``devmem`` of the unit's
  input Arrays and stores outputs with ``set_devmem``.  This replaces the
  reference's build_program/get_kernel/execute_kernel triple: geometry that
  the reference baked into kernels via ``#define`` is a static Python
  attribute captured at trace time, and XLA re-specializes per shape the
  same way the reference rebuilt programs per instance.

Eager per-unit execution through ``run()`` exists for standalone use and
tier-1 tests; the training hot loop instead fuses the whole accelerated
segment into one jitted step (znicz_tpu.parallel.step), the same way the
reference's hot loop enqueued all kernels on one device queue.
"""

from __future__ import annotations

from typing import Optional

import jax

from znicz_tpu.core.backends import Device, NumpyDevice
from znicz_tpu.core.memory import Array
from znicz_tpu.core.units import Unit
from znicz_tpu.core.workflow import Workflow


class AcceleratedUnit(Unit):
    """A Unit whose work runs on the selected backend."""

    def __init__(self, workflow=None, **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.device: Optional[Device] = None
        #: true (unpadded) minibatch row count, usually data-linked to the
        #: loader; see current_batch_size()
        self.batch_size = None

    # -- dispatch -----------------------------------------------------------
    @property
    def backend_suffix(self) -> str:
        return self.device.suffix if self.device is not None else "numpy"

    def initialize(self, device=None, **kwargs) -> None:
        self.device = device if isinstance(device, Device) else NumpyDevice()
        self._common_init(**kwargs)
        getattr(self, f"{self.backend_suffix}_init", self.numpy_init)()
        self.initialized = True

    def run(self) -> None:
        getattr(self, f"{self.backend_suffix}_run", self.numpy_run)()

    # -- override points ----------------------------------------------------
    def _common_init(self, **kwargs) -> None:
        """Backend-independent setup: shapes, Array allocation."""

    def numpy_init(self) -> None:
        pass

    def numpy_run(self) -> None:
        raise NotImplementedError(
            f"{type(self).__name__} must implement numpy_run")

    def xla_init(self) -> None:
        pass

    def xla_run(self) -> None:
        # default: oracle fallback through host memory — correct everywhere,
        # overridden by every unit with a device-side compute path
        self.numpy_run()

    # -- helpers ------------------------------------------------------------
    def init_array(self, *arrays: Array) -> None:
        for arr in arrays:
            arr.initialize(self.device)

    def current_batch_size(self, fallback: Optional[Array] = None) -> int:
        """True (unpadded) minibatch size: the data-linked ``batch_size``
        when wired, else the row count of ``fallback``; never 0."""
        bs = self.batch_size
        if bs is None and fallback is not None:
            bs = len(fallback)
        return max(int(bs or 0), 1)

    @staticmethod
    def jit(fn, **jit_kwargs):
        """Compile a pure function once per shape signature (the rebuild of
        the reference's kernel cache keyed on cache_file_name + defines)."""
        return jax.jit(fn, **jit_kwargs)


class AcceleratedWorkflow(Workflow):
    """Workflow whose initialize injects a Device into accelerated children
    (reference: veles/accelerated_units.py :: AcceleratedWorkflow)."""


class DeviceBenchmark:
    """Measure the device's achieved dense-GEMM throughput (reference row:
    veles/accelerated_units.py :: DeviceBenchmark — there it ranked device
    speed for master scheduling; here it validates the live chip against
    the analytic peak table MFU reporting divides by, utils/flops.py).

    ``run()`` times ``reps`` chained ``size x size`` matmuls in the
    device's compute dtype (bf16 on accelerators) and returns achieved
    GFLOP/s plus fraction-of-peak when the chip generation is known.
    """

    def __init__(self, size: int = 2048, reps: int = 8) -> None:
        self.size = int(size)
        self.reps = int(reps)

    def run(self, device=None) -> dict:
        import time

        import jax.numpy as jnp
        import numpy as np

        from znicz_tpu.core.backends import XLADevice
        from znicz_tpu.utils import flops as flops_mod

        device = device or XLADevice()
        dtype = getattr(device, "compute_dtype", jnp.float32)
        n = self.size
        a = jnp.asarray(
            np.random.default_rng(0).normal(size=(n, n)), dtype)

        def chain(x):
            for _ in range(self.reps):
                # the cheap epilogue add keeps the chain un-foldable
                # without charging VPU transcendental work against the
                # MXU peak the result is compared to
                x = x @ a + jnp.asarray(0.5, dtype)
            return x

        fn = jax.jit(chain)
        x0 = jnp.eye(n, dtype=dtype)
        jax.block_until_ready(fn(x0))            # compile + warm
        iters = 10                               # amortize dispatch + fence
        t0 = time.perf_counter()
        out = x0
        for _ in range(iters):
            out = fn(out)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        gflops = 2.0 * n * n * n * self.reps * iters / dt / 1e9
        peak = flops_mod.peak_flops()
        result = {"size": n, "reps": self.reps, "dtype": str(dtype.__name__),
                  "seconds": dt, "gflops": round(gflops, 1)}
        if peak and jax.default_backend() != "cpu":
            # the peak table is TPU generations — a CPU run reporting a
            # fraction of TPU peak would be noise, not a measurement
            result["fraction_of_peak"] = round(gflops * 1e9 / peak, 4)
        return result
