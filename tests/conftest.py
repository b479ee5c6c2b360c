"""Test harness: run everything on a virtual 8-device CPU platform.

SPMD/collective logic is CI-testable without TPU hardware via
XLA's host-platform device-count override (SURVEY.md §5 tier-3).  The
suite is a CPU suite wherever it runs: the platform is pinned below so
that a machine with a chip gives the same results as the sandbox
(``chip_smoke.py`` is what runs on the chip).
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

# ISSUE 7: the persistent compilation cache defaults ON in production but
# stays OFF under the suite unless a test configures it explicitly
# (tests/test_compilecache.py does, against tmp dirs): a process-shared
# on-disk cache would couple hundreds of tests through one directory for
# no extra coverage.  $JAX_COMPILATION_CACHE_DIR outranks the off switch
# (znicz_tpu/compilecache.py), so a directory placed from outside is
# dropped here for the same reason.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ.setdefault("ZNICZ_TPU_COMPILE_CACHE", "off")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs
