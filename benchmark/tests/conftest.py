"""The benchmark's own tests run on the CPU (four virtual devices for the
data-parallel rehearsal) with this directory's modules importable; they
are separate from the repo's tier-1 suite:

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
