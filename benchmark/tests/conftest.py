"""The benchmark's own tests run on the CPU at toy sizes:
``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``. They
are no part of the repo's tier-1 run (``tests/``)."""

import os
import sys

# four virtual devices, for the rehearsal of a four-chip cell; has to
# be in the environment before JAX starts
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
