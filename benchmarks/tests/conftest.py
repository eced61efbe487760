"""benchmarks/tests run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Tier-1 (`tests/`) does not collect this directory."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
