"""The benchmark's own tests: CPU, small widths, no part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# a cell on four chips rehearses on four host devices, and JAX takes the
# flag when its backend starts: before any test of this directory
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""),
     "--xla_force_host_platform_device_count=4"]).strip()

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (TESTS, BENCH, ROOT) if p not in sys.path]
