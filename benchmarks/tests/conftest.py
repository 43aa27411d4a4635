"""The benchmark's own tests: CPU, small widths, no part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (TESTS, BENCH, ROOT) if p not in sys.path]
