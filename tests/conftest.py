"""Test harness config: force JAX onto a virtual 8-device CPU platform so
multi-chip sharding paths run without TPU hardware (the driver separately
dry-runs the sharded path via __graft_entry__.dryrun_multichip).

The pinning itself lives in qrack_tpu.utils.platform (shared with the
driver entry point) and must run before any backend init.  The driver
runs this suite with JAX_PLATFORMS=cpu; the chip is exercised by
benchmarks/run.py (the cells of BENCHMARK.json: every time comes from
there) and by chip_smoke.py (the served batch and the default stack,
which have no cell yet, and nothing else), never from here."""

import faulthandler
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from qrack_tpu.utils.platform import pin_host_cpu  # noqa: E402

pin_host_cpu(8)

# Hang forensics: a hung dispatch shows up as a silent stuck suite.
# Dump every thread's stack to stderr after QRACK_TEST_DUMP_AFTER seconds (default 15 min — inside
# the driver's kill window, past any legitimately slow test), repeating
# so a long hang leaves multiple samples.  SIGTERM (the watchdogs'
# first signal) also dumps before dying.
faulthandler.enable()
_dump_after = float(os.environ.get("QRACK_TEST_DUMP_AFTER", "900"))
if _dump_after > 0:
    faulthandler.dump_traceback_later(_dump_after, repeat=True)
try:
    import signal

    faulthandler.register(signal.SIGTERM, chain=True)
except (AttributeError, ValueError):
    pass  # platform without SIGTERM registration (e.g. non-main thread)


import pytest  # noqa: E402


@pytest.fixture
def no_prefix_cache(monkeypatch):
    """For tests of the batcher, the pipeline and the program cache: with
    ``QRACK_SERVE_PREFIX`` at its default the prefix cache claims
    identical circuits from pristine sessions before the co-batcher sees
    them (docs/SERVING.md), so such a test would watch the wrong
    mechanism."""
    monkeypatch.setenv("QRACK_SERVE_PREFIX", "0")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soak/benchmark tests (tier-1 runs -m 'not slow')")
