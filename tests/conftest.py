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


@pytest.fixture(autouse=True)
def _no_compile_cache_left_behind():
    """A test that configures a compilation cache directory (a
    ``QrackService`` with a checkpoint directory does, through
    ``checkpoint.warmstart.enable_compile_cache``) does not leave it to
    the tests its worker runs next: beside XLA's executables the
    directory holds the window programs themselves since PR 52
    (``warmstart.stored_program``), so a later test that rebuilds a
    program, patched or not, would be handed the stored one."""
    import jax

    from qrack_tpu.checkpoint import warmstart

    was = jax.config.jax_compilation_cache_dir, warmstart._ENABLED_DIR
    yield
    if (jax.config.jax_compilation_cache_dir, warmstart._ENABLED_DIR) != was:
        jax.config.update("jax_compilation_cache_dir", was[0])
        warmstart._ENABLED_DIR = was[1]


@pytest.fixture
def program_store(tmp_path):
    """A compilation cache directory configured, as the benchmark's
    harness and ``enable_compile_cache`` configure one on a TPU, so that
    window programs are stored beside it
    (``checkpoint/warmstart.stored_program``); yields the programs'
    directory.  XLA's own cache stays off: the store is what such a
    test watches."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from qrack_tpu.checkpoint import warmstart

    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    yield str(tmp_path / warmstart.PROGRAM_DIR)
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_enable_compilation_cache", was[1])
    cc.reset_cache()
