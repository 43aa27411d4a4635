"""Host-platform pinning for CPU-mesh validation.

The tests and the driver's dryrun_multichip exercise the sharded paths
on virtual host devices.  They must pin the cpu backend and size the
virtual device count BEFORE any backend init: on a machine with a chip
JAX would otherwise take the TPU, and XLA_FLAGS is read only once.
"""

from __future__ import annotations

import os
import re


def pin_host_cpu(n_devices: int = 8) -> None:
    """Pin JAX to the cpu backend with >= n_devices virtual host devices.

    Must be called before any JAX backend initialization (jax.devices(),
    first jit execution, ...) — XLA_FLAGS and jax_platforms are read only
    at first backend init, so a late call would silently do nothing.
    Raises RuntimeError in that case instead.  Safe to call when
    XLA_FLAGS already holds a smaller device count: the flag is
    rewritten upward.
    """
    import jax

    try:
        # private, but there is no public "is a backend up?" probe;
        # checked on jax 0.9.0: an empty dict until the first init
        from jax._src import xla_bridge

        if xla_bridge._backends:
            if jax.default_backend() == "cpu" and len(jax.devices("cpu")) >= n_devices:
                return  # already pinned adequately (idempotent call)
            raise RuntimeError(
                "pin_host_cpu called after a JAX backend was initialized; "
                "the cpu pin and host device count cannot take effect")
    except (ImportError, AttributeError):
        pass  # private API moved: fall through, best effort

    flags = os.environ.get("XLA_FLAGS", "")
    pat = re.compile(r"--xla_force_host_platform_device_count=(\d+)")
    m = pat.search(flags)
    if m is None:
        flags = (flags + f" --xla_force_host_platform_device_count={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        flags = pat.sub(f"--xla_force_host_platform_device_count={n_devices}", flags)
    os.environ["XLA_FLAGS"] = flags
    jax.config.update("jax_platforms", "cpu")
