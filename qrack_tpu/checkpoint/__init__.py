"""Durable state: snapshots, spill/restore, crash recovery, warm start.

Four pieces (docs/CHECKPOINT.md):

* **container** — the versioned, checksummed npz+manifest file format
  with atomic writes and corruption detection.
* **registry** — ``save_state(obj, path)`` / ``load_state(path,
  into=None)`` over every simulator representation, rng streams
  included.
* **store** — the bounded on-disk session store backing serve's idle
  spill, crash-recovery manifest, and pending-job journal.
* **warmstart** — JAX persistent compilation cache wiring + the
  digest-keyed program manifest that lets a fresh serving process
  pre-trace previously served circuit shapes.

This package is NOT imported by ``import qrack_tpu`` — the library
path costs nothing unless checkpointing is enabled (serve wires it
lazily behind QRACK_SERVE_CHECKPOINT_DIR).
"""

from __future__ import annotations

from .container import (FORMAT, VERSION, CheckpointCorrupt, CheckpointError,
                        CheckpointVersionError, load_container,
                        save_container)
from .registry import (build, capture, load_snapshot, load_state,
                       restore_into, save_state)

__all__ = [
    "FORMAT", "VERSION",
    "CheckpointError", "CheckpointCorrupt", "CheckpointVersionError",
    "save_container", "load_container",
    "capture", "restore_into", "build",
    "save_state", "load_state", "load_snapshot",
    "CheckpointStore", "StoreLeaseHeld", "StoreLockTimeout",
    "enable_compile_cache", "ProgramManifest",
]


def __getattr__(name):
    # store/warmstart stay un-imported until first touched
    if name in ("CheckpointStore", "StoreLeaseHeld", "StoreLockTimeout"):
        from . import store

        return getattr(store, name)
    if name in ("enable_compile_cache", "ProgramManifest"):
        from . import warmstart

        return getattr(warmstart, name)
    raise AttributeError(name)
