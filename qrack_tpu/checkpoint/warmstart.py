"""Persistent warm start: compile once per machine, not once per process.

Two layers:

* :func:`enable_compile_cache` turns on JAX's persistent compilation
  cache with the thresholds zeroed, so every XLA executable this
  process compiles — fused circuit programs, vmapped batch programs,
  gate kernels — lands on disk and a later process deserializes instead
  of recompiling.  The directory is ``JAX_COMPILATION_CACHE_DIR`` where
  that is set (then no directory is set in code), else the fixed
  ``<checkout>/.xla_cache``: the path is part of the cache key, so a
  directory that moves (a mkdtemp checkpoint dir) never hits.
* :class:`ProgramManifest` (rooted in the checkpoint directory)
  records every circuit shape the serving
  batcher compiles (digest-keyed by ``QCircuit.shape_key`` + batch
  size, the exact program-cache identity) together with the circuit
  itself in a container file.  A fresh process calls :meth:`prewarm`
  BEFORE taking traffic: each recorded circuit re-traces and re-jits —
  cheap, because the XLA cache supplies the compiled binary — so the
  first real job is a program-cache hit instead of a cold compile.

Nothing here imports jax at module load; both hooks are wired lazily
by QrackService when QRACK_SERVE_CHECKPOINT_DIR is set.
"""

from __future__ import annotations

import json
import os
import tempfile
from typing import Optional

from .. import telemetry as _tele
from .container import CheckpointCorrupt, CheckpointError
from .store import load_circuit, save_circuit

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_ENABLED_DIR: Optional[str] = None


def enable_compile_cache() -> str:
    """Turn on the JAX persistent compilation cache (with the size/time
    admission thresholds disabled — window and serving programs are
    many and individually small) for chip_smoke.py and QrackService
    alike.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    already reads it and no directory is set here; otherwise the cache
    is ``<checkout>/.xla_cache``.  Idempotent; returns the directory.

    A Mosaic kernel is serialized into its program with each operation's
    Python traceback as its location, so with the default ten frames the
    cache's key holds the lines on the way to the first call, and
    telemetry switched on (another branch of ``_JitProgram.__call__``)
    is another key (PERF.md, PR 27).  One frame, the operation's own,
    makes the key depend on the program alone.  Dropping tracebacks
    altogether (``jax_include_full_tracebacks_in_locations=False``, as
    the benchmark does) would do that too, but it also drops the name
    stack from every ``op_name``: no ``jax.named_scope`` and no kernel
    name would reach a device trace (PERF.md, PR 28)."""
    global _ENABLED_DIR
    if _ENABLED_DIR is not None:
        return _ENABLED_DIR
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 1)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".xla_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _ENABLED_DIR = cache_dir
    if _tele._ENABLED:
        _tele.event("checkpoint.warmstart.enabled", dir=cache_dir)
    return cache_dir


class ProgramManifest:
    """Digest-keyed record of every (circuit, width, batch) program the
    batcher compiled, durable enough to pre-trace them next boot."""

    def __init__(self, root: str):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self._index_path = os.path.join(self.root, "programs.json")
        try:
            with open(self._index_path) as f:
                self._index = json.load(f)
        except (OSError, json.JSONDecodeError):
            self._index = {}

    @staticmethod
    def _key(shape_key, batch: int) -> str:
        n, bucket, digest = shape_key
        return f"{n}:{batch}:{digest}"

    def record(self, circuit, n: int, batch: int) -> None:
        """Idempotent: a known (shape, batch) is a no-op, so the hot
        batcher path costs one dict probe.  Best-effort: the record is
        advisory warm-start metadata, so a store that has vanished out
        from under the manifest (dir removed after its service closed —
        the batcher module global outlives any one service) must never
        fail the dispatch it rides on."""
        shape = circuit.shape_key(n)
        key = self._key(shape, batch)
        if key in self._index:
            return
        # circuit files are keyed by the structure digest alone: the
        # same circuit served at several widths/batches is stored once
        digest = shape[2]
        path = os.path.join(self.root, f"{digest}.qckpt")
        try:
            if not os.path.exists(path):
                save_circuit(path, circuit)
            self._index[key] = {"width": int(n), "batch": int(batch),
                                "circuit": os.path.basename(path)}
            self._write_index()
        except OSError:
            return
        if _tele._ENABLED:
            _tele.inc("checkpoint.warmstart.recorded")

    def _write_index(self) -> None:
        fd, tmp = tempfile.mkstemp(prefix=".programs-", suffix=".tmp",
                                   dir=self.root)
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(self._index, f, sort_keys=True)
            os.replace(tmp, self._index_path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def __len__(self) -> int:
        return len(self._index)

    def prewarm(self, limit: Optional[int] = None) -> int:
        """Re-trace + re-compile every recorded program and leave it hot
        in the batcher's program cache AND jit's dispatch cache.  With
        the persistent XLA cache enabled the compile step is a disk
        read; returns how many programs were warmed.  Damaged circuit
        files are dropped from the manifest, not fatal."""
        import jax.numpy as jnp

        from ..config import get_config
        from ..serve import batcher as _batcher

        dtype = get_config().device_real_dtype()
        warmed = 0
        dead = []
        for key, rec in list(self._index.items()):
            if limit is not None and warmed >= limit:
                break
            path = os.path.join(self.root, rec["circuit"])
            try:
                circ, _ = load_circuit(path)
            except (CheckpointCorrupt, CheckpointError, OSError):
                dead.append(key)
                continue
            n, batch = int(rec["width"]), int(rec["batch"])
            fn = _batcher.batch_program(circ, n, batch)
            # jax.jit is lazy — building the wrapper traces nothing.
            # Run it once on dummy |0..0> plane lanes (same pytree shape
            # and dtype run_batch dispatches) so trace + compile happen
            # HERE, not under the first tenant's job.
            plane = jnp.zeros((2, 1 << n), dtype=dtype).at[0, 0].set(1.0)
            _batcher.sync_scalar(fn([plane] * batch))
            warmed += 1
        for key in dead:
            self._index.pop(key, None)
        if dead:
            self._write_index()
        if warmed and _tele._ENABLED:
            _tele.inc("checkpoint.warmstart.prewarmed", warmed)
        return warmed
