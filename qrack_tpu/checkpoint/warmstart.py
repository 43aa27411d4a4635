"""Persistent warm start: compile once per machine, not once per process.

Three layers:

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
* :func:`stored_program` keeps a window program's lowered module under
  that same cache directory (``<cache dir>/qrack_programs/<digest>``,
  a serialized ``jax.export.Exported``), so the re-trace is cheap too:
  the first process of a machine to call a program exports it, and it
  and every later process dispatch the stored module through
  ``jax.jit(exported.call)``, one module text a program, without
  tracing a kernel body.  The window programs of the dense engine and
  the pager are built through it (ops/fusion.py, parallel/pager.py).

Nothing here imports jax at module load; the first two hooks are wired
lazily by QrackService when QRACK_SERVE_CHECKPOINT_DIR is set.
"""

from __future__ import annotations

import hashlib
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


def _replace_file(path: str, data: bytes) -> None:
    """``data`` at ``path``, whole or not at all: a temporary file beside
    it and a rename, so that a reader never meets a part and of two
    writers of one path both end with a whole file."""
    fd, tmp = tempfile.mkstemp(prefix=".tmp-", dir=os.path.dirname(path))
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# ---------------------------------------------------------------------------
# the program store: lowered window programs beside the compile cache
# ---------------------------------------------------------------------------

PROGRAM_DIR = "qrack_programs"
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SOURCE_DIGEST: Optional[str] = None


def source_digest() -> str:
    """A digest of every ``.py`` of the package, path and bytes, taken
    once a process: a stored program is the text its source traced to,
    so a changed kernel line must never meet it (that is a wrong ket,
    not a slow one)."""
    global _SOURCE_DIGEST
    if _SOURCE_DIGEST is None:
        h = hashlib.sha256()
        for root, dirs, files in os.walk(_PACKAGE):
            dirs.sort()
            for name in sorted(f for f in files if f.endswith(".py")):
                path = os.path.join(root, name)
                h.update(os.path.relpath(path, _PACKAGE).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
        _SOURCE_DIGEST = h.hexdigest()
    return _SOURCE_DIGEST


def program_dir() -> Optional[str]:
    """Where stored programs live: under JAX's persistent compilation
    cache directory where one is configured, else None (nothing is
    stored, a program is traced as it always was)."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    return os.path.join(cache_dir, PROGRAM_DIR) if cache_dir else None


def _aval(aval):
    return tuple(aval.shape), str(aval.dtype), bool(aval.weak_type)


def _laid(arg):
    """How a call's argument is laid over devices, as far as a program
    depends on it: nothing for a host array, one device, or a mesh by
    its shape and axis names (which chips carry it is the call's, not
    the program's).  Raises TypeError for a tracer or any other
    sharding: such a call is not stored."""
    import jax
    from jax.sharding import NamedSharding

    if isinstance(arg, jax.core.Tracer):
        raise TypeError("a tracer")
    sharding = getattr(arg, "sharding", None)
    if sharding is None:
        return None
    if isinstance(sharding, NamedSharding):
        return ("mesh", tuple(sharding.mesh.shape.items()),
                str(sharding.spec), sharding.memory_kind)
    if len(sharding.device_set) == 1:
        return ("device", sharding.memory_kind)
    raise TypeError(type(sharding).__name__)


def _device_of(args):
    """A device of the call: the first device array's, else the
    default backend's."""
    import jax

    for a in args:
        if isinstance(a, jax.Array):
            return min(a.devices(), key=lambda d: d.id)
    return jax.devices()[0]


def program_digest(key, args, jit_kw) -> str:
    """The file name of a stored program: the in-process key, the
    arguments' avals and how they are laid (:func:`_laid`: TypeError
    where a call is not one to store), ``jax.jit``'s arguments, JAX's
    and the runtime's versions, the device kind, the numeric modes a
    trace reads and the package's own source."""
    import jax
    import jaxlib

    device = _device_of(args)
    parts = (repr(key), [_aval(jax.typeof(a)) + (_laid(a),) for a in args],
             sorted(jit_kw.items()), jax.__version__, jaxlib.__version__,
             device.platform, device.client.platform_version,
             device.device_kind, bool(jax.config.jax_enable_x64),
             str(jax.config.jax_default_matmul_precision), source_digest())
    return hashlib.sha256(repr(parts).encode()).hexdigest()


def _load_program(path: str, args):
    """The ``Exported`` stored at ``path`` if it is there (else a miss)
    and is a program of these arguments, else None: a file that does
    not deserialize or holds other avals is stale, and is written
    over."""
    import jax
    from jax import export

    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        if _tele._ENABLED:
            _tele.inc("warmstart.program.miss")
        return None
    with _tele.span("warmstart.program.load") as sp:
        try:
            exported = export.deserialize(bytearray(blob))
            if [_aval(a) for a in exported.in_avals] != [
                    _aval(jax.typeof(a)) for a in args]:
                exported = None
        except Exception:  # whatever a damaged file makes the reader raise
            exported = None
        if exported is None and _tele._ENABLED:
            # the span's name is what the benchmark counts as loaded
            sp.name = "warmstart.program.stale"
    if _tele._ENABLED:
        if exported is not None:
            _tele.inc("warmstart.program.hit")
        else:
            _tele.inc("warmstart.program.stale")
    return exported


def _export_program(jitted, path: str, args):
    """Export ``jitted`` on the arguments' avals and shardings and store
    it at ``path`` (a temporary file and a rename: of two processes that
    build one program at once, both end with a whole file).  None where
    ``jax.export`` refuses the program; a store that cannot be written
    costs the next process its trace, never this one its dispatch."""
    import jax
    from jax import export

    with _tele.span("warmstart.program.export"):
        # the file is read by this jax, jaxlib and runtime alone (they
        # are in its name): lowered as jax.jit lowers it, not in the
        # older forms an export keeps for a month for readers behind it
        # (a Mosaic kernel's serialization version among them)
        compat = jax.config.jax_export_ignore_forward_compatibility
        jax.config.update("jax_export_ignore_forward_compatibility", True)
        try:
            exported = export.export(
                jitted, platforms=[_device_of(args).platform])(*args)
            blob = exported.serialize()
        except Exception:  # an effect or a primitive export does not take
            if _tele._ENABLED:
                _tele.inc("warmstart.program.unexportable")
            return None
        finally:
            jax.config.update("jax_export_ignore_forward_compatibility",
                              compat)
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            _replace_file(path, bytes(blob))
        except OSError:
            pass
    return exported


def _jit_exported(exported, args, jit_kw):
    """``jax.jit`` of the stored module under the name it was exported
    with, so that the compiled module is named as the direct program's
    (``jit_qrack_kernel_window``, …: device traces are read by those
    names).  Donation is re-stated here: an export does not carry it.
    A program over several devices gets its results' shardings on the
    arguments' mesh, as ``jax.jit`` of the ``shard_map`` gives them."""
    import jax
    from jax.sharding import NamedSharding

    def call(*operands):
        return exported.call(*operands)

    call.__name__ = call.__qualname__ = exported.fun_name
    kw = dict(jit_kw)
    if exported.nr_devices > 1:
        meshes = [a.sharding.mesh for a in args
                  if isinstance(getattr(a, "sharding", None), NamedSharding)]
        if meshes:
            kw["out_shardings"] = jax.tree.unflatten(
                exported.out_tree, exported.out_shardings_jax(meshes[0]))
    return jax.jit(call, **kw)


class _StoredProgram:
    """A jitted program that is resolved at its first call, when the
    arguments' avals and shardings are known: the stored module where
    the store holds it, else the function traced, exported and stored.
    After that it is the plain jitted callable it resolved to."""

    __slots__ = ("_key", "_make_fn", "_jit_kw", "_jitted")

    def __init__(self, key, make_fn, jit_kw):
        self._key, self._make_fn, self._jit_kw = key, make_fn, jit_kw
        self._jitted = None

    def __call__(self, *args):
        jitted = self._jitted
        if jitted is None:
            jitted = self._jitted = self._resolve(args)
        return jitted(*args)

    def _direct(self):
        import jax

        return jax.jit(self._make_fn(), **self._jit_kw)

    def _resolve(self, args):
        root = program_dir()
        if root is None:
            return self._direct()
        try:
            digest = program_digest(self._key, args, self._jit_kw)
        except TypeError:  # tracers, or a sharding the store does not key
            return self._direct()
        path = os.path.join(root, digest)
        exported = _load_program(path, args)
        if exported is None:
            direct = self._direct()
            exported = _export_program(direct, path, args)
            if exported is None:
                return direct
        return _jit_exported(exported, args, self._jit_kw)

    def _cache_size(self) -> int:
        return 0 if self._jitted is None else self._jitted._cache_size()

    def __getattr__(self, attr):  # lower, trace, …: the jitted program's
        jitted = self._jitted
        return getattr(self._direct() if jitted is None else jitted, attr)


def stored_program(key, make_fn, **jit_kw):
    """``jax.jit(make_fn(), **jit_kw)``, through the program store.

    ``key`` is the program's in-process cache key less what names this
    process alone (a mesh's id); ``make_fn`` builds the function to jit
    and is not called where the store holds its program.  Returns what
    ``telemetry.instrument_jit`` wraps: a callable with
    ``_cache_size()``.  Where no compilation cache directory is
    configured at the first call it is ``jax.jit(make_fn(), **jit_kw)``
    and no file is written; a dispatch never fails because of the
    store (counters ``warmstart.program.hit`` / ``.miss`` / ``.stale``
    / ``.unexportable``, spans ``warmstart.program.load`` /
    ``.export``, docs/OBSERVABILITY.md)."""
    return _StoredProgram(key, make_fn, jit_kw)


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
        _replace_file(self._index_path,
                      json.dumps(self._index, sort_keys=True).encode())

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
