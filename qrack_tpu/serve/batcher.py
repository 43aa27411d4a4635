"""Shape-bucketed batch execution over stacked amplitude planes.

N tenants running the same circuit should pay ONE dispatch round-trip,
not N (the mpiQulacs / TensorCircuit-NG batching result the ISSUE
cites).  QCircuit.compile_fn already traces a whole circuit into one
XLA program over (2, 2^n) planes; here that body is vmapped over a
leading batch axis and wrapped so the lane stack, the padding, and the
per-lane output split all happen INSIDE the compiled program: the host
hands over a list of B plane references and gets a tuple of B outputs
back for the cost of a single jit dispatch.

Batch identity is QCircuit.shape_key(n) — width + gate-count bucket +
a content digest covering payload values, because compile_fn bakes
gate matrices into the trace as constants: only literally identical
circuits share a program.  Compiled batch programs live in a PR-1
ProgramCache (`compile.serve_batch.*` counters) keyed by
(shape_key, B), so the second session with a known shape is a cache
hit, never a recompile.

Batch sizes are BUCKETED to the next power of two before compilation
(``QRACK_SERVE_BATCH_PAD=0`` restores exact sizes): arrival-limited
traffic produces every occupancy in 1..max_batch, and with exact-size
keys each occupancy is its own 1-2s jit compile — a compile storm the
loadgen bench measured at ~30x steady-state throughput loss.  Padding
lanes replicate the batch's first ket (a real normalized state, so no
zero-norm lane can NaN under normalizing ops); only the real lanes
are written back.  The padded FLOPs are bounded at 2x and the compile
count drops from O(max_batch) to O(log max_batch) per shape.
"""

from __future__ import annotations

import os
from typing import List

import numpy as np

from .. import telemetry as _tele

# bounded LRU of jitted vmapped batch programs (PR-1 ProgramCache)
_PROGRAMS = _tele.ProgramCache("serve_batch",
                               cap_env="QRACK_SERVE_PROGRAM_CACHE_CAP",
                               default_cap=128)

# optional warm-start hook: a checkpoint.warmstart.ProgramManifest that
# records every compiled shape so the next process can prewarm it
_MANIFEST = None


def set_manifest(manifest) -> None:
    global _MANIFEST
    _MANIFEST = manifest


def batch_program(circuit, n: int, batch: int):
    """The jitted program applying `circuit` to `batch` independent
    kets: takes a LIST of `batch` (2, 2^n) plane arrays, returns a
    TUPLE of `batch` (2, 2^n) outputs.  Stacking the lanes, the
    vmapped circuit body, and the per-lane split are all INSIDE the
    one compiled program: dispatching a batch costs one jit call
    instead of ~2B host-side jax ops (the B-input stack, the padding
    concat, and B output slices each paid ~1-2 ms of per-op dispatch
    overhead — more than the window the pipeline hides).  The stack is
    a fresh buffer inside the program (resident planes are never
    donated), so a failed dispatch leaves every session's state intact
    for failover replay."""
    key = (circuit.shape_key(n), batch)

    def build():
        import jax
        import jax.numpy as jnp

        body = circuit.compile_batched_fn(n)

        # named for the compiled module (jit_qrack_serve_dispatch): what
        # a device trace knows a served batch's program by
        def qrack_serve_dispatch(planes):
            out = body(jnp.stack(planes))
            return tuple(out[i] for i in range(batch))

        return jax.jit(qrack_serve_dispatch)

    fn = _PROGRAMS.get_or_build(key, build)
    if _MANIFEST is not None:
        _MANIFEST.record(circuit, n, batch)
    return fn


def _bucket(b: int) -> int:
    """Next power of two >= b — the compiled batch sizes traffic of any
    occupancy maps onto."""
    return 1 << max(b - 1, 0).bit_length()


def run_batch(jobs: List, engines: List):
    """Dispatch one same-shape batch: hand the sessions' resident
    planes to the batch program as a list (padding lanes up to the
    power-of-two bucket are duplicate references to lane 0 — free on
    the host), run it as ONE jit call, bind each real output lane back
    to its engine, and return the output tuple (the executor's
    honest-sync target).  Raises whatever the dispatch raises — the
    executor owns guarding and failover."""
    from .. import resilience as _res

    job0 = jobs[0]
    n = job0.session.width
    padded = (len(jobs)
              if os.environ.get("QRACK_SERVE_BATCH_PAD", "1") == "0"
              else _bucket(len(jobs)))
    fn = batch_program(job0.circuit, n, padded)
    planes = [eng.device_planes for eng in engines]
    if padded > len(jobs):
        planes.extend(planes[:1] * (padded - len(jobs)))
        if _tele._ENABLED:
            _tele.inc("serve.batch.pad_lanes", padded - len(jobs))
    if _res._ACTIVE:
        out = _res.call_guarded("serve.dispatch", fn, (planes,))
    else:
        out = fn(planes)
    for i, eng in enumerate(engines):
        eng.device_planes = out[i]
    if _tele._ENABLED:
        _tele.inc("serve.batch.dispatches")
        _tele.inc("serve.batch.jobs", len(jobs))
    return out


def sync_scalar(arr) -> None:
    """Honest completion for a batch output: one real device->host read
    of a single element (the devget discipline —
    block_until_ready on a remote-attached device acks dispatch, not completion).
    Reading ANY element of ANY output forces the whole producing
    program to finish, so for the tuple a batch program returns it
    suffices to read lane 0."""
    import jax

    if isinstance(arr, (tuple, list)):
        arr = arr[0]
    np.asarray(jax.device_get(arr[(slice(0, 1),) * arr.ndim]))


def stats() -> dict:
    return _PROGRAMS.stats()


def clear_programs() -> None:
    """Drop cached batch programs (tests)."""
    _PROGRAMS.clear()
