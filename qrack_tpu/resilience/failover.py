"""Graceful degradation: TPU→CPU (and pager→single-device) failover.

When a guarded dispatch escalates past retry — the breaker is open
(:class:`~.errors.BreakerOpen`) or retries are exhausted
(:class:`~.errors.DispatchGiveUp`) — the circuit in flight should
still COMPLETE, just slower.  The mechanism:

1. snapshot the resident ket off the failing engine
   (``GetQuantumState`` — a host read that still works when the
   failure was injected/transient, and is taken under
   ``faults.suspended()`` so a device_get fault cannot block its own
   recovery),
2. build the next engine in the fallback chain
   (``QPager @ 2^k pages → QPager @ 2^(k-1) pages`` (elastic shrink,
   in place on the surviving device prefix) ``→ QEngineTPU`` (width
   permitting, breaker willing) ``→ QEngineCPU``), carrying the rng so
   measurement streams continue unbroken,
3. rehydrate via ``SetQuantumState`` and replay the failed call.

The elastic shrink step keeps a faulting pager ON the mesh, so a
persistent fault re-fires on the shrunk engine's replay — recovery is
therefore a LOOP (:func:`replay_with_failover`) that keeps descending
the strictly-shrinking chain until the replay lands or the chain is
exhausted.  Degraded pagers grow back at call boundaries through the
health probe in resilience/elastic.py (docs/ELASTICITY.md).

Because every injected fault fires at site entry and real XLA errors
surface before results commit (see dispatch.py), the snapshot equals
the pre-call state and the replayed call produces the same result the
healthy path would have — the oracle-equivalence property
tests/test_resilience.py asserts.

Two consumers:

* :class:`ResilientEngine` — a forwarding proxy the factory wraps
  around bare ``tpu``/``pager`` terminals (factory.py
  ``_maybe_resilient``).
* :class:`QHybrid` — already a router; it fails over in place via
  :func:`fail_over_engine` (engines/hybrid.py).
"""

from __future__ import annotations

import os
import time
from typing import Optional

from .. import telemetry as _tele
from . import breaker as _breaker
from . import faults as _faults
from .errors import FAILOVER_ERRORS

_persist_seq = 0


def _snapshot_is_finite(snap) -> bool:
    """Walk a captured snapshot tree and reject any non-finite float
    array.  Guards the persist path: a nan-poisoned ket written over the
    previous good snapshot would turn recovery evidence into the thing
    that re-poisons the recovery."""
    import numpy as np

    for arr in snap.get("arrays", {}).values():
        a = np.asarray(arr)
        if (np.issubdtype(a.dtype, np.floating)
                or np.issubdtype(a.dtype, np.complexfloating)):
            if not np.all(np.isfinite(a)):
                return False
    return all(_snapshot_is_finite(c)
               for c in snap.get("children", {}).values())


def _persist_snapshot(engine, cause) -> Optional[str]:
    """Durable post-mortem evidence: with QRACK_TPU_FAILOVER_PERSIST set
    to a directory, write the failing engine's full checkpoint container
    (ket + rng stream) there before rehydrating, so the pre-call state
    survives even if the fallback itself dies.  Best-effort: a persist
    failure must never block the failover it documents.

    The capture is VERIFIED before it is written: a snapshot holding a
    non-finite plane is rejected (`resilience.failover.persist_rejected`)
    so the newest file in the persist directory stays the newest GOOD
    state.  Write-side integrity beyond finiteness rides the checkpoint
    container's own per-array sha256 manifest (checkpoint/container.py),
    which load_container re-verifies."""
    global _persist_seq
    root = os.environ.get("QRACK_TPU_FAILOVER_PERSIST")
    if not root:
        return None
    try:
        from ..checkpoint.registry import (STATE_KIND_PREFIX, _flatten,
                                           capture, save_container)

        snap = capture(engine)
        if not _snapshot_is_finite(snap):
            if _tele._ENABLED:
                _tele.event("resilience.failover.persist_rejected",
                            cause=type(cause).__name__ if cause else "")
            return None
        os.makedirs(root, exist_ok=True)
        _persist_seq += 1
        name = (f"failover-{int(time.time())}-{os.getpid()}"
                f"-{_persist_seq:03d}.qckpt")
        path = os.path.join(root, name)
        flat = {}
        tree = _flatten(snap, "", flat)
        save_container(path, flat, meta={"tree": tree},
                       kind=STATE_KIND_PREFIX + snap["kind"])
    except Exception:  # noqa: BLE001
        if _tele._ENABLED:
            _tele.inc("resilience.failover.persist_failed")
        return None
    if _tele._ENABLED:
        _tele.event("resilience.failover.persisted", path=path,
                    cause=type(cause).__name__ if cause else "")
        _tele.inc("resilience.failover.persisted")
    return path

# attributes that live on the proxy itself, never forwarded
_SELF_ATTRS = ("_engine", "_chain_pos")


def _engine_kind(engine) -> str:
    name = type(engine).__name__
    return {"QPager": "pager", "QEngineTPU": "tpu",
            "QEngineCPU": "cpu",
            "QEngineTurboQuant": "turboquant",
            "QPagerTurboQuant": "turboquant_pager"}.get(name, name.lower())


def _fallback_candidates(engine):
    """Yield (kind, builder) pairs downstream of `engine` in the chain
    pager -> tpu -> cpu.  Builders take (qubit_count, state, rng).
    Quantized engines climb the PRECISION ladder first — turboquant ->
    full f32 planes — so exhausted drift replays land on a
    representation without quantization error instead of the host."""
    from ..engines.cpu import QEngineCPU
    from ..engines.tpu import MAX_DENSE_QB, QEngineTPU

    kind = _engine_kind(engine)
    n = engine.qubit_count
    if kind in ("pager", "turboquant_pager") \
            and getattr(engine, "can_shrink", None) and engine.can_shrink():
        # elastic first: halve the page count onto the surviving device
        # prefix and stay on the mesh (docs/ELASTICITY.md).  Mutates the
        # SAME engine object; the snapshot the caller took is handed in
        # so nothing re-reads the failing topology.
        yield "pager_shrunk", lambda st, rng: engine.shrink_pages(state=st)
    if kind == "pager" and n <= MAX_DENSE_QB \
            and _breaker.get_breaker().state == "closed":
        # single-device TPU is only worth trying when the accelerator is not
        # the thing that just failed (breaker still closed => the
        # failure was local to the paged path, e.g. one exchange site)
        yield "tpu", lambda st, rng: _rehydrate(QEngineTPU, n, st, rng)
    if kind in ("turboquant", "turboquant_pager") and n <= MAX_DENSE_QB:
        # drift giveup is a precision phenomenon, not a device failure,
        # so this rung is NOT breaker-gated: if the accelerator really is
        # down the dense build fails and the chain falls through to cpu
        yield "tpu", lambda st, rng: _rehydrate(QEngineTPU, n, st, rng)
    yield "cpu", lambda st, rng: _rehydrate(QEngineCPU, n, st, rng)


def _rehydrate(cls, n, state, rng):
    eng = cls(n, rng=rng)
    eng.SetQuantumState(state)
    return eng


def fail_over_engine(engine, cause: Optional[BaseException] = None):
    """Snapshot `engine`'s ket and return a rehydrated fallback engine.
    Raises the original `cause` (or RuntimeError) when the whole chain
    is exhausted — e.g. a pager wider than QRACK_MAX_CPU_QB."""
    with _faults.suspended():
        _persist_snapshot(engine, cause)
        state = engine.GetQuantumState()
        rng = getattr(engine, "rng", None)
        src = _engine_kind(engine)
        last_err: Optional[BaseException] = cause
        for kind, build in _fallback_candidates(engine):
            try:
                fallback = build(state, rng)
            except Exception as e:  # noqa: BLE001 — try next in chain
                last_err = e
                continue
            if _tele._ENABLED:
                _tele.event(f"resilience.failover.{src}_to_{kind}",
                            width=engine.qubit_count,
                            cause=type(cause).__name__ if cause else "")
                _tele.inc("resilience.failovers")
            return fallback
    raise last_err if last_err is not None else RuntimeError(
        f"no failover target for {src} width {engine.qubit_count}")


def replay_with_failover(engine, cause, replay, commit=None, max_steps=16):
    """Descend the failover chain until the failed call replays cleanly;
    returns ``(engine, result)``.

    One transition is no longer guaranteed to be enough: the elastic
    shrink candidate keeps a faulting pager on the mesh, so a persistent
    fault re-fires on the shrunk engine's replay.  Each iteration moves
    strictly down the chain (2^k pages → … → 1 page → tpu → cpu), so the
    loop terminates — :func:`fail_over_engine` raises when the chain is
    exhausted, and `max_steps` is a backstop well past any real depth.
    ``commit(new_engine)`` runs after EVERY transition so the caller's
    reference is durable even when the subsequent replay fails too.
    """
    err = cause
    for _ in range(max_steps):
        engine = fail_over_engine(engine, err)
        if commit is not None:
            commit(engine)
        try:
            return engine, replay(engine)
        except FAILOVER_ERRORS as e:
            err = e
    raise err


class ResilientEngine:
    """Forwarding proxy: any engine method that escalates with a
    FAILOVER_ERRORS exception is transparently replayed down the
    failover chain (state snapshotted pre-call — see module doc) until
    it lands.  After a terminal failover (tpu/cpu) subsequent calls stay
    on the fallback — a healed device is the NEXT circuit's business,
    via the breaker's half-open probe on a fresh engine.  An ELASTIC
    failover (pager shrink) does grow back: while the wrapped pager is
    degraded, every call boundary probes for recovery and re-expands in
    place (resilience/elastic.py)."""

    def __init__(self, engine):
        object.__setattr__(self, "_engine", engine)

    @classmethod
    def build(cls, factory, *args, **kwargs):
        """Construction-time failover: when building the primary engine
        itself dies on a guarded site (discover/first-compile), fall
        back to QEngineCPU at the same width."""
        try:
            return cls(factory(*args, **kwargs))
        except FAILOVER_ERRORS as e:
            from ..engines.cpu import QEngineCPU

            n = args[0] if args else kwargs.get("qubit_count")
            if _tele._ENABLED:
                _tele.event("resilience.failover.init_to_cpu", width=n,
                            cause=type(e).__name__)
                _tele.inc("resilience.failovers")
            kw = {k: kwargs[k] for k in ("init_state", "rng") if k in kwargs}
            return cls(QEngineCPU(n, **kw))

    # -- plumbing ------------------------------------------------------

    def _fail_over(self, cause):
        fallback = fail_over_engine(self._engine, cause)
        object.__setattr__(self, "_engine", fallback)
        return fallback

    def __getattr__(self, name):
        val = getattr(object.__getattribute__(self, "_engine"), name)
        if not callable(val):
            return val

        def call(*args, **kwargs):
            eng = object.__getattribute__(self, "_engine")
            if getattr(eng, "_elastic_target_g", None) is not None:
                # degraded pager: one probe per call boundary, growing
                # back to full page count as soon as the device returns
                from . import elastic as _elastic

                _elastic.maybe_reexpand(eng)
            try:
                return getattr(self._engine, name)(*args, **kwargs)
            except FAILOVER_ERRORS as e:
                _, out = replay_with_failover(
                    self._engine, e,
                    lambda fb: getattr(fb, name)(*args, **kwargs),
                    commit=lambda fb: object.__setattr__(self, "_engine", fb))
                return out

        call.__name__ = name
        return call

    def __setattr__(self, name, value):
        if name in _SELF_ATTRS:
            object.__setattr__(self, name, value)
        else:
            setattr(self._engine, name, value)

    def __repr__(self):
        return f"ResilientEngine({self._engine!r})"

    # len()/indexing style helpers some call sites use
    @property
    def engine(self):
        return self._engine
