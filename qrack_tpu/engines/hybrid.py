"""QHybrid: transparent CPU <-> TPU <-> pager switching by width.

Re-design of the reference QHybrid (reference: include/qhybrid.hpp:35,
SwitchGpuMode :105, SwitchPagerMode :127): below `tpu_threshold_qubits`
the numpy engine wins (TPU dispatch latency dwarfs the math on tiny
kets — SURVEY.md §7 "Tiny-state dispatch overhead"); above it the JAX
engine; above `max_page_qubits` the sharded QPager. The wrapper forwards
the entire QInterface surface to the active engine and re-materializes
the ket across representations on width changes (the reference's
CopyStateVec hand-off).

Precision escalation: the dense halves honor the FPPOW policy
(QRACK_TPU_FPPOW, config.py) and — with QRACK_TPU_AUTO_F64_DRIFT set —
self-escalate their planes f32->f64 when running-norm drift exceeds the
threshold (QEngineTPU._drift_tick), so deep circuits under QHybrid
upgrade precision in place without a CPU round-trip."""

from __future__ import annotations

from typing import Optional

from .. import resilience as _res
from .. import telemetry as _tele
from ..config import get_config
from ..utils.rng import QrackRandom
from .cpu import QEngineCPU
from .tpu import QEngineTPU


class QHybrid:
    def __init__(self, qubit_count: int, init_state: int = 0,
                 rng: Optional[QrackRandom] = None,
                 tpu_threshold_qubits: Optional[int] = None,
                 pager_threshold_qubits: Optional[int] = None,
                 devices=None, **kwargs):
        cfg = get_config()
        self._tpu_threshold = (
            tpu_threshold_qubits if tpu_threshold_qubits is not None
            else cfg.hybrid_tpu_threshold_qubits
        )
        self._pager_threshold = (
            pager_threshold_qubits if pager_threshold_qubits is not None
            else cfg.max_page_qubits
        )
        self._devices = devices
        self._kwargs = dict(kwargs)
        self._kwargs["rng"] = rng if rng is not None else QrackRandom()
        # failover ceiling: None = healthy; "tpu" = pager died, never
        # re-promote past single-device; "cpu" = accelerator unusable, pin
        # to host (resilience layer, docs/RESILIENCE.md)
        self._failed_over: Optional[str] = None
        self._engine = self._make_engine(qubit_count, init_state)

    # ------------------------------------------------------------------

    def _mode_for(self, qubit_count: int) -> str:
        if self._failed_over == "cpu" or qubit_count < self._tpu_threshold:
            return "cpu"
        if qubit_count <= self._pager_threshold or self._failed_over == "tpu":
            return "tpu"
        return "pager"

    def _make_engine(self, qubit_count: int, init_state: int = 0, mode: Optional[str] = None):
        if mode is None:
            mode = self._mode_for(qubit_count)
        try:
            if mode == "cpu":
                return QEngineCPU(qubit_count, init_state=init_state, **self._kwargs)
            if mode == "tpu":
                return QEngineTPU(qubit_count, init_state=init_state, **self._kwargs)
            from ..parallel.pager import QPager

            return QPager(qubit_count, init_state=init_state, devices=self._devices,
                          **self._kwargs)
        except _res.FAILOVER_ERRORS as e:
            # construction-time failover (discover/first-compile died):
            # degrade the target mode and rebuild
            from .tpu import MAX_DENSE_QB

            fallback = ("tpu" if mode == "pager"
                        and qubit_count <= MAX_DENSE_QB else "cpu")
            self._failed_over = fallback
            if _tele._ENABLED:
                _tele.event(f"resilience.failover.init_{mode}_to_{fallback}",
                            width=qubit_count, cause=type(e).__name__)
                _tele.inc("resilience.failovers")
            return self._make_engine(qubit_count, init_state, mode=fallback)

    def _maybe_switch(self) -> None:
        """Re-materialize the ket when the width crosses a threshold
        (reference: SwitchGpuMode / SwitchPagerMode)."""
        n = self._engine.qubit_count
        want = self._mode_for(n)
        have = (
            "cpu" if isinstance(self._engine, QEngineCPU)
            else "tpu" if isinstance(self._engine, QEngineTPU)
            else "pager"
        )
        if want == have:
            return
        if _tele._ENABLED:
            _tele.event(f"hybrid.switch.{have}_to_{want}", width=n)
        state = self._engine.GetQuantumState()
        rng = self._engine.rng
        new = self._make_engine(n)
        new.rng = rng
        new.SetQuantumState(state)
        self._engine = new

    # ------------------------------------------------------------------
    # full-surface forwarding with structural hooks
    # ------------------------------------------------------------------

    def _fail_over(self, cause) -> None:
        """In-place degradation: snapshot the ket off the failing engine
        and continue the circuit on the next engine down (elastic pager
        shrink → tpu → cpu).  A tpu/cpu landing pins the ceiling; the
        un-pin probe (:meth:`_maybe_recover`) lifts it at a later call
        boundary once the device looks healthy again."""
        from ..resilience.failover import fail_over_engine

        fallback = fail_over_engine(self._engine, cause)
        self._commit_fallback(fallback)

    def _commit_fallback(self, engine) -> None:
        from ..resilience.failover import _engine_kind

        self._engine = engine
        kind = _engine_kind(engine)
        if kind in ("tpu", "cpu"):
            # a shrunk pager is NOT a ceiling — it re-expands on its own
            # through the elastic probe; only terminal hops pin the mode
            self._failed_over = kind

    def _maybe_recover(self) -> None:
        """Breaker-gated un-pin probe — the inverse of :meth:`_fail_over`
        (docs/ELASTICITY.md).  At a call boundary: re-expand a degraded
        pager in place, and when a tpu/cpu ceiling is pinned but the
        health probe passes, rebuild the width-appropriate engine and
        carry state+rng onto it, re-adopting the recovered device
        instead of staying down until process restart."""
        from ..resilience import elastic as _elastic

        eng = self._engine
        if getattr(eng, "_elastic_target_g", None) is not None:
            _elastic.maybe_reexpand(eng)
        if self._failed_over is None:
            return
        if not _elastic.health_probe():
            return
        prev = self._failed_over
        self._failed_over = None
        n = self._engine.qubit_count
        want = self._mode_for(n)
        have = (
            "cpu" if isinstance(self._engine, QEngineCPU)
            else "tpu" if isinstance(self._engine, QEngineTPU)
            else "pager"
        )
        if want == have:
            return  # ceiling lifted; the current engine already fits
        try:
            state = self._engine.GetQuantumState()
            rng = self._engine.rng
            new = self._make_engine(n)  # re-pins the ceiling on failure
            new.rng = rng
            new.SetQuantumState(state)
            self._engine = new
            if _tele._ENABLED:
                _tele.event(f"hybrid.unpin.{prev}_to_{want}", width=n)
                _tele.inc("elastic.hybrid.unpinned")
        except _res.FAILOVER_ERRORS:
            self._failed_over = prev

    def __getattr__(self, name):
        val = getattr(self._engine, name)
        if not _res._ACTIVE or not callable(val):
            return val

        def call(*args, **kwargs):
            if (self._failed_over is not None
                    or getattr(self._engine, "_elastic_target_g", None)
                    is not None):
                self._maybe_recover()
            try:
                return getattr(self._engine, name)(*args, **kwargs)
            except _res.FAILOVER_ERRORS as e:
                from ..resilience.failover import replay_with_failover

                _, out = replay_with_failover(
                    self._engine, e,
                    lambda fb: getattr(fb, name)(*args, **kwargs),
                    commit=self._commit_fallback)
                return out

        return call

    def _grow_to(self, n_new: int, mode: str, full_state) -> None:
        """Host-stage into a target-mode engine at the grown width (it
        may not exist at the current width, e.g. a pager with more pages
        than 2^n_cur)."""
        if _tele._ENABLED:
            _tele.event(f"hybrid.grow.{mode}", width=n_new)
        rng = self._engine.rng
        grown = self._make_engine(n_new, mode=mode)
        grown.rng = rng
        grown.SetQuantumState(full_state)
        self._engine = grown

    def Compose(self, other, start=None) -> int:
        inner = other._engine if isinstance(other, QHybrid) else other
        n_cur = self._engine.qubit_count
        n_new = n_cur + inner.qubit_count
        want = self._mode_for(n_new)
        if want == self._mode_for(n_cur):
            return self._engine.Compose(inner, start)
        from ..utils.states import compose_states

        if start is None:
            start = n_cur
        self._grow_to(n_new, want, compose_states(
            self._engine.GetQuantumState(), inner.GetQuantumState(),
            n_cur, inner.qubit_count, start))
        return start

    def Decompose(self, start, dest) -> None:
        inner = dest._engine if isinstance(dest, QHybrid) else dest
        self._engine.Decompose(start, inner)
        self._maybe_switch()
        if isinstance(dest, QHybrid):
            dest._maybe_switch()

    def Dispose(self, start, length, disposed_perm=None) -> None:
        self._engine.Dispose(start, length, disposed_perm)
        self._maybe_switch()

    def Allocate(self, start, length=1) -> int:
        n_cur = self._engine.qubit_count
        want = self._mode_for(n_cur + length)
        if want != self._mode_for(n_cur):
            import numpy as np

            from ..utils.states import compose_states

            zeros = np.zeros(1 << length, dtype=np.complex128)
            zeros[0] = 1.0
            self._grow_to(n_cur + length, want, compose_states(
                self._engine.GetQuantumState(), zeros, n_cur, length, start))
            return start
        res = self._engine.Allocate(start, length)
        self._maybe_switch()
        return res

    def Clone(self) -> "QHybrid":
        c = QHybrid.__new__(QHybrid)
        c._tpu_threshold = self._tpu_threshold
        c._pager_threshold = self._pager_threshold
        c._devices = self._devices
        c._kwargs = dict(self._kwargs)
        # fresh stream: the clone must not consume the original's RNG
        c._kwargs["rng"] = self._kwargs["rng"].spawn()
        c._failed_over = self._failed_over
        c._engine = self._engine.Clone()
        return c

    @property
    def qubit_count(self) -> int:
        return self._engine.qubit_count

    # ------------------------------------------------------------------
    # checkpoint protocol (checkpoint/registry.py): thresholds +
    # failover ceiling + the live engine (restored INTO this stack's
    # engine when the mode matches, else rebuilt standalone)
    # ------------------------------------------------------------------

    _ckpt_kind = "hybrid"

    def _ckpt_capture(self, capture_child):
        return {"kind": "hybrid",
                "meta": {"n": self.qubit_count,
                         "tpu_threshold": int(self._tpu_threshold),
                         "pager_threshold": int(self._pager_threshold),
                         "failed_over": self._failed_over},
                "children": {"engine": capture_child(self._engine)}}

    def _ckpt_restore(self, arrays, meta, children, restore_child):
        if int(meta["n"]) != self.qubit_count:
            raise ValueError("checkpoint width mismatch")
        self._tpu_threshold = int(meta["tpu_threshold"])
        self._pager_threshold = int(meta["pager_threshold"])
        self._failed_over = meta.get("failed_over")
        self._engine = restore_child(children["engine"], self._engine)
        rng = getattr(self._engine, "rng", None)
        if rng is not None:
            # future mode switches must carry the restored stream
            self._kwargs["rng"] = rng
