"""Integrity guard plane: silent-data-corruption detection, scoped
window replay, and device quarantine (docs/INTEGRITY.md).

The resilience layer (PR 2/PR 6) catches faults that ANNOUNCE
themselves — hangs, timeouts, raised device loss.  A flipped bit in
HBM or a corrupted ICI exchange is silent: the dispatch returns, the
planes look plausible, and the error sails through to the user.  This
module closes that gap with three mechanisms, all gated behind the
same off-by-default discipline as the rest of resilience/ (one module
attribute read + truth test per site when inactive):

* **Boundary invariants** — every state fingerprint is checked against
  two invariants: finiteness, and a norm-drift budget whose tolerance
  is scheduled on gates-since-last-verified (a freshly verified ket
  must sum to ``running_norm`` within ``tol``; each further gate earns
  ``tol_per_gate`` of slack for legitimate f32 rounding).  Fingerprints
  are cheap: per-page probability sums for the pager (one reduction,
  ``n_pages`` scalars over the wire), a single norm scalar for the
  dense engine, and at devget-honest read boundaries the already-
  fetched host array is checked in place so the invariant costs no
  extra HBM sweep.

* **Scoped window replay** — detection wraps the gate-stream flush
  (ops/fusion.py): the fuser holds gates until a flush succeeds, so a
  violated invariant restores the pre-flush planes from a host
  snapshot and re-dispatches the SAME kept window — exactly-once by
  construction.  A replay that comes back clean proves the corruption
  transient; the page whose fingerprint differed between the corrupt
  and clean runs is the attribution (exact, no oracle needed).  A
  replay that corrupts again escalates as DispatchGiveUp into the
  existing shrink-staircase / failover chain with the GOOD planes
  restored, so failover snapshots never capture poison.

* **Device quarantine** — attributed strikes accumulate per device id;
  past ``QRACK_TPU_QUARANTINE_STRIKES`` the device joins a process-
  wide quarantine list consumed by the pager's elastic re-paging
  (parallel/pager.py ``_device_pool``): the flaky chip is excluded and
  a spare takes its place at the next job boundary, instead of the
  whole-device breaker tripping.

The serve-side canary verifier (serve/canary.py) feeds the same strike
table from full-fidelity oracle replays of sampled jobs.

Env knobs:

* ``QRACK_TPU_INTEGRITY`` — "0" disables the plane even when
  resilience is active; any other value (or unset) leaves it armed
  WHEN resilience is active.  With resilience inactive (the bench /
  library default) every hook costs one attribute read.
* ``QRACK_TPU_INTEGRITY_TOL`` (default 1e-3) — base norm budget.
* ``QRACK_TPU_INTEGRITY_TOL_PER_GATE`` (default 1e-6) — per-gate slack.
* ``QRACK_TPU_INTEGRITY_REPLAYS`` (default 2) — window replays before
  escalating to the failover chain.
* ``QRACK_TPU_QUARANTINE_STRIKES`` (default 3) — strikes before a
  device is quarantined.

Telemetry (`integrity.*`, scripts/telemetry_report.py `== integrity ==`):
``integrity.violation`` events (site/reason/attempt),
``integrity.replay.repaired`` / ``integrity.replay.giveup``,
``integrity.quarantine.strike`` / ``integrity.quarantine.device``,
``integrity.canary.*`` (serve/canary.py), and the
``integrity.quarantined`` gauge.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

import numpy as np

from .. import telemetry as _tele
from .errors import CorruptionDetected, DispatchGiveUp

_ENABLED: bool = os.environ.get("QRACK_TPU_INTEGRITY", "") != "0"

_LOCK = threading.Lock()
_STRIKES: Dict[int, int] = {}      # device id -> attributed strikes
_QUARANTINED: frozenset = frozenset()
#: bumped on every quarantine-set change; consumers (pager job-boundary
#: probe) cache the last epoch seen so the healthy-path cost is one
#: module attribute read + int compare
_EPOCH: int = 0


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def armed() -> bool:
    """True when the guard plane should act: resilience active AND the
    integrity gate on.  Callers on hot paths check ``_res._ACTIVE``
    first so the inactive cost stays one attribute read."""
    from . import _ACTIVE

    return _ACTIVE and _ENABLED


# -- budgets -----------------------------------------------------------


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def drift_budget(gates_since: int) -> float:
    """Norm tolerance scheduled on gates since the last verified
    fingerprint: base + per-gate slack for legitimate f32 rounding."""
    base = _env_float("QRACK_TPU_INTEGRITY_TOL", 1e-3)
    per_gate = _env_float("QRACK_TPU_INTEGRITY_TOL_PER_GATE", 1e-6)
    return base + per_gate * max(0, int(gates_since))


def quant_slack(eng) -> float:
    """Extra norm tolerance for quantized (turboquant) engines: every
    flush requantizes the touched chunks, so chunk masses legitimately
    walk by O(scale/qmax) per window.  Additive on top of the dense
    drift budget; default scales with the code resolution."""
    qmax = getattr(eng, "_qmax", None)
    if qmax is None or getattr(eng, "_tq_bits", None) is None:
        return 0.0
    return _env_float("QRACK_TPU_INTEGRITY_TOL_QUANT", 4.0 / float(qmax))


def max_replays() -> int:
    try:
        return int(os.environ.get("QRACK_TPU_INTEGRITY_REPLAYS", "2"))
    except ValueError:
        return 2


def strike_threshold() -> int:
    try:
        return int(os.environ.get("QRACK_TPU_QUARANTINE_STRIKES", "3"))
    except ValueError:
        return 3


# -- fingerprints ------------------------------------------------------


def fingerprint(eng) -> np.ndarray:
    """Per-page probability sums (pager) or the one-element norm vector
    (dense engine) of the RESIDENT planes — the cheap proxy every
    invariant is checked against.  Reads ``_state_raw`` directly: the
    guard runs inside a flush, where the property getter is a re-entry
    hazard."""
    from . import faults as _faults

    if getattr(eng, "_tq_bits", None) is not None:
        # turboquant: per-chunk probability masses straight off the
        # resident int codes (no decompression — the block rotation is
        # orthogonal, so row norms survive compression).  Raw-attribute
        # reads for the same re-entry reason as `_state_raw` below.
        with _faults.suspended():
            C, cb = eng._n_chunks(), eng._chunk_blocks
            return np.asarray(eng._chunk_masses(
                eng._codes_raw.reshape(C, cb, -1),
                eng._scales_raw.reshape(C, cb)),
                dtype=np.float64).reshape(-1)
    state = eng._state_raw
    with _faults.suspended():
        # the verification read must neither advance fault-spec call
        # counters (injection stays deterministic under the guard) nor
        # be corrupted/refused itself — same discipline as failover
        # snapshot reads
        probs_prog = getattr(eng, "_p_page_probs", None)
        if probs_prog is not None:
            return np.asarray(probs_prog()(state),
                              dtype=np.float64).reshape(-1)
        from ..engines.tpu import _j_prob_mask

        return np.asarray([float(_j_prob_mask(state, 0, 0))],
                          dtype=np.float64)


def host_fingerprint(planes: np.ndarray, n_pages: int = 1) -> np.ndarray:
    """Fingerprint of a HOST snapshot (the pre-flush keep): per-page
    probability sums computed in numpy, page p owning the p-th
    contiguous slice of axis 1 — the pager's P(None, "pages") layout."""
    planes = np.asarray(planes, dtype=np.float64)
    pages = planes.reshape(2, n_pages, -1)
    return np.sum(pages[0] ** 2 + pages[1] ** 2, axis=1)


def verify(eng, site: str) -> np.ndarray:
    """Check the resident planes against the boundary invariants.
    Returns the (clean) fingerprint; raises CorruptionDetected with the
    offending fingerprint attached on a violation.  A pass re-anchors
    the engine's drift budget (``_integ_mark``)."""
    fp = fingerprint(eng)
    gate_count = int(getattr(eng, "_gate_count", 0))
    if not np.all(np.isfinite(fp)):
        raise CorruptionDetected(site, "non-finite fingerprint", fp=fp)
    expected = float(getattr(eng, "running_norm", 1.0) or 1.0)
    gates_since = gate_count - int(getattr(eng, "_integ_mark", 0))
    budget = drift_budget(gates_since)
    total = float(fp.sum())
    drift = abs(total - expected)
    slack = quant_slack(eng)
    if slack:
        # quantized engines: requantization walks the mass away from
        # running_norm over a long circuit, so ALSO accept the last
        # verified mass as an anchor — corruption shows as a jump
        # against both, legitimate quant drift tracks the anchor.  A
        # blind reset (SetPermutation/SetQuantumState) lands back on
        # running_norm, so the stale anchor cannot false-positive.
        budget += slack
        anchor = getattr(eng, "_integ_mass_anchor", None)
        if anchor is not None:
            drift = min(drift, abs(total - float(anchor)))
    if drift > budget:
        raise CorruptionDetected(
            site, f"norm drift {drift:.3e} exceeds budget {budget:.3e} "
            f"({gates_since} gates since last verify)", fp=fp)
    eng._integ_mark = gate_count
    if slack:
        eng._integ_mass_anchor = total
    return fp


def check_host(site: str, arr, *, norm_expected: Optional[float] = None,
               gates_since: int = 0) -> None:
    """Boundary invariant over an ALREADY-FETCHED host array (the
    devget-honest read path) — no extra device traffic.  Finiteness
    always; norm only when the caller read a whole ket and passes its
    expected norm."""
    from . import faults as _faults

    if _faults.is_suspended():
        return  # recovery reads (failover snapshot, re-page gather)
    a = np.asarray(arr)
    if not np.issubdtype(a.dtype, np.floating) and \
            not np.issubdtype(a.dtype, np.complexfloating):
        return
    if not np.all(np.isfinite(a)):
        _violation(site, "non-finite host read")
        raise CorruptionDetected(site, "non-finite value in host read")
    if norm_expected is not None:
        nrm = float(np.sum(np.abs(a) ** 2))
        budget = drift_budget(gates_since)
        if abs(nrm - norm_expected) > budget:
            _violation(site, "host-read norm drift")
            raise CorruptionDetected(
                site, f"host-read norm {nrm:.6f} vs expected "
                f"{norm_expected:.6f} (budget {budget:.3e})")


def _violation(site: str, reason: str, **fields) -> None:
    if _tele._ENABLED:
        _tele.event("integrity.violation", site=site, reason=reason,
                    **fields)


# -- scoped window replay ----------------------------------------------


def _snapshot(eng):
    """Host copy of the resident planes taken BEFORE a flush dispatch.
    Donation invalidates the input buffers whether or not the dispatch
    corrupts, so replay is only possible from a copy that left the
    device first.  Quantized engines snapshot (codes, scales) — the
    compressed form IS the state, and copying it costs the compression
    ratio less than a decompressed ket would."""
    if getattr(eng, "_tq_bits", None) is not None:
        return (np.asarray(eng._codes_raw), np.asarray(eng._scales_raw))
    return np.asarray(eng._state_raw)


def _tq_host_fingerprint(eng, keep) -> np.ndarray:
    """Per-chunk masses of a HOST (codes, scales) snapshot, computed in
    numpy — the quantized analogue of :func:`host_fingerprint`."""
    codes, scales = keep
    C, cb = eng._n_chunks(), eng._chunk_blocks
    y = (codes.astype(np.float64).reshape(C, cb, -1)
         * (scales.astype(np.float64).reshape(C, cb)
            / float(eng._qmax))[..., None])
    return np.sum(y * y, axis=(1, 2))


def _restore(eng, keep) -> None:
    """Re-put the pre-flush planes.  Assigns the raw attribute — the
    property setter's drop-on-overwrite discipline must not fire for a
    repair that is about to re-dispatch the kept window."""
    import jax
    import jax.numpy as jnp

    if isinstance(keep, tuple):
        # quantized keep: land via the engine's own placement hook
        # (sharded subclass re-meshes).  The flush envelope holds the
        # fuser's _flushing latch, so the property setters inside
        # _ckpt_place cannot drop the kept window.
        codes, scales = keep
        eng._ckpt_place(np.asarray(codes, dtype=eng._code_np),
                        np.asarray(scales, dtype=np.float32))
        return
    sharding = getattr(eng, "sharding", None)
    if sharding is not None:
        eng._state_raw = jax.device_put(
            np.asarray(keep, dtype=eng.dtype), sharding)
    else:
        put = getattr(eng, "_put", None)
        planes = jnp.asarray(keep, dtype=eng.dtype)
        eng._state_raw = put(planes) if put is not None else planes


def _attribute(eng, corrupt_fp: np.ndarray, clean_fp: np.ndarray,
               site: str) -> Optional[int]:
    """Which device produced the corruption: the page whose fingerprint
    differs between the corrupt and the clean run of the SAME window —
    exact for a repaired replay (deterministic program, same input), a
    pre-flush-baseline heuristic when escalating."""
    if corrupt_fp is None or clean_fp is None or \
            corrupt_fp.shape != clean_fp.shape:
        return None
    bad = ~np.isfinite(corrupt_fp)
    if bad.any():
        page = int(np.argmax(bad))
    else:
        page = int(np.argmax(np.abs(corrupt_fp - clean_fp)))
    try:
        dev = eng.GetDeviceList()[page]
    except Exception:  # noqa: BLE001 — attribution is best-effort
        return None
    record_strike(dev, site, page=page)
    return dev


def guarded_flush(eng, flush_fn, site: str = "tpu.fuse.flush") -> int:
    """Snapshot → dispatch → verify → replay envelope around one fused-
    window flush.  Corruption inside the window (the flush program, or
    the single-op fast path it lowers to — ``pager.exchange`` global
    gates included) restores the pre-flush planes and re-dispatches the
    same kept gates; a replay that corrupts again gives up with good
    planes restored, handing the existing shrink/failover chain an
    uncorrupted base."""
    keep = _snapshot(eng)
    # the placement table travels with the planes: a flush that commits
    # a remap before verify catches corruption must roll BOTH back, or
    # the replay would translate the kept gates through the wrong table
    keep_map = getattr(eng, "_qmap", None)
    keep_map = list(keep_map) if keep_map is not None else None
    keep_fp = (_tq_host_fingerprint(eng, keep) if isinstance(keep, tuple)
               else host_fingerprint(keep, getattr(eng, "n_pages", 1)))
    corrupt_fp = None
    cause = None
    for attempt in range(max_replays() + 1):
        dispatched = flush_fn()
        try:
            clean_fp = verify(eng, site)
        except CorruptionDetected as e:
            _violation(site, e.detail, attempt=attempt)
            corrupt_fp, cause = e.fp, e
            _restore(eng, keep)
            if keep_map is not None:
                eng._map_assign(keep_map)
            continue
        if attempt:
            _attribute(eng, corrupt_fp, clean_fp, site)
            if _tele._ENABLED:
                _tele.event("integrity.replay.repaired", site=site,
                            replays=attempt)
        return dispatched
    # every replay corrupted: attribute against the pre-flush baseline
    # (heuristic — a legitimate window moves mass between pages too),
    # restore the good planes, and escalate to shrink/failover
    _attribute(eng, corrupt_fp, keep_fp, site)
    _restore(eng, keep)
    if keep_map is not None:
        eng._map_assign(keep_map)
    if _tele._ENABLED:
        _tele.event("integrity.replay.giveup", site=site,
                    replays=max_replays())
    raise DispatchGiveUp(site, cause)


# -- quarantine --------------------------------------------------------


def record_strike(device_id, site: str, page: Optional[int] = None) -> None:
    """One attributed corruption against ``device_id``; quarantines the
    device once strikes reach the threshold."""
    global _QUARANTINED, _EPOCH
    if device_id is None:
        return
    with _LOCK:
        n = _STRIKES.get(device_id, 0) + 1
        _STRIKES[device_id] = n
        newly = n >= strike_threshold() and device_id not in _QUARANTINED
        if newly:
            _QUARANTINED = _QUARANTINED | {device_id}
            _EPOCH += 1
    if _tele._ENABLED:
        _tele.event("integrity.quarantine.strike", device=device_id,
                    site=site, strikes=n,
                    **({} if page is None else {"page": page}))
        if newly:
            _tele.event("integrity.quarantine.device", device=device_id,
                        site=site)
        _tele.gauge("integrity.quarantined", float(len(_QUARANTINED)))


def quarantined() -> frozenset:
    return _QUARANTINED


def strikes() -> Dict[int, int]:
    with _LOCK:
        return dict(_STRIKES)


def healthy_devices(devices: List) -> List:
    """Filter a device list through the quarantine set (order kept)."""
    q = _QUARANTINED
    if not q:
        return list(devices)
    out = [d for d in devices if getattr(d, "id", None) not in q]
    # never filter down to an unusable pool: a fully-quarantined mesh
    # still has to serve (degraded beats dead — breaker semantics)
    return out if out else list(devices)


def reset() -> None:
    """Drop all strikes and quarantined devices (tests)."""
    global _QUARANTINED, _EPOCH
    with _LOCK:
        _STRIKES.clear()
        _QUARANTINED = frozenset()
        _EPOCH += 1


def snapshot() -> dict:
    with _LOCK:
        return {"enabled": _ENABLED, "strikes": dict(_STRIKES),
                "quarantined": sorted(_QUARANTINED),
                "epoch": _EPOCH}
