"""Per-dispatch roofline ledger: planned HBM bytes vs devget-honest walls.

Every guarded dispatch site (``tpu.fuse.flush``, ``pager.exchange``,
``serve.dispatch``, turboquant sweeps) reports the bytes it *planned* to move
through :func:`note_bytes`; sites that also own an honest wall clock call
:func:`record`, which derives implied HBM bandwidth through the one shared
formula in :mod:`qrack_tpu.telemetry.sentinel` and publishes

- ``roofline.<site>.implied_hbm_gbps``   histogram (+ p50/p95/p99 gauges)
- ``roofline.<site>.peak_frac``          achieved-vs-peak-fraction gauge
  (with per-width / per-stack facets when the caller supplies them)
- ``roofline.<site>.planned_bytes`` / ``.dispatches`` counters

Timing honesty is structural: a sample whose implied bandwidth exceeds the
device-class peak is the dispatch-ack signature (dispatch acked, completion
never timed).  Such samples bump ``roofline.honesty.clamped`` (counter +
event) and ``roofline.<site>.clamped``, and are **excluded** from the
histogram and gauges.

The device-class fingerprint (kind, HBM bytes, peak GB/s) is captured from an
*already-initialized* jax backend only — this module never triggers backend
init, because init on an unhealthy backend can hang — and is
persisted in the checkpoint store as
``device_class.json`` (the substrate the roadmap's autotuner reads).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
from typing import Optional

from qrack_tpu import telemetry as _tele
from .sentinel import implied_gbps, peak_gbps, plane_pass_bytes  # noqa: F401

FINGERPRINT_FILE = "device_class.json"

_FP: Optional[dict] = None


def _probe_backend() -> dict:
    """Best-effort device identity from an already-initialized jax backend.

    Returns {} unless jax is imported AND a backend exists — probing must be
    free of side effects (no init, no RPC) so the ledger is safe to call from
    processes that never touched the device."""
    if "jax" not in sys.modules:
        return {}
    try:
        # private; checked on jax 0.9.0: empty until the first init
        from jax._src import xla_bridge

        if not getattr(xla_bridge, "_backends", None):
            return {}
        import jax

        devs = jax.devices()
        dev = devs[0]
        out = {
            "platform": str(getattr(dev, "platform", "") or ""),
            "kind": str(getattr(dev, "device_kind", "") or ""),
            "n_devices": len(devs),
        }
        stats = getattr(dev, "memory_stats", None)
        if callable(stats):
            try:
                hbm = (stats() or {}).get("bytes_limit")
                if hbm:
                    out["hbm_bytes"] = int(hbm)
            except Exception:
                pass
        return out
    except Exception:
        return {}


def device_class(refresh: bool = False,
                 platform_hint: Optional[str] = None) -> dict:
    """The device-class fingerprint: kind, platform, HBM bytes, peak GB/s.

    Resolution order: ``QRACK_TPU_DEVICE_KIND`` env override, live backend
    probe (side-effect free), persisted fingerprint from the checkpoint
    store, then the caller's platform hint (e.g. a bench child's reported
    platform when the parent never imports jax)."""
    global _FP
    if _FP is not None and not refresh:
        if _FP.get("kind") not in ("", "unknown") or platform_hint is None:
            return dict(_FP)
    fp = {"kind": "unknown", "platform": "", "hbm_bytes": None}
    env_kind = os.environ.get("QRACK_TPU_DEVICE_KIND", "")
    probed = _probe_backend()
    if probed:
        fp["platform"] = probed.get("platform", "")
        fp["kind"] = probed.get("kind") or probed.get("platform") or "unknown"
        if probed.get("hbm_bytes"):
            fp["hbm_bytes"] = probed["hbm_bytes"]
        if probed.get("n_devices"):
            fp["n_devices"] = probed["n_devices"]
    else:
        loaded = load_fingerprint(os.environ.get(
            "QRACK_SERVE_CHECKPOINT_DIR", ""))
        if loaded:
            fp.update({k: loaded[k] for k in
                       ("kind", "platform", "hbm_bytes", "n_devices")
                       if k in loaded})
        elif platform_hint:
            fp["kind"] = fp["platform"] = str(platform_hint)
    if env_kind:
        fp["kind"] = env_kind
    fp["peak_gbps"] = peak_gbps(fp["kind"])
    _FP = dict(fp)
    return fp


def _reset_fingerprint_cache() -> None:
    """Test hook: drop the cached fingerprint."""
    global _FP
    _FP = None


def persist_fingerprint(checkpoint_dir: str) -> Optional[str]:
    """Write the fingerprint as <dir>/device_class.json.

    A persisted known kind is never overwritten by an unknown one (the serve
    process may restart while the backend is hung).  Best-effort: never
    raises."""
    try:
        fp = device_class()
        path = os.path.join(checkpoint_dir, FINGERPRINT_FILE)
        if fp.get("kind") in ("", "unknown"):
            prior = load_fingerprint(checkpoint_dir)
            if prior and prior.get("kind") not in ("", "unknown", None):
                return path
        os.makedirs(checkpoint_dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=checkpoint_dir, suffix=".tmp")
        with os.fdopen(fd, "w") as fh:
            json.dump(fp, fh, sort_keys=True)
        os.replace(tmp, path)
        return path
    except Exception:
        return None


def load_fingerprint(checkpoint_dir: str) -> Optional[dict]:
    if not checkpoint_dir:
        return None
    try:
        with open(os.path.join(checkpoint_dir, FINGERPRINT_FILE)) as fh:
            fp = json.load(fh)
        return fp if isinstance(fp, dict) else None
    except (OSError, ValueError):
        return None


def note_bytes(site: str, nbytes: float) -> None:
    """Ledger entry for a dispatch whose wall is timed elsewhere (or not at
    all): planned HBM bytes + dispatch count per site."""
    if not _tele._ENABLED:
        return
    _tele.inc(f"roofline.{site}.dispatches")
    _tele.inc(f"roofline.{site}.planned_bytes", float(nbytes))


def record(site: str, nbytes: float, wall_s: float,
           width: Optional[int] = None, stack: Optional[str] = None,
           platform: Optional[str] = None) -> dict:
    """Full roofline sample for a devget-honest dispatch: planned bytes +
    wall → implied GB/s, peak fraction, and the honesty clamp.

    Returns the sample dict (implied_hbm_gbps, hbm_peak_gbps,
    hbm_roofline_frac, clamped, device_class) for callers that stamp JSON
    lines; telemetry publication is skipped when disabled, but the sample is
    always computed."""
    gbps = implied_gbps(nbytes, wall_s)
    dev = device_class(platform_hint=platform)
    peak = dev["peak_gbps"]
    frac = gbps / peak if peak else 0.0
    clamped = gbps > peak
    sample = {
        "implied_hbm_gbps": round(gbps, 2),
        "hbm_peak_gbps": peak,
        "hbm_roofline_frac": round(frac, 4),
        "clamped": clamped,
        "device_class": dev,
    }
    if not _tele._ENABLED:
        return sample
    note_bytes(site, nbytes)
    if clamped:
        _tele.inc(f"roofline.{site}.clamped")
        _tele.event("roofline.honesty.clamped", site=site,
                    gbps=round(gbps, 1), peak=peak, width=width)
        return sample
    _tele.observe(f"roofline.{site}.implied_hbm_gbps", gbps)
    _tele.gauge(f"roofline.{site}.peak_frac", round(frac, 4))
    if width is not None:
        facet = f"{stack}.w{width}" if stack else f"w{width}"
        _tele.gauge(f"roofline.{site}.{facet}.peak_frac", round(frac, 4))
    return sample
