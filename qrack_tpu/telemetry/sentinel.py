"""Shared roofline formula, device peak table, and the perf-regression sentinel.

This module is deliberately **stdlib-only**: no jax, no numpy, and no
package-relative imports.  ``scripts/perf_sentinel.py`` loads it directly by
file path with ``importlib`` so the campaign's evidence bookkeeping (which
runs under ``env -u PYTHONPATH`` while the backend may be hung) can
never hang on backend init.  Everything here is the single source of truth:

- ``implied_gbps``      — the one implied-bandwidth formula (bytes/wall/1e9),
  for turboquant_bench.py and the roofline ledger.
- ``PEAK_GBPS`` / ``peak_gbps`` — the one per-device-class HBM peak table
  (v5e 819 GB/s default), env-overridable via ``QRACK_TPU_PEAK_GBPS``.
- ``plane_pass_bytes``  — bytes moved by one full sweep over the two ket
  planes (read + write).
- Trajectory loading + verdicts — parse the committed evidence
  (``docs/tpu_results.jsonl`` and the embedded JSONL ``"tail"`` strings in
  ``BENCH_*.json``) and stamp every fresh line better/same/worse/new within
  a noise band (``QRACK_SENTINEL_NOISE_BAND``, default 10%).
"""

from __future__ import annotations

import glob
import json
import os
import time
from typing import Dict, List, Optional

# HBM peak bandwidth per device class, GB/s.  Matched by substring against a
# lowercased device-kind string (jax reports e.g. "TPU v5 lite").  The v5e
# figure (819) is the number every committed evidence line has been
# honesty-checked against; it is also the fallback for cpu/unknown so CPU
# anchor lines quote their fraction of the *accelerator* roofline.
DEFAULT_PEAK_GBPS = 819.0
PEAK_GBPS = (
    ("v5 lite", 819.0),
    ("v5e", 819.0),
    ("v5litepod", 819.0),
    ("v5p", 2765.0),
    ("v6e", 1640.0),
    ("trillium", 1640.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)

# Relative noise band for sentinel verdicts: a fresh wall within +/- band of
# the best committed wall is "same".
DEFAULT_NOISE_BAND = 0.10

VERDICTS = ("better", "same", "worse", "new", "replay")


def peak_gbps(kind: Optional[str]) -> float:
    """Peak HBM GB/s for a device-kind string; env override wins."""
    env = os.environ.get("QRACK_TPU_PEAK_GBPS", "")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    low = (kind or "").lower()
    for sub, peak in PEAK_GBPS:
        if sub in low:
            return peak
    return DEFAULT_PEAK_GBPS


def noise_band() -> float:
    env = os.environ.get("QRACK_SENTINEL_NOISE_BAND", "")
    if env:
        try:
            return max(0.0, float(env))
        except ValueError:
            pass
    return DEFAULT_NOISE_BAND


def implied_gbps(nbytes: float, wall_s: float) -> float:
    """The one implied-bandwidth formula: bytes moved / wall seconds / 1e9."""
    return float(nbytes) / max(float(wall_s), 1e-12) / 1e9


def plane_pass_bytes(width: int, esize: int = 4) -> int:
    """HBM bytes for one full sweep over the ket: 2 planes * 2^width amps
    * esize bytes, read + write."""
    return 2 * (1 << int(width)) * int(esize) * 2


def is_clamped(line: dict, peak: Optional[float] = None) -> bool:
    """True when a line's implied bandwidth exceeds the device-class peak —
    the dispatch-ack signature (dispatch acked, completion never timed)."""
    gbps = line.get("implied_hbm_gbps")
    if gbps is None:
        gbps = line.get("implied_codes_gbps")
    if gbps is None:
        return False
    if peak is None:
        dev = line.get("device_class") or {}
        peak = dev.get("peak_gbps") or peak_gbps(dev.get("kind"))
    try:
        return float(gbps) > float(peak)
    except (TypeError, ValueError):
        return False


# ---------------------------------------------------------------------------
# Committed-trajectory loading and verdicts
# ---------------------------------------------------------------------------

def line_key(line: dict) -> Optional[str]:
    """Stable comparison key for an evidence line (replay suffix folded in)."""
    metric = line.get("metric")
    if metric:
        key = str(metric)
        if key.endswith("_committed_evidence"):
            key = key[: -len("_committed_evidence")]
        return key
    gate = line.get("gate")
    if gate:
        key = "gate_%s_w%s" % (gate, line.get("width", "?"))
        bits = line.get("bits")
        if bits:
            key += "_b%s" % bits
        return key
    return None


def line_value(line: dict) -> Optional[float]:
    """Lower-is-better wall seconds for an evidence line, or None."""
    for field in ("value", "wall_s", "avg_wall_s", "avg"):
        v = line.get(field)
        if v is not None:
            try:
                v = float(v)
            except (TypeError, ValueError):
                return None
            return v if v > 0 else None
    return None


def _iter_jsonl(text: str):
    for raw in text.splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            d = json.loads(raw)
        except ValueError:
            continue
        if isinstance(d, dict):
            yield d


def load_trajectory(root: str = ".") -> Dict[str, List[float]]:
    """Committed per-key wall history from docs/tpu_results.jsonl and the
    embedded JSONL ``"tail"`` strings of BENCH_*.json / MULTICHIP_*.json."""
    hist: Dict[str, List[float]] = {}

    def add(d: dict) -> None:
        if d.get("suspect_timing") or d.get("roofline_clamped"):
            return
        key, val = line_key(d), line_value(d)
        if key and val is not None:
            hist.setdefault(key, []).append(val)

    jsonl = os.path.join(root, "docs", "tpu_results.jsonl")
    if os.path.exists(jsonl):
        try:
            with open(jsonl) as fh:
                for d in _iter_jsonl(fh.read()):
                    add(d)
        except OSError:
            pass
    for pat in ("BENCH_*.json", "MULTICHIP_*.json"):
        for path in sorted(glob.glob(os.path.join(root, pat))):
            try:
                with open(path) as fh:
                    doc = json.load(fh)
            except (OSError, ValueError):
                continue
            tail = doc.get("tail") if isinstance(doc, dict) else None
            if isinstance(tail, str):
                for d in _iter_jsonl(tail):
                    add(d)
    return hist


def verdict(key: Optional[str], value: Optional[float],
            traj: Dict[str, List[float]],
            band: Optional[float] = None) -> str:
    """Compare a fresh wall against the best committed wall for its key."""
    if key is None or value is None:
        return "new"
    prior = traj.get(key)
    if not prior:
        return "new"
    if band is None:
        band = noise_band()
    best = min(prior)
    if value <= best * (1.0 - band):
        return "better"
    if value >= best * (1.0 + band):
        return "worse"
    return "same"


def stamp(line: dict, traj: Dict[str, List[float]],
          band: Optional[float] = None) -> str:
    """Stamp sentinel verdict (+ reference wall) into a line, in place.
    Replayed `_committed_evidence` lines get the "replay" verdict so they are
    distinguishable from fresh on-chip measurements at a glance."""
    metric = str(line.get("metric") or "")
    if metric.endswith("_committed_evidence") or line.get("replayed"):
        line["sentinel"] = "replay"
        line["fresh"] = False
        return "replay"
    key, val = line_key(line), line_value(line)
    v = verdict(key, val, traj, band)
    line["sentinel"] = v
    line["fresh"] = True
    prior = traj.get(key or "")
    if prior:
        line["sentinel_ref_wall_s"] = min(prior)
        line["sentinel_band"] = band if band is not None else noise_band()
    return v


def stamp_evidence_line(line: dict, traj: Dict[str, List[float]],
                        stage: Optional[str] = None,
                        default_device: Optional[dict] = None) -> dict:
    """Full campaign-evidence stamping: timestamp, stage, sentinel verdict,
    and a device-class fingerprint (kept if the line already carries one)."""
    line.setdefault("ts", time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    if stage:
        line.setdefault("stage", stage)
    stamp(line, traj)
    if "device_class" not in line:
        dev = dict(default_device or {})
        if not dev:
            kind = os.environ.get("QRACK_TPU_DEVICE_KIND", "") or "unknown"
            dev = {"kind": kind, "peak_gbps": peak_gbps(kind)}
        line["device_class"] = dev
    return line
