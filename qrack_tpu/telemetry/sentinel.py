"""Shared roofline formula and device peak table.

This module is deliberately **stdlib-only**: no jax, no numpy, and no
package-relative imports, so bookkeeping that must not wait on backend
init can load it.  Everything here is the single source of truth:

- ``implied_gbps``      — the one implied-bandwidth formula (bytes/wall/1e9),
  for turboquant_bench.py and the roofline ledger.
- ``PEAK_GBPS`` / ``peak_gbps`` — the one per-device-class HBM peak table
  (v5e 819 GB/s default), env-overridable via ``QRACK_TPU_PEAK_GBPS``.
- ``plane_pass_bytes``  — bytes moved by one full sweep over the two ket
  planes (read + write).
- ``is_clamped``        — the honesty clamp: a line whose implied bandwidth
  exceeds its device class's peak timed a dispatch, not a completion.

The regression record is the driver's ``PERF_LEDGER.jsonl``.
"""

from __future__ import annotations

import os
from typing import Optional

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

