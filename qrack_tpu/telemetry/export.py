"""Telemetry exporters: JSONL snapshots and Chrome trace-event JSON.

Formats:

* **JSONL** — one :func:`qrack_tpu.telemetry.snapshot` dict per line,
  appended (a long campaign accumulates a history; consumers take the
  last line).  Armed at process exit by ``QRACK_TPU_TELEMETRY_OUT``.
* **Chrome trace-event JSON** — the `{"traceEvents": [...]}` object
  format (https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU):
  spans become ``"ph": "X"`` complete events, discrete telemetry events
  become ``"ph": "i"`` instants, and every counter's final value is one
  ``"ph": "C"`` sample at the end of the trace.  Loads directly in
  Perfetto / chrome://tracing.
* **Merged fleet trace** — :func:`merged_chrome_trace` folds N
  processes' trace sources (live snapshots or flight-recorder black
  boxes, each carrying its own ``epoch_unix_s`` wall anchor) into ONE
  Perfetto-loadable timeline, one track per worker incarnation, with
  every span's distributed-trace id in its args — so a single submit
  can be followed from the front door's ``frontdoor.apply`` through the
  worker's ``worker.submit.journal`` to the executor's
  ``serve.execute`` devget on one screen.
"""

from __future__ import annotations

import json
import os
from typing import Optional

_US = 1e6
_ATEXIT_ARMED = False


def write_jsonl(path: Optional[str] = None) -> str:
    """Append one snapshot line to `path` (default:
    QRACK_TPU_TELEMETRY_OUT).  Returns the path written."""
    from . import snapshot

    if path is None:
        path = os.environ.get("QRACK_TPU_TELEMETRY_OUT", "")
    if not path:
        raise ValueError(
            "no output path: pass one or set QRACK_TPU_TELEMETRY_OUT")
    with open(path, "a") as f:
        f.write(json.dumps(snapshot()) + "\n")
    return path


def _dump() -> None:
    """The registered exit hook: re-reads the enable gate and the out
    path at exit time, and never raises."""
    from . import _ENABLED

    if _ENABLED and os.environ.get("QRACK_TPU_TELEMETRY_OUT"):
        try:
            write_jsonl()
        except Exception:
            pass  # exit hooks must never raise


def arm_atexit() -> None:
    """Register the one-shot exit dump (idempotent; no-op without an
    out path at exit time)."""
    global _ATEXIT_ARMED
    if _ATEXIT_ARMED:
        return
    _ATEXIT_ARMED = True
    import atexit

    atexit.register(_dump)


def chrome_trace() -> dict:
    """Trace-event JSON object for the current telemetry state."""
    from . import _EVENTS, _LOCK, _TRACE, snapshot

    pid = os.getpid()
    evs = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": "qrack_tpu"},
    }]
    with _LOCK:
        trace = list(_TRACE)
        events = list(_EVENTS)
    end_us = 0.0
    for t in trace:
        ts = t["ts_s"] * _US
        dur = t["dur_s"] * _US
        end_us = max(end_us, ts + dur)
        evs.append({
            "name": t["name"], "ph": "X", "cat": "span",
            "ts": ts, "dur": dur, "pid": pid, "tid": t["tid"],
            "args": {"depth": t["depth"], "id": t["id"],
                     "parent": t["parent"]},
        })
    for e in events:
        ts = e["t_s"] * _US
        end_us = max(end_us, ts)
        args = {k: v for k, v in e.items() if k not in ("name", "t_s")}
        evs.append({
            "name": e["name"], "ph": "i", "cat": "event", "s": "p",
            "ts": ts, "pid": pid, "tid": 0, "args": args,
        })
    snap = snapshot(include_events=False)
    for name, value in sorted(snap["counters"].items()):
        evs.append({
            "name": name, "ph": "C", "ts": end_us, "pid": pid, "tid": 0,
            "args": {"value": value},
        })
    # roofline gauges ride as counter tracks too: achieved-vs-peak
    # fractions next to the spans that produced them
    for name, value in sorted(snap["gauges"].items()):
        if name.startswith("roofline."):
            evs.append({
                "name": name, "ph": "C", "ts": end_us, "pid": pid,
                "tid": 0, "args": {"value": value},
            })
    return {"traceEvents": evs, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(), f)
    return path


# ---------------------------------------------------------------------------
# merged fleet trace
# ---------------------------------------------------------------------------

def local_trace_source(name: Optional[str] = None) -> dict:
    """This process's trace rings as a merge source for
    :func:`merged_chrome_trace` (same shape as a flight-recorder black
    box: name/pid/epoch_unix_s/spans/events)."""
    from . import _EPOCH_WALL, _EVENTS, _GAUGES, _LOCK, _TRACE

    pid = os.getpid()
    with _LOCK:
        return {"name": name or f"pid{pid}", "pid": pid,
                "epoch_unix_s": _EPOCH_WALL,
                "spans": list(_TRACE), "events": list(_EVENTS),
                "gauges": dict(_GAUGES)}


def merged_chrome_trace(sources) -> dict:
    """One Perfetto-loadable timeline from N processes' trace sources.

    Each source dict carries ``name`` (track label), ``pid``,
    ``epoch_unix_s`` (the wall clock at that process's telemetry import
    — see telemetry/__init__.py), and ``spans``/``events`` ring dumps.
    Relative timestamps are re-anchored as ``epoch_unix_s + ts_s`` and
    normalized to the earliest instant across the fleet, so spans from
    different processes land in true wall-clock order.  Every source
    gets its OWN display pid (sequential) even when OS pids collide —
    one track per worker incarnation; span trace ids ride in ``args``
    so Perfetto's query/args panel correlates a submit across tracks.
    """
    evs = []
    anchors = []
    for src in sources:
        epoch = float(src.get("epoch_unix_s") or 0.0)
        for t in src.get("spans") or []:
            anchors.append(epoch + t["ts_s"])
        for e in src.get("events") or []:
            anchors.append(epoch + e["t_s"])
    t0 = min(anchors) if anchors else 0.0
    for disp_pid, src in enumerate(sources, start=1):
        epoch = float(src.get("epoch_unix_s") or 0.0)
        label = src.get("name") or f"pid{src.get('pid')}"
        evs.append({"name": "process_name", "ph": "M", "pid": disp_pid,
                    "tid": 0,
                    "args": {"name": f"{label} (pid {src.get('pid')})"}})
        for t in src.get("spans") or []:
            args = {"depth": t.get("depth"), "id": t.get("id"),
                    "parent": t.get("parent")}
            if t.get("trace") is not None:
                args["trace"] = t["trace"]
            evs.append({
                "name": t["name"], "ph": "X", "cat": "span",
                "ts": (epoch + t["ts_s"] - t0) * _US,
                "dur": t["dur_s"] * _US,
                "pid": disp_pid, "tid": t.get("tid", 0), "args": args,
            })
        src_end = 0.0
        for e in src.get("events") or []:
            args = {k: v for k, v in e.items() if k not in ("name", "t_s")}
            evs.append({
                "name": e["name"], "ph": "i", "cat": "event", "s": "p",
                "ts": (epoch + e["t_s"] - t0) * _US,
                "pid": disp_pid, "tid": 0, "args": args,
            })
        for t in src.get("spans") or []:
            src_end = max(src_end, (epoch + t["ts_s"] - t0 + t["dur_s"]))
        for e in src.get("events") or []:
            src_end = max(src_end, (epoch + e["t_s"] - t0))
        # roofline gauges (live snapshots and flight-recorder black
        # boxes both carry them) become per-source Perfetto counter
        # tracks, sampled at that source's last instant
        for gname, gval in sorted((src.get("gauges") or {}).items()):
            if gname.startswith("roofline."):
                evs.append({
                    "name": gname, "ph": "C", "ts": src_end * _US,
                    "pid": disp_pid, "tid": 0, "args": {"value": gval},
                })
    return {"traceEvents": evs, "displayTimeUnit": "ms"}


def write_merged_chrome_trace(path: str, sources) -> str:
    with open(path, "w") as f:
        json.dump(merged_chrome_trace(sources), f)
    return path
