#!/usr/bin/env python
"""Perf-regression sentinel over the committed evidence trajectory.

Two modes:

``--stamp --stage NAME [FILE]``
    Campaign evidence filter (replaces the old inline heredoc in
    ``scripts/tpu_campaign.sh``).  Reads a stage's output (FILE or stdin),
    keeps the JSON evidence lines (``{"metric"...`` / ``{"gate"...``),
    stamps each with timestamp, stage, sentinel verdict, and device-class
    fingerprint, and prints them to stdout for appending to
    ``docs/tpu_results.jsonl``.  Lines whose implied bandwidth exceeds the
    device-class peak (the dispatch-ack signature) are **dropped** from the
    evidence stream, reported on stderr, and the process exits 3 so the
    campaign marks the stage FAILED — clamped samples never enter committed
    evidence.

``[FILE ...]`` (report mode, default)
    Compares the latest line per metric key in FILE(s) (default
    ``docs/tpu_results.jsonl``) against the committed trajectory and prints
    a verdict table.  Exits 4 if any fresh line is "worse".

Stdlib-only by construction: loads ``qrack_tpu/telemetry/sentinel.py`` by
file path so it never imports the package (and thus never touches jax) —
safe under the campaign's ``env -u PYTHONPATH`` hung-backend context.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_sentinel():
    path = os.path.join(REPO, "qrack_tpu", "telemetry", "sentinel.py")
    spec = importlib.util.spec_from_file_location("_qrack_sentinel", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _evidence_lines(text):
    for raw in text.splitlines():
        raw = raw.strip()
        if raw.startswith('{"metric"') or raw.startswith('{"gate"'):
            try:
                d = json.loads(raw)
            except ValueError:
                continue
            if isinstance(d, dict):
                yield d


def _stamp_mode(sen, args, text):
    traj = sen.load_trajectory(args.root)
    clamped = 0
    kept = 0
    for d in _evidence_lines(text):
        if sen.is_clamped(d):
            clamped += 1
            print("perf_sentinel: CLAMPED (implied %s GB/s > device peak) "
                  "dropped from evidence: %s" % (
                      d.get("implied_hbm_gbps", d.get("implied_codes_gbps")),
                      sen.line_key(d)), file=sys.stderr)
            continue
        sen.stamp_evidence_line(d, traj, stage=args.stage)
        print(json.dumps(d, sort_keys=True))
        kept += 1
    if clamped:
        print("perf_sentinel: stage %r FAILED roofline honesty clamp "
              "(%d clamped, %d kept)" % (args.stage, clamped, kept),
              file=sys.stderr)
        return 3
    return 0


def _report_mode(sen, args):
    traj = sen.load_trajectory(args.root)
    latest = {}
    files = args.files or [os.path.join(args.root, "docs",
                                        "tpu_results.jsonl")]
    for path in files:
        try:
            with open(path) as fh:
                text = fh.read()
        except OSError as e:
            print("perf_sentinel: %s" % e, file=sys.stderr)
            continue
        for d in _evidence_lines(text):
            key = sen.line_key(d)
            if key:
                latest[key] = d
    worse = 0
    for key in sorted(latest):
        d = latest[key]
        val = sen.line_value(d)
        v = d.get("sentinel")
        if v is None:
            v = sen.stamp(d, traj)
        if v == "worse" and d.get("fresh", True):
            worse += 1
        ref = d.get("sentinel_ref_wall_s")
        print("%-44s %-7s wall=%s%s" % (
            key, v, "%.6g s" % val if val is not None else "-",
            "  best_committed=%.6g s" % ref if ref is not None else ""))
    if worse:
        print("perf_sentinel: %d metric(s) WORSE than committed trajectory "
              "(noise band %.0f%%)" % (worse, 100 * sen.noise_band()),
              file=sys.stderr)
        return 4
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*", help="evidence/stage-output files")
    ap.add_argument("--stamp", action="store_true",
                    help="campaign mode: stamp + filter stage output")
    ap.add_argument("--stage", default="",
                    help="stage name stamped into each line (with --stamp)")
    ap.add_argument("--root", default=REPO,
                    help="repo root holding the committed trajectory")
    args = ap.parse_args(argv)
    sen = _load_sentinel()
    if args.stamp:
        if args.files:
            with open(args.files[0]) as fh:
                text = fh.read()
        else:
            text = sys.stdin.read()
        return _stamp_mode(sen, args, text)
    return _report_mode(sen, args)


if __name__ == "__main__":
    sys.exit(main())
