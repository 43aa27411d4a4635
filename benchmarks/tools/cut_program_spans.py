#!/usr/bin/env python3
"""Cut a traced run small for ``tests/test_program_spans.py``:

    python3 benchmarks/tools/cut_program_spans.py <dir or .xplane.pb> out.json [--applications 1]

Writes what ``program_spans.ProgramSpans.from_events`` reads back: per
device plane the leaf operations ``[name, start_ns, dur_ns, tf_op]`` and
the ``qrack.*`` and ``bench.*`` host events ``[name, start_ns, dur_ns,
thread]`` of the first applications, with a ``bench.window`` around
them.  ``tools/describe_trace.py --cut`` keeps neither an operation's
``tf_op`` nor the program's spans.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import program_spans  # noqa: E402
import tracing  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("out")
    ap.add_argument("--applications", type=int, default=1)
    args = ap.parse_args()
    path = args.path
    if os.path.isdir(path):
        path = tracing.newest_xplane(path)
    device, spans = program_spans.read_xplane(path)
    apps = sorted(s for s in spans if s[0] == "bench.application")
    apps = sorted(apps, key=lambda s: s[1])[:args.applications]
    start, end = apps[0][1], apps[-1][1] + apps[-1][2]
    keep = lambda e: start <= e[1] and e[1] + e[2] <= end  # noqa: E731
    cut = {"devices": {k: [list(e) for e in v if keep(e)]
                       for k, v in device.items()},
           "spans": [list(s) for s in spans
                     if keep(s) and s[0] != "bench.window"]
           + [["bench.window", start, end - start, apps[0][3]]],
           "applications": len(apps)}
    with open(args.out, "w") as f:
        json.dump(cut, f)
    print(json.dumps({"cut": args.out, "bytes": os.path.getsize(args.out),
                      "spans": len(cut["spans"]),
                      "device_events": {k: len(v)
                                        for k, v in cut["devices"].items()}}))


if __name__ == "__main__":
    main()
