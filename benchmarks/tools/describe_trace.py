#!/usr/bin/env python3
"""Look at a trace by hand, and cut one small for the reducers' test.

    python3 benchmarks/tools/describe_trace.py <dir or .xplane.pb> [--cut out.json --applications 2]

Prints what the trace holds (planes, lines, commonest event names).
``--cut`` writes the device's leaf operations and the benchmark's host
spans of the first few applications as JSON, which
``tracing.Trace.from_events`` reads back.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tracing  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--cut")
    ap.add_argument("--applications", type=int, default=2)
    args = ap.parse_args()
    print(json.dumps(tracing.describe(args.path), indent=1))
    if args.cut:
        trace = tracing.load(args.path)
        apps = [s for s in trace.spans if s[0] == "application"]
        apps = apps[:args.applications]
        start, end = apps[0][1], apps[-1][1] + apps[-1][2]
        keep = lambda e: start <= e[1] and e[1] + e[2] <= end  # noqa: E731
        cut = {"devices": {k: [list(e) for e in v if keep(e)]
                           for k, v in trace.devices.items()},
               "spans": [list(s) for s in trace.spans if keep(s)]
               + [["window", start, end - start]],
               "applications": len(apps)}
        with open(args.cut, "w") as f:
            json.dump(cut, f)
        print(json.dumps({"cut": args.cut, "bytes": os.path.getsize(args.cut)}))


if __name__ == "__main__":
    main()
