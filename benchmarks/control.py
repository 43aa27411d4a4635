#!/usr/bin/env python3
"""The control of "How correct is decided", on the chip at a cell's own
size: the same run with the ket held one precision below the
configuration's float32, which has to come out as not correct.

    python3 benchmarks/control.py --workload <name> --seeds 1 2 3 [--seconds 3]

The program has such a path of its own: planes in bfloat16
(``QRACK_TPU_FPPOW``).  Mosaic refuses the window kernel on bfloat16
planes (compiled for a described v5e, PR 27: "Rotate with non-32-bit
data"), so the control takes the program's XLA chain
(``QRACK_TPU_FUSE_KERNEL=off``).  Every seed runs in this one process.
The benchmark's own runs never run this; its numbers set the limits in
the configuration files (PERF.md section 2).  Exit code 0 when every
seed came out as not correct by a *number* over its limit.
"""

import os
import sys

os.environ["QRACK_TPU_FPPOW"] = "bfloat16"
os.environ["QRACK_TPU_FUSE_KERNEL"] = "off"

import argparse
import json

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402  (after the environment is set)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    caught = []
    for seed in args.seeds:
        code, line, checks = run.execute(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0,
            rehearse_cpu=args.rehearse_cpu))
        if checks is None:
            return code
        numbers = [r for r in checks.records if "limit_key" in r]
        over = [r for r in numbers if not r["ok"]]
        print(json.dumps({
            "control": "bfloat16 planes, XLA chain", "seed": seed,
            "not_correct_by_a_number": bool(over),
            "smallest_value_over_its_limit": min(
                (r["value"] / r["limit"] for r in over), default=None),
            "numbers": [{k: r[k] for k in ("check", "value", "limit", "ok")}
                        for r in numbers]}), flush=True)
        caught.append(bool(over))
    return 0 if all(caught) else 1


if __name__ == "__main__":
    sys.exit(main())
