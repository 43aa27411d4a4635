#!/usr/bin/env python3
"""The spread a bound is set from: for each end-to-end metric of the
result lines given, per set, the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) as a share of the
median; the bound is about five times the widest, never under 1 %.

    python3 benchmarks/tools/spread.py set1/*.txt -- set2/*.txt
"""

import json
import statistics
import sys


def last_line(path):
    with open(path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def main():
    args, sets = sys.argv[1:], [[]]
    for a in args:
        if a == "--":
            sets.append([])
        else:
            sets[-1].append(last_line(a))
    names = sorted(sets[0][0]["metrics"])
    for name in names:
        row = {"metric": name}
        for i, runs in enumerate(sets, 1):
            values = [r["metrics"][name]["value"] for r in runs]
            q1, _, q3 = statistics.quantiles(values, n=4)
            med = statistics.median(values)
            row[f"set{i}"] = {"n": len(values), "median": med,
                              "spread": (q3 - q1) / med,
                              "min": min(values), "max": max(values)}
        widest = max(row[f"set{i}"]["spread"] for i in range(1, len(sets) + 1))
        row["widest_spread"] = widest
        row["five_times"] = 5 * widest
        print(json.dumps(row))
    print(json.dumps({"all_correct": all(r["correct"] for s in sets for r in s),
                      "runs": sum(len(s) for s in sets)}))


if __name__ == "__main__":
    main()
