#!/usr/bin/env python3
"""Median and spread of each metric over one set of runs, as the contract
measures it: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median.

    python3 benchmarks/tools/spread.py chiprun_out/T1_*.out
"""
import json
import statistics
import sys


def main(paths) -> None:
    rows = {}
    for p in paths:
        with open(p) as f:
            last = json.loads(f.read().strip().splitlines()[-1])
        for k, v in last["metrics"].items():
            rows.setdefault(k, []).append(v["value"])
        for k, c in last["checks"].items():
            rows.setdefault("check " + k, []).append(c["value"])
        rows.setdefault("correct", []).append(int(last["correct"]))
    for k, v in rows.items():
        med = statistics.median(v)
        q = statistics.quantiles(v, n=4) if len(v) > 1 else [med] * 3
        spread = (q[2] - q[0]) / med if med else 0.0
        print(f"{k}: median {med!r} spread {spread:.5f} min {min(v)!r} "
              f"max {max(v)!r} n={len(v)}")


if __name__ == "__main__":
    main(sys.argv[1:])
