#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip: the cell at each of a
few rates, one process, no reference. A rate is sustained when the queue does
not grow through the window: the time to first token of the last third of
the requests is no worse than twice that of the middle third, and the drain
after the window is short.

    python3 benchmarks/tools/sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 30
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=31337)
    args = ap.parse_args()
    import harness
    import serve_cell

    for rate in (float(r) for r in args.rates.split(",")):
        cell = harness.load_cell(args.workload)
        cell.traffic["rate_rps"] = rate
        t0 = time.perf_counter()
        result, checks, notes = serve_cell.run(
            cell, args.seed, args.seconds, False, t0, reference_too=False)
        print(json.dumps({"rate_rps": rate,
                          "metrics": {k: v["value"] for k, v in
                                      result["metrics"].items()},
                          "attempted": result["attempted"],
                          "failed": result["failed"]}), flush=True)
        for n in notes[:2]:
            print(n, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
