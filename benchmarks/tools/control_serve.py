#!/usr/bin/env python3
"""`control.py`'s serving modes for a cell whose traffic `kind` is not
`serve` but whose runner takes the same arguments (`<kind>_cell.run(...,
control_too=...)`): readings for the limit of `served_logit_gap`, on the chip,
at the cell's own size.

    python3 benchmarks/tools/control_serve.py --workload <cell> \
        --mode program|control --seeds 1,2,3 [--seconds 20]

`program`: the cell over a short window; prints each number compared.
`control`: the same run, and beside it, at each served position of the
checked requests, the gap of the token that the reference in fp8 puts first;
that reading goes through the harness's own comparison in the program's
place and has to come out not `correct`. One process, seed after seed, one
JSON line a seed. The benchmark's own runs never call this.
"""
import argparse
import importlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True, choices=("program", "control"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.load_cell(args.workload)
    runner = importlib.import_module(cell.traffic["kind"] + "_cell")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result, checks, notes = runner.run(
            cell, seed, args.seconds, False, t0,
            control_too=args.mode == "control")
        got = result["_got"]
        if "control_check" in got:
            checks = [got["control_check"] if c.name == "served_logit_gap"
                      else c for c in checks]
        print(json.dumps({
            "workload": cell.name, "mode": args.mode, "seed": seed,
            "correct": all(c.ok for c in checks),
            "checks": harness.checks_dict(checks),
            "program_correct": result["correct"],
            "gaps_by_request": {k: v for k, v in got.items()
                                if k != "control_check"},
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
