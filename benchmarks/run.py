#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run. It needs the chips the cell asks for and
fails without them; it prints the run's notes, then the numbers that
`correct` compared, each beside its limit (stderr), and as the last line of
stdout one JSON object: `correct`, `attempted`, `failed`, `metrics`,
`device`, with `--trace 1` `breakdown`, and last `checks`.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    try:
        cell = harness.load_cell(args.workload)
        kind = cell.traffic["kind"]
        try:
            runner = importlib.import_module(f"{kind}_cell")
        except ModuleNotFoundError as e:
            raise harness.BenchError(
                f"traffic kind {kind!r}: no {kind}_cell.py") from e
        result, checks, notes = runner.run(
            cell, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    except harness.BenchError as e:
        print(f"benchmark cannot run: {e}", file=sys.stderr)
        return 2
    result = {k: v for k, v in result.items() if not k.startswith("_")}
    harness.emit(result, checks, notes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
