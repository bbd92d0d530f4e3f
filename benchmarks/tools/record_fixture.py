#!/usr/bin/env python3
"""Record the small trace kept under `tests/data/`: two steps of the training
cell on the chip, with what the reduction reads from it. PR 25 ran this once;
run it again only if the profiler's format changes."""
import json
import os
import shutil
import sys
import time

T0 = time.perf_counter()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main(out_dir: str) -> None:
    import harness
    import train_cell
    import xplane

    cell = harness.load_cell("train_dscoder1b_seq4k")
    cell.traffic.update(sink_every=2, warm_steps=4, trace_seconds=0.2)
    train_cell.run(cell, 77, 1.0, True, T0, reference_too=False)
    path = xplane.find_xplane(os.path.join(
        harness.ROOT, ".bench_out", cell.name, "trace"))
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "train_v5e_2steps.xplane.pb"))
    t = xplane.Trace(path)
    pattern = "^flash_attention"
    seconds, n = t.op_seconds(pattern)
    runs = t.program_runs("jit_local_step")
    with open(os.path.join(out_dir, "train_v5e_2steps.expected.json"),
              "w") as f:
        json.dump({"busy_s": t.busy_s(), "flash_pattern": pattern,
                   "flash_events": n, "flash_seconds": seconds,
                   "program": "jit_local_step", "program_runs": len(runs),
                   "program_seconds": sum(runs),
                   "top3": [k for k, _ in t.top_ops(3)],
                   "gap_names": ["unmarked", "bench.dispatch",
                                 "bench.between_steps"]}, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1])
