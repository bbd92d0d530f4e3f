#!/usr/bin/env python3
"""Readings for the limits of `correct`, on the chip, at the cell's own size.

    python3 benchmarks/control.py --workload <cell> --mode <mode> --seeds 1,2,3

`program`: the cell itself over a short window; prints each number compared.
`control`: the plain reference put in the program's place with every matrix
product's operands in fp8 (training: three steps against the float32
reference over the same rows; serving: runs the cell and, at each served
position of the checked requests, reads the gap of the token the fp8 forward
pass puts first). `half_batch` (training): the reference fed half of each
batch, against the reference fed all of it. Whatever the mode, the readings
go through the harness's own comparison against the cell's limits
(`harness.Check`), and each line says whether they came out `correct`: the
control and the fault have to read false. One process, seed after seed, one
JSON line a seed. The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(HERE), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def train_batches(cell, dims, seed: int):
    """The rows the trainer's data path would cut from the seed's stream."""
    import numpy as np
    import train_cell

    tr = cell.traffic
    tok = train_cell.SeedTokenizer(dims.vocab, seed, tr["seq_len"],
                                   {"data": []})
    rows = cell.chips * tr["batch_per_chip"]
    return [np.asarray([tok.encode("") for _ in range(rows)], np.int32)
            for _ in range(tr["checked_steps"])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    choices=("program", "control", "half_batch"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import harness
    import reference as ref

    cell = harness.load_cell(args.workload)
    kind = cell.traffic["kind"]
    dims = ref.Dims.from_config(cell.config)
    harness.device_info(cell.chips)
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        seed32 = seed % 2 ** 32
        if kind == "train" and args.mode != "program":
            import train_cell

            batches = train_batches(cell, dims, seed)
            want = ref.train_three(seed32, dims, batches, cell.traffic["lr"])
            if args.mode == "control":
                got = ref.train_three(seed32, dims, batches,
                                      cell.traffic["lr"], ref.CONTROL)
            else:
                got = ref.train_three(
                    seed32, dims, [b[: len(b) // 2] for b in batches],
                    cell.traffic["lr"])
            checks, loss_gap = train_cell.compare_training(got, want,
                                                           cell.limits)
            extra = {"losses": got["losses"], "ref_losses": want["losses"],
                     "loss_gap": loss_gap}
        else:
            runner = __import__(kind + "_cell")
            kw = {"control_too": True} if (kind == "serve"
                                           and args.mode == "control") else {}
            result, checks, notes = runner.run(
                cell, seed, args.seconds, False, t0, **kw)
            got = result["_got"] if kind == "serve" else {}
            extra = {"program_correct": result["correct"],
                     "gaps_by_request": {k: v for k, v in got.items()
                                         if k != "control_check"},
                     "metrics": {k: v["value"]
                                 for k, v in result["metrics"].items()}}
            if "control_check" in got:
                # the control's reading in the place of the program's
                checks = [got["control_check"] if c.name == "served_logit_gap"
                          else c for c in checks]
        print(json.dumps({"workload": cell.name, "mode": args.mode,
                          "seed": seed,
                          "correct": all(c.ok for c in checks),
                          "checks": harness.checks_dict(checks),
                          "where": {c.name: c.where for c in checks
                                    if getattr(c, "where", None)},
                          "seconds": time.perf_counter() - t0, **extra}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
