#!/usr/bin/env python3
"""Readings for a training cell's limits of `correct`, split by what needs
the cell's chips: the program on the cell's mesh, the references on one chip,
the comparison on none.

    # no chip: the rows of each seed's checked steps, cut as the trainer's
    # data path cuts them
    python3 benchmarks/tools/train_limits.py --workload <cell> --mode rows \
        --seeds 1,2,3 --out <rows>
    # on one chip: the float32 reference over those rows, the fp8 control
    # and the reference fed only chip 0's rows
    python3 benchmarks/tools/train_limits.py --workload <cell> \
        --mode reference --out <rows> [--control 1,2] [--alone 1,2]
    # on the cell's chips: the trainer's own step, first `checked_steps`
    # steps, through the cell's hooks
    python3 benchmarks/tools/train_limits.py --workload <cell> --mode program \
        --seeds 1,2,3 --out <dir> [--rows <rows>] [--fault no_exchange]
    # no chip: the program's readings against the references
    python3 benchmarks/tools/train_limits.py --workload <cell> --mode compare \
        --out <dir> --want <rows>

`rows` writes `<rows>/<seed>.npz`. `reference` writes `<rows>/<seed>.json`
(what `correct` compares the program with) and prints one JSON line a seed:
the reference's seconds, and the gaps of the fp8 control (`--control`:
`reference.CONTROL` over the same rows) and of the reference fed only the
rows of chip 0 (`--alone`: what chip 0's Adam is handed when the chips do
not exchange). `program` runs `train_llm_dp` as `train_cell.run` does (the
same tokenizer hook, the same `StepProbe`) for `checked_steps` iterations
and no window, and writes `<dir>/<seed>.npz`: the rows it was fed and the
numbers `correct` compares; with `--rows` the trainer is handed the kept
rows in place of its data path's (the same rows, and `compare` checks that
they are, without the host's seconds of skipping 5,000 rows a later shard
while the chips wait). `--fault no_exchange` hands each chip its own
gradient where the step would average them (the planted fault of
`benchmarks/tests/test_grad_sync.py`). `compare` holds each program reading
to its seed's reference, after checking that both saw the same rows. Every
gap goes through `train_cell.compare_training` against the cell's limits.
`--rehearse` runs on the CPU at whatever size the checkout holds. The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def no_exchange() -> None:
    """Leave the gradient's all-reduce out, in this process."""
    from ddl25spring_tpu.telemetry import comm

    pmean = comm.pmean

    def local_only(x, axis_name, *, label=None, scale=1):
        return x if label == "grad_allreduce" else pmean(
            x, axis_name, label=label, scale=scale)

    comm.pmean = local_only


def cpu_devices(chips: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "peaks": {}, "devices": devs[:chips]}


def kept_rows(cell, path: str):
    """Hand the trainer the rows kept at `path` where its data path would
    cut them from the stream (the same rows, without the seconds its skip
    of 5,000 rows a later shard takes on the host)."""
    import numpy as np

    from ddl25spring_tpu.train import llm

    tr = cell.traffic
    rows = np.load(path)["rows"].reshape(
        int(tr["checked_steps"]), cell.chips, tr["batch_per_chip"], -1)

    def replay(tok, per_shard_batch, seq_len, n_shards, **kw):
        assert (n_shards, per_shard_batch, seq_len) == rows.shape[1:]
        while True:
            yield from rows

    llm.sharded_batches = replay


def run_program(cell, dims, seed: int, out: str, fault,
                rows_dir: str = "") -> dict:
    import numpy as np

    import reference as ref
    import train_cell
    from ddl25spring_tpu.config import TrainConfig
    from ddl25spring_tpu.train.llm import train_llm_dp

    tr = cell.traffic
    checked = int(tr["checked_steps"])
    seed32 = seed % 2 ** 32
    spans = {"data": [], "dispatch": []}
    tok = train_cell.SeedTokenizer(dims.vocab, seed, tr["seq_len"], spans)
    probe = train_cell.StepProbe(seed32, dims, checked, spans)
    tcfg = TrainConfig(batch_size=tr["batch_per_chip"], seq_len=tr["seq_len"],
                       lr=tr["lr"], iters=checked, seed=seed32,
                       data=cell.chips, optimizer=tr["optimizer"])
    if rows_dir:
        kept_rows(cell, os.path.join(rows_dir, f"{seed}.npz"))
    t0 = time.perf_counter()
    report = train_llm_dp(train_cell.model_config(cell, dims), tcfg,
                          tokenizer=tok, aggregation=tr["aggregation"],
                          log_every=0, log_fn=lambda s: None,
                          fault_plan=probe)
    got = {"losses": [float(x) for x in report.losses[:checked]],
           "grad_norms": {k: float(v) / (1.0 - ref.ADAM_B1)
                          for k, v in probe.grad_norms.items()},
           "param_change": {k: float(v)
                            for k, v in probe.param_change.items()}}
    rows = np.concatenate(probe.batches)
    np.savez(os.path.join(out, f"{seed}.npz"), rows=rows,
             got=json.dumps(got), fault=fault or "")
    return {"rows": list(rows.shape), "losses": got["losses"],
            "compile_seconds": [round(c.seconds, 2)
                                for c in probe.step_fn.compiles],
            "seconds": time.perf_counter() - t0}


def cell_rows(cell, dims, seed: int):
    """[checked_steps x rows, seq_len] ids: each step's rows as the
    trainer's data path cuts them from the seed's stream, shard after
    shard."""
    import numpy as np

    import train_cell
    from ddl25spring_tpu.data.tokens import sharded_batches

    tr = cell.traffic
    tok = train_cell.SeedTokenizer(dims.vocab, seed, tr["seq_len"],
                                   {"data": []})
    stream = sharded_batches(tok, tr["batch_per_chip"], tr["seq_len"],
                             cell.chips, shard_skip=5000,
                             seed=seed % 2 ** 32)
    return np.concatenate([next(stream).reshape(-1, tr["seq_len"])
                           for _ in range(int(tr["checked_steps"]))])


def gaps(cell, got: dict, want: dict) -> dict:
    import train_cell
    from harness import checks_dict

    checks, loss_gap = train_cell.compare_training(got, want, cell.limits)
    return {"correct": all(c.ok for c in checks),
            "checks": checks_dict(checks),
            "where": {c.name: c.where for c in checks}, "loss_gap": loss_gap}


def read_references(cell, dims, seed: int, out: str, control: bool,
                    alone: bool) -> dict:
    import numpy as np

    import reference as ref

    rows = np.load(os.path.join(out, f"{seed}.npz"))["rows"]
    batches = list(rows.reshape(int(cell.traffic["checked_steps"]), -1,
                                rows.shape[-1]))
    seed32 = seed % 2 ** 32
    lr = cell.traffic["lr"]
    t0 = time.perf_counter()
    want = ref.train_three(seed32, dims, batches, lr)
    line = {"reference_s": time.perf_counter() - t0,
            "reference_losses": want["losses"]}
    if control:
        line["control"] = gaps(cell, ref.train_three(
            seed32, dims, batches, lr, ref.CONTROL), want)
    if alone:
        line["chip0_rows_alone"] = gaps(cell, ref.train_three(
            seed32, dims, [b[: len(b) // cell.chips] for b in batches], lr),
            want)
    with open(os.path.join(out, f"{seed}.json"), "w") as f:
        json.dump({"want": want, **line}, f)
    return line


def compare(cell, seed: int, out: str, wants: str) -> dict:
    import numpy as np

    kept = np.load(os.path.join(out, f"{seed}.npz"))
    rows = np.load(os.path.join(wants, f"{seed}.npz"))["rows"]
    if not np.array_equal(kept["rows"], rows):
        raise SystemExit(f"seed {seed}: the program saw other rows")
    with open(os.path.join(wants, f"{seed}.json")) as f:
        want = json.load(f)["want"]
    return {str(kept["fault"]) or "program": gaps(
        cell, json.loads(str(kept["got"])), want)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    choices=("rows", "reference", "program", "compare"))
    ap.add_argument("--out", required=True)
    ap.add_argument("--want", help="compare: the references' directory")
    ap.add_argument("--rows", default="",
                    help="program: feed the rows kept here by `rows`")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--fault", choices=("no_exchange",))
    ap.add_argument("--control", default="")
    ap.add_argument("--alone", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import harness
    import reference as ref

    if args.rehearse:
        harness.device_info = cpu_devices
    cell = harness.load_cell(args.workload)
    dims = ref.Dims.from_config(cell.config)
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(x) for x in args.seeds.split(",") if x]
    if args.mode in ("reference", "compare") and not seeds:
        seeds = sorted(int(f[:-4]) for f in os.listdir(args.out)
                       if f.endswith(".npz"))
    if args.mode == "rows":
        import numpy as np

        for seed in seeds:
            np.savez(os.path.join(args.out, f"{seed}.npz"),
                     rows=cell_rows(cell, dims, seed))
        return 0
    if args.mode == "compare":
        for seed in seeds:
            print(json.dumps({"workload": cell.name, "seed": seed,
                              **compare(cell, seed, args.out, args.want)}))
        return 0
    harness.enable_compile_cache()
    if args.mode == "program":
        harness.device_info(cell.chips)
        if args.fault:
            no_exchange()
        for seed in seeds:
            line = run_program(cell, dims, seed, args.out, args.fault,
                               args.rows)
            print(json.dumps({"workload": cell.name, "mode": "program",
                              "fault": args.fault, "seed": seed, **line}),
                  flush=True)
        return 0
    harness.device_info(1)
    control = {int(x) for x in args.control.split(",") if x}
    alone = {int(x) for x in args.alone.split(",") if x}
    for seed in seeds:
        line = read_references(cell, dims, seed, args.out, seed in control,
                               seed in alone)
        print(json.dumps({"workload": cell.name, "mode": "reference",
                          "seed": seed, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
