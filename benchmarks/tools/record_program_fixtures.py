#!/usr/bin/env python3
"""Record the two small traces that carry the program's own spans, counters
and scope names, kept gzipped under `tests/data/` with what the new readers
read from them: two steps of the training cell and a few ticks of the backlog
cell, on the chip.

    python3 benchmarks/tools/record_program_fixtures.py train|serve <out_dir>

One kind a process (a process holds the chip, and the training cell ends its
loop by a signal). Beside each fixture it writes `<name>.by_hand.txt`, what
one looks at by hand first: where the scope paths stand, the operations that
took most time with their paths, the first spans with their counters. PR 27
ran this once; run it again when the profiler's format or the names change.
`record_fixture.py` and its trace of PR 25 stay: those kernels carry the old
names."""
import collections
import gzip
import json
import os
import shutil
import sys
import time

T0 = time.perf_counter()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "readers")]

CELLS = {"train": ("train_dscoder1b_seq4k", "train_v5e_2steps_named"),
         "serve": ("serve_dsllm7b_backlog", "serve_v5e_ticks_named")}


def by_hand(pt, trace) -> str:
    lines = [f"file {pt.path} bytes {pt.size}",
             f"programs {trace.program_names()}",
             f"ops {len(pt.ops)} with a scope path "
             f"{sum(1 for o in pt.ops if o[3])}", "",
             "seconds by scope path less its last part (containers out):"]
    acc = collections.Counter()
    for _, s, e, path in pt.leaf_ops_inside(*trace.span()):
        acc[path.rpartition("/")[0]] += e - s
    lines += [f"  {ns / 1e9:.6f}  {p}" for p, ns in acc.most_common(60)]
    lines += ["", "top operations:"]
    lines += [f"  {sec:.6f}  {name}  [{p}]" for name, sec, p in pt.top_ops(40)]
    lines += ["", "first spans (name, start ns, ns, depth, counters):"]
    lines += [f"  {'  ' * s.depth}{s.name} {s.start} {s.end - s.start} "
              f"{s.counters}" for s in pt.spans[:60]]
    return "\n".join(lines) + "\n"


def main(kind: str, out_dir: str) -> None:
    import harness
    import program_trace
    import xplane

    workload, name = CELLS[kind]
    cell = harness.load_cell(workload)
    if kind == "train":
        import train_cell

        cell.traffic.update(sink_every=2, warm_steps=4, trace_seconds=0.2)
        result, _, _ = train_cell.run(cell, 77, 1.0, True, T0,
                                      reference_too=False)
    else:
        import serve_cell

        cell.traffic.update(trace_seconds=0.6)
        result, _, _ = serve_cell.run(cell, 77, 5.0, True, T0,
                                      reference_too=False)
    path = xplane.find_xplane(os.path.join(
        harness.ROOT, ".bench_out", cell.name, "trace"))
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "rb") as src, gzip.open(
            os.path.join(out_dir, name + ".xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    trace = xplane.Trace(path)
    pt = program_trace.ProgramTrace(path)
    with open(os.path.join(out_dir, name + ".by_hand.txt"), "w") as f:
        f.write(by_hand(pt, trace))
    expected = {
        "workload": workload, "device_kind": result["device"]["kind"],
        "steps": result["attempted"] if kind == "train" else None,
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "busy_s": trace.busy_s(), "span_s": trace.span_s(),
        "programs": {k: len(trace.program_intervals(k))
                     for k in trace.program_names()},
        "span_names": sorted({s.name for s in pt.spans}),
    }
    with open(os.path.join(out_dir, name + ".expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
