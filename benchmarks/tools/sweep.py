#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip: the cell at each of a
few rates, one process, no reference. A rate is sustained when the queue does
not grow through the window: the time to first token of the last third of
the requests is no worse than twice that of the middle third, and the drain
after the window is short: no longer than the mix's longest answer takes at
the run's own 95th-percentile gap, and a quarter. Each row says so
(`sustained`), with the thirds, the drain, and the share of token gaps whose
tick also carried a prefill chunk with the median tick of either kind: where
that share lies near 5% the 95th-percentile gap sits on the edge between the
two kinds of tick and jumps with the seed's order (PERF.md, PR 35).

    python3 benchmarks/tools/sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 30
"""
import argparse
import json
import os
import re
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def keep_ledgers(serve_cell) -> list:
    """`serve_cell.run` builds its ledger itself and hands it the
    scheduler's records every tick: keep each ledger built, so that the
    sweep reads both once the run is over."""
    kept = []

    class Kept(serve_cell.TickLedger):
        def __init__(self, engine):
            super().__init__(engine)
            kept.append(self)

        def after_tick(self, tick, t, emitted, records):
            super().after_tick(tick, t, emitted, records)
            self.records = records

    serve_cell.TickLedger = Kept
    return kept


def thirds_of_first_tokens(records) -> list:
    """Median seconds to the first token of the first, middle and last third
    of the requests, in the order they were offered."""
    ttft = [r.ttft_s for _, r in sorted(records.items())
            if r.ttft_s is not None]
    k = len(ttft) // 3
    if k == 0:
        return []
    return [statistics.median(part)
            for part in (ttft[:k], ttft[k:len(ttft) - k], ttft[-k:])]


def ticks_by_kind(ledger) -> dict:
    """Of the token gaps the decode steps end, the share whose tick also
    carried a prefill chunk, and the median length of a tick of either kind
    (a tick is timed from the tick before it where that one decoded too)."""
    rows = ledger.rows
    with_chunk = {True: 0, False: 0}
    lengths = {True: [], False: []}
    last = None
    for d in ledger.decode_steps:
        k = d["tick"]
        chunk = rows[k]["tokens_processed"] > d["active"]
        with_chunk[chunk] += d["active"]
        if last == k - 1:
            lengths[chunk].append(rows[k]["t"] - rows[k - 1]["t"])
        last = k
    gaps = sum(with_chunk.values())
    med = lambda v: 1e3 * statistics.median(v) if v else None  # noqa: E731
    return {"chunk_gap_share": with_chunk[True] / gaps if gaps else None,
            "tick_ms_plain": med(lengths[False]),
            "tick_ms_with_chunk": med(lengths[True])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=31337)
    args = ap.parse_args()
    import harness
    import serve_cell

    kept = keep_ledgers(serve_cell)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = harness.load_cell(args.workload)
        cell.traffic["rate_rps"] = rate
        t0 = time.perf_counter()
        result, checks, notes = serve_cell.run(
            cell, args.seed, args.seconds, False, t0, reference_too=False)
        ledger = kept.pop()
        ledger.engine = None        # the next rate builds its own
        thirds = thirds_of_first_tokens(ledger.records)
        drained = float(re.search(r"drained_s=([-0-9.]+)", notes[0]).group(1))
        metrics = {k: v["value"] for k, v in result["metrics"].items()}
        longest = (cell.traffic["output_len"]["max"]
                   * metrics["itl_p95_ms"] / 1e3)
        sustained = (bool(thirds) and thirds[2] <= 2 * thirds[1]
                     and drained <= 1.25 * longest)
        print(json.dumps({"rate_rps": rate, "seed": args.seed,
                          "sustained": sustained,
                          "ttft_s_by_third": thirds, "drained_s": drained,
                          **ticks_by_kind(ledger), "metrics": metrics,
                          "attempted": result["attempted"],
                          "failed": result["failed"]}), flush=True)
        for n in notes[:2]:
            print(n, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
