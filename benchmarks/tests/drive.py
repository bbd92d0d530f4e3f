#!/usr/bin/env python3
"""Drive one run of a cell from a checkout, skipping the harness's look for
a chip: `drive.py <checkout> <workload> <seed> <seconds> <trace> [fault]`.
For the rehearsal tests only: `harness.device_info`, which ends a run that
finds no chip, is replaced here, in the test's own process, by one that hands
the CPU device over with peaks named as the test's; everything after it is
the code `run.py` runs."""
import importlib
import sys
import time

T0 = time.perf_counter()


def main(checkout, workload, seed, seconds, trace, fault=None):
    sys.path[:0] = [checkout, checkout + "/benchmarks"]
    import harness

    def cpu_device(chips):
        import jax

        devs = jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                          "hbm_bytes": 1e10},
                "devices": devs[:chips]}

    harness.device_info = cpu_device
    cell = harness.load_cell(workload)
    runner = importlib.import_module(cell.traffic["kind"] + "_cell")
    result, checks, notes = runner.run(
        cell, int(seed), float(seconds), bool(int(trace)), T0,
        fault=fault or None)
    result = {k: v for k, v in result.items() if not k.startswith("_")}
    harness.emit(result, checks, notes)


if __name__ == "__main__":
    main(*sys.argv[1:])
