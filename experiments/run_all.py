"""Run the full parity-evidence suite: every homework experiment battery,
then render plots. ``--quick`` shrinks datasets/rounds for smoke testing
(the committed results under experiments/results/ come from a full run).
"""

from __future__ import annotations

import argparse
import json
import time


def main(quick: bool = False, skip=(), hw1_sizes=None, hw3_sizes=None) -> dict:
    from . import generative, hw1_fl, hw1b_llm, hw2_vfl, hw3_defenses, plots

    def sized(fn, sizes):
        if sizes is None:
            return fn
        return lambda quick=False: fn(quick=quick, n_train=sizes[0],
                                      n_test=sizes[1])

    summary = {}
    stages = [
        ("hw1_fl", sized(hw1_fl.main, hw1_sizes)),
        ("hw1b_llm", hw1b_llm.main),
        ("hw2_vfl", hw2_vfl.main),
        ("hw3_defenses", sized(hw3_defenses.main, hw3_sizes)),
        ("generative", generative.main),
    ]
    for name, fn in stages:
        if name in skip:
            continue
        t0 = time.perf_counter()
        print(f"=== {name} ===")
        out = fn(quick=quick)
        summary[name] = {str(k): (round(v, 4) if isinstance(v, float) else v)
                         for k, v in out.items()}
        print(f"=== {name} done in {time.perf_counter() - t0:.1f}s ===\n")
    plots.main()
    print(json.dumps(summary, indent=1))
    return summary


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip", nargs="*", default=[])
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU platform (the parity protocol does "
                         "not depend on the platform)")
    a = ap.parse_args()
    hw1_sizes = hw3_sizes = None
    if a.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
        # The single-core CPU platform cannot chew 60k-sample corpora in
        # reasonable time; smaller synthetic corpora keep the exact
        # N/C/E/B/lr/seed protocols (corpus size is not a parity quantity
        # on synthetic data — hw1_fl.main docstring). hw3 runs its 21-config
        # grid, so it gets the smallest corpus.
        hw1_sizes = (12000, 2000)
        hw3_sizes = (6000, 2000)
    main(quick=a.quick, skip=set(a.skip), hw1_sizes=hw1_sizes,
         hw3_sizes=hw3_sizes)
