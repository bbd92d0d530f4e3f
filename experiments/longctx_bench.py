"""Long-context train-step throughput on the real accelerator.

The reference caps sequence length at 256 (lab/tutorial_1b/primer/
intro.py:10); long context is a capability this framework adds. Two legs of
evidence already exist: standalone attention timing across sequence lengths
(experiments/attn_bench.py — the flash kernel's 25x at T=8192) and ring-
attention per-device memory scaling on the virtual mesh (experiments/
sp_bench.py). This harness closes the loop end-to-end: the full train step
(fused head+CE + Adam) at long sequence lengths on one chip, tokens held
roughly constant per step, so the tokens/s column shows how throughput decays
as T grows — i.e. what the O(T^2) attention leg costs in a real step when
the rest of the step is O(T).

One process times every (seq, attention) point in turn and fails without a
TPU; a failed point ends the run. Results ->
``experiments/results/longctx_bench.csv`` with ``platform`` and
``device_kind`` columns as JAX reports them.

Run (on the chip):
    python -m experiments.longctx_bench
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

# (seq_len, per-step batch): ~16k tokens/step at every row, the measured
# bench.py optimum at T=256.
GRID = [(256, 64), (1024, 16), (2048, 8), (4096, 4), (8192, 2)]
VARIANTS = {
    # "flash" pins the pallas dh-major kernel (the path config.py's "auto"
    # routes to at T>=256 on TPU); "xla" pins the dot_general+softmax path.
    # The two columns show where the quadratic [T, T] score tensor starts to
    # dominate the step and how much the flash kernel buys back.
    "flash": {"attention_impl": "pallas", "flash_dh_major": True,
              "flash_block": 512},
    "xla": {"attention_impl": "xla"},
}


def main(quick: bool = False) -> None:
    import jax

    from ddl25spring_tpu.bench_utils import time_train_step
    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.parallel import make_mesh

    from . import common

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"longctx_bench measures the TPU and found "
                 f"{device.platform!r}; there is no CPU path")
    mesh = make_mesh({"data": 1})
    sink = common.sink("longctx_bench.csv")
    grid = GRID[:2] if quick else GRID
    for seq, batch in grid:
        for variant, overrides in VARIANTS.items():
            cfg = dataclasses.replace(
                LlamaConfig(dtype="bfloat16", ctx_size=seq), **overrides)
            tps = time_train_step(mesh, cfg, batch, seq=seq, timed_steps=10)
            step_ms = batch * seq / tps * 1e3
            sink.write({"seq": seq, "batch": batch, "variant": variant,
                        "platform": device.platform,
                        "device_kind": device.device_kind,
                        "tokens_per_sec": round(tps, 1),
                        "step_ms": round(step_ms, 3)})
            print(f"T={seq:5d} {variant:5s}: {tps:10.0f} tok/s "
                  f"({step_ms:.1f} ms/step)", flush=True)
    print(f"-> {sink.path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(quick=ap.parse_args().quick)
