"""XLA vs Pallas-flash attention comparison across sequence lengths.

Times forward and forward+backward of the two attention backends
(models/llama._xla_attention vs ops/flash_attention.flash_attention) at the
model's head geometry (H=6, Dh=48) on the real accelerator, holding
tokens-per-call constant. Results → ``experiments/results/attn_bench.csv``
(each row carries a ``platform`` column; a CSV is only evidence for the
``flash_min_seq`` crossover if that column says tpu — run this on the chip
and commit the output).

Measured shape of the numbers (v5e, committed CSV): the row-major flash
kernel loses below T≈4096 — it pads Dh=48 to 128 lanes on every HBM
transfer — but the dh-major variant with whole-sequence blocks
(``flash_dhm_wide``: dense [BH, Dh, T] layout, block_q=block_k=min(T,512))
wins at every swept length, from 2.5% at the canonical T=256 to 25x at
T=8192. ``LlamaConfig(attention_impl="auto")`` encodes exactly that
result (dh-major wide pallas iff T ≥ flash_min_seq=256 on TPU).
"""

from __future__ import annotations

import argparse
import time
from typing import Dict

import jax
import jax.numpy as jnp

from ddl25spring_tpu.models.llama import _xla_attention
from ddl25spring_tpu.ops.flash_attention import flash_attention

from . import common


def _sync(r):
    float(jnp.asarray(jax.tree.leaves(r)[0]).reshape(-1)[0])


def _time(f, *args, n=20) -> float:
    for _ in range(3):  # compile + settle before the timed repetitions
        r = f(*args)
    _sync(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = f(*args)
    _sync(r)
    return (time.perf_counter() - t0) / n * 1e3


def main(quick: bool = False) -> Dict[str, Dict[str, float]]:
    sink = common.sink("attn_bench.csv")
    h, dh = 6, 48
    configs = [(64, 256), (16, 1024)] if quick else \
              [(64, 256), (16, 1024), (4, 4096), (1, 8192)]
    results: Dict[str, Dict[str, float]] = {}
    platform = jax.devices()[0].platform
    for b, t in configs:
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (b, t, h, dh), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, t, h, dh), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, t, h, dh), jnp.bfloat16)
        row: Dict[str, float] = {}
        variants = (
            ("xla", lambda q, k, v: _xla_attention(q, k, v, causal=True)),
            ("flash", lambda q, k, v: flash_attention(q, k, v, causal=True)),
            # dh-major: dense [BH, Dh, T] operand layout — the head-packing
            # lever for Dh=48 (lane padding costs the row-major kernels
            # 2.67x HBM bytes per q/k/v/o transfer).
            ("flash_dhm", lambda q, k, v: flash_attention(
                q, k, v, causal=True, dh_major=True)),
            # Whole-sequence blocks at T<=512: one grid step per (b, h),
            # no online-softmax recurrence.
            ("flash_dhm_wide", lambda q, k, v: flash_attention(
                q, k, v, causal=True, dh_major=True,
                block_q=min(q.shape[1], 512), block_k=min(q.shape[1], 512))),
        )
        for name, fn in variants:
            fwd = jax.jit(fn)
            fb = jax.jit(jax.grad(
                lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)))
            row[f"{name}_fwd_ms"] = _time(fwd, q, k, v)
            row[f"{name}_fwdbwd_ms"] = _time(fb, q, k, v)
        rec = {"batch": b, "seq": t, "heads": h, "head_dim": dh,
               "platform": platform, **{k2: round(v2, 3) for k2, v2 in row.items()}}
        sink.write(rec)
        results[f"b{b}_t{t}"] = row
        fb = {n: ms for n, ms in row.items() if n.endswith("_fwdbwd_ms")}
        winner = min(fb, key=fb.get).replace("_fwdbwd_ms", "")
        print(f"B={b:3d} T={t:5d}: " +
              "   ".join(f"{n.replace('_fwdbwd_ms', '')} f+b {ms:8.2f} ms"
                         for n, ms in fb.items()) +
              f"   ({winner} wins)", flush=True)
    print(f"-> {sink.path} [{platform}]")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(quick=ap.parse_args().quick)
