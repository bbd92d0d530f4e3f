"""The experts' grouped product on a chip, at the LFM2 cell's two shapes:
``python -m experiments.grouped_matmul_sweep`` (a TPU or nothing; about
three minutes).

A decode step (or a prefill chunk) of the cell routes 256 rows, 4 pairs a
row, over 64 experts: 1,024 rows by ``[2048, 3072]`` (gate | up) and by
``[1536, 2048]`` (down) a group, in a stack of two layers' experts (128
groups, this layer's at an offset of 64, the other layer's empty). Each line
gives milliseconds a call for ``lax.ragged_dot`` and for
``ops/grouped_matmul.py`` over its tile sizes ``(tm, tn)``, and the hit
experts' weights over 819 GB/s, the bound a stream of weights cannot beat.
Group sizes: the router's (each row's 4 of 64 drawn at random: about 16 rows
a group, every expert hit), over every tile size; 16 groups of 64 rows and 64
groups of 1 at the rule's tiles (``grouped_matmul.tiling``). At the
routed sizes both forms are also held against the same product in float32
(the largest gap, and the share of values in which kernel and ``ragged_dot``
differ). PERF.md (section 6) has what was read.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ddl25spring_tpu.ops import grouped_matmul as gm

ROWS, HELD, TOP_K = 1024, 64, 4
SHAPES = ((2048, 3072), (1536, 2048))           # (D, N): gate | up, down
TM = (32, 64, 128, 256)
BANDWIDTH = 819e9            # v5e (benchmarks/peaks.json)


def timed(fn, *args, runs: int = 30) -> float:
    """Milliseconds a call, the device's work awaited."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / runs * 1e3


def routed_sizes(rng) -> np.ndarray:
    """Group sizes of 256 rows' top 4 of 64 experts, drawn at random."""
    picks = np.stack([rng.choice(HELD, TOP_K, replace=False)
                      for _ in range(ROWS // TOP_K)]).ravel()
    return np.bincount(picks, minlength=HELD).astype(np.int32)


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("a measurement: needs a TPU, found " + dev.platform)
    rng = np.random.default_rng(0)

    def say(**kw):
        print(json.dumps(kw), flush=True)

    cases = {"routed": routed_sizes(rng),
             "16 of 64 rows": np.where(np.arange(HELD) < 16, ROWS // 16,
                                       0).astype(np.int32),
             "64 of 1 row": np.ones(HELD, np.int32)}
    say(device=dev.device_kind, rows=ROWS,
        routed=dict(min=int(cases["routed"].min()),
                    max=int(cases["routed"].max())))
    xla = jax.jit(lambda x, w, groups: lax.ragged_dot(x, w, groups))
    for d, n in SHAPES:
        key = jax.random.key(d)
        w = jax.jit(lambda k: (jax.random.normal(k, (2 * HELD, d, n),
                                                 jnp.float32)
                               * d ** -0.5).astype(jnp.bfloat16))(key)
        x = jax.random.normal(jax.random.key(1), (ROWS, d), jnp.bfloat16)
        offset = jnp.int32(HELD)
        for name, sizes in cases.items():
            groups = jnp.asarray(np.concatenate(
                [np.zeros(HELD, np.int32), sizes]))
            s = jnp.asarray(sizes)
            hit = int((sizes > 0).sum())
            bound = hit * d * n * 2 / BANDWIDTH * 1e3
            base = dict(d=d, n=n, sizes=name, bound_ms=bound)
            say(**base, form="ragged_dot", ms=timed(xla, x, w, groups))
            tns = [t for t in range(256, n + 1, 256) if n % t == 0]
            for tm in TM if name == "routed" else ():
                for tn in tns:
                    if tm * tn > 256 * 3072:
                        continue
                    fn = jax.jit(lambda x, w, s, o, t=(tm, tn):
                                 gm.grouped_matmul(x, w, s, o, tiles=t,
                                                   interpret=False))
                    try:
                        ms = timed(fn, x, w, s, offset)
                        say(**base, form="kernel", tiles=[tm, tn], ms=ms,
                            roofline=bound / ms)
                    except Exception as e:  # a tile the compiler refuses
                        say(**base, form="kernel", tiles=[tm, tn],
                            refused=str(e)[:200])
            rule = gm.tiling(ROWS, d, n, jnp.bfloat16)
            ms = timed(jax.jit(lambda x, w, s, o: gm.grouped_matmul(
                x, w, s, o, interpret=False)), x, w, s, offset)
            say(**base, form="kernel, the rule", tiles=list(rule), ms=ms,
                roofline=bound / ms)
            if name != "routed":
                continue
            t = int(sizes.sum())
            want = np.asarray(jax.jit(lambda x, w, g: lax.ragged_dot(
                x.astype(jnp.float32), w[HELD:].astype(jnp.float32), g,
                precision=lax.Precision.HIGHEST))(x, w, s))[:t]
            got = {"ragged_dot": np.asarray(xla(x, w, groups), np.float32),
                   "kernel": np.asarray(gm.grouped_matmul(
                       x, w, s, offset, interpret=False), np.float32)}
            for form, out in got.items():
                say(**base, form=form, against="float32 highest",
                    max_abs=float(np.abs(out[:t] - want).max()),
                    rms_of_output=float(np.sqrt((want ** 2).mean())))
            say(**base, form="kernel", against="ragged_dot",
                share_of_values_that_differ=float(
                    (got["kernel"][:t] != got["ragged_dot"][:t]).mean()))


if __name__ == "__main__":
    main()
