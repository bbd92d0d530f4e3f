"""The state-space decode step's update on a chip, at the Falcon-H1 cell's
shape: ``python -m experiments.state_step_sweep`` (a TPU or nothing; about
two minutes).

A decode step of ``serve_falconh1_chat_backlog`` advances the state of its
96 slots in each of 6 layers: a store ``[6, 96, 32, 128, 256]`` in float32,
403 MB a layer, 2 groups of B and C. Each line gives milliseconds a layer for
XLA's form (``state_space.scan_step`` on the layer's slice and the slice
written back into the donated store, as the decode program had it) and for
``ops/state_step.py`` over its heads a block, with every slot decoding and
with half of them, and the least time the decoding slots' state takes to be
read and written once at 819 GB/s. The kernel's ``y`` and the layer it writes
are held against XLA's form (the largest gap), and the store's other rows
against what they were (bit for bit). PERF.md (section 6) has what was read.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ddl25spring_tpu.config import StateSpaceMixer
from ddl25spring_tpu.models import state_space
from ddl25spring_tpu.ops import state_step as ss

LAYERS, SLOTS, HEADS, P, N, GROUPS = 6, 96, 32, 128, 256, 2
LAYER = 3
HEADS_PER_BLOCK = (4, 8, 16, 32)
BANDWIDTH = 819e9            # v5e (benchmarks/peaks.json)
MIXER = StateSpaceMixer(d_inner=HEADS * P, heads=HEADS, head_dim=P,
                        groups=GROUPS, state=N, conv=4, chunk=256)


def timed(fn, store, *args, runs: int = 20):
    """Milliseconds a call of ``fn(store, *args) -> (y, store)`` with the
    store donated and handed on, the device's work awaited; the store."""
    y, store = fn(store, *args)
    jax.block_until_ready(store)
    t0 = time.perf_counter()
    for _ in range(runs):
        y, store = fn(store, *args)
    jax.block_until_ready((y, store))
    return (time.perf_counter() - t0) / runs * 1e3, store


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("a measurement: needs a TPU, found " + dev.platform)

    def say(**kw):
        print(json.dumps(kw), flush=True)

    keys = jax.random.split(jax.random.key(0), 6)
    store = jax.jit(lambda k: jax.random.normal(
        k, (LAYERS, SLOTS, HEADS, P, N), jnp.float32))(keys[0])
    x = jax.random.normal(keys[1], (SLOTS, HEADS, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[2], (SLOTS, HEADS)))
    a = -jnp.exp(jax.random.normal(keys[3], (HEADS,)))
    b = jax.random.normal(keys[4], (SLOTS, GROUPS, N), jnp.float32)
    c = jax.random.normal(keys[5], (SLOTS, GROUPS, N), jnp.float32)
    layer = jnp.int32(LAYER)
    say(device=dev.device_kind, store=list(store.shape))

    def xla_step(store, layer, x, dt, a, b, c):
        y, s1 = state_space.scan_step(x, dt, a, b, c, store[layer], MIXER)
        return y, store.at[layer].set(s1)

    xla = jax.jit(xla_step, donate_argnums=(0,))

    def kernel(hb):
        def step(store, layer, x, dt, a, b, c, decoding):
            return ss.state_step(store, layer, x, dt, a, b, c,
                                 ss.plan(decoding), heads_per_block=hb,
                                 interpret=False)
        return jax.jit(step, donate_argnums=(0,))

    for share in (1.0, 0.5):
        decoding = jnp.arange(SLOTS) < int(SLOTS * share)
        dts = jnp.where(decoding[:, None], dt, 0.0)
        live = int(decoding.sum())
        bound = live * HEADS * P * N * 4 * 2 / BANDWIDTH * 1e3
        base = dict(decoding=live, bound_ms=bound)
        ms, store = timed(xla, store, layer, x, dts, a, b, c)
        say(**base, form="xla", ms=ms, roofline=bound / ms)
        for hb in HEADS_PER_BLOCK + (None,):
            fn = kernel(hb)
            try:
                ms, store = timed(fn, store, layer, x, dts, a, b, c,
                                  decoding)
            except Exception as e:  # a block the compiler refuses
                say(**base, form="kernel", heads_per_block=hb,
                    refused=str(e)[:300])
                continue
            say(**base, form="kernel" if hb else "kernel, the rule",
                heads_per_block=hb or ss.tiling(HEADS, GROUPS, P, N), ms=ms,
                roofline=bound / ms)
    # one step each way from the same store, held together
    decoding = jnp.arange(SLOTS) % 3 != 1
    dts = jnp.where(decoding[:, None], dt, 0.0)
    before = jnp.copy(store)
    want_y, want = xla(jnp.copy(store), layer, x, dts, a, b, c)
    got_y, got = kernel(None)(store, layer, x, dts, a, b, c, decoding)
    d = np.asarray(decoding)
    say(against="xla", y_max_abs=float(jnp.abs(got_y - want_y)[d].max()),
        y_rms=float(jnp.sqrt(jnp.mean(want_y[d] ** 2))),
        state_max_abs=float(jnp.abs(got[LAYER] - want[LAYER])[d].max()),
        other_layers_same=all(bool(jnp.array_equal(got[i], before[i]))
                              for i in range(LAYERS) if i != LAYER),
        idle_slots_same=bool(jnp.array_equal(got[LAYER][~d],
                                             before[LAYER][~d])))


if __name__ == "__main__":
    main()
