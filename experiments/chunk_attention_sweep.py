"""The prefill chunk's attention kernel on a chip, at the A.X-K1 cell's
shape: ``python -m experiments.chunk_attention_sweep`` (a TPU or nothing;
about three minutes).

One slot, 512 queries at an offset over a table of 8192 positions, 64 heads
of 192 | 128, bf16: ``latent.attend_expanded`` whole (the expansion of the
rows, then the attention) as XLA forms it and with
``ops/chunk_attention.py`` in the attention's place, at 1024 to 8192 live
keys and over the kernel's block sizes, each beside the expansion alone, so
that the attention is what is left. Each line also gives the kernel's
required operations over the chip's peak (a causal chunk's live pairs, 2 x
64 x 320 a pair) and, at two live lengths, how far each form lies from the
same attention in float32 at ``highest`` over the same bf16 K and V: the
rounding the kernel may not widen (PERF.md, PR 33, has what was read).
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ddl25spring_tpu.config import ModelDescription
from ddl25spring_tpu.models import latent
from ddl25spring_tpu.ops import chunk_attention as ca

T, K, HEADS = 512, 8192, 64
LIVE = (1024, 2560, 4096, 6144, 8192)
BLOCKS = ((512, 256), (512, 512), (256, 512), (256, 1024), (512, 1024),
          (512, 2048))
PEAK = 197e12                # v5e, bf16 (benchmarks/peaks.json)


def description() -> ModelDescription:
    cfg = dict(num_hidden_layers=1, vocab_size=256, hidden_size=7168,
               intermediate_size=128, num_attention_heads=HEADS,
               kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, rms_norm_eps=1e-6,
               rope_theta=10000,
               rope_scaling=dict(factor=32, mscale=1, mscale_all_dim=1))
    return ModelDescription.from_published(
        cfg, ctx_size=K, dtype="bfloat16", param_dtype="bfloat16")


def timed(fn, *args, runs: int = 20) -> float:
    """Milliseconds a call, the device's work awaited."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / runs * 1e3


def reference(w_kvb, q, rows, pos, desc):
    """``attend_expanded``'s arithmetic in float32 at ``highest`` over the
    same bf16 keys and values, a head at a time."""
    att, h = desc.attention, desc.num_heads
    kv = (rows[..., :att.kv_rank] @ w_kvb).reshape(K, h, -1)
    k_rope = rows[0, :, att.kv_rank:att.row_dim].astype(jnp.float32)
    mask = pos[0][:, None] >= jnp.arange(K)[None, :]

    def one(qh, kvh):
        qh, kvh = qh.astype(jnp.float32), kvh.astype(jnp.float32)
        sc = (jnp.dot(qh[:, :att.nope_dim], kvh[:, :att.nope_dim].T,
                      precision="highest")
              + jnp.dot(qh[:, att.nope_dim:], k_rope.T, precision="highest")
              ) * latent.softmax_scale(att)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.dot(p, kvh[:, att.nope_dim:], precision="highest")
    return jax.lax.map(lambda a: one(*a), (q[0].swapaxes(0, 1),
                                           kv.swapaxes(0, 1))).swapaxes(0, 1)


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("a measurement: needs a TPU, found " + dev.platform)
    desc = description()
    att = desc.attention
    rng = np.random.default_rng(0)
    rows = np.zeros((1, K, 640), np.float32)
    rows[..., :att.row_dim] = rng.normal(size=(1, K, att.row_dim))
    rows = jnp.asarray(rows, jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(1, T, HEADS, att.qk_dim)), jnp.bfloat16)
    w_kvb = jnp.asarray(rng.normal(size=(att.kv_rank, HEADS * 256))
                        * att.kv_rank ** -0.5, jnp.bfloat16)

    def say(**kw):
        print(json.dumps(kw), flush=True)

    say(device=dev.device_kind, t=T, k=K, heads=HEADS)
    expansion = jax.jit(lambda r, w: r[..., :att.kv_rank] @ w)
    say(form="expansion alone", ms=timed(expansion, rows, w_kvb))
    xla = jax.jit(lambda w, q, r, p: latent.attend_expanded(w, q, r, p, desc))

    def with_kernel(bq, bk):
        def fn(w, q, r, p, live):
            def fused(q, kv, k_rope, pos):
                return ca.chunk_attention(
                    q, kv, k_rope, pos, live, nope_dim=att.nope_dim,
                    scale=latent.softmax_scale(att), block_q=bq, block_k=bk,
                    interpret=False)
            return latent.attend_expanded(w, q, r, p, desc, fused=fused)
        return jax.jit(fn)

    kernels = {b: with_kernel(*b) for b in BLOCKS}
    for live in LIVE:
        pos = jnp.arange(live - T, live, dtype=jnp.int32)[None]
        n = jnp.asarray([live], jnp.int32)
        pairs = T * (live - T) + T * (T + 1) / 2
        need = 2 * HEADS * 320 * pairs / PEAK * 1e3
        say(live=live, form="xla", ms=timed(xla, w_kvb, q, rows, pos),
            required_ms_at_peak=need)
        for name, fn in kernels.items():
            try:
                say(live=live, form="kernel", blocks=name,
                    ms=timed(fn, w_kvb, q, rows, pos, n))
            except Exception as e:      # a block the compiler refuses
                say(live=live, form="kernel", blocks=name,
                    refused=str(e)[:200])
        if live in (2560, 6144):
            want = np.asarray(jax.jit(
                lambda w, q, r, p: reference(w, q, r, p, desc))(
                    w_kvb, q, rows, pos))
            got = {"xla": xla(w_kvb, q, rows, pos),
                   "kernel": kernels[ca.blocks(T, K)](w_kvb, q, rows, pos, n)}
            got = {k: np.asarray(v[0], np.float32) for k, v in got.items()}
            for form, out in got.items():
                d = out - want
                say(live=live, form=form, against="float32 highest",
                    max_abs=float(np.abs(d).max()),
                    rms=float(np.sqrt((d ** 2).mean())),
                    rms_of_output=float(np.sqrt((want ** 2).mean())))
            d = got["kernel"] - got["xla"]
            say(live=live, form="kernel", against="xla",
                max_abs=float(np.abs(d).max()),
                share_of_values_that_differ=float((d != 0).mean()))


if __name__ == "__main__":
    main()
