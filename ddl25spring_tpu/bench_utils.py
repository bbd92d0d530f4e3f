"""Shared benchmark timing cores, used by bench.py and experiments/*.

One implementation of "time the DP train step / the decode loop on this
platform" so the headline bench and the experiment harnesses cannot drift
in timing methodology. JAX returns before the device finishes, so every
timed chain ends in ``jax.block_until_ready``.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp

from .config import LlamaConfig
from .models import llama
from .ops.adam import fused_adam
from .parallel import dp


def make_optimizer(opt_name: str, lr: float = 8e-4):
    """"fused" = single-pass fused Adam (ops/adam.py — same update as
    optax.adam, asserted ≤1e-6 in tests/test_core.py, fewer HBM round trips
    over the parameter-sized state); "pallas" = the fully-fused Pallas apply
    (ops/pallas_adam.py — moments + param write in one kernel pass per
    leaf); "master" = fp32-master-weight Adam for bf16 params
    (ops/mixed_precision.py — pair with ``param_dtype="bfloat16"``). The
    optimizer leg is memory-bound either way; benches measure which fusion
    wins on the chip at hand."""
    if opt_name == "pallas":
        from .ops.pallas_adam import FusedApplyAdam
        return FusedApplyAdam(lr)
    if opt_name == "master":
        from .ops.mixed_precision import master_weight_adam
        return master_weight_adam(lr)
    if opt_name != "fused":
        raise ValueError(
            f"unknown optimizer {opt_name!r}: expected one of "
            "'fused', 'pallas', 'master'")
    return fused_adam(lr)


def time_train_step(mesh, cfg: LlamaConfig, batch_size: int, *,
                    seq: Optional[int] = None, opt_name: str = "fused",
                    wire: Optional[str] = None,
                    warmup: int = 3, timed_steps: int = 20,
                    steps_per_dispatch: int = 1,
                    aggregation: str = "gradient",
                    overlap_microbatches: int = 0,
                    comm_buckets: int = 1) -> float:
    """Total tokens/sec of the DP train step at the given per-chip batch.

    ``seq`` defaults to ``cfg.ctx_size``. The caller divides by its device
    count for a per-chip figure. ``wire`` ∈ {None, "bf16", "int8_ef"}
    selects the compressed-allreduce step (parallel/compress.py) — on one
    chip the collective is local, so the measurement is the compression
    math's overhead (quantize + error-feedback).

    ``steps_per_dispatch`` = K > 1 times the fused K-step scan driver
    (parallel/dp.py ``make_multi_step``): the same warmup/timed step budget
    is spent in ceil-divided windows of K, so the token accounting stays
    comparable with the per-step rows while the dispatch overhead is paid
    once per window. ``aggregation`` ∈ {"gradient", "zero1"} picks the
    plain pmean path or the ZeRO-1 sharded weight update; both compose
    with ``steps_per_dispatch`` (``make_zero1_multi_step``).

    ``overlap_microbatches`` = M >= 1 times the overlapped ring driver
    (parallel/compress.py ``make_overlap_*``) instead — the path where
    ``wire`` (fp32/bf16/int8_ef in-flight ring chunks) composes with
    zero1 AND steps_per_dispatch; M = 0 keeps the legacy composition
    rules, where ``wire`` needs per-step gradient aggregation. On a
    hierarchical mesh (hier_data_mesh), pass the per-axis dict
    ``wire={"ici": ..., "dcn": ...}`` (requires M >= 1) — the two-level
    topology-aware driver; ``dp.shard_batch``/``shard_batch_window``
    place the batch over both data axes automatically.

    ``comm_buckets`` = B > 1 (requires M >= 1) runs the bucketed
    backward: per-bucket ring dispatch in VJP emission order, so the
    first hop overlaps the remaining grad compute — the ISSUE 19
    sub-1/n chunking rows."""
    seq = seq or cfg.ctx_size
    n_dev = mesh.devices.size
    K = max(1, int(steps_per_dispatch))
    M = int(overlap_microbatches)
    B = max(1, int(comm_buckets))
    params = llama.init_llama(jax.random.key(0), cfg)
    opt = make_optimizer(opt_name)

    def loss_fn(p, batch):
        return llama.forward_loss(p, batch, cfg)

    if M == 0 and wire is not None and (aggregation != "gradient"
                                        or K != 1):
        raise ValueError("wire compression composes with per-step gradient "
                         "aggregation only (pass overlap_microbatches >= 1 "
                         "for the composing ring driver)")
    if M == 0 and B > 1:
        raise ValueError("comm_buckets > 1 needs the overlapped ring driver "
                         "(pass overlap_microbatches >= 1)")
    if M >= 1:
        from .parallel import compress
        maker = (compress.make_overlap_multi_step if K > 1
                 else compress.make_overlap_step)
        state, step = maker(loss_fn, opt, mesh, params, microbatches=M,
                            wire=wire or "fp32", aggregation=aggregation,
                            comm_buckets=B)
    elif wire == "bf16":
        from .parallel import compress
        state = dp.replicate(mesh, dp.init_state(params, opt))
        step = compress.make_bf16_grad_step(loss_fn, opt, mesh)
    elif wire == "int8_ef":
        from .parallel import compress
        state = compress.init_ef_state(mesh, params, opt)
        step = compress.make_int8_ef_grad_step(loss_fn, opt, mesh)
    elif wire is None and aggregation == "zero1":
        if K > 1:
            state, step = dp.make_zero1_multi_step(loss_fn, opt, mesh, params)
        else:
            state, step = dp.make_zero1_step(loss_fn, opt, mesh, params)
    elif wire is None and aggregation == "gradient":
        if K > 1:
            step = dp.make_multi_step(loss_fn, opt, mesh)
        else:
            step = dp.make_grad_aggregation_step(loss_fn, opt, mesh)
        state = dp.replicate(mesh, dp.init_state(params, opt))
    else:
        raise ValueError(f"unknown wire/aggregation {wire!r}/{aggregation!r}")
    tokens = jax.random.randint(jax.random.key(1), (n_dev * batch_size, seq),
                                0, cfg.vocab_size)
    if K > 1:
        window = dp.shard_batch_window(
            mesh, jnp.broadcast_to(tokens, (K,) + tokens.shape))
        warm_chunks = max(1, -(-warmup // K))
        timed_chunks = max(1, -(-timed_steps // K))
        for _ in range(warm_chunks):
            state, losses = step(state, window)
        jax.block_until_ready(losses)  # hard sync before the timer
        t0 = time.perf_counter()
        for _ in range(timed_chunks):
            state, losses = step(state, window)
        jax.block_until_ready((state, losses))  # the whole timed chain
        dt = time.perf_counter() - t0
        del state
        return n_dev * batch_size * seq * timed_chunks * K / dt

    batch = dp.shard_batch(mesh, tokens)
    for _ in range(warmup):
        state, loss = step(state, batch)
    jax.block_until_ready(loss)  # hard sync before the timer
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        state, loss = step(state, batch)
    jax.block_until_ready((state, loss))  # the whole timed chain
    dt = time.perf_counter() - t0
    del state
    return n_dev * batch_size * seq * timed_steps / dt


def time_decode(cfg: LlamaConfig, batch: int, prompt_len: int = 64,
                new_tokens: int = 128, bf16_params: bool = False,
                kv_dtype: Optional[str] = None, reps: int = 3) -> float:
    """Generated tokens/sec for the KV-cache decode loop (models/generate).

    The two serving levers, matching the decode roofline's two HBM streams
    (experiments/ROOFLINE.md): ``bf16_params`` halves the weight bytes —
    dominant at batch 1 (training keeps fp32 master params; casting a copy
    for inference is the deployment shape); ``kv_dtype="bfloat16"`` halves
    the cache bytes — dominant once the batch amortizes the weights."""
    from .models import generate as gen
    params = llama.init_llama(jax.random.key(0), cfg)
    if bf16_params:
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, params)
    prompt = jax.random.randint(jax.random.key(1), (batch, prompt_len),
                                0, cfg.vocab_size)
    out = gen.generate(params, prompt, cfg, new_tokens, kv_dtype=kv_dtype)
    jax.block_until_ready(out)                      # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = gen.generate(params, prompt, cfg, new_tokens,
                           kv_dtype=kv_dtype)
    jax.block_until_ready(out)
    return batch * new_tokens * reps / (time.perf_counter() - t0)
