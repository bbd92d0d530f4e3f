#!/usr/bin/env python
"""Chip smoke: the trainer and the serving engine on a TPU, end to end.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: DP-4, DP2xPP2, DP2xTP2
                                     # against one device, and nothing else

One process. It takes whatever ``jax.devices()`` gives and fails unless that
is a TPU: there is no CPU path to the success line. Every phase prints one
JSON line that names platform, device kind and device count; any exception
in any phase ends the run non-zero. On success the last stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The model is the one LLM the repo supports at full width (dmodel 288,
6 heads x 48, 6 layers, ctx 256, vocab 32000, bf16 compute), driven through
the entry points a user calls: ``train_llm_dp`` / ``_pp`` / ``_tp`` and
``run_serving``. Weights and data are made from seeds; nothing is read or
written outside the checkout (``chip_smoke_out/``, git-ignored, and the
compile cache — ``utils/compilation_cache.py``). It claims no speed.

``--rehearse`` is for a machine without the chip: tiny sizes, interpreted
kernels, no platform check. It ends with ``"ok": false`` whatever happens.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chip_smoke_out")

BF16_EPS = 2.0 ** -8


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized by. ``FULL`` is the published width; ``TINY`` is
    the rehearsal's."""
    model: dict            # LlamaConfig overrides on top of dtype=bfloat16
    vocab: int
    batch: int             # per-chip batch of the one-chip train phase
    global_batch: int      # global batch of every four-chip comparison
    attn_shape: tuple      # [B, T, H, Dh] of the flash kernel check
    flash_blocks: tuple
    adam_leaf: tuple       # shape of the leaf the Adam kernel updates
    prompt_lens: tuple
    max_news: tuple
    n_requests: int
    block_len: int
    prefill_chunk: int


FULL = Sizes(model={}, vocab=32000, batch=64, global_batch=64,
             attn_shape=(64, 256, 6, 48), flash_blocks=(256, 128),
             adam_leaf=(288, 32000), prompt_lens=(16, 64, 160),
             max_news=(8, 16, 32), n_requests=32, block_len=16,
             prefill_chunk=32)
TINY = Sizes(model=dict(dmodel=64, num_heads=2, n_layers=2, ctx_size=64),
             vocab=512, batch=4, global_batch=8,
             attn_shape=(2, 64, 2, 32), flash_blocks=(64, 32),
             adam_leaf=(128, 512), prompt_lens=(4, 12, 24),
             max_news=(4, 8), n_requests=8, block_len=8, prefill_chunk=8)

TRAIN_SEED = 0
N_SLOTS = 8


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _wide_tokenizer(vocab: int):
    """The byte tokenizer, declaring the model's full vocabulary. The
    trainers overwrite ``vocab_size`` with the tokenizer's, and a checkout
    holds no SentencePiece asset, so the default would train a 259-symbol
    model and call it full width. Ids stay below 259; the embedding and the
    head are full size."""
    from ddl25spring_tpu.tokenizers.spm import ByteTokenizer

    tok = ByteTokenizer()
    tok.vocab_size = vocab
    return tok


def _peak_bytes(devices) -> list:
    stats = [d.memory_stats() for d in devices]
    return [s.get("peak_bytes_in_use") if s else None for s in stats]


# --------------------------------------------------------------- kernels

def phase_kernels(sizes: Sizes, interpret: bool) -> dict:
    """The Pallas kernels of the main path, compiled (``interpret=False``
    on the chip), against their plain references."""
    import jax
    import jax.numpy as jnp

    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.ops.adam import fused_adam
    from ddl25spring_tpu.ops.flash_attention import flash_attention
    from ddl25spring_tpu.ops.pallas_adam import (FusedApplyAdam,
                                                 _pallas_eligible)

    def worst(got, want) -> float:
        """max|got - want| over the tolerance the dtype allows: 8 bf16
        epsilons of the reference's largest magnitude."""
        got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
        tol = 8 * BF16_EPS * max(1.0, float(jnp.abs(want).max()))
        return float(jnp.abs(got - want).max()) / tol

    kq, kk, kv, kw = jax.random.split(jax.random.key(1), 4)
    q, k, v, w = (jax.random.normal(key, sizes.attn_shape, jnp.bfloat16)
                  for key in (kq, kk, kv, kw))

    def ref(q, k, v):
        return llama._xla_attention(q, k, v, causal=True)

    want = ref(q, k, v)
    want_g = jax.grad(lambda *a: jnp.sum(ref(*a) * w).astype(jnp.float32),
                      (0, 1, 2))(q, k, v)
    flash = {}
    for dh_major in (False, True):
        for blk in sizes.flash_blocks:
            def f(q, k, v):
                return flash_attention(q, k, v, causal=True, block_q=blk,
                                       block_k=blk, dh_major=dh_major,
                                       interpret=interpret)
            got = f(q, k, v)
            got_g = jax.grad(
                lambda *a: jnp.sum(f(*a) * w).astype(jnp.float32),
                (0, 1, 2))(q, k, v)
            name = f"{'dh_major' if dh_major else 'row_major'}-b{blk}"
            flash[name] = {"fwd": worst(got, want),
                           **{f"d{n}": worst(a, b) for n, a, b in
                              zip("qkv", got_g, want_g)}}
            _check(all(math.isfinite(x) and x <= 1.0
                       for x in flash[name].values()),
                   f"flash {name} off its reference: {flash[name]} "
                   "(units of the bf16 tolerance)")

    # One real leaf through the fused Adam apply, three steps so that the
    # bias corrections ride in as data.
    leaf = jax.random.normal(jax.random.key(2), sizes.adam_leaf, jnp.float32)
    _check(_pallas_eligible(leaf, leaf), "the Adam leaf must take the kernel")
    pallas_opt = FusedApplyAdam(8e-4, interpret=interpret)
    plain_opt = fused_adam(8e-4)
    p_k = p_x = {"lm_head": 0.02 * leaf}
    s_k, s_x = pallas_opt.init(p_k), plain_opt.init(p_x)

    @jax.jit
    def plain_step(p, g, s):
        u, s = plain_opt.update(g, s, p)
        return jax.tree.map(jnp.add, p, u), s

    pallas_step = jax.jit(pallas_opt.apply_gradients)
    for i in range(3):
        g = {"lm_head": jax.random.normal(jax.random.key(3 + i),
                                          sizes.adam_leaf, jnp.float32)}
        p_k, s_k = pallas_step(p_k, g, s_k)
        p_x, s_x = plain_step(p_x, g, s_x)
    adam = {n: float(jnp.abs(a["lm_head"] - b["lm_head"]).max())
            for n, a, b in (("p", p_k, p_x), ("m", s_k.mu, s_x.mu),
                            ("v", s_k.nu, s_x.nu))}
    _check(all(x <= 1e-6 for x in adam.values()),
           f"pallas Adam off ops/adam.py by {adam} (float32, atol 1e-6)")
    return {"interpret": interpret, "attn_shape": list(sizes.attn_shape),
            "flash_err_over_tol": flash, "adam_leaf": list(sizes.adam_leaf),
            "adam_max_abs_err": adam}


# ----------------------------------------------------------------- train

def _train_once(trainer, model_cfg, train_cfg, tok, run_dir: str, **kw):
    """One call of a trainer with telemetry and a checkpoint directory under
    ``run_dir``. Returns the report, the run's events by type, the state the
    final save published, and the trainer's log lines."""
    from ddl25spring_tpu.telemetry import Telemetry, read_events

    saved, logs = {}, []
    with Telemetry(os.path.join(run_dir, f"telemetry-to-{train_cfg.iters}"),
                   step_every=1) as tel:
        report = trainer(
            model_cfg, train_cfg, tokenizer=tok, log_every=0,
            log_fn=logs.append, checkpoint_dir=os.path.join(run_dir, "ckpt"),
            on_checkpoint=lambda step, state: saved.update(step=step,
                                                           state=state),
            telemetry=tel, **kw)
    events: dict = {}
    for e in read_events(tel.events_path, strict=True):
        events.setdefault(e["type"], []).append(e)
    return report, events, saved, logs


def _compile_record(events: dict, label: str) -> dict:
    """The CompileWatch's record of one trainer call: every compilation
    after the first dispatch's is one after warm-up, and there is none."""
    compiles = events.get("compile", [])
    _check(len(compiles) == 1 and compiles[0]["cache_size"] == 1
           and not compiles[0]["retrace"],
           f"{label}: {len(compiles) - 1} compilations after warm-up "
           f"({[(c['name'], c['cache_size']) for c in compiles]})")
    return {"compile_seconds": round(compiles[0]["seconds"], 3),
            "compilations_after_warmup": len(compiles) - 1}


def phase_train(sizes: Sizes, out_dir: str, on_chip: bool) -> dict:
    """``train_llm_dp`` at full width: 8 steps and a save, then the same
    call again to 12 steps, which restores the save into the mesh. Once per
    aggregation."""
    import jax
    import jax.numpy as jnp

    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.train.llm import train_llm_dp
    from ddl25spring_tpu.utils import pytree

    tok = _wide_tokenizer(sizes.vocab)
    model_cfg = LlamaConfig(dtype="bfloat16", **sizes.model)
    _check(model_cfg.attention_impl == "auto", "the chip picks the path")
    ln_v = math.log(sizes.vocab)
    runs = {}
    for label, aggregation, spd in (("gradient", "gradient", 1),
                                    ("zero1-k4", "zero1", 4)):
        run_dir = os.path.join(out_dir, "train", label)
        legs, losses = [], []
        for iters in (8, 12):
            cfg = TrainConfig(batch_size=sizes.batch,
                              seq_len=model_cfg.ctx_size, iters=iters,
                              seed=TRAIN_SEED, steps_per_dispatch=spd)
            report, events, saved, logs = _train_once(
                train_llm_dp, model_cfg, cfg, tok, run_dir,
                aggregation=aggregation)
            manifest = events["manifest"][0]
            used = manifest["model_cfg"]
            _check(used["vocab_size"] == sizes.vocab,
                   f"trained at vocab {used['vocab_size']}")
            if on_chip:
                _check(manifest["platform"] == "tpu"
                       and manifest["attention"]
                       == {"impl": "pallas", "interpret": False},
                       f"step built with {manifest['attention']} on "
                       f"{manifest['platform']}")
            _check(saved.get("step") == iters, f"no save at {iters}: {saved}")
            legs.append({"iters": iters, "start_step": report.start_step,
                         **_compile_record(events, label),
                         "resumed": [m for m in logs if "resumed" in m]})
            losses += report.losses
        _check(legs[0]["start_step"] == 0 and legs[1]["start_step"] == 8
               and legs[1]["resumed"] == ["resumed from step 8"],
               f"{label}: no restore of step 8 ({legs})")
        _check(len(losses) == 12 and all(map(math.isfinite, losses)),
               f"{label}: losses {losses}")
        _check(abs(losses[0] - ln_v) < 0.5,
               f"{label}: first loss {losses[0]} is not ln(V)={ln_v:.2f}")
        # Falling, through the restore too: a restore into fresh weights
        # would start again from ln(V).
        _check(max(losses[8:]) < min(losses[:2]) - 0.5,
               f"{label}: losses do not fall: {losses}")
        params = saved["state"].params
        init = llama.init_llama(jax.random.key(TRAIN_SEED),
                                LlamaConfig(**used))
        moved = jax.tree.map(
            lambda a, b: float(jnp.abs(a - jnp.asarray(b)).max()), params,
            init)
        _check(all(x > 0 for x in jax.tree.leaves(moved)),
               f"{label}: parameters did not move: {moved}")
        runs[label] = {
            "aggregation": aggregation, "steps_per_dispatch": spd,
            "losses": [round(x, 4) for x in losses], "legs": legs,
            "attention": manifest["attention"],
            "n_params": pytree.param_count(params),
            "vocab_size": used["vocab_size"]}
    return {"batch": sizes.batch, "seq": model_cfg.ctx_size,
            "ln_vocab": round(ln_v, 4), "runs": runs}


# ----------------------------------------------------------------- serve

def phase_serve(sizes: Sizes) -> dict:
    """``run_serving`` at full width under seeded mixed-length traffic, in
    the bf16 the trainer computes in, and the engine's own bar — a greedy
    request emits what ``generate()`` emits for it alone — in float32.

    Why float32: on the chip the bar does not hold in bf16. Logits rounded
    to bf16 over 32000 classes tie, the engine's 8-slot decode program and
    ``generate``'s one-row scan round differently, and the first flipped
    tie changes every later token (my chip run, PR 21: 30 of 32 tokens
    equal on the longest prompt). How far bf16 agrees is reported, not
    asserted; the same traffic served in float32 is held to equality."""
    import jax

    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.serving import (PagedKVConfig, reference_stream,
                                         run_serving, synthetic_workload)

    cfg = LlamaConfig(dtype="bfloat16", vocab_size=sizes.vocab,
                      **sizes.model)
    params = llama.init_llama(jax.random.key(0), cfg)
    per_seq = cfg.ctx_size // sizes.block_len
    # A block-table row holds a whole context, and the pool every slot's
    # row at once (plus the trash block).
    paged = PagedKVConfig(num_blocks=N_SLOTS * per_seq + 1,
                          block_len=sizes.block_len,
                          max_blocks_per_seq=per_seq)
    workload = synthetic_workload(
        seed=0, n_requests=sizes.n_requests, rate_rps=200.0,
        vocab_size=cfg.vocab_size, prompt_lens=sizes.prompt_lens,
        max_news=sizes.max_news)
    greedy = [r for r in workload if r.temperature == 0.0]
    checked = [greedy[0], max(greedy, key=lambda r: len(r.prompt))]

    def serve(cfg):
        rep = run_serving(params, cfg, paged, workload, num_slots=N_SLOTS,
                          prefill_chunk=sizes.prefill_chunk)
        for req in workload:
            rec = rep.records[req.rid]
            _check(rec.done_t is not None
                   and len(rec.tokens) == req.max_new,
                   f"{cfg.dtype} {req.rid}: {len(rec.tokens)} of "
                   f"{req.max_new} tokens, done={rec.done_t}")
        _check(rep.retraces == 0 and rep.compiles == 2,
               f"{cfg.dtype}: {rep.compiles} compiles, "
               f"{rep.retraces} retraces")
        agreed = {}
        for req in checked:
            want = reference_stream(params, cfg, paged, req)
            got = list(rep.records[req.rid].tokens)
            same = next((i for i, (a, b) in enumerate(zip(got, want))
                         if a != b), len(want))
            agreed[req.rid] = {"prompt": len(req.prompt),
                               "equal_tokens": same, "of": len(want)}
        return rep, agreed

    rep, agreed_bf16 = serve(cfg)
    _, agreed_f32 = serve(cfg.replace(dtype="float32"))
    _check(all(a["equal_tokens"] == a["of"] for a in agreed_f32.values()),
           f"float32 engine != generate(): {agreed_f32}")
    return {"requests": len(workload), "slots": N_SLOTS,
            "pool_blocks": rep.pool_blocks,
            "peak_blocks_in_use": rep.peak_blocks_in_use,
            "peak_concurrency": rep.peak_concurrency,
            "total_tokens": rep.aggregates["total_tokens"],
            "compiles": rep.compiles, "retraces": rep.retraces,
            "wall_s": round(rep.wall_s, 3),
            "greedy_equal_generate_float32": agreed_f32,
            "greedy_equal_generate_bfloat16": agreed_bf16}


# ------------------------------------------------------------ four chips

def _placement(state, devices, on_chip: bool) -> dict:
    """Where a live train state lies: how many devices hold the leaf spread
    widest, how many leaves are partitioned (a shard smaller than the leaf),
    and each device's bytes in use."""
    import jax

    leaves = [x for x in jax.tree.leaves(state) if isinstance(x, jax.Array)]
    widest = max(len({s.device for s in x.addressable_shards})
                 for x in leaves)
    partitioned = sum(x.addressable_shards[0].data.shape != x.shape
                      for x in leaves)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    _check(widest == len(devices) and partitioned > 0,
           f"state on {widest} of {len(devices)} devices, "
           f"{partitioned} leaves partitioned")
    if on_chip:
        _check(all(b and b > 0 for b in in_use),
               f"a device holds no bytes: {in_use}")
    return {"leaf_devices": widest, "partitioned_leaves": int(partitioned),
            "bytes_in_use": in_use}


def _one_device_losses(model_cfg, tok, n_shards: int, per_shard: int,
                       steps: int, lr: float, device) -> list:
    """The comparison: the same global batches — the ``n_shards`` disjoint
    stream windows the trainers read, side by side — through the plain DP
    step on a one-device mesh, from the trainers' initial weights."""
    import jax
    import optax

    from ddl25spring_tpu.data.tokens import sharded_batches
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.parallel import dp, make_mesh

    mesh = make_mesh({"data": 1}, devices=[device])
    opt = optax.adam(lr)
    step = dp.make_grad_aggregation_step(
        lambda p, b: llama.forward_loss(p, b, model_cfg), opt, mesh)
    state = dp.replicate(mesh, dp.init_state(
        llama.init_llama(jax.random.key(TRAIN_SEED), model_cfg), opt))
    batches = sharded_batches(tok, per_shard, model_cfg.ctx_size, n_shards,
                              shard_skip=5000, seed=TRAIN_SEED)
    losses = []
    for _ in range(steps):
        batch = next(batches).reshape(n_shards * per_shard,
                                      model_cfg.ctx_size)
        state, loss = step(state, dp.shard_batch(mesh, batch))
        losses.append(float(loss))
    return losses


def phase_four_chips(sizes: Sizes, out_dir: str, on_chip: bool) -> dict:
    """DP-4 ZeRO-1, DP2xPP2 (1F1B, 2 microbatches) and DP2xTP2 through
    their trainers on four devices, each against the same global batch on
    one device. Losses agree within ``LOSS_ATOL``: the programs differ in
    the order of bf16 reductions, not in the arithmetic they describe."""
    import warnings

    import jax

    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.train.llm import (train_llm_dp, train_llm_pp,
                                           train_llm_tp)

    loss_atol, steps = 0.05, 8
    devices = jax.devices()[:4]
    tok = _wide_tokenizer(sizes.vocab)
    model_cfg = LlamaConfig(dtype="bfloat16", vocab_size=sizes.vocab,
                            **sizes.model)
    lr = TrainConfig().lr
    warnings.filterwarnings("ignore", message="mesh .* uses 1 of")
    ref = {n: _one_device_losses(model_cfg, tok, n, sizes.global_batch // n,
                                 steps, lr, devices[0]) for n in (4, 2)}
    cases = (   # label, trainer, mesh, other TrainConfig fields, arguments
        ("dp4-zero1", train_llm_dp, dict(data=4), {},
         dict(aggregation="zero1")),
        ("dp2-pp2-1f1b", train_llm_pp, dict(data=2, stage=2),
         dict(microbatches=2), dict(schedule="1f1b")),
        ("dp2-tp2", train_llm_tp, dict(data=2, model=2), {}, {}),
    )
    runs = {}
    for label, trainer, layout, more, kw in cases:
        n = layout["data"]
        cfg = TrainConfig(batch_size=sizes.global_batch // n,
                          seq_len=model_cfg.ctx_size, iters=steps,
                          seed=TRAIN_SEED, **layout, **more)
        report, events, saved, _ = _train_once(
            trainer, model_cfg, cfg, tok, os.path.join(out_dir, label), **kw)
        manifest = events["manifest"][0]
        _check(manifest["mesh"] == layout
               and manifest["model_cfg"]["vocab_size"] == sizes.vocab,
               f"{label}: ran on {manifest['mesh']}")
        gap = max(abs(a - b) for a, b in zip(report.losses, ref[n]))
        _check(len(report.losses) == steps and gap <= loss_atol,
               f"{label}: losses {report.losses} against one device "
               f"{ref[n]}: max gap {gap} > {loss_atol}")
        runs[label] = {
            "mesh": manifest["mesh"],
            "losses": [round(x, 4) for x in report.losses],
            "one_device_losses": [round(x, 4) for x in ref[n]],
            "max_abs_gap": round(gap, 5),
            **_compile_record(events, label),
            "attention": manifest["attention"],
            **_placement(saved["state"], devices, on_chip)}
        del saved
    return {"global_batch": sizes.global_batch, "steps": steps,
            "loss_atol": loss_atol, "runs": runs}


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the four-chip comparison and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, no platform check, never ok")
    args = ap.parse_args(argv)

    import jax

    from ddl25spring_tpu.utils.compilation_cache import \
        enable_compilation_cache

    cache_dir = enable_compilation_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": args.chips}
    on_chip = device["platform"] == "tpu"
    refused = ("chip_smoke.py needs a TPU" if not (on_chip or args.rehearse)
               else f"{len(devices)} devices, --chips {args.chips}"
               if len(devices) < args.chips else None)
    if refused:
        print(json.dumps({"ok": False, "device": device, "error": refused}))
        return 2

    sizes = TINY if args.rehearse else FULL
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    stamp = {"platform": device["platform"], "device_kind": device["kind"],
             "device_count": args.chips}
    print(json.dumps({"phase": "start", **stamp, "jax": jax.__version__,
                      "compile_cache_dir": cache_dir, "out_dir": OUT_DIR,
                      "rehearsal": args.rehearse}), flush=True)
    if args.chips == 4:
        phases = [("four_chips",
                   lambda: phase_four_chips(sizes, OUT_DIR, on_chip))]
    else:
        phases = [("kernels", lambda: phase_kernels(sizes, not on_chip)),
                  ("train", lambda: phase_train(sizes, OUT_DIR, on_chip)),
                  ("serve", lambda: phase_serve(sizes))]
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            record = phase()
        except Exception as e:
            # The one boundary: say which phase failed, and fail.
            traceback.print_exc()
            print(json.dumps({"ok": False, "device": device,
                              "failed_phase": name,
                              "error": f"{type(e).__name__}: {e}"[:2000]}))
            return 1
        print(json.dumps({"phase": name, **stamp, **record,
                          "seconds": round(time.perf_counter() - t0, 2),
                          "peak_bytes_in_use":
                              _peak_bytes(devices[:args.chips])}),
              flush=True)
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
