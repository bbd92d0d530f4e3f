#!/usr/bin/env python3
"""sha256 of the lowered text of the engine's two programs at each serving
cell's sizes, for a described v5e with ``jax.default_backend`` steered to the
TPU (so that ``engine.paged_attention_path`` and ``chunk_attention_path``
name the kernels, as on the chip). Lowering only: no compile, no chip.

A change that must leave a model's programs alone proves it by running this
on the parent's checkout and on its own and comparing line for line:

    ln -sfn /root/scratch/parent /root/scratch/x
    JAX_PLATFORMS=cpu python3 experiments/lowered_text_sha.py --root /root/scratch/x
    ln -sfn /root/repo /root/scratch/x
    JAX_PLATFORMS=cpu python3 experiments/lowered_text_sha.py --root /root/scratch/x

``--root`` is the checkout whose ``ddl25spring_tpu`` and ``benchmarks`` are
imported; ``--workload`` (may repeat) names cells, default every serving cell
of that checkout's BENCHMARK.json.
"""
import argparse
import hashlib
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--workload", action="append")
    ap.add_argument("--dump", help="write <dump>_<cell>_<program>.txt too")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path[:0] = [root, os.path.join(root, "benchmarks")]

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    from ddl25spring_tpu.config import describe
    from ddl25spring_tpu.models import generate
    from ddl25spring_tpu.serving import engine as eng
    from ddl25spring_tpu.serving import kvcache

    assert os.path.abspath(eng.__file__).startswith(root), eng.__file__
    jax.config.update("jax_enable_compilation_cache", False)
    # A kernel's serialised text carries the source location of each of its
    # operations. By default that is the whole call stack, so any edit that
    # moves a line of engine.py changes the bytes of a kernel it never
    # touched. Keep the innermost frame only (the kernel's own file), and
    # import both checkouts under one path (a symlink handed as --root), so
    # that equal bytes mean equal operations from equal kernel sources.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"

    def shaped(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    for name in args.workload or cells:
        cell = harness.load_cell(name)
        tr = cell.traffic
        if not tr["kind"].startswith("serve"):
            continue
        runner = importlib.import_module(tr["kind"] + "_cell")
        dims = runner.ref.Dims.from_config(cell.config)
        mcfg = runner.model_config(cell, dims)
        desc = describe(mcfg)
        paged = kvcache.PagedKVConfig(
            num_blocks=tr["num_blocks"], block_len=tr["block_len"],
            max_blocks_per_seq=tr["max_blocks_per_seq"],
            kv_dtype=cell.config["cache_dtype"])
        params = jax.eval_shape(
            lambda: runner.ref.make_weights(0, dims, mcfg.param_dtype))
        s, mb, tc = tr["num_slots"], paged.max_blocks_per_seq, tr["prefill_chunk"]
        pool = jax.eval_shape(lambda: kvcache.init_pool(mcfg, paged))
        slot = ()
        if getattr(desc, "mixer", None) is not None:
            pool = {**pool, **jax.eval_shape(
                lambda: kvcache.init_state(mcfg, s))}
            slot = (sds((), i32),)
        if desc.plain:
            weights = (shaped(params), shaped(jax.eval_shape(
                generate._fuse_blocks, params["blocks"])))
        else:
            weights = (shaped({k: v for k, v in params.items()
                               if k != "runs"}), shaped(tuple(params["runs"])))
        for prog, fn, argv in (
                ("decode_step", eng.make_decode_step(mcfg, paged, s, None, None),
                 (sds((s, mb), i32), sds((s,), i32), sds((s,), i32),
                  sds((s, 2), u32), sds((s,), f32), sds((s,), jnp.bool_))),
                ("prefill_chunk",
                 eng.make_prefill_chunk(mcfg, paged, tc, None, None),
                 (sds((mb,), i32), sds((tc,), i32), sds((), i32),
                  sds((), i32), sds((), i32), sds((2,), u32), sds((), f32))
                 + slot)):
            text = fn.lower(shaped(pool), *weights, *argv).as_text()
            if args.dump:
                with open(f"{args.dump}_{name}_{prog}.txt", "w") as f:
                    f.write(text)
            print(f"{name} {prog} {hashlib.sha256(text.encode()).hexdigest()}"
                  f" kernel={'tpu_custom_call' in text} chars={len(text)}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
