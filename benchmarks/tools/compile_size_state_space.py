#!/usr/bin/env python3
"""`compile_size_described.py` for a cell whose model carries a state-space
mixer's state beside the paged pool (`serve_state_space_cell.py`): compile the
engine's two programs for a described v5e at the cell's own shapes, with
`jax.default_backend` steered to the TPU so that the decode step's attention
is the kernel it is on the chip, and print what the compiler counts, the state
store's bytes beside the pool's and the weights'. The engine holds one copy
of each weight and donates pool and state, so arguments + temporaries of the
larger program is what the chip must hold. A compile, never a chip run.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_size_state_space.py \
        --workload serve_falconh1_chat_backlog [--layers 2] \
        [--set num_slots=80] [--hlo /root/scratch/falconh1]
"""
import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int)
    ap.add_argument("--set", action="append", default=[],
                    help="traffic key=value (a number), may repeat")
    ap.add_argument("--hlo", help="write <hlo>_<program>.txt, the compiled text")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    from ddl25spring_tpu.serving import engine as eng
    from ddl25spring_tpu.serving.kvcache import (PagedKVConfig, init_pool,
                                                 init_state)

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    if args.layers:
        cell.config["num_hidden_layers"] = args.layers
    for kv in args.set:
        k, v = kv.split("=")
        cell.traffic[k] = json.loads(v)
    tr = cell.traffic
    runner = importlib.import_module(tr["kind"] + "_cell")
    dims = runner.ref.Dims.from_config(cell.config)
    mcfg = runner.model_config(cell, dims)
    paged = PagedKVConfig(num_blocks=tr["num_blocks"],
                          block_len=tr["block_len"],
                          max_blocks_per_seq=tr["max_blocks_per_seq"],
                          kv_dtype=cell.config["cache_dtype"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"

    def shaped(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    s, mb, tc = tr["num_slots"], paged.max_blocks_per_seq, tr["prefill_chunk"]
    params = jax.eval_shape(
        lambda: runner.ref.make_weights(0, dims, mcfg.param_dtype))
    head = {k: v for k, v in params.items() if k != "runs"}
    runs = tuple(params["runs"])
    pool = jax.eval_shape(lambda: init_pool(mcfg, paged))
    state = jax.eval_shape(lambda: init_state(mcfg, s))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree.leaves(t))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    decode = eng.make_decode_step(mcfg, paged, s, None, None)
    prefill = eng.make_prefill_chunk(mcfg, paged, tc, None, None)
    out = {"layers": dims.layers, "traffic": {k: tr[k] for k in (
        "num_slots", "block_len", "max_blocks_per_seq", "num_blocks",
        "prefill_chunk")},
        "params_bytes": nbytes(params), "pool_bytes": nbytes(pool),
        "state_bytes": nbytes(state),
        "state_bytes_a_slot": nbytes(state) // s,
        "decode_attention": eng.paged_attention_path(
            1, *pool["k"].shape[3:], pool["k"].dtype, paged.block_len)["impl"]}
    both = {**pool, **state}
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    for name, fn, argv in (
            ("decode_step", decode,
             (shaped(both), shaped(head), shaped(runs), sds((s, mb), i32),
              sds((s,), i32), sds((s,), i32), sds((s, 2), u32),
              sds((s,), f32), sds((s,), jnp.bool_))),
            ("prefill_chunk", prefill,
             (shaped(both), shaped(head), shaped(runs), sds((mb,), i32),
              sds((tc,), i32), sds((), i32), sds((), i32), sds((), i32),
              sds((2,), u32), sds((), f32), sds((), i32)))):
        compiled = fn.lower(*argv).compile()
        m = compiled.memory_analysis()
        out[name] = {"arguments": m.argument_size_in_bytes,
                     "temporaries": m.temp_size_in_bytes,
                     "outputs": m.output_size_in_bytes,
                     "aliased": m.alias_size_in_bytes,
                     "args_plus_temps_gb": (m.argument_size_in_bytes
                                            + m.temp_size_in_bytes) / 1e9}
        if args.hlo:
            with open(f"{args.hlo}_{name}.txt", "w") as f:
                f.write(compiled.as_text())
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
