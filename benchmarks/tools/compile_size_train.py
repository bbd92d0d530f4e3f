#!/usr/bin/env python3
"""Size a training cell without a chip: compile the trainer's data-parallel
step for a described v5e at the cell's own shapes (a mesh of `chips`
described devices, the state replicated, `chips x batch_per_chip` rows a
step) and print what the compiler counts a device, with the collectives of
the compiled text and the scope path each carries. A compile, never a chip
run.

The step is built by the call `train_llm_dp` makes for a cell of
`aggregation="gradient"` with the fp32 wire, no accumulation and one step a
dispatch: `dp.make_grad_aggregation_step` over `llama.forward_loss` and
`optax.adam(lr)` (what `_make_trainer_optimizer` returns for `"adam"`). It
leaves out what the trainer wraps round the compiled step on the host (the
compile watch, the cell's `fault_plan=` probe), which lowers nothing. Only
cells of that kind are sized right.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_size_train.py \
        --workload train_dscoder1b_dp4 [--set batch_per_chip=2]
"""
import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

COLLECTIVE = re.compile(r" (all-reduce|reduce-scatter|all-gather)"
                        r"(-start|-done)?\(")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="traffic key=value (a number), may repeat")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import harness
    import reference as ref
    import train_cell
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.parallel import dp

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    for kv in args.set:
        k, v = kv.split("=")
        cell.traffic[k] = json.loads(v)
    tr = cell.traffic
    dims = ref.Dims.from_config(cell.config)
    mcfg = train_cell.model_config(cell, dims)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the step asks the backend which attention to build: the chip's
    jax.default_backend = lambda: "tpu"
    mesh = Mesh(np.array(topo.devices[:cell.chips]), ("data",))
    opt = optax.adam(tr["lr"])
    step = dp.make_grad_aggregation_step(
        lambda p, b: llama.forward_loss(p, b, mcfg), opt, mesh)
    replicated = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=replicated),
        jax.eval_shape(lambda: dp.init_state(
            llama.init_llama(jax.random.key(0), mcfg), opt)))
    batch = jax.ShapeDtypeStruct(
        (cell.chips * tr["batch_per_chip"], tr["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    ops = []
    for line in text.splitlines():
        m = COLLECTIVE.search(line)
        if m:
            path = re.search(r'op_name="([^"]*)"', line)
            ops.append({"op": line.split(" = ")[0].strip().lstrip("%"),
                        "kind": m.group(1) + (m.group(2) or ""),
                        "grad_sync": bool(path and "grad_sync"
                                          in path.group(1)),
                        "op_name": path.group(1) if path else None})
    print(json.dumps({
        "workload": cell.name, "chips": cell.chips,
        "rows_a_step": cell.chips * tr["batch_per_chip"],
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "program_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes,
        "flash_kernels": text.count("tpu_custom_call"),
        "collectives": ops}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
