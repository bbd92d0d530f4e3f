"""The paged attention's share of its roofline: summed over the traced decode
runs that pair with an `engine.decode.dispatch` span of the program, the least
time the chip could take for that step's attention over all the device time
the step spends on the cache: the operations under the paged-attention scopes
and those under no scope of work (`program_trace.DECODE_PARTS`: `paged` and
`unscoped`), so hoisting an operation out of a scope cannot flatter it, in %.

The work is the program's own counter `live_positions` (the cache positions
the step must attend to), so it is the same whatever implements the
attention: K and V of every live position over all layers are read once
(`model_cost.kv_bytes_per_position`), and each position costs a query-key and
a probability-value product over all heads, 4 d operations a layer. A run that
pairs with no span (its dispatch lies before the trace) leaves both sums; the
note counts them."""
import model_cost
import program_trace


def attention_cost(dims, live_positions: int, itemsize: int) -> dict:
    """Operations and bytes one decode step's attention requires."""
    return {"flops": 4.0 * dims.d * dims.layers * live_positions,
            "bytes": float(live_positions * model_cost.kv_bytes_per_position(
                dims, itemsize))}


def read(ctx, program):
    pt = program_trace.of(ctx)
    if pt is None or not pt.has_paths():
        return None
    pairs, unpaired = program_trace.paired_decode_runs(ctx, pt, program)
    itemsize = program_trace.ITEMSIZE[ctx.cell.config["cache_dtype"]]
    least = 0.0
    paged = unscoped = live = gathered = 0
    for span, _, _, ops in pairs:
        n = span.counters["live_positions"]
        c = attention_cost(ctx.dims, n, itemsize)
        least += max(c["flops"] / ctx.peaks["flops_per_s"],
                     c["bytes"] / ctx.peaks["hbm_bytes_per_s"])
        p, u = program_trace.cache_ns(ops)
        paged += p
        unscoped += u
        live += n
        gathered += span.counters["gathered_positions"]
    runs = len(ctx.trace.program_intervals(program))
    program_trace.note_once((pt.path, "paged"), (
        f"note paged attention: decode runs={runs} paired={len(pairs)} "
        f"unpaired={unpaired} cut_by_the_edge="
        f"{runs - len(pairs) - unpaired} live_positions={live} "
        f"gathered_positions={gathered} least_s={least:.6f} "
        f"paged_s={paged / 1e9:.6f} unscoped_s={unscoped / 1e9:.6f}"))
    if paged <= 0:
        return None                 # a program without the paged.* scopes
    return 100.0 * least / ((paged + unscoped) / 1e9)
