"""The decode step as a kernel: for each traced decode step, the least time
the chip could take for that step's work whatever implements it (weights
once, the live cache positions of the active slots, `model_cost`), summed,
over the summed device time of those steps' runs of the decode program.

A run in the trace is paired with the benchmark's count of live positions
through the numbered annotation (`bench.tick#<n>`) that covers the run's
start: the tick that dispatched it. A run with no such row is left out of
both sums."""
import model_cost


def read(ctx, program):
    if ctx.trace is None or not ctx.steps:
        return None
    by_tick = {s["tick"]: s for s in ctx.steps}
    least = took = 0.0
    for r0, r1 in ctx.trace.program_intervals(program):
        mark = ctx.trace.mark_at("bench.tick#", r0)
        step = by_tick.get(int(mark.partition("#")[2])) if mark else None
        if step is None:
            continue
        c = model_cost.decode_step_cost(ctx.dims, step["live_positions"],
                                        step["active"])
        least += max(c["flops"] / ctx.peaks["flops_per_s"],
                     c["bytes"] / ctx.peaks["hbm_bytes_per_s"])
        took += (r1 - r0) / 1e9
    if took <= 0:
        return None
    return 100.0 * least / took
