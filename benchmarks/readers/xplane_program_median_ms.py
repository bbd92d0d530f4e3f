"""Median device time of one run of a compiled program, in ms."""
import statistics


def read(ctx, program):
    if ctx.trace is None:
        return None
    runs = ctx.trace.program_runs(program)
    if not runs:
        return None
    return 1e3 * statistics.median(runs)
