"""Device time of the runs of one compiled program over the device's busy
time, in %. `program` is a part of the program's name in `XLA Modules`."""


def read(ctx, program):
    if ctx.trace is None:
        return None
    runs = ctx.trace.program_runs(program)
    busy = ctx.trace.busy_s()
    if not runs or busy <= 0:
        return None
    return 100.0 * sum(runs) / busy
