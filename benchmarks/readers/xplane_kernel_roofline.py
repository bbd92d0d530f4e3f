"""A kernel's share of its roofline: the least time the chip could take for
the traced calls (the larger of required operations over peak FLOP/s and
required bytes over peak bytes/s, from `model_cost`) over the summed device
time of the kernel's events in the trace. `pattern` is a regular expression
on the operation's name.

The work is counted by runs of the compiled program, not by kernel events:
a run counts where it holds as many of the kernel's events as the fullest
run does (one cut by the trace's edge holds fewer), and only the events
inside those runs are timed. Each run stands for the optimizer steps the host counted in
the window over the program's runs, so kernels that are fused or split
change the time and never the work."""
import model_cost


def read(ctx, pattern, cost):
    if ctx.trace is None:
        return None
    per_run = [x for x in ctx.trace.ops_inside(
        pattern, ctx.trace.program_intervals()) if x[1] > 0]
    if not per_run:
        return None
    whole = max(n for _, n in per_run)
    runs = [sec for sec, n in per_run if n == whole]
    seconds = sum(runs)
    if seconds <= 0:
        return None
    if cost == "flash_train":
        tr = ctx.cell.traffic
        one = model_cost.flash_train_cost(ctx.dims, tr["batch_per_chip"],
                                          tr["seq_len"])
        steps_a_run = max(1, round(ctx.counters.get("steps", 0)
                                   / len(per_run)))
    else:
        raise ValueError(cost)
    least = max(one["flops"] / ctx.peaks["flops_per_s"],
                one["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * steps_a_run * len(runs) / seconds
