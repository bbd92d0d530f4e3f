"""1 - (union of the device-op intervals, averaged over chips) over the
trace's own span, from its first to its last recorded event: numerator and
denominator are of one clock."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    span = ctx.trace.span_s()
    if span <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / span)
