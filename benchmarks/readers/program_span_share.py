"""Share of the trace's span that the host spent inside the named spans of
the program (`train.data`, `train.dispatch`, ...: its own
`TraceAnnotation`s, read from the trace), in %: the union of their intervals,
cut to the span `xplane.Trace.span` gives, over that span."""
import program_trace
import xplane


def read(ctx, spans):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    t0, t1 = ctx.trace.span()
    mine = [(max(s.start, t0), min(s.end, t1)) for s in pt.spans
            if s.name in spans]
    mine = xplane._union([(a, b) for a, b in mine if b > a])
    if not mine or t1 <= t0:
        return None
    return 100.0 * sum(b - a for a, b in mine) / (t1 - t0)
