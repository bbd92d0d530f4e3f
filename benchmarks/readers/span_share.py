"""Share of the window that the host spent inside the named spans, in %.
The spans are the benchmark's own stamps round its calls into the program,
on the clock that times the window."""


def read(ctx, spans):
    t0, t1 = ctx.window
    total = 0.0
    seen = False
    for name in spans:
        for s, e in ctx.spans.get(name, []):
            seen = True
            total += max(0.0, min(e, t1) - max(s, t0))
    if not seen or t1 <= t0:
        return None
    return 100.0 * total / (t1 - t0)
