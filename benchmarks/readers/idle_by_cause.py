"""Share of the trace's span, in %, in which chip 0 runs nothing and the
innermost span of the program over that instant is of one cause
(`program_trace.IDLE_CAUSES`): `stage` (`engine.*.stage`, `engine.*.dispatch`:
the host builds and sends the step), `sync` (`engine.*.fetch`: the host waits
for the device, which has nothing left to run), `book`
(`engine.decode.book`, `serve.emit`, `serve.admit`: per-token Python). A gap
is split by instant among the spans that cover it. With what is left
(`idle_other`, in the note) they sum to `device_idle_share.serve`."""
import program_trace


def read(ctx, cause):
    split = program_trace.idle_split(ctx)
    return None if split is None else split[cause]
