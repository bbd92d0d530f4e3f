"""Median, over the whole runs of the decode program in the trace, of the
device time of one part of the step, in ms (`program_trace.decode_parts`):
`paged`, the operations under the paged-attention scopes (`paged.write`,
`paged.gather`, `paged.attend`), or `unscoped`, the operations inside the run
under none of the program's scopes of work (`embed`, `qkv`, `paged.*`,
`attn_out`, `mlp`, `head`, `sample`): the scan's slicing and writing back of
the stacked pool, whole-pool copies, whatever the compiler hoists out of a
scope. With the rest of the run (`dense`, in the note) they are the step."""
import program_trace


def read(ctx, program, part):
    parts = program_trace.decode_parts(ctx, program)
    return None if parts is None else parts[part]
