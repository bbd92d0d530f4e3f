"""Device milliseconds a training step in one phase of the step program, told
by the operations' scope paths (`program_trace.step_phases`): `backward`
(under `transpose(...)`, the recomputed forward left out), `remat` (the
recomputed forward, `rematted_computation`), `optimizer` and `head_loss` (the
program's `jax.named_scope`s; the head forward and backward). Whole runs of
the step program only, over the steps those runs stand for."""
import program_trace


def read(ctx, phase, program=""):
    phases = program_trace.step_phases(ctx, program)
    if phases is None:
        return None
    if phase in ("optimizer", "head_loss") and phases[phase] <= 0:
        return None                 # a program without that scope
    return phases[phase]
