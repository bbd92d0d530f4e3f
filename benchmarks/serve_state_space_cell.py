"""A serving cell of a model whose blocks run a state-space mixer beside
grouped-query attention (`model_type` `falcon_h1`): the accepted loop of
`serve_cell.py`, handed another model, as `serve_latent_experts_cell.py`
hands it one.

`serve_cell.run` looks the model up under three names of its module; this
runner puts there `ref` (the sizes and the program's weights,
`references/state_space.py`), `model_config` (the engine's description,
`ModelDescription.from_published` of the configuration file: the same
function as the latent cell's) and `served_gaps` (the latent cell's walk over
the checked requests, with this reference under its `ref`: the float32 pass a
layer's weights at a time). The engine builds its state store itself from the
description and `num_slots`, so nothing else differs from a `serve` cell.

The faults of the rehearsal tests are planted in the program before the call:
`state_not_carried` (a prefill chunk after the first starts from a zero
state), `tail_dropped` (it starts from zeros in place of the convolution's
carried inputs) and `group_misread` (query head `i` reads key/value head
`i % 4` in place of `i // 5`).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import harness
import serve_cell
import serve_latent_experts_cell as described
from references import state_space as ref

model_config = described.model_config


def served_gaps(*args, **kw):
    with described._names(described, ref=ref):
        return described.served_gaps(*args, **kw)


@contextlib.contextmanager
def _planted(fault: Optional[str]):
    """The program with one fault in it, for the tests."""
    if fault is None or fault == "token_altered":
        yield
        return
    from ddl25spring_tpu.models import state_space
    from ddl25spring_tpu.serving import engine as eng

    if fault in ("state_not_carried", "tail_dropped"):
        plain = state_space.mixer

        def mixer(block, u, state, tail, n_valid, desc):
            if u.shape[1] > 1 and fault == "state_not_carried":
                state = state * 0
            if u.shape[1] > 1 and fault == "tail_dropped":
                tail = tail * 0
            return plain(block, u, state, tail, n_valid, desc)

        with described._names(state_space, mixer=mixer):
            yield
    elif fault == "group_misread":
        def attend(q, ck, cv, q_positions):
            s, tq, hq, dh = q.shape
            h = ck.shape[2]
            g = hq // h
            rows = q.reshape(s, tq, g, h, dh).transpose(0, 2, 1, 3, 4)
            out = eng._attend_paged(rows.reshape(s, g * tq, h, dh), ck, cv,
                                    eng.jnp.tile(q_positions, (1, g)))
            return out.reshape(s, g, tq, h, dh).transpose(
                0, 2, 1, 3, 4).reshape(s, tq, hq, dh)

        with described._names(eng, _attend_grouped=attend):
            yield
    else:
        raise harness.BenchError(f"unknown fault {fault!r}")


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, *, fault: Optional[str] = None, **kw):
    with described._names(serve_cell, ref=ref, model_config=model_config,
                          served_gaps=served_gaps), _planted(fault):
        return serve_cell.run(
            cell, seed, seconds, trace, t_process,
            fault=fault if fault == "token_altered" else None, **kw)
