"""A serving cell of a model the engine reads from its published keys: the
accepted loop of `serve_cell.py`, handed another model.

`serve_cell.run` is the window, the stamps, the checks and the result; it
looks up the model under three names of its module, and this runner puts its
own there for the length of the call: `ref` (the sizes and the program's
weights, `references/latent_experts.py`), `model_config` (the engine's
description of the model, `ModelDescription.from_published` of the
configuration file) and `served_gaps` (the float32 reference over the checked
requests, a layer's weights at a time: whole, in float32, they would not fit
the chip). Nothing else differs from a `serve` cell.

The faults of the rehearsal tests are planted in the program before the
call: `expert_left_out` (the first held expert's output is dropped from the
sum) and `row_before_rotation` (the cache's row is written with `k_rope`
un-rotated).
"""

from __future__ import annotations

import contextlib
from typing import List, Optional

import numpy as np

import harness
import serve_cell
from references import latent_experts as ref


def model_config(cell: harness.Cell, dims: ref.Dims):
    from ddl25spring_tpu.config import ModelDescription

    tr = cell.traffic
    return ModelDescription.from_published(
        cell.config, ctx_size=tr["block_len"] * tr["max_blocks_per_seq"],
        dtype=cell.config["compute_dtype"],
        param_dtype=cell.config["weights_dtype"]["serve"])


def served_gaps(seed32: int, dims: ref.Dims, samples: List[tuple],
                pad_to: int, control: bool = False) -> List[float]:
    """`serve_cell.served_gaps` with the model made a layer at a time from
    the seed: for each (prompt, served tokens), the widest gap by which a
    chosen token's logit lies below the reference's best. `control=True`:
    the chosen tokens are what the fp8 pass puts first at the same
    positions."""
    import jax.numpy as jnp

    model = ref.Seeded(seed32, dims, "bfloat16")
    low = ref.Seeded(seed32, dims, "bfloat16", ref.CONTROL) if control else None
    out = []
    for prompt, served in samples:
        toks = np.zeros(pad_to, np.int32)
        n = len(prompt) + len(served)
        toks[:len(prompt)] = prompt
        toks[len(prompt):n] = served
        toks_j = jnp.asarray(toks)
        chosen = ref.first_choice(low, toks_j) if control else toks_j[1:]
        gaps = np.asarray(ref.gap_below_best(model, toks_j, chosen))
        out.append(float(gaps[len(prompt) - 1: n - 1].max()))
    return out


@contextlib.contextmanager
def _names(module, **names):
    old = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


@contextlib.contextmanager
def _planted(fault: Optional[str]):
    """The program with one fault in it, for the tests."""
    if fault is None or fault == "token_altered":
        yield
        return
    from ddl25spring_tpu.models import experts, latent

    if fault == "expert_left_out":
        plain = experts.expert_layer

        def expert_layer(block, x, spec, valid=None, group_offset=None):
            first = 0 if group_offset is None else group_offset
            block = dict(block, we_down=block["we_down"].at[first].set(0))
            return plain(block, x, spec, valid, group_offset)

        with _names(experts, expert_layer=expert_layer):
            yield
    elif fault == "row_before_rotation":
        plain = latent.latent_row

        def latent_row(block, xn, cos, sin, desc):
            return plain(block, xn, cos * 0 + 1, sin * 0, desc)

        with _names(latent, latent_row=latent_row):
            yield
    else:
        raise harness.BenchError(f"unknown fault {fault!r}")


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, *, fault: Optional[str] = None, **kw):
    with _names(serve_cell, ref=ref, model_config=model_config,
                served_gaps=served_gaps), _planted(fault):
        return serve_cell.run(
            cell, seed, seconds, trace, t_process,
            fault=fault if fault == "token_altered" else None, **kw)
