"""A serving cell of a model whose layers mix by a gated short convolution
or by grouped-query attention, a mixer a layer, with routed experts
(`model_type` `lfm2_moe`): the accepted loop of `serve_cell.py`, handed
another model, as `serve_state_space_cell.py` hands it one.

`serve_cell.run` looks the model up under three names of its module; this
runner puts there `ref` (the sizes and the program's weights,
`references/conv_experts.py`), `model_config` (the engine's description,
`ModelDescription.from_published` of the configuration file) and
`served_gaps` (the latent cell's walk over the checked requests, the float32
pass a layer's weights at a time, reading each request's mean gap over its
served positions where that cell reads the widest). The
engine builds its store of the convolutions' tails itself from the
description and `num_slots`, so nothing else differs from a `serve` cell.

The faults of the rehearsal tests are planted in the program before the call:
`tail_dropped` (a prefill chunk after the first starts from a zero tail),
`bias_ignored` (the experts are the top 4 of the scores alone),
`bias_weighted` (the selection bias weighs the chosen experts too),
`qk_norm_skipped` (queries and keys are not normed a head) and
`group_misread` (query head `i` reads key/value head `i % H` in place of
`i // (Hq / H)`).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import harness
import serve_cell
import serve_latent_experts_cell as described
from references import conv_experts as ref

model_config = described.model_config


def served_gaps(seed32: int, dims: ref.Dims, samples, pad_to: int,
                control: bool = False):
    """For each (prompt, served tokens): the MEAN, over the served
    positions, of the gap by which the chosen token's logit lies below the
    reference's best; the float32 pass a layer's weights at a time.
    `control=True`: the chosen tokens are what the fp8 pass puts first at
    the same positions.

    The mean, where the other cells read the widest gap: nearly every
    served position lies within the reach, through the convolutions' taps,
    of a near-tie among the scores that choose 4 of 64 experts, which bf16
    rounding tips another way than float32 does; the token then meets
    another expert, and the widest gap is such a choice, which fp8 makes
    about as wide. How often and by how much the served tokens fall below
    the reference's best is what the precision moves (PERF.md section 2)."""
    import jax.numpy as jnp
    import numpy as np

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
        out.append(float(gaps[len(prompt) - 1:n - 1].mean()))
    return out


@contextlib.contextmanager
def _planted(fault: Optional[str]):
    """The program with one fault in it, for the tests."""
    if fault is None or fault == "token_altered":
        yield
        return
    import jax
    import jax.numpy as jnp

    from ddl25spring_tpu.models import experts, short_conv
    from ddl25spring_tpu.serving import engine as eng

    if fault == "tail_dropped":
        plain = short_conv.mixer

        def mixer(block, u, tail, n_valid):
            return plain(block, u, tail * 0 if u.shape[1] > 1 else tail,
                         n_valid)

        with described._names(short_conv, mixer=mixer):
            yield
    elif fault in ("bias_ignored", "bias_weighted"):
        plain = experts.route

        def route(w_r, x, spec, bias=None):
            if fault == "bias_ignored" or bias is None:
                return plain(w_r, x, spec)
            idx, _ = plain(w_r, x, spec, bias)
            scores = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), w_r.astype(jnp.float32))) + bias
            top = jnp.take_along_axis(scores, idx, axis=-1)
            top = top / (jnp.sum(top, axis=-1, keepdims=True) + spec.norm_eps)
            return idx, top * spec.scale

        with described._names(experts, route=route):
            yield
    elif fault == "qk_norm_skipped":
        import types

        plain = eng.nn.rmsnorm

        def rmsnorm(params, x, *, eps):
            # the per-head norms are the engine's only ones over [S, T, H, Dh]
            return x if x.ndim == 4 else plain(params, x, eps=eps)

        with described._names(eng, nn=types.SimpleNamespace(rmsnorm=rmsnorm)):
            yield
    elif fault == "group_misread":
        def attend(q, ck, cv, q_positions):
            s, tq, hq, dh = q.shape
            h = ck.shape[2]
            g = hq // h
            rows = q.reshape(s, tq, g, h, dh).transpose(0, 2, 1, 3, 4)
            out = eng._attend_paged(rows.reshape(s, g * tq, h, dh), ck, cv,
                                    jnp.tile(q_positions, (1, g)))
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
