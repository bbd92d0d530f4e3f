"""A layer of routed experts that is told which experts it holds.

One chip of an expert-parallel deployment: the router scores ALL of the
layer's experts (``ExpertLayer.n_experts``, its published width), each token
takes its ``top_k`` of them, and this chip computes the part of the result
that the experts it holds give, the contiguous range ``[held_start,
held_start + held_count)``, for exactly the token-expert pairs routed to
them. What the experts held elsewhere would add is left out, and the shared
expert, which every chip computes alike, is added once. That partial result
is what goes on. Nothing here stands in for the absent chips or their
exchange (``parallel/ep.py`` has the all-to-all of the trainer's small MoE;
``models/moe.py`` its capacity-dropping route).

No token is dropped whatever the imbalance: the pairs are sorted by expert
into a buffer of ``tokens x top_k`` rows, the worst case, with the pairs of
experts held elsewhere behind the last group, and the two products are
``lax.ragged_dot`` over the groups, so every shape is static.

Weights of one layer (``init_layer``): ``w_r`` [D, n_experts], ``we_gu``
[held, D, 2 F] (gate | up), ``we_down`` [held, F, D], ``ws_gu`` [D, 2 F s],
``ws_down`` [F s, D] for ``s`` shared experts of width ``F``.

The scopes (docs/COMPONENTS.md): ``moe.router``, ``moe.grouped``,
``moe.shared``, ``moe.combine``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..config import ExpertLayer

# What ``expert_layer`` counts, in this order (int32 [3]).
STATS = ("pairs_held", "experts_hit", "max_pairs")


def route(w_r: jnp.ndarray, x: jnp.ndarray, spec: ExpertLayer,
          bias: jnp.ndarray = None):
    """x [N, D] -> (expert ids [N, k] int32, weights [N, k] float32).
    Sigmoid scores over all experts in float32, top-k over all of them (no
    groups), the chosen scores normalised to sum 1 (``norm_topk``, plus
    ``spec.norm_eps`` in the divisor) and scaled. ``bias`` [n_experts] (a
    selection bias) is added to the scores that choose and to none that
    weigh; without it the top-k is plain (``topk_method`` "none")."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_r.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    if bias is None:
        top, idx = lax.top_k(scores, spec.top_k)
    else:
        _, idx = lax.top_k(scores + bias.astype(jnp.float32), spec.top_k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    if spec.norm_topk:
        total = jnp.sum(top, axis=-1, keepdims=True)
        top = top / (total + spec.norm_eps if spec.norm_eps else total)
    return idx.astype(jnp.int32), top * spec.scale


def swiglu(x: jnp.ndarray, w_gu: jnp.ndarray, w_down: jnp.ndarray):
    gu = x @ w_gu.astype(x.dtype)
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ w_down.astype(x.dtype)


def expert_layer(block: dict, x: jnp.ndarray, spec: ExpertLayer,
                 valid: jnp.ndarray = None, group_offset=None):
    """x [N, D] -> (y [N, D], stats int32 [3]): the held experts' part of
    ``sum_e w_e SwiGLU_e(x)`` plus the shared experts (none: nothing is
    computed for them). ``block["expert_bias"]``, where the layer's weights
    have one, is ``route``'s selection bias. ``valid`` [N] marks
    the rows that are tokens (padding and idle slots route nowhere and are
    not counted). ``stats`` is ``STATS``: the pairs computed here, the held
    experts with at least one, the most any one took.

    ``group_offset`` (a traced scalar): ``we_gu``/``we_down`` then hold the
    held experts of SEVERAL layers, [layers x held, ...], and this layer's
    stand at ``group_offset``. The grouped product takes them all as its
    groups, every other layer's of size 0: a layer scan that sliced this
    layer's experts out of the stack would copy them, 1 GB a layer at
    A.X-K1's widths, because the product is a call the slice cannot fuse
    into (PERF.md, PR 29)."""
    n, d = x.shape
    k, held = spec.top_k, spec.held_count
    with jax.named_scope("moe.router"):
        idx, w = route(block["w_r"], x, spec, block.get("expert_bias"))
        local = idx - spec.held_start
        here = (local >= 0) & (local < held)
        if valid is not None:
            here = here & valid[:, None]
        # pairs of experts held elsewhere sort behind the last group
        gid = jnp.where(here, local, held).reshape(n * k)
        order = jnp.argsort(gid, stable=True)
        sizes = jnp.sum(gid[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
        groups = sizes if group_offset is None else lax.dynamic_update_slice(
            jnp.zeros(block["we_gu"].shape[0], jnp.int32), sizes,
            (group_offset,))
    with jax.named_scope("moe.grouped"):
        xs = x[order // k]                                  # [N k, D]
        gu = lax.ragged_dot(xs, block["we_gu"].astype(x.dtype), groups)
        f = gu.shape[-1] // 2
        act = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        out = lax.ragged_dot(act, block["we_down"].astype(x.dtype), groups)
    shared = None
    if spec.n_shared:
        with jax.named_scope("moe.shared"):
            shared = swiglu(x, block["ws_gu"], block["ws_down"])
    with jax.named_scope("moe.combine"):
        # back to (token, choice) order; rows behind the last group hold
        # nothing that was computed, and weigh nothing
        out = out[jnp.argsort(order)].reshape(n, k, d)
        wk = jnp.where(here, w, 0.0)
        routed = jnp.sum(jnp.where(here[..., None],
                                   out.astype(jnp.float32), 0.0)
                         * wk[..., None], axis=1)
        y = routed.astype(x.dtype)
        if shared is not None:
            y = y + shared
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0, dtype=jnp.int32),
                       jnp.max(sizes)])
    return y, stats


def init_layer(key, d: int, spec: ExpertLayer, std: float, out_std: float,
               dtype) -> dict:
    """One layer's expert weights: normal(0, std), the down projections
    normal(0, out_std). Key order: (w_r, we_gu, we_down, ws_gu, ws_down)."""
    dt = jnp.dtype(dtype)
    ks = jax.random.split(key, 5)
    f, fs = spec.width, spec.width * spec.n_shared

    def normal(key, shape, s):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s, dt)

    return {"w_r": normal(ks[0], (d, spec.n_experts), std),
            "we_gu": normal(ks[1], (spec.held_count, d, 2 * f), std),
            "we_down": normal(ks[2], (spec.held_count, f, d), out_std),
            "ws_gu": normal(ks[3], (d, 2 * fs), std),
            "ws_down": normal(ks[4], (fs, d), out_std)}
