"""Latent attention (MLA) and the decoder built on it, as the engine serves it.

``c_q = RMSNorm(x W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` per head;
``[c_kv | k_rope] = x W_kva``, ``c_kv = RMSNorm(c_kv)``; RoPE (YaRN) on
``q_rope`` and on ``k_rope``, ONE row that all heads share; ``[k_nope | v] =
c_kv W_kvb`` per head; ``score = (q_nope k_nope + q_rope k_rope) scale``,
causal softmax in float32, ``out = concat_h(P v) W_o``.

What a position leaves behind is ``latent_row``: ``[c_kv | k_rope]`` after
the norm and the rotation, ``LatentAttention.row_dim`` values, nothing per
head. Attention over such rows has two arithmetic forms with one result:

- ``attend_folded``: ``W_kvb``'s key half is folded into the query and its
  value half applied after the weighted sum, so the heads attend over the
  shared rows as they lie in the cache and no per-head K or V ever exists;
  ``2 H (row_dim + kv_rank)`` operations a query-key pair;
- ``attend_expanded``: the rows are expanded to per-head keys and values
  first (``2 kv_rank H (nope_dim + v_dim)`` operations a row, once), then
  ``2 H (qk_dim + v_dim)`` a pair.

``expand_pays(t, att, heads)`` says which needs fewer operations for ``t``
queries a row; a program picks by the query length it is compiled for (a
decode step folds, a prefill chunk expands), not by an option. After the
expansion, ``attend_expanded``'s scores, softmax and weighted sum are XLA
operations here; on a TPU the engine hands it one kernel in their place
(``ops/chunk_attention.py``; ``serving/engine.py::chunk_attention_path``).

Weights (``init_params``; the layers stacked by run of one kind, in
``params["runs"]``): ``attn_norm``, ``w_qa`` [D, q_rank], ``q_norm``,
``w_qb`` [q_rank, H (nope + rope)], ``w_kva`` [D, kv_rank + rope],
``kv_norm``, ``w_kvb`` [kv_rank, H (nope + v)], ``w_o`` [H v, D],
``mlp_norm``, then ``w_gu`` [D, 2 F] and ``w_down`` [F, D] in a dense layer
or ``models/experts.py``'s weights in an expert layer. The engine holds them
as they are; nothing is fused beside them.

RoPE pairs are the repo's ``(x[:half], x[half:])``.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import nn
from ..config import LatentAttention, ModelDescription
from . import experts

# ---------------------------------------------------------------------- YaRN


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(att: LatentAttention) -> float:
    """``qk_dim^-1/2 m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``."""
    return att.qk_dim ** -0.5 * _yarn_mscale(att.rope_factor,
                                             att.mscale_all_dim) ** 2


def rope_scale(att: LatentAttention) -> float:
    """The factor on the rotation's cos and sin: mscale / mscale_all_dim."""
    return (_yarn_mscale(att.rope_factor, att.mscale)
            / _yarn_mscale(att.rope_factor, att.mscale_all_dim))


def yarn_inv_freq(att: LatentAttention, theta: float) -> np.ndarray:
    """The rotation's frequencies [rope_dim / 2]: those that turn more than
    ``beta_fast`` times over the original context keep ``theta^(-2i/d)``,
    those that turn fewer than ``beta_slow`` times are divided by
    ``factor``, with a linear ramp between."""
    dim = att.rope_dim
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if att.rope_factor <= 1:
        return extra.astype(np.float32)

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(att.rope_original_ctx
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(att.beta_fast)), 0)
    high = min(math.ceil(correction_dim(att.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / att.rope_factor * ramp + extra * (1.0 - ramp)
            ).astype(np.float32)


def rope_tables(positions: jnp.ndarray, att: LatentAttention, theta: float
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos, sin [..., rope_dim / 2] at absolute ``positions`` [...]."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        yarn_inv_freq(att, theta))
    s = rope_scale(att)
    return jnp.cos(ang) * s, jnp.sin(ang) * s


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x [..., rope_dim] with cos/sin broadcastable to [..., rope_dim / 2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


# ----------------------------------------------------------------- attention

def queries(block: dict, xn: jnp.ndarray, cos, sin, desc: ModelDescription):
    """xn [S, T, D] (normed) -> q [S, T, H, nope + rope], the rope part
    rotated by cos/sin [S, T, rope / 2]."""
    att = desc.attention
    s, t, _ = xn.shape
    cq = nn.rmsnorm(block["q_norm"], xn @ block["w_qa"].astype(xn.dtype),
                    eps=desc.norm_eps)
    q = (cq @ block["w_qb"].astype(xn.dtype)).reshape(
        s, t, desc.num_heads, att.qk_dim)
    q_rope = rotate(q[..., att.nope_dim:], cos[:, :, None, :],
                    sin[:, :, None, :])
    return jnp.concatenate([q[..., :att.nope_dim], q_rope], axis=-1)


def latent_row(block: dict, xn: jnp.ndarray, cos, sin,
               desc: ModelDescription) -> jnp.ndarray:
    """xn [S, T, D] (normed) -> the cache's row [S, T, kv_rank + rope]:
    ``c_kv`` after its norm beside ``k_rope`` after the rotation."""
    att = desc.attention
    ckv = xn @ block["w_kva"].astype(xn.dtype)
    c = nn.rmsnorm(block["kv_norm"], ckv[..., :att.kv_rank],
                   eps=desc.norm_eps)
    return jnp.concatenate([c, rotate(ckv[..., att.kv_rank:], cos, sin)],
                           axis=-1)


def expand_pays(t: int, att: LatentAttention, heads: int) -> bool:
    """Whether expanding the rows to per-head K and V first needs fewer
    operations than folding, for ``t`` queries a row."""
    folded = 2 * heads * (att.row_dim + att.kv_rank)
    expanded = 2 * heads * (att.qk_dim + att.v_dim)
    expansion = 2 * att.kv_rank * heads * (att.nope_dim + att.v_dim)
    return t * (folded - expanded) > expansion


def _masked_softmax(scores: jnp.ndarray, q_positions: jnp.ndarray, dtype):
    """scores [S, H, T, K] float32, causal by absolute position. The row
    maximum stands behind an ``optimization_barrier``: without it the TPU
    compiler fuses the maximum with its subtraction as a ``reduce-window``
    as wide as the row, which at 8192 keys took 20 ms a block of 128 queries
    (PERF.md, PR 29); with it the maximum is one pass over the scores."""
    mask = (q_positions[:, None, :, None]
            >= jnp.arange(scores.shape[-1])[None, None, None, :])
    scores = jnp.where(mask, scores, -jnp.inf)
    top = lax.optimization_barrier(jnp.max(scores, axis=-1, keepdims=True))
    e = jnp.exp(scores - top)
    return (e / jnp.sum(e, axis=-1, keepdims=True)).astype(dtype)


def attend_folded(w_kvb: jnp.ndarray, q: jnp.ndarray, rows: jnp.ndarray,
                  q_positions: jnp.ndarray, desc: ModelDescription):
    """q [S, T, H, nope + rope] over rows [S, K, >= row_dim] -> [S, T, H, v],
    in latent space: no per-head key or value is formed. Lanes of ``rows``
    past ``row_dim`` are the pool's zero padding and meet zeros of the
    query, so the rows are read as they lie."""
    att = desc.attention
    w = w_kvb.astype(q.dtype).reshape(att.kv_rank, desc.num_heads,
                                      att.nope_dim + att.v_dim)
    q_lat = jnp.einsum("sthd,chd->sthc", q[..., :att.nope_dim],
                       w[..., :att.nope_dim])
    qq = jnp.concatenate([q_lat, q[..., att.nope_dim:]], axis=-1)
    qq = jnp.pad(qq, ((0, 0),) * 3 + ((0, rows.shape[-1] - att.row_dim),))
    rows = rows.astype(q.dtype)
    scores = jnp.einsum("sthc,skc->shtk", qq, rows,
                        preferred_element_type=jnp.float32
                        ) * softmax_scale(att)
    probs = _masked_softmax(scores, q_positions, q.dtype)
    o_lat = jnp.einsum("shtk,skc->sthc", probs, rows[..., :att.kv_rank])
    return jnp.einsum("sthc,chd->sthd", o_lat, w[..., att.nope_dim:])


def attend_expanded(w_kvb: jnp.ndarray, q: jnp.ndarray, rows: jnp.ndarray,
                    q_positions: jnp.ndarray, desc: ModelDescription,
                    q_block: int = 128, fused=None):
    """The same result with the rows expanded to per-head keys and values
    first: fewer operations where a row meets many queries. The queries go
    ``q_block`` at a time, so the float32 scores of all heads exist for
    one block only. ``fused(q, kv, k_rope, q_positions)``, where the caller
    has one (``ops/chunk_attention.py``), takes the place of everything
    after the expansion: it is handed the expansion's product ``kv`` [S, K,
    H (nope + v)] and the rows' rotated part [S, K, rope], the very numbers
    the keys and values below are made of."""
    att = desc.attention
    s, k_len, _ = rows.shape
    t, h = q.shape[1], desc.num_heads
    rows = rows.astype(q.dtype)
    kv = rows[..., :att.kv_rank] @ w_kvb.astype(q.dtype)
    if fused is not None:
        return fused(q, kv, rows[..., att.kv_rank:att.row_dim], q_positions)
    kv = kv.reshape(s, k_len, h, att.nope_dim + att.v_dim)
    k_rope = jnp.broadcast_to(rows[:, :, None, att.kv_rank:att.row_dim],
                              (s, k_len, h, att.rope_dim))
    keys = jnp.concatenate([kv[..., :att.nope_dim], k_rope], axis=-1)
    values = kv[..., att.nope_dim:]

    def block(q_pos):
        qb, pos = q_pos
        scores = jnp.einsum("sthd,skhd->shtk", qb, keys,
                            preferred_element_type=jnp.float32
                            ) * softmax_scale(att)
        probs = _masked_softmax(scores, pos, q.dtype)
        return jnp.einsum("shtk,skhd->sthd", probs, values)

    if t <= q_block or t % q_block:
        return block((q, q_positions))
    n = t // q_block
    out = lax.map(block, (
        q.reshape(s, n, q_block, h, att.qk_dim).swapaxes(0, 1),
        q_positions.reshape(s, n, q_block).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(s, t, h, att.v_dim)


def attend(w_kvb, q, rows, q_positions, desc: ModelDescription, fused=None):
    """Folded or expanded by the query length ``q`` was traced with;
    ``fused``: ``attend_expanded``'s."""
    if expand_pays(q.shape[1], desc.attention, desc.num_heads):
        return attend_expanded(w_kvb, q, rows, q_positions, desc, fused=fused)
    return attend_folded(w_kvb, q, rows, q_positions, desc)


# ------------------------------------------------------------------ weights

def init_layer(key, desc: ModelDescription, kind: str, dtype) -> dict:
    """One layer's weights: normal(0, s) with s = ``desc.init_std``, the
    projections back to the residual (``w_o``, ``w_down``, the experts'
    down) normal(0, s / sqrt(2 L)), norm scales 1. Key order: (w_qa, w_qb, w_kva, w_kvb,
    w_o, the layer's second half), then a dense layer's (w_gu, w_down) or
    ``experts.init_layer``'s."""
    att, d, h = desc.attention, desc.dmodel, desc.num_heads
    dt = jnp.dtype(dtype)
    std, out_std = desc.init_std, desc.init_std / math.sqrt(2 * desc.n_layers)
    ks = jax.random.split(key, 6)

    def normal(key, shape, s):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s, dt)

    def ones(n):
        return {"scale": jnp.ones((n,), dt)}

    block = {"attn_norm": ones(d),
             "w_qa": normal(ks[0], (d, att.q_rank), std),
             "q_norm": ones(att.q_rank),
             "w_qb": normal(ks[1], (att.q_rank, h * att.qk_dim), std),
             "w_kva": normal(ks[2], (d, att.row_dim), std),
             "kv_norm": ones(att.kv_rank),
             "w_kvb": normal(ks[3], (att.kv_rank,
                                     h * (att.nope_dim + att.v_dim)), std),
             "w_o": normal(ks[4], (h * att.v_dim, d), out_std),
             "mlp_norm": ones(d)}
    if kind == "dense":
        k_gu, k_down = jax.random.split(ks[5])
        block["w_gu"] = normal(k_gu, (d, 2 * desc.ffn_hidden), std)
        block["w_down"] = normal(k_down, (desc.ffn_hidden, d), out_std)
    else:
        block.update(experts.init_layer(ks[5], d, desc.experts, std, out_std,
                                        dt))
    return block


def init_params(key, desc: ModelDescription, dtype=None) -> dict:
    """Weights from a key. Key order: (embed, layers, head), then one key a
    layer. ``params["runs"]`` holds one stacked tree a run of layers of one
    kind (``ModelDescription.runs``)."""
    dt = jnp.dtype(dtype or desc.param_dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    keys = jax.random.split(k_layers, desc.n_layers)
    runs = tuple(
        jax.vmap(lambda k, kind=kind: init_layer(k, desc, kind, dt))(
            keys[start:start + count])
        for kind, start, count in desc.runs())
    std = jnp.asarray(desc.init_std, dt)
    return {"embed": jax.random.normal(
                k_embed, (desc.vocab_size, desc.dmodel), dt) * std,
            "runs": runs,
            "final_norm": {"scale": jnp.ones((desc.dmodel,), dt)},
            "lm_head": jax.random.normal(
                k_head, (desc.dmodel, desc.vocab_size), dt) * std}


# ------------------------------------------------------------- second halves

def second_half(block: dict, kind: str, x: jnp.ndarray,
                desc: ModelDescription, valid=None, group_offset=None):
    """The layer after its attention: x [S, T, D] -> (x + f(norm(x)),
    routing stats or None). ``group_offset``: ``experts.expert_layer``'s."""
    xn = nn.rmsnorm(block["mlp_norm"], x, eps=desc.norm_eps)
    if kind == "dense":
        with jax.named_scope("mlp"):
            return x + experts.swiglu(xn, block["w_gu"], block["w_down"]), None
    s, t, d = x.shape
    y, stats = experts.expert_layer(
        block, xn.reshape(s * t, d), desc.experts,
        None if valid is None else valid.reshape(s * t), group_offset)
    return x + y.reshape(s, t, d), stats
