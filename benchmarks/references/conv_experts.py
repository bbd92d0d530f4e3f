"""The plain reference of a decoder whose layers mix by a gated short
convolution or by grouped-query attention, a mixer a layer, with routed
experts after its leading dense layers, in float32 `jax.numpy`: what
`model_type` `lfm2_moe` (LiquidAI/LFM2-24B-A2B) computes.

With `h` a position's hidden vector: `h = E[token]`. Layer `l`: `u =
RMSNorm_op(h)`; `h = h + Mixer_l(u)`; `h = h + FFN_l(RMSNorm_ffn(h))`.

- `conv` (`layer_types[l]`): `[B | C | x] = u W_in`, `v_t = B_t x_t`,
  `c_t = w_0 v_{t-2} + w_1 v_{t-1} + w_2 v_t` (`conv_L_cache` 3 taps a
  channel, no bias, `v` before the first position 0), `y_t = C_t c_t`,
  `y W_out`. No activation.
- `full_attention`: `q = u W_q` (32 heads), `k`, `v` (8 heads), each query
  and key head RMS-normed over its values with a scale of its own, then
  RoPE; query head `i` reads key/value head `i // 4`; causal softmax at
  `head_dim^-1/2`; `W_o`.
- `FFN_l` for `l < num_dense_layers`: `W_2(silu(W_1 v) W_3 v)`. Otherwise:
  `s = sigmoid(v W_r)` over all experts, the chosen `e` the top `k` of
  `s + b` (`b` the selection bias), their weights `s_e` normalised by
  `sum + 1e-6` and scaled by `routed_scaling_factor`, `y = sum_e g_e
  SwiGLU_e(v)`; no shared expert.
- `logits = RMSNorm_f(h) E^T` (the head tied to the embedding).

The convolution here runs position after position from zeros over one whole
sequence, attention is expanded to every query head, and the experts are a
loop over all of them, each over every token with the weight the router gave
it (0 where it was not chosen): no carried tail, no cache, no paging, no
grouping of pairs, one sequence at a time. It imports nothing of the
program. The weights are made here from the seed, in the layout the program
is handed (`make_weights`, one jitted call, for the engine); the reference
itself never holds them whole (`Seeded` makes each layer's from the seed
when it reaches the layer, the same values) and takes the head a block of
the vocabulary at a time. Every product with a weight and the attention's
two go through `reference.Precision` (float32 at `highest`; the control:
operands rounded to fp8); the router's scores are float32 in both.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from reference import (CONTROL, REFERENCE, Precision, _rmsnorm,  # noqa: F401
                       _rope)

HEAD_BLOCKS = 8          # the head is taken this many blocks of rows
# The selection bias is drawn normal(0, BIAS_STD): at the cell's widths it
# changes 28 to 31% of the top-4 choices against a zero bias (the set of four
# of 83 to 86% of the tokens; tests/test_conv_experts.py); a trained bias is
# not zero, and a zero one would hide a program that ignores it.
BIAS_STD = 0.05
NORM_EPS = 1e-6          # added to the sum the chosen weights are divided by


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under this file's own names."""
    vocab: int
    d: int
    heads: int
    kv_heads: int
    layers: int
    ffn: int                # the leading dense layers' SwiGLU width
    eps: float
    theta: float
    taps: int
    mixers: tuple           # "conv" or "attention", a layer
    n_dense: int
    experts: int
    top_k: int
    width: int              # an expert's width
    norm_topk: bool
    scale: float
    init_std: float = 0.02

    @property
    def head_dim(self) -> int:
        return self.d // self.heads

    @property
    def qkv_dim(self) -> int:
        return (self.heads + 2 * self.kv_heads) * self.head_dim

    def kind(self, layer: int) -> str:
        return "dense" if layer < self.n_dense else "experts"

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        if cfg.get("model_type") != "lfm2_moe":
            raise ValueError(f"no such reference for {cfg.get('model_type')}")
        n = cfg["num_hidden_layers"]
        types = tuple(cfg["layer_types"][:n])
        rope = cfg["rope_parameters"]
        if (cfg["conv_bias"] or not cfg["use_expert_bias"]
                or not cfg.get("tie_word_embeddings", True)
                or rope.get("rope_type", "default") != "default"
                or len(types) != n
                or not set(types) <= {"conv", "full_attention"}):
            raise ValueError("the reference has convolutions without a bias, "
                             "a selection bias, plain RoPE and a head tied "
                             "to the embedding")
        return cls(
            vocab=cfg["vocab_size"], d=cfg["hidden_size"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], layers=n,
            ffn=cfg["intermediate_size"], eps=float(cfg["norm_eps"]),
            theta=float(rope["rope_theta"]), taps=cfg["conv_L_cache"],
            mixers=tuple("conv" if t == "conv" else "attention"
                         for t in types),
            n_dense=cfg["num_dense_layers"], experts=cfg["num_experts"],
            top_k=cfg["num_experts_per_tok"],
            width=cfg["moe_intermediate_size"],
            norm_topk=bool(cfg["norm_topk_prob"]),
            scale=float(cfg["routed_scaling_factor"]),
            init_std=float(cfg.get("initializer_range", 0.02)))


def groups(dims: Dims):
    """How the program stacks the layers (`config.ModelDescription.periods`,
    reckoned here again): each run of one kind as whole periods of its
    mixers' pattern, then what is left of one; (pattern, periods, first
    layer) a group."""
    out, start = [], 0
    for end in range(1, dims.layers + 1):
        if end < dims.layers and dims.kind(end) == dims.kind(start):
            continue
        mix, count = dims.mixers[start:end], end - start
        p = next(p for p in range(1, count + 1)
                 if all(mix[i] == mix[i % p] for i in range(count)))
        out.append((mix[:p], count // p, start))
        if count % p:
            out.append((mix[:count % p], 1, end - count % p))
        start = end
    return out


# ------------------------------------------------------------------ weights

def layer_weights(key, dims: Dims, layer: int, dtype) -> dict:
    """One layer from its key: normal(0, s) with s the configuration's
    `initializer_range` (0.02), the projections back to the residual (w_out,
    w_o, w_down, the experts' down) normal(0, s/sqrt(2 L)), norm scales 1,
    the convolution uniform(+-taps^-1/2), the selection bias normal(0,
    `BIAS_STD`) in float32. Key order: (w_in or w_qkv, conv_w, w_out or w_o,
    w_gu or w_r, w_down or we_gu, we_down, expert_bias). q, k and v stand
    side by side in one matrix, as gate and up do (gate first)."""
    dt = jnp.dtype(dtype)
    d = dims.d
    std, out_std = dims.init_std, dims.init_std / math.sqrt(2 * dims.layers)
    ks = jax.random.split(key, 7)

    def normal(key, shape, s):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s, dt)

    def ones(n):
        return {"scale": jnp.ones((n,), dt)}

    w = {"op_norm": ones(d), "mlp_norm": ones(d)}
    if dims.mixers[layer] == "conv":
        bound = dims.taps ** -0.5
        w.update(w_in=normal(ks[0], (d, 3 * d), std),
                 conv_w=jax.random.uniform(ks[1], (dims.taps, d), jnp.float32,
                                           -bound, bound).astype(dt),
                 w_out=normal(ks[2], (d, d), out_std))
    else:
        w.update(w_qkv=normal(ks[0], (d, dims.qkv_dim), std),
                 q_norm=ones(dims.head_dim), k_norm=ones(dims.head_dim),
                 w_o=normal(ks[2], (dims.heads * dims.head_dim, d), out_std))
    if dims.kind(layer) == "dense":
        w.update(w_gu=normal(ks[3], (d, 2 * dims.ffn), std),
                 w_down=normal(ks[4], (dims.ffn, d), out_std))
    else:
        f = dims.width
        w.update(w_r=normal(ks[3], (d, dims.experts), std),
                 we_gu=normal(ks[4], (dims.experts, d, 2 * f), std),
                 we_down=normal(ks[5], (dims.experts, f, d), out_std),
                 expert_bias=jax.random.normal(ks[6], (dims.experts,),
                                               jnp.float32) * BIAS_STD)
    return w


def _keys(key, dims: Dims):
    """(embedding key, one key a layer)."""
    k_embed, k_layers = jax.random.split(key)
    return k_embed, jax.random.split(k_layers, dims.layers)


def init_weights(key, dims: Dims, dtype) -> dict:
    """The whole tree as the program takes it: `embed`, `runs` (a group of
    `groups` a tuple of the pattern's layers, each stacked over the group's
    periods), `final_norm`; no `lm_head`, the head is the embedding's."""
    dt = jnp.dtype(dtype)
    k_embed, keys = _keys(key, dims)
    runs = tuple(
        tuple(jax.vmap(lambda k, layer=first + j: layer_weights(
            k, dims, layer, dt))(keys[first + j:first + periods * len(pattern)
                                      :len(pattern)])
              for j in range(len(pattern)))
        for pattern, periods, first in groups(dims))
    return {"embed": jax.random.normal(k_embed, (dims.vocab, dims.d), dt)
            * jnp.asarray(dims.init_std, dt),
            "runs": runs,
            "final_norm": {"scale": jnp.ones((dims.d,), dt)}}


def make_weights(seed: int, dims: Dims, dtype) -> dict:
    """`init_weights` of `jax.random.key(seed)` as one jitted call."""
    return jax.jit(partial(init_weights, dims=dims, dtype=dtype))(
        jax.random.key(seed))


# ------------------------------------------------------------------ forward

def _conv(w, u, dims: Dims, p: Precision):
    """The gated short convolution over one sequence: u [T, D] (normed)."""
    t, d = u.shape
    bcx = p.mm(u, w["w_in"])
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    v = jnp.pad(b * x, ((dims.taps - 1, 0), (0, 0)))
    taps = w["conv_w"].astype(jnp.float32)
    conv = sum(v[i:i + t] * taps[i] for i in range(dims.taps))
    return p.mm(c * conv, w["w_out"])


def _attention(w, u, dims: Dims, p: Precision):
    """Grouped-query attention over one sequence, every query head with its
    own copy of its key/value head: u [T, D] (normed)."""
    t = u.shape[0]
    hq, h, dh = dims.heads, dims.kv_heads, dims.head_dim
    qkv = p.mm(u, w["w_qkv"])
    q = _rmsnorm(w["q_norm"]["scale"], qkv[:, :hq * dh].reshape(t, hq, dh),
                 dims.eps)
    k = _rmsnorm(w["k_norm"]["scale"],
                 qkv[:, hq * dh:(hq + h) * dh].reshape(t, h, dh), dims.eps)
    v = qkv[:, (hq + h) * dh:].reshape(t, h, dh)
    q, k = _rope(q, dims.theta), _rope(k, dims.theta)
    k, v = (jnp.repeat(m, hq // h, axis=1) for m in (k, v))
    scores = p.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    att = p.einsum("hqk,khd->qhd", probs, v).reshape(t, hq * dh)
    return p.mm(att, w["w_o"])


def _swiglu(gu, down, v, p: Precision):
    a = p.mm(v, gu)
    f = a.shape[-1] // 2
    return p.mm(jax.nn.silu(a[:, :f]) * a[:, f:], down)


def route(w, v, dims: Dims):
    """The weight each token gives each expert, [T, experts] float32: the
    chosen `k` by `sigmoid(v W_r) + b`, weighed by the sigmoid alone."""
    s = jax.nn.sigmoid(jnp.matmul(v, w["w_r"].astype(jnp.float32),
                                  precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(s + w["expert_bias"].astype(jnp.float32),
                          dims.top_k)
    picked = jnp.sum(jax.nn.one_hot(chosen, dims.experts), axis=1) > 0
    g = jnp.where(picked, s, 0.0)
    if dims.norm_topk:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + NORM_EPS)
    return g * dims.scale


def _experts(w, v, dims: Dims, p: Precision):
    """Every expert over every token, weighed by `route` (0 where not
    chosen), one expert at a time."""
    g = route(w, v, dims)

    def one(acc, e):
        gu, down, ge = e
        return acc + ge[:, None] * _swiglu(gu, down, v, p), None

    out, _ = lax.scan(one, jnp.zeros_like(v),
                      (w["we_gu"], w["we_down"], g.T))
    return out


def layer(w, x, dims: Dims, index: int, p: Precision = REFERENCE):
    """Layer `index` over one sequence x [T, D] (float32)."""
    u = _rmsnorm(w["op_norm"]["scale"], x, dims.eps)
    mix = _conv if dims.mixers[index] == "conv" else _attention
    x = x + mix(w, u, dims, p)
    v = _rmsnorm(w["mlp_norm"]["scale"], x, dims.eps)
    if dims.kind(index) == "dense":
        return x + _swiglu(w["w_gu"], w["w_down"], v, p)
    return x + _experts(w, v, dims, p)


def logits_at(final_norm, embed, hid, dims: Dims, p: Precision = REFERENCE):
    """The tied head a block of the vocabulary at a time (`HEAD_BLOCKS`
    where the vocabulary divides): the float32 copy of a block is all that
    exists."""
    hn = _rmsnorm(final_norm["scale"], hid, dims.eps)
    nb = HEAD_BLOCKS if dims.vocab % HEAD_BLOCKS == 0 else 1
    blocks = embed.reshape(nb, dims.vocab // nb, dims.d)
    out = lax.map(lambda blk: p.mm(hn, blk.T), blocks)      # [nb, T, V / nb]
    return out.swapaxes(0, 1).reshape(hid.shape[0], dims.vocab)


class Seeded:
    """The model of a seed, a layer at a time: each layer's weights are made
    from the seed in `dtype` when the pass reaches the layer (the values
    `make_weights` hands the program) and dropped after it. They are made by
    one compiled call and used by another: made and used inside one, the
    compiler may keep them wider than `dtype` (`xla_allow_excess_precision`),
    and they would no longer be the program's."""

    def __init__(self, seed: int, dims: Dims, dtype, p: Precision = REFERENCE):
        self.dims, self.p = dims, p
        dt = jnp.dtype(dtype)
        self.k_embed, self.keys = _keys(jax.random.key(seed), dims)
        std = jnp.asarray(dims.init_std, dt)
        self._table = jax.jit(lambda key: jax.random.normal(
            key, (dims.vocab, dims.d), dt) * std)
        self._weights = jax.jit(
            lambda key, index: layer_weights(key, dims, index, dt),
            static_argnums=1)
        self._apply = jax.jit(
            lambda w, x, index: layer(w, x, dims, index, p), static_argnums=2)
        self._head = jax.jit(lambda embed, hid: logits_at(
            {"scale": jnp.ones((dims.d,), dt)}, embed, hid, dims, p))

    def logits(self, tokens):
        """tokens [T] -> float32 logits [T-1, V] of the rows that decide
        tokens 1..T-1, one full pass."""
        embed = self._table(self.k_embed)
        x = embed[tokens].astype(jnp.float32)
        for i, key in enumerate(self.keys):
            x = self._apply(self._weights(key, i), x, i)
        return self._head(embed, x[:-1])


# ------------------------------------------------------------------ serving

def gap_below_best(model: Seeded, tokens, chosen):
    """How far the logit of `chosen[i]` lies below the best of row `i` in
    one full reference pass over `tokens`; 0 where it is the best."""
    lg = model.logits(tokens)
    return jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, chosen[:, None], 1)[:, 0]


def first_choice(model: Seeded, tokens):
    """The token `model`'s pass puts first after each prefix."""
    return jnp.argmax(model.logits(tokens), axis=-1)
