"""The plain reference of a decoder whose every block runs a Mamba-2
state-space mixer and grouped-query attention side by side, in float32
`jax.numpy`: what `model_type` `falcon_h1` (tiiuae/Falcon-H1) computes.

With `h` a position's hidden vector and the multipliers by their config keys:
`h = embed[token] * embedding_multiplier`. Block: `u = RMSNorm_in(h)`;
`h = h + ssm_out_multiplier * SSM(u) + attention_out_multiplier *
Attn(attention_in_multiplier * u)`; `v = RMSNorm_ff(h)`; `h = h +
mlp_multipliers[1] * W_down(W_up v * silu(mlp_multipliers[0] * W_gate v))`.
`Attn`: `Hq` query heads over `Hkv` key/value heads of `head_dim` (query head
`i` reads key/value head `i // (Hq / Hkv)`), `k = key_multiplier * W_k u`,
RoPE on q and k, causal softmax at `head_dim^-1/2`, `W_o`. `SSM`: `p = W_in
(ssm_in_multiplier * u)` split `[z | x | B | C | dt]`, each part times its
entry of `ssm_multipliers`; `[x | B | C]` through a causal depthwise
convolution of `conv` taps with bias, then silu; `dt = softplus(dt +
dt_bias)`, `A = -exp(A_log)`; per head `j`, with its group `g = j // (heads /
groups)`, `S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t`, `y_t = S_t C_t
+ D x_t`; `RMSNorm(y silu(z))` over each group's values, `W_out`. `logits =
lm_head_multiplier * W_head RMSNorm_f(h)`.

The recurrence here is a sequential `lax.scan` over positions from a zero
state: no chunks, no carried state or convolution tail, no cache, one
sequence at a time, so nothing is shared with the program's chunked scan or
its one-step form. It imports nothing of the program. The weights are made
here from the seed by the initialisation the configuration file states, in
the layout the program is handed (`make_weights`, one jitted call, for the
engine); the reference itself never holds them whole (`Seeded` makes each
layer's from the seed when it reaches the layer, the same values) and takes
the head a block of the vocabulary at a time. Every product with a weight and
the attention's two go through `reference.Precision` (float32 at `highest`;
the control: operands rounded to fp8, the head's a block of the vocabulary at
a time); the recurrence is float32 in both.
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

HEAD_BLOCKS = 8          # the head is taken this many blocks of columns


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under this file's own names."""
    vocab: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    ffn: int
    eps: float
    theta: float
    d_inner: int
    ssm_heads: int
    ssm_head_dim: int
    groups: int
    state: int
    conv: int
    chunk: int
    m_embed: float
    m_head: float
    m_attn_in: float
    m_attn_out: float
    m_key: float
    m_ssm_in: float
    m_ssm_out: float
    m_ssm: tuple            # z, x, B, C, dt
    m_mlp: tuple            # gate, down
    init_std: float = 0.02

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    @property
    def proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.ssm_heads

    @property
    def qkv_dim(self) -> int:
        return (self.heads + 2 * self.kv_heads) * self.head_dim

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        if cfg.get("model_type") != "falcon_h1":
            raise ValueError(f"no such reference for {cfg.get('model_type')}")
        if (cfg["mamba_n_heads"] * cfg["mamba_d_head"] != cfg["mamba_d_ssm"]
                or not cfg["mamba_rms_norm"] or cfg["mamba_norm_before_gate"]
                or not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]
                or cfg["attention_bias"] or cfg["mlp_bias"]
                or cfg["projectors_bias"] or cfg["rope_scaling"]
                or cfg["tie_word_embeddings"]
                or cfg["attn_layer_indices"] is not None):
            raise ValueError("the reference has the gated norm after the "
                             "gate, a convolution bias and no other, plain "
                             "RoPE, an untied head, attention in every layer")
        return cls(
            vocab=cfg["vocab_size"], d=cfg["hidden_size"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            layers=cfg["num_hidden_layers"], ffn=cfg["intermediate_size"],
            eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
            d_inner=cfg["mamba_d_ssm"], ssm_heads=cfg["mamba_n_heads"],
            ssm_head_dim=cfg["mamba_d_head"], groups=cfg["mamba_n_groups"],
            state=cfg["mamba_d_state"], conv=cfg["mamba_d_conv"],
            chunk=cfg["mamba_chunk_size"],
            m_embed=float(cfg["embedding_multiplier"]),
            m_head=float(cfg["lm_head_multiplier"]),
            m_attn_in=float(cfg["attention_in_multiplier"]),
            m_attn_out=float(cfg["attention_out_multiplier"]),
            m_key=float(cfg["key_multiplier"]),
            m_ssm_in=float(cfg["ssm_in_multiplier"]),
            m_ssm_out=float(cfg["ssm_out_multiplier"]),
            m_ssm=tuple(float(m) for m in cfg["ssm_multipliers"]),
            m_mlp=tuple(float(m) for m in cfg["mlp_multipliers"]),
            init_std=float(cfg.get("initializer_range", 0.02)))


# ------------------------------------------------------------------ weights

def layer_weights(key, dims: Dims, dtype) -> dict:
    """One layer from its key: normal(0, s) with s the configuration's
    `initializer_range` (0.02), the projections back to the residual (w_o,
    w_out, w_down) normal(0, s/sqrt(2 L)), norm scales and `d_skip` 1, the
    convolution uniform(+-conv^-1/2) with a zero bias, `A` uniform over
    [1, 16], `dt_bias` the inverse softplus of a step log-uniform over
    [1e-3, 1e-1]. Key order: (w_qkv, w_o, w_in, conv_w, dt, a, w_out, w_gu,
    w_down). q, k and v stand side by side in one matrix, as gate and up do
    (gate first)."""
    dt = jnp.dtype(dtype)
    d = dims.d
    std, out_std = dims.init_std, dims.init_std / math.sqrt(2 * dims.layers)
    ks = jax.random.split(key, 9)

    def normal(key, shape, s):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s, dt)

    def ones(n):
        return {"scale": jnp.ones((n,), dt)}

    step = jnp.exp(jax.random.uniform(ks[4], (dims.ssm_heads,), jnp.float32)
                   * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    bound = dims.conv ** -0.5
    return {
        "in_norm": ones(d),
        "w_qkv": normal(ks[0], (d, dims.qkv_dim), std),
        "w_o": normal(ks[1], (dims.heads * dims.head_dim, d), out_std),
        "w_in": normal(ks[2], (d, dims.proj_dim), std),
        "conv_w": jax.random.uniform(ks[3], (dims.conv, dims.conv_dim),
                                     jnp.float32, -bound, bound).astype(dt),
        "conv_b": jnp.zeros((dims.conv_dim,), dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "a_log": jnp.log(jax.random.uniform(
            ks[5], (dims.ssm_heads,), jnp.float32, 1.0, 16.0)).astype(dt),
        "d_skip": jnp.ones((dims.ssm_heads,), dt),
        "ssm_norm": ones(dims.d_inner),
        "w_out": normal(ks[6], (dims.d_inner, d), out_std),
        "ff_norm": ones(d),
        "w_gu": normal(ks[7], (d, 2 * dims.ffn), std),
        "w_down": normal(ks[8], (dims.ffn, d), out_std)}


def _keys(key, dims: Dims):
    """(embedding key, one key a layer, head key)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    return k_embed, jax.random.split(k_layers, dims.layers), k_head


def init_weights(key, dims: Dims, dtype) -> dict:
    """The whole tree as the program takes it: `embed`, `runs` (the layers
    stacked, one run), `final_norm`, `lm_head`."""
    dt = jnp.dtype(dtype)
    k_embed, keys, k_head = _keys(key, dims)
    std = jnp.asarray(dims.init_std, dt)
    return {"embed": jax.random.normal(k_embed, (dims.vocab, dims.d), dt) * std,
            "runs": (jax.vmap(lambda k: layer_weights(k, dims, dt))(keys),),
            "final_norm": {"scale": jnp.ones((dims.d,), dt)},
            "lm_head": jax.random.normal(k_head, (dims.d, dims.vocab), dt)
            * std}


def make_weights(seed: int, dims: Dims, dtype) -> dict:
    """`init_weights` of `jax.random.key(seed)` as one jitted call."""
    return jax.jit(partial(init_weights, dims=dims, dtype=dtype))(
        jax.random.key(seed))


# ------------------------------------------------------------------ forward

def _attention(w, u, dims: Dims, p: Precision):
    """Grouped-query attention over one sequence: u [T, D] (normed)."""
    t = u.shape[0]
    hq, h, dh = dims.heads, dims.kv_heads, dims.head_dim
    qkv = p.mm(u * dims.m_attn_in, w["w_qkv"])
    q = _rope(qkv[:, :hq * dh].reshape(t, hq, dh), dims.theta)
    k = _rope((qkv[:, hq * dh:(hq + h) * dh] * dims.m_key).reshape(t, h, dh),
              dims.theta)
    v = qkv[:, (hq + h) * dh:].reshape(t, h, dh)
    scores = p.einsum("qgid,kgd->giqk", q.reshape(t, h, hq // h, dh), k
                      ) / math.sqrt(dh)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                           axis=-1)
    att = p.einsum("giqk,kgd->qgid", probs, v).reshape(t, hq * dh)
    return p.mm(att, w["w_o"]) * dims.m_attn_out


def recurrence(x, dt, a, b, c):
    """`S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t`, `y_t = S_t C_t`,
    position after position from a zero state: x [T, H, P], dt [T, H],
    a [H], b, c [T, G, N] (head `j` reads group `j // (H / G)`) -> (y
    [T, H, P], the last state [H, P, N]), float32."""
    h, g = x.shape[1], b.shape[1]

    def step(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        b_t, c_t = (jnp.repeat(m, h // g, axis=0) for m in (b_t, c_t))
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    s0 = jnp.zeros((h, x.shape[2], b.shape[2]), jnp.float32)
    last, y = lax.scan(step, s0, (x, dt, b, c))
    return y, last


def _mixer(w, u, dims: Dims, p: Precision):
    """The state-space mixer over one sequence: u [T, D] (normed)."""
    t = u.shape[0]
    f32 = jnp.float32
    gn = dims.groups * dims.state
    widths = (dims.d_inner, dims.d_inner, gn, gn, dims.ssm_heads)
    proj = p.mm(u * dims.m_ssm_in, w["w_in"]) * jnp.concatenate(
        [jnp.full((n,), m, f32) for n, m in zip(widths, dims.m_ssm)])
    z = proj[:, :dims.d_inner]
    xbc = proj[:, dims.d_inner:dims.d_inner + dims.conv_dim]
    dt = proj[:, dims.d_inner + dims.conv_dim:]
    padded = jnp.pad(xbc, ((dims.conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["conv_b"].astype(f32) + sum(
        padded[i:i + t] * w["conv_w"][i].astype(f32)
        for i in range(dims.conv)))
    x = xbc[:, :dims.d_inner].reshape(t, dims.ssm_heads, dims.ssm_head_dim)
    b = xbc[:, dims.d_inner:dims.d_inner + gn].reshape(t, dims.groups, -1)
    c = xbc[:, dims.d_inner + gn:].reshape(t, dims.groups, -1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(f32))
    y, _ = recurrence(x, dt, -jnp.exp(w["a_log"].astype(f32)), b, c)
    y = y + w["d_skip"].astype(f32)[:, None] * x
    gated = (y.reshape(t, dims.d_inner) * jax.nn.silu(z)).reshape(
        t, dims.groups, -1)
    normed = gated / jnp.sqrt(jnp.mean(gated * gated, axis=-1, keepdims=True)
                              + dims.eps)
    normed = normed.reshape(t, dims.d_inner) * w["ssm_norm"]["scale"].astype(f32)
    return p.mm(normed, w["w_out"]) * dims.m_ssm_out


def layer(w, x, dims: Dims, p: Precision = REFERENCE):
    """One layer over one sequence x [T, D] (float32)."""
    u = _rmsnorm(w["in_norm"]["scale"], x, dims.eps)
    x = x + _mixer(w, u, dims, p) + _attention(w, u, dims, p)
    v = _rmsnorm(w["ff_norm"]["scale"], x, dims.eps)
    gu = p.mm(v, w["w_gu"])
    gate = jax.nn.silu(gu[:, :dims.ffn] * dims.m_mlp[0])
    return x + p.mm(gate * gu[:, dims.ffn:], w["w_down"]) * dims.m_mlp[1]


def logits_at(final_norm, lm_head, hid, dims: Dims, p: Precision = REFERENCE):
    """The head a block of the vocabulary at a time (`HEAD_BLOCKS` where the
    vocabulary divides): the float32 copy of a block is all that exists."""
    hn = _rmsnorm(final_norm["scale"], hid, dims.eps)
    nb = HEAD_BLOCKS if dims.vocab % HEAD_BLOCKS == 0 else 1
    blocks = lm_head.reshape(dims.d, nb, dims.vocab // nb).swapaxes(0, 1)
    out = lax.map(lambda blk: p.mm(hn, blk), blocks)        # [nb, T, V / nb]
    return out.swapaxes(0, 1).reshape(hid.shape[0], dims.vocab) * dims.m_head


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
        self.k_embed, self.keys, self.k_head = _keys(jax.random.key(seed),
                                                     dims)
        std = jnp.asarray(dims.init_std, dt)
        self._table = jax.jit(lambda key, shape: jax.random.normal(
            key, shape, dt) * std, static_argnums=1)
        self._weights = jax.jit(lambda key: layer_weights(key, dims, dt))
        self._embed = jax.jit(lambda table, tokens: table[tokens].astype(
            jnp.float32) * dims.m_embed)
        self._apply = jax.jit(lambda w, x: layer(w, x, dims, p))
        self._head = jax.jit(lambda lm_head, hid: logits_at(
            {"scale": jnp.ones((dims.d,), dt)}, lm_head, hid, dims, p))

    def logits(self, tokens):
        """tokens [T] -> float32 logits [T-1, V] of the rows that decide
        tokens 1..T-1, one full pass."""
        d = self.dims
        x = self._embed(self._table(self.k_embed, (d.vocab, d.d)), tokens)
        for key in self.keys:
            x = self._apply(self._weights(key), x)
        return self._head(self._table(self.k_head, (d.d, d.vocab)), x[:-1])


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
