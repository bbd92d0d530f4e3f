"""The plain reference of a latent-attention decoder with routed experts, in
float32 `jax.numpy`: what `model_type` `axk1` (skt/A.X-K1) computes, as one
chip of an expert-parallel deployment holds it.

RMSNorm before each sub-layer, residual after, untied embedding and head.
Every layer: latent attention, `c_q = norm(x W_qa)`, `[q_nope | q_rope] =
c_q W_qb` per head, `[c_kv | k_rope] = x W_kva`, `c_kv = norm(c_kv)`, YaRN
rotation of `q_rope` and of the one `k_rope` row all heads share, `[k_nope |
v] = c_kv W_kvb` per head, `score = (q_nope k_nope + q_rope k_rope) *
qk^-1/2 * m^2`, causal softmax, `W_o`. Here the keys and values are expanded
per head and no cache exists, so nothing is shared with the program's folded
form. The first `first_dense` layers end in a dense SwiGLU; the others in
`sigmoid(x W_r)` over all experts, the `top_k` largest, their scores
normalised and scaled, `sum_e w_e SwiGLU_e(x)` over the experts HELD HERE
(`[held_start, held_start + held)`; what the others would add is left out)
plus the shared expert, once.

It imports nothing of the program. The weights are made here from the seed
by the initialisation the configuration file states, in the layout the
program is handed (`make_weights`, one jitted call, for the engine); the
reference itself never holds them whole (float32, they would be 19 GB):
`Seeded` makes each layer's weights from the seed when it reaches the layer,
the same values, and attention is computed a block of queries at a time.
Every product goes through `reference.Precision` (float32 at `highest`; the
control: operands rounded to fp8), the router's too.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from reference import CONTROL, REFERENCE, Precision, _rmsnorm  # noqa: F401


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under this file's own names."""
    vocab: int
    d: int
    heads: int
    layers: int
    first_dense: int
    ffn: int
    eps: float
    theta: float
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    yarn_factor: float
    yarn_ctx: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    experts: int            # the router's width: the published count
    held_start: int
    held: int
    top_k: int
    width: int
    shared: int
    route_scale: float
    norm_topk: bool
    init_std: float = 0.02

    @property
    def kinds(self) -> tuple:
        return tuple("dense" if i < self.first_dense else "experts"
                     for i in range(self.layers))

    @property
    def runs(self) -> tuple:
        """(kind, first layer, count) of each run of layers of one kind."""
        out = [("dense", 0, min(self.first_dense, self.layers)),
               ("experts", self.first_dense, self.layers - self.first_dense)]
        return tuple(r for r in out if r[2] > 0)

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        if cfg.get("model_type") != "axk1":
            raise ValueError(f"no such reference for {cfg.get('model_type')}")
        if cfg.get("topk_method", "none") != "none" or \
                cfg.get("scoring_func") != "sigmoid" or \
                cfg.get("moe_layer_freq", 1) != 1:
            raise ValueError("the reference routes by plain top-k of "
                             "sigmoid scores, every layer after the dense")
        rs = cfg["rope_scaling"]
        return cls(
            vocab=cfg["vocab_size"], d=cfg["hidden_size"],
            heads=cfg["num_attention_heads"],
            layers=cfg["num_hidden_layers"],
            first_dense=cfg["first_k_dense_replace"],
            ffn=cfg["intermediate_size"], eps=float(cfg["rms_norm_eps"]),
            theta=float(cfg["rope_theta"]), q_rank=cfg["q_lora_rank"],
            kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
            rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
            yarn_factor=float(rs["factor"]),
            yarn_ctx=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]),
            experts=cfg.get("published", {}).get("n_routed_experts",
                                                 cfg["n_routed_experts"]),
            held_start=cfg.get("first_held_expert", 0),
            held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
            width=cfg["moe_intermediate_size"],
            shared=cfg["n_shared_experts"],
            route_scale=float(cfg["routed_scaling_factor"]),
            norm_topk=bool(cfg["norm_topk_prob"]),
            init_std=float(cfg.get("initializer_range", 0.02)))


# ------------------------------------------------------------------ weights

def layer_weights(key, dims: Dims, kind: str, dtype) -> dict:
    """One layer from its key: normal(0, s) with s the configuration's
    `initializer_range` (0.02), the projections back to the residual
    normal(0, s/sqrt(2 L)), norm scales 1. Key order: (w_qa,
    w_qb, w_kva, w_kvb, w_o, rest); rest is (w_gu, w_down) in a dense layer
    and (w_r, we_gu, we_down, ws_gu, ws_down) in an expert layer. Gate and
    up stand side by side in one matrix, gate first."""
    dt = jnp.dtype(dtype)
    d, h = dims.d, dims.heads
    std, out_std = dims.init_std, dims.init_std / math.sqrt(2 * dims.layers)
    ks = jax.random.split(key, 6)

    def normal(key, shape, s):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s, dt)

    def ones(n):
        return {"scale": jnp.ones((n,), dt)}

    w = {"attn_norm": ones(d),
         "w_qa": normal(ks[0], (d, dims.q_rank), std),
         "q_norm": ones(dims.q_rank),
         "w_qb": normal(ks[1], (dims.q_rank, h * (dims.nope + dims.rope)),
                        std),
         "w_kva": normal(ks[2], (d, dims.kv_rank + dims.rope), std),
         "kv_norm": ones(dims.kv_rank),
         "w_kvb": normal(ks[3], (dims.kv_rank, h * (dims.nope + dims.v)),
                         std),
         "w_o": normal(ks[4], (h * dims.v, d), out_std),
         "mlp_norm": ones(d)}
    if kind == "dense":
        k_gu, k_down = jax.random.split(ks[5])
        w["w_gu"] = normal(k_gu, (d, 2 * dims.ffn), std)
        w["w_down"] = normal(k_down, (dims.ffn, d), out_std)
    else:
        ke = jax.random.split(ks[5], 5)
        f, fs = dims.width, dims.width * dims.shared
        w["w_r"] = normal(ke[0], (d, dims.experts), std)
        w["we_gu"] = normal(ke[1], (dims.held, d, 2 * f), std)
        w["we_down"] = normal(ke[2], (dims.held, f, d), out_std)
        w["ws_gu"] = normal(ke[3], (d, 2 * fs), std)
        w["ws_down"] = normal(ke[4], (fs, d), out_std)
    return w


def _keys(key, dims: Dims):
    """(embedding key, one key a layer, head key)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    return k_embed, jax.random.split(k_layers, dims.layers), k_head


def init_weights(key, dims: Dims, dtype) -> dict:
    """The whole tree as the program takes it: `embed`, `runs` (one stacked
    tree a run of layers of one kind), `final_norm`, `lm_head`."""
    dt = jnp.dtype(dtype)
    k_embed, keys, k_head = _keys(key, dims)
    std = jnp.asarray(dims.init_std, dt)
    runs = tuple(
        jax.vmap(lambda k, kind=kind: layer_weights(k, dims, kind, dt))(
            keys[start:start + count])
        for kind, start, count in dims.runs)
    return {"embed": jax.random.normal(k_embed, (dims.vocab, dims.d), dt) * std,
            "runs": runs,
            "final_norm": {"scale": jnp.ones((dims.d,), dt)},
            "lm_head": jax.random.normal(k_head, (dims.d, dims.vocab), dt)
            * std}


def make_weights(seed: int, dims: Dims, dtype) -> dict:
    """`init_weights` of `jax.random.key(seed)` as one jitted call."""
    return jax.jit(partial(init_weights, dims=dims, dtype=dtype))(
        jax.random.key(seed))


# ------------------------------------------------------------------ forward

def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _inv_freq(dims: Dims):
    """YaRN: frequencies that turn more than `beta_fast` times over the
    original context are kept, those that turn fewer than `beta_slow` times
    are divided by `factor`, a linear ramp over the pair index between."""
    dim = dims.rope
    extra = 1.0 / dims.theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                 / dim)
    if dims.yarn_factor <= 1:
        return extra

    def correction(rotations):
        return (dim * math.log(dims.yarn_ctx / (rotations * 2 * math.pi))
                / (2 * math.log(dims.theta)))

    low = max(math.floor(correction(dims.beta_fast)), 0)
    high = min(math.ceil(correction(dims.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / dims.yarn_factor * ramp + extra * (1.0 - ramp)


def _rope(x, dims: Dims):
    """x [T, ..., rope] at positions 0..T-1: pairs (x[:half], x[half:]),
    cos and sin scaled by mscale/mscale_all_dim."""
    t = x.shape[0]
    half = dims.rope // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * _inv_freq(dims)[None, :]
    m = (_yarn_mscale(dims.yarn_factor, dims.mscale)
         / _yarn_mscale(dims.yarn_factor, dims.mscale_all_dim))
    shape = (t,) + (1,) * (x.ndim - 2) + (half,)
    c, s = (jnp.cos(ang) * m).reshape(shape), (jnp.sin(ang) * m).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _attention(w, x, dims: Dims, p: Precision, q_block: int = 256):
    """Latent attention over one sequence x [T, D], keys and values
    expanded per head, a block of `q_block` queries at a time."""
    t = x.shape[0]
    h = dims.heads
    xn = _rmsnorm(w["attn_norm"]["scale"], x, dims.eps)
    cq = _rmsnorm(w["q_norm"]["scale"], p.mm(xn, w["w_qa"]), dims.eps)
    q = p.mm(cq, w["w_qb"]).reshape(t, h, dims.nope + dims.rope)
    q_nope, q_rope = q[..., :dims.nope], _rope(q[..., dims.nope:], dims)
    ckv = p.mm(xn, w["w_kva"])
    c = _rmsnorm(w["kv_norm"]["scale"], ckv[:, :dims.kv_rank], dims.eps)
    k_rope = _rope(ckv[:, dims.kv_rank:], dims)              # [T, rope]
    kv = p.mm(c, w["w_kvb"]).reshape(t, h, dims.nope + dims.v)
    k_nope, v = kv[..., :dims.nope], kv[..., dims.nope:]
    scale = ((dims.nope + dims.rope) ** -0.5
             * _yarn_mscale(dims.yarn_factor, dims.mscale_all_dim) ** 2)
    bq = q_block if t % q_block == 0 else t
    kpos = jnp.arange(t)

    def block(args):
        qn, qr, qpos = args                                  # [bq, H, .]
        scores = (p.einsum("qhd,khd->hqk", qn, k_nope)
                  + p.einsum("qhr,kr->hqk", qr, k_rope)) * scale
        causal = qpos[:, None] >= kpos[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                               axis=-1)
        return p.einsum("hqk,khd->qhd", probs, v)

    att = lax.map(block, (q_nope.reshape(t // bq, bq, h, dims.nope),
                          q_rope.reshape(t // bq, bq, h, dims.rope),
                          kpos.reshape(t // bq, bq)))
    return x + p.mm(att.reshape(t, h * dims.v), w["w_o"])


def _swiglu(x, w_gu, w_down, p: Precision):
    gu = p.mm(x, w_gu)
    f = gu.shape[-1] // 2
    return p.mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_down)


def routing(w_r, xn, dims: Dims, p: Precision):
    """xn [T, D] -> per-expert weight [T, experts]: the normalised, scaled
    score where the expert is among the token's `top_k`, else 0."""
    scores = jax.nn.sigmoid(p.mm(xn, w_r))
    top, idx = lax.top_k(scores, dims.top_k)
    if dims.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * dims.route_scale
    return jnp.sum(jax.nn.one_hot(idx, dims.experts, dtype=jnp.float32)
                   * top[..., None], axis=1)


def _second_half(w, kind: str, x, dims: Dims, p: Precision):
    xn = _rmsnorm(w["mlp_norm"]["scale"], x, dims.eps)
    if kind == "dense":
        return x + _swiglu(xn, w["w_gu"], w["w_down"], p)
    weight = routing(w["w_r"], xn, dims, p)
    y = _swiglu(xn, w["ws_gu"], w["ws_down"], p)             # shared, once
    for e in range(dims.held):                               # a loop over experts
        y = y + weight[:, dims.held_start + e, None] * _swiglu(
            xn, w["we_gu"][e], w["we_down"][e], p)
    return x + y


def layer(w, kind: str, x, dims: Dims, p: Precision = REFERENCE):
    """One layer over one sequence x [T, D] (float32)."""
    return _second_half(w, kind, _attention(w, x, dims, p), dims, p)


def logits_at(final_norm, lm_head, hid, dims: Dims, p: Precision = REFERENCE):
    return p.mm(_rmsnorm(final_norm["scale"], hid, dims.eps), lm_head)


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
        self._weights = jax.jit(
            lambda key, kind: layer_weights(key, dims, kind, dt),
            static_argnums=1)
        self._embed = jax.jit(
            lambda table, tokens: table[tokens].astype(jnp.float32))
        self._apply = jax.jit(lambda w, x, kind: layer(w, kind, x, dims, p),
                              static_argnums=2)
        self._head = jax.jit(lambda lm_head, hid: logits_at(
            {"scale": jnp.ones((dims.d,), dt)}, lm_head, hid, dims, p))

    def logits(self, tokens):
        """tokens [T] -> float32 logits [T-1, V] of the rows that decide
        tokens 1..T-1, one full pass."""
        d = self.dims
        x = self._embed(self._table(self.k_embed, (d.vocab, d.d)), tokens)
        for key, kind in zip(self.keys, d.kinds):
            x = self._apply(self._weights(key, kind), x, kind)
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
