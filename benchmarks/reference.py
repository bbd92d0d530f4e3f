"""The plain reference: one dense pre-norm decoder in float32 `jax.numpy`.

RMSNorm, rotary embedding (half-split pairs, no scaling), causal softmax
attention with as many key/value heads as query heads, SwiGLU, untied
embedding and head, no biases. It serves every configuration under
`benchmarks/configs/` whose `model_type` is `llama`, and imports nothing of
the program: weights are made here from the seed, by the initialisation the
configuration file states.

Every matrix product goes through `Precision.mm`/`Precision.einsum`. The
reference runs them in float32 at `highest`; the *control* of a cell's
`correct` is this same code with the operands of every product rounded to
fp8 (e4m3 forward, e5m2 for the gradient arriving at a product, scaled per
tensor), the precision below the bf16 the configurations state. Nothing else
differs between the two.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under this file's own names."""
    vocab: int
    d: int
    heads: int
    ffn: int
    layers: int
    eps: float
    theta: float

    @property
    def head_dim(self) -> int:
        return self.d // self.heads

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        if cfg.get("model_type") != "llama":
            raise ValueError(f"no plain reference for {cfg.get('model_type')}")
        if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
            raise ValueError("the reference has as many kv heads as q heads")
        return cls(vocab=cfg["vocab_size"], d=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   ffn=cfg["intermediate_size"],
                   layers=cfg["num_hidden_layers"],
                   eps=float(cfg["rms_norm_eps"]),
                   theta=float(cfg["rope_theta"]))


# ------------------------------------------------------------------ weights

def init_weights(key, dims: Dims, dtype=jnp.float32) -> dict:
    """Weights from a key, on the device, in `dtype`: normal(0, 0.02),
    output projections normal(0, 0.02/sqrt(2 L)), norm scales 1. Blocks are
    stacked on a leading layer axis. Key order: (embed, blocks, head), then
    one key a layer, then (wq, wk, wv, wo, w_gate, w_up, w_down)."""
    dt = jnp.dtype(dtype)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    std, out_std = 0.02, 0.02 / math.sqrt(2 * dims.layers)

    def normal(key, shape, s):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s, dt)

    def block(key):
        ks = jax.random.split(key, 7)
        d, f = dims.d, dims.ffn
        return {"attn_norm": {"scale": jnp.ones((d,), dt)},
                "wq": normal(ks[0], (d, d), std),
                "wk": normal(ks[1], (d, d), std),
                "wv": normal(ks[2], (d, d), std),
                "wo": normal(ks[3], (d, d), out_std),
                "mlp_norm": {"scale": jnp.ones((d,), dt)},
                "w_gate": normal(ks[4], (d, f), std),
                "w_up": normal(ks[5], (d, f), std),
                "w_down": normal(ks[6], (f, d), out_std)}

    return {"embed": normal(k_embed, (dims.vocab, dims.d), std),
            "blocks": jax.vmap(block)(jax.random.split(k_blocks, dims.layers)),
            "final_norm": {"scale": jnp.ones((dims.d,), dt)},
            "lm_head": normal(k_head, (dims.d, dims.vocab), std)}


def make_weights(seed: int, dims: Dims, dtype) -> dict:
    """`init_weights` of `jax.random.key(seed)` as one jitted call; the key
    is an argument, so every seed runs the same compiled program."""
    return jax.jit(partial(init_weights, dims=dims, dtype=dtype))(
        jax.random.key(seed))


# ---------------------------------------------------------------- precision

def _fp8_round(x, dtype):
    """Scale so that the largest magnitude is the type's largest finite
    value, round to the fp8 type, scale back: per-tensor scaled fp8."""
    x = x.astype(jnp.float32)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    """An operand of a product rounded to e4m3; the rounding passes the
    gradient straight through, as fp8 training does."""
    return _fp8_round(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    """Identity forward; the gradient that flows back into the product is
    rounded to e5m2, so the backward products have fp8 operands too."""
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_fp8_round(g, jnp.float8_e5m2),))


@dataclasses.dataclass(frozen=True)
class Precision:
    """How the matrix products are computed. `fp8=False`: float32 operands
    at `highest`. `fp8=True` (the control): both operands of every product
    are rounded to per-tensor scaled float8_e4m3fn, the gradient arriving at
    the product to float8_e5m2, and the products of the rounded values are
    taken in float32: fp8 training's arithmetic."""
    fp8: bool = False

    def _q(self, x):
        return _fp8_operand(x) if self.fp8 else x.astype(jnp.float32)

    def _out(self, y):
        return _fp8_cotangent(y) if self.fp8 else y

    def mm(self, a, b):
        return self._out(jnp.matmul(self._q(a), self._q(b),
                                    precision=lax.Precision.HIGHEST))

    def einsum(self, spec, a, b):
        return self._out(jnp.einsum(spec, self._q(a), self._q(b),
                                    precision=lax.Precision.HIGHEST))


REFERENCE = Precision(fp8=False)
CONTROL = Precision(fp8=True)


# ------------------------------------------------------------------ forward

def _rmsnorm(scale, x, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [T, H, Dh] at positions 0..T-1: pairs (x[:half], x[half:])."""
    t, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _block(blk, x, dims: Dims, p: Precision):
    """One layer over one sequence x [T, D]."""
    t = x.shape[0]
    h, dh = dims.heads, dims.head_dim
    xn = _rmsnorm(blk["attn_norm"]["scale"], x, dims.eps)
    q = _rope(p.mm(xn, blk["wq"]).reshape(t, h, dh), dims.theta)
    k = _rope(p.mm(xn, blk["wk"]).reshape(t, h, dh), dims.theta)
    v = p.mm(xn, blk["wv"]).reshape(t, h, dh)
    scores = p.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    att = p.einsum("hqk,khd->qhd", probs, v).reshape(t, h * dh)
    x = x + p.mm(att, blk["wo"])
    xn = _rmsnorm(blk["mlp_norm"]["scale"], x, dims.eps)
    gate = jax.nn.silu(p.mm(xn, blk["w_gate"])) * p.mm(xn, blk["w_up"])
    return x + p.mm(gate, blk["w_down"])


def hidden(weights, tokens, dims: Dims, p: Precision = REFERENCE,
           remat: bool = True):
    """tokens [T] -> final hidden states [T, D], before the last norm."""
    x = weights["embed"][tokens].astype(jnp.float32)
    fn = jax.checkpoint(partial(_block, dims=dims, p=p)) if remat \
        else partial(_block, dims=dims, p=p)

    def body(carry, blk):
        return fn(blk, carry), None

    x, _ = lax.scan(body, x, weights["blocks"])
    return x


def logits_at(weights, hid, dims: Dims, p: Precision = REFERENCE):
    """Head over hidden rows [N, D] -> float32 logits [N, V]."""
    hn = _rmsnorm(weights["final_norm"]["scale"], hid, dims.eps)
    return p.mm(hn, weights["lm_head"])


def sequence_nll_sum(weights, tokens, dims: Dims, p: Precision = REFERENCE,
                     head_rows: int = 1024):
    """Sum over the T-1 next-token positions of one sequence of the
    cross-entropy, the head taken `head_rows` rows at a time so that the
    [T, V] logits never exist whole."""
    hid = hidden(weights, tokens, dims, p)[:-1]
    labels = tokens[1:]
    n = hid.shape[0]
    pad = (-n) % head_rows
    hid = jnp.pad(hid, ((0, pad), (0, 0)))
    labels_p = jnp.pad(labels, (0, pad))
    valid = (jnp.arange(n + pad) < n).astype(jnp.float32)

    @jax.checkpoint
    def rows(hc, lc, vc):
        lg = logits_at(weights, hc, dims, p)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum((lse - jnp.take_along_axis(lg, lc[:, None], 1)[:, 0])
                       * vc)

    def body(acc, xs):
        return acc + rows(*xs), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                        (hid.reshape(-1, head_rows, dims.d),
                         labels_p.reshape(-1, head_rows),
                         valid.reshape(-1, head_rows)))
    return total


# ----------------------------------------------------------------- training

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def make_row_grad(dims: Dims, p: Precision):
    """A jitted `(weights, row [T], acc) -> (nll sum of the row, acc + its
    gradient)`: one row of the batch at a time, so that a layer's [H, T, T]
    scores exist for one row, and the sum of gradients updated in place."""
    @partial(jax.jit, donate_argnums=(2,))
    def row_grad(weights, row, acc):
        nll, g = jax.value_and_grad(
            lambda w: sequence_nll_sum(w, row, dims, p))(weights)
        return nll, jax.tree.map(jnp.add, acc, g)

    return row_grad


def loss_and_grad(row_grad, weights, batch):
    """(mean loss, gradient of the mean loss) over a batch [B, T]."""
    acc = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w))(weights)
    total = jnp.zeros((), jnp.float32)
    for row in batch:
        nll, acc = row_grad(weights, jnp.asarray(row), acc)
        total = total + nll
    scale = jnp.float32(1.0 / (batch.shape[0] * (batch.shape[1] - 1)))
    return total * scale, jax.jit(
        lambda g: jax.tree.map(lambda x: x * scale, g), donate_argnums=0)(acc)


@partial(jax.jit, donate_argnums=(0, 1, 2), static_argnames=("lr",))
def adam_update(weights, mu, nu, grads, count, *, lr: float):
    """Adam as published (Kingma & Ba), bias-corrected, no weight decay."""
    count = count + 1
    c1 = 1.0 - ADAM_B1 ** count
    c2 = 1.0 - ADAM_B2 ** count
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      nu, grads)
    weights = jax.tree.map(
        lambda w, m, v: w - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        weights, mu, nu)
    return weights, mu, nu, count


def leaf_norms(tree) -> dict:
    """{leaf path: l2 norm} as device scalars, in one jitted reduction."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return dict(zip(names, norms))


def leaf_diff_norms(a, b) -> dict:
    """{leaf path: |a - b|_2} of two trees of one structure."""
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree_util.tree_flatten_with_path(b)[0]
    diff = jax.jit(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))))
    return {jax.tree_util.keystr(pa): diff(xa, xb)
            for (pa, xa), (_, xb) in zip(flat_a, flat_b)}


def train_three(seed: int, dims: Dims, batches, lr: float,
                p: Precision = REFERENCE) -> dict:
    """Follow `len(batches)` optimizer steps from the seed's weights.
    Returns each step's loss, the first gradient's norm per leaf, and the
    norm per leaf of the parameters' change over all the steps."""
    weights = make_weights(seed, dims, jnp.float32)
    zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w))
    mu, nu = zeros(weights), zeros(weights)
    count = jnp.zeros((), jnp.float32)
    row_grad = make_row_grad(dims, p)
    losses, grad_norms = [], None
    for batch in batches:
        loss, grads = loss_and_grad(row_grad, weights, batch)
        losses.append(loss)
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        weights, mu, nu, count = adam_update(weights, mu, nu, grads, count,
                                             lr=float(lr))
        del grads
    del mu, nu
    change = leaf_diff_norms(weights, make_weights(seed, dims, jnp.float32))
    return {"losses": [float(x) for x in losses],
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "param_change": {k: float(v) for k, v in change.items()}}


# ------------------------------------------------------------------ serving

def make_first_choice(dims: Dims, p: Precision):
    """A jitted `(weights, tokens [T]) -> choice [T-1]`: the token that the
    forward pass in precision `p` puts first after each prefix."""
    @jax.jit
    def first_choice(weights, tokens):
        hid = hidden(weights, tokens, dims, p, remat=False)
        return jnp.argmax(logits_at(weights, hid[:-1], dims, p), axis=-1)

    return first_choice


def make_gap_below_best(dims: Dims):
    """A jitted `(weights, tokens [T], chosen [T-1]) -> gap [T-1]`: one full
    reference forward pass over `tokens`; `gap[i]` is how far the logit of
    `chosen[i]` lies below the best logit of row `i`, the row that decides
    token `i + 1`. 0 where the reference would have chosen the same."""
    @jax.jit
    def gap_below_best(weights, tokens, chosen):
        hid = hidden(weights, tokens, dims, REFERENCE, remat=False)
        lg = logits_at(weights, hid[:-1], dims, REFERENCE)
        return jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, chosen[:, None], 1)[:, 0]

    return gap_below_best
