"""The Mamba-2 state-space mixer of a ``falcon_h1`` block, as the engine
serves it beside grouped-query attention (``config.StateSpaceMixer``).

``p = W_in (ssm_in u)`` for a normed input ``u``, split ``[z | x | B | C |
dt]`` and each part scaled by its multiplier; ``[x | B | C]`` through a causal
depthwise convolution of ``conv`` taps with bias, then silu; ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a scalar a head. Per head ``j``
with its group ``g = j // (heads / groups)``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t        S: [head_dim, state]
    y_t = S_t C_t + D x_t

then ``RMSNorm_grouped(y silu(z))`` over each group's ``d_inner / groups``
values and ``W_out``.

What a slot carries from call to call is ``S`` of every head and the
convolution's last ``conv - 1`` inputs (the *tail*). ``mixer`` takes both and
returns both, for ``n_valid`` real positions of the ``T`` it is given: a
padded position has ``dt = 0``, which carries ``S`` through unchanged
(``exp(0) S + 0``), and the new tail ends at the last real input, so a slot
with ``n_valid = 0`` (not decoding) keeps state and tail as they were.

Two forms of the recurrence with one result. ``scan_step`` (``T == 1``, a
decode step) is the recurrence itself, elementwise over the state. For a
prefill chunk ``scan_chunked`` is the chunked scan (SSD): within a chunk of
``chunk`` positions the outputs are a masked product ``(C B^T * decay) x``,
each chunk's contribution to the state is one product, the states go from
chunk to chunk by a short sequential pass that starts from the carried
``S``, and what the earlier chunks left reaches the outputs through ``C S``.
Both run in ``state_dtype`` (float32: products at ``highest``, so that the
matrix unit rounds nothing to bf16); everything round them is in the compute
type. tests/test_state_space.py holds the chunked form to the sequential one.

Weights of a layer (``init_layer``; stacked in ``params["runs"][0]``):
``in_norm``, ``w_qkv`` [D, (Hq + 2 Hkv) Dh] (q, k, v side by side), ``w_o``
[Hq Dh, D], ``w_in`` [D, proj_dim], ``conv_w`` [conv, conv_dim], ``conv_b``,
``dt_bias``, ``a_log``, ``d_skip`` [heads], ``ssm_norm`` [d_inner], ``w_out``
[d_inner, D], ``ff_norm``, ``w_gu`` [D, 2 F] (gate first), ``w_down`` [F, D].
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import nn
from ..config import ModelDescription, StateSpaceMixer

_HIGHEST = lax.Precision.HIGHEST


def column_multipliers(desc: ModelDescription) -> jnp.ndarray:
    """The projection's per-column multiplier [proj_dim]: ``ssm_multipliers``
    over ``[z | x | B | C | dt]``."""
    mx, m = desc.mixer, desc.multipliers.ssm
    gn = mx.groups * mx.state
    widths = (mx.d_inner, mx.d_inner, gn, gn, mx.heads)
    return jnp.concatenate([jnp.full((w,), v, jnp.float32)
                            for w, v in zip(widths, m)])


def convolve(block: dict, xbc: jnp.ndarray, tail: jnp.ndarray,
             n_valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The causal depthwise convolution over ``[tail | xbc]``: xbc
    [S, T, C] after tail [S, conv - 1, C] -> (silu(conv + bias) [S, T, C],
    the new tail: the last ``conv - 1`` inputs up to ``n_valid`` [S])."""
    k = tail.shape[1] + 1
    t = xbc.shape[1]
    window = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w = block["conv_w"].astype(xbc.dtype)
    out = block["conv_b"].astype(xbc.dtype) + sum(
        window[:, i:i + t] * w[i] for i in range(k))
    rows = n_valid[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(window, rows[:, :, None], axis=1)
    return jax.nn.silu(out), new_tail.astype(tail.dtype)


def _by_group(a: jnp.ndarray, mx: StateSpaceMixer, axis: int) -> jnp.ndarray:
    """Split the head axis into (group, heads of the group)."""
    return a.reshape(a.shape[:axis] + (mx.groups, mx.heads // mx.groups)
                     + a.shape[axis + 1:])


def scan_step(x, dt, a, b, c, s0, mx: StateSpaceMixer):
    """One step of the recurrence for every slot: x [S, H, P], dt [S, H],
    a [H] (negative), b, c [S, G, N], s0 [S, H, P, N] -> (y [S, H, P],
    S [S, H, P, N]), all in s0's type."""
    sg = _by_group(s0, mx, 1)                               # [S, G, Hg, P, N]
    decay = _by_group(jnp.exp(dt * a), mx, 1)[..., None, None]
    dtx = _by_group(dt[..., None] * x, mx, 1)               # [S, G, Hg, P]
    s1 = sg * decay + dtx[..., None] * b[:, :, None, None, :]
    y = jnp.sum(s1 * c[:, :, None, None, :], axis=-1)
    return y.reshape(x.shape), s1.reshape(s0.shape)


def scan_chunked(x, dt, a, b, c, s0, mx: StateSpaceMixer):
    """The recurrence over T positions as the chunked scan: x [S, T, H, P],
    dt [S, T, H] (0 at a padded position), a [H], b, c [S, T, G, N], s0
    [S, H, P, N] -> (y [S, T, H, P], the state after the last position).
    T is a multiple of ``mx.chunk``, or shorter and one chunk (how the
    positions are cut into chunks is not part of the result)."""
    s, t, h, p = x.shape
    q, g, hg = min(mx.chunk, t), mx.groups, mx.heads // mx.groups
    n = t // q
    x = x.reshape(s, n, q, g, hg, p)
    dt = dt.reshape(s, n, q, g, hg)
    b = b.reshape(s, n, q, g, -1)
    c = c.reshape(s, n, q, g, -1)
    # the log of the decay from a chunk's start up to and including i
    acum = jnp.cumsum(dt * a.reshape(g, hg), axis=2)        # [S, n, Q, G, Hg]
    # within a chunk: y_i += sum_{j<=i} (C_i . B_j) decay(j -> i) dt_j x_j
    cb = jnp.einsum("snigN,snjgN->sngij", c, b, precision=_HIGHEST)
    seg = acum[:, :, :, None] - acum[:, :, None, :]         # [S, n, i, j, G, Hg]
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    seg = jnp.where(causal[None, None, :, :, None, None], seg, -jnp.inf)
    w = (jnp.exp(seg) * cb.transpose(0, 1, 3, 4, 2)[..., None]
         * dt[:, :, None])                                  # [S, n, i, j, G, Hg]
    y = jnp.einsum("snijgh,snjghp->snighp", w, x, precision=_HIGHEST)
    # what each chunk adds to the state by its end
    to_end = jnp.exp(acum[:, :, -1:] - acum) * dt           # [S, n, Q, G, Hg]
    add = jnp.einsum("snjghp,snjgN->snghpN", x * to_end[..., None], b,
                     precision=_HIGHEST)
    whole = jnp.exp(acum[:, :, -1])                         # [S, n, G, Hg]

    def carry(state, chunk):
        add_c, whole_c = chunk
        return state * whole_c[..., None, None] + add_c, state

    last, before = lax.scan(
        carry, s0.reshape(s, g, hg, p, -1),
        (add.swapaxes(0, 1), whole.swapaxes(0, 1)))
    before = before.swapaxes(0, 1)                          # [S, n, G, Hg, P, N]
    # what the state at the chunk's start gives position i
    y = y + jnp.einsum("snigN,snghpN->snighp", c, before,
                       precision=_HIGHEST) * jnp.exp(acum)[..., None]
    return y.reshape(s, t, h, p), last.reshape(s0.shape)


def gated_norm(block: dict, y: jnp.ndarray, z: jnp.ndarray,
               desc: ModelDescription) -> jnp.ndarray:
    """``RMSNorm(y silu(z))`` over each group's values: y, z [S, T, d_inner]."""
    mx = desc.mixer
    shape = y.shape[:-1] + (mx.groups, mx.d_inner // mx.groups)
    scale = {"scale": block["ssm_norm"]["scale"].reshape(shape[-2:])}
    return nn.rmsnorm(scale, (y * jax.nn.silu(z)).reshape(shape),
                      eps=desc.norm_eps).reshape(y.shape)


def mixer(block: dict, u: jnp.ndarray, state: jnp.ndarray, tail: jnp.ndarray,
          n_valid: jnp.ndarray, desc: ModelDescription):
    """The mixer over u [S, T, D] (normed) from each slot's carried ``state``
    [S, H, P, N] and ``tail`` [S, conv - 1, conv_dim], of which the first
    ``n_valid`` [S] positions are real -> (its output [S, T, D] before
    ``ssm_out_multiplier``, the new state, the new tail). The scopes are how
    a device trace tells the parts apart (docs/COMPONENTS.md)."""
    mx, m = desc.mixer, desc.multipliers
    s, t, _ = u.shape
    sd = jnp.dtype(mx.state_dtype)
    gn = mx.groups * mx.state
    with jax.named_scope("ssm.proj"):
        proj = ((u * jnp.asarray(m.ssm_in, u.dtype))
                @ block["w_in"].astype(u.dtype))
        proj = proj * column_multipliers(desc).astype(u.dtype)
        z = proj[..., :mx.d_inner]
        xbc = proj[..., mx.d_inner:mx.d_inner + mx.conv_dim]
        dt = proj[..., mx.d_inner + mx.conv_dim:]
    with jax.named_scope("ssm.conv"):
        xbc, tail = convolve(block, xbc, tail, n_valid)
    with jax.named_scope("ssm.scan"):
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] < n_valid[:, None]
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + block["dt_bias"].astype(jnp.float32))
        dt = jnp.where(valid[..., None], dt, 0.0).astype(sd)
        a = -jnp.exp(block["a_log"].astype(jnp.float32)).astype(sd)
        x = xbc[..., :mx.d_inner].reshape(s, t, mx.heads, mx.head_dim)
        b = xbc[..., mx.d_inner:mx.d_inner + gn].reshape(s, t, mx.groups, -1)
        c = xbc[..., mx.d_inner + gn:].reshape(s, t, mx.groups, -1)
        xs, bs, cs = x.astype(sd), b.astype(sd), c.astype(sd)
        if t == 1:
            y, state = scan_step(xs[:, 0], dt[:, 0], a, bs[:, 0], cs[:, 0],
                                 state, mx)
            y = y[:, None]
        else:
            y, state = scan_chunked(xs, dt, a, bs, cs, state, mx)
        y = y.astype(u.dtype) + x * block["d_skip"].astype(u.dtype)[:, None]
    with jax.named_scope("ssm.norm"):
        y = gated_norm(block, y.reshape(s, t, mx.d_inner), z, desc)
    with jax.named_scope("ssm.out"):
        return y @ block["w_out"].astype(u.dtype), state, tail


# ------------------------------------------------------------------ weights

def init_layer(key, desc: ModelDescription, dtype) -> dict:
    """One layer's weights: normal(0, s) with s = ``desc.init_std``, the
    projections back to the residual (``w_o``, ``w_out``, ``w_down``)
    normal(0, s / sqrt(2 L)), norm scales and ``d_skip`` 1, the
    convolution uniform(+-conv^-1/2) with a zero bias, and the mixer's own
    as its family draws them: ``A`` uniform over [1, 16], ``dt_bias`` the
    inverse softplus of a step log-uniform over [1e-3, 1e-1]. Key order:
    (w_qkv, w_o, w_in, conv_w, dt, a, w_out, w_gu, w_down)."""
    mx, d, dh = desc.mixer, desc.dmodel, desc.head_dim
    dt = jnp.dtype(dtype)
    std, out_std = desc.init_std, desc.init_std / math.sqrt(2 * desc.n_layers)
    ks = jax.random.split(key, 9)

    def normal(key, shape, s):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s, dt)

    def ones(n):
        return {"scale": jnp.ones((n,), dt)}

    step = jnp.exp(jax.random.uniform(ks[4], (mx.heads,), jnp.float32)
                   * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    bound = mx.conv ** -0.5
    return {
        "in_norm": ones(d),
        "w_qkv": normal(ks[0], (d, (desc.num_heads + 2 * desc.num_kv_heads)
                                * dh), std),
        "w_o": normal(ks[1], (desc.num_heads * dh, d), out_std),
        "w_in": normal(ks[2], (d, mx.proj_dim), std),
        "conv_w": jax.random.uniform(ks[3], (mx.conv, mx.conv_dim),
                                     jnp.float32, -bound, bound).astype(dt),
        "conv_b": jnp.zeros((mx.conv_dim,), dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "a_log": jnp.log(jax.random.uniform(ks[5], (mx.heads,), jnp.float32,
                                            1.0, 16.0)).astype(dt),
        "d_skip": jnp.ones((mx.heads,), dt),
        "ssm_norm": ones(mx.d_inner),
        "w_out": normal(ks[6], (mx.d_inner, d), out_std),
        "ff_norm": ones(d),
        "w_gu": normal(ks[7], (d, 2 * desc.ffn_hidden), std),
        "w_down": normal(ks[8], (desc.ffn_hidden, d), out_std)}


def init_params(key, desc: ModelDescription, dtype=None) -> dict:
    """Weights from a key, in the tree the engine takes (``models/latent.py``
    has the same): ``embed``, ``runs`` (the layers stacked, one run),
    ``final_norm``, ``lm_head``. Key order: (embed, layers, head), then one
    key a layer."""
    dt = jnp.dtype(dtype or desc.param_dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    std = jnp.asarray(desc.init_std, dt)
    return {"embed": jax.random.normal(
                k_embed, (desc.vocab_size, desc.dmodel), dt) * std,
            "runs": (jax.vmap(lambda k: init_layer(k, desc, dt))(
                jax.random.split(k_layers, desc.n_layers)),),
            "final_norm": {"scale": jnp.ones((desc.dmodel,), dt)},
            "lm_head": jax.random.normal(
                k_head, (desc.dmodel, desc.vocab_size), dt) * std}
