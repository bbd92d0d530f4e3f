"""The gated short convolution of an ``lfm2_moe`` layer, as the engine serves
it beside grouped-query attention in the model's other layers
(``config.ShortConv``).

For a normed input ``u``: ``[B | C | x] = u W_in`` (three thirds of
``dmodel``, in that order), ``v_t = B_t * x_t``, a causal depthwise
convolution of ``taps`` positions a channel with no bias,
``c_t = w_0 v_{t-taps+1} + ... + w_{taps-1} v_t``, then ``y_t = C_t * c_t``
and ``y W_out``. There is no activation anywhere in it, and no recurrent
state: what a slot carries from call to call is the last ``taps - 1`` values
of ``v`` (the *tail*; zeros before a request's first position).

``mixer`` takes the tail and returns the new one, for ``n_valid`` real
positions of the ``T`` it is given (a prefill chunk, or ``T == 1`` for every
slot of a decode step): the new tail ends at the last real position, so a
slot with ``n_valid = 0`` keeps its tail as it was.

Weights of a conv layer: ``w_in`` [D, 3 D], ``conv_w`` [taps, D], ``w_out``
[D, D]. The scopes (docs/COMPONENTS.md): ``conv.proj``, ``conv.taps`` (the
taps and the tail's write, which the engine does under the same name),
``conv.out``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def mixer(block: dict, u: jnp.ndarray, tail: jnp.ndarray,
          n_valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """u [S, T, D] (normed) after each slot's ``tail`` [S, taps - 1, D], of
    which the first ``n_valid`` [S] positions are real -> (the mixer's output
    [S, T, D], the new tail [S, taps - 1, D] in the tail's type)."""
    t, d = u.shape[1], u.shape[2]
    k = tail.shape[1] + 1
    with jax.named_scope("conv.proj"):
        bcx = u @ block["w_in"].astype(u.dtype)
        b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        v = b * x
    with jax.named_scope("conv.taps"):
        window = jnp.concatenate([tail.astype(v.dtype), v], axis=1)
        w = block["conv_w"].astype(v.dtype)
        conv = sum(window[:, i:i + t] * w[i] for i in range(k))
        rows = n_valid[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
        new_tail = jnp.take_along_axis(window, rows[:, :, None], axis=1)
    with jax.named_scope("conv.out"):
        y = (c * conv) @ block["w_out"].astype(u.dtype)
    return y, new_tail.astype(tail.dtype)
