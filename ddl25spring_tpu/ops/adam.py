"""Single-pass fused Adam — an optax-compatible GradientTransformation.

Why: ``optax.adam`` composes scale_by_adam → scale(-lr), each stage a
separate tree_map producing materialized intermediates (updated moments,
bias-corrected copies, scaled updates). On a memory-bound optimizer step
that is several extra HBM round trips over the full parameter footprint.
Here the whole update rule is one jnp expression per leaf —

    m ← β1·m + (1−β1)·g
    v ← β2·v + (1−β2)·g²
    u = −lr · (m/(1−β1^t)) / (√(v/(1−β2^t)) + ε)

— so XLA fuses it into a single read of (g, m, v) and a single write of
(u, m, v) per leaf. Semantics match ``optax.adam(lr, b1, b2, eps)`` bitwise
up to float re-association (asserted ≤1e-6 in tests/test_core.py).

Drop-in: ``fused_adam(8e-4)`` anywhere an ``optax.GradientTransformation``
is accepted (dp/pp/ep steps, train.llm).

ZeRO-1 note (parallel/dp.py): Adam is elementwise — the update at
coordinate i depends only on (g, m, v) at i — so applying it to a 1/N
slice of the flattened parameter vector commutes with slicing. That is
the property the sharded weight update relies on for exact equivalence
with the replicated update, and it holds for every transformation in this
module.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax


def resize_zero_padded(vec, new_len: int):
    """Resize a ZeRO-1 padded flat vector (params / Adam mu / Adam nu slice
    stack) from its N-way padded length to an M-way padded length — the
    elementwise core of cross-topology optimizer-state resharding
    (resilience/elastic.py, checkpoint reshard-on-load).

    Valid because the pad region of every ZeRO-1 flat vector is EXACTLY
    zero, forever: the padded gradient tail is zero by construction
    (``jnp.pad`` in ``parallel/dp.py``), so mu/nu at pad coordinates stay
    ``b·0 + (1−b)·0 = 0`` and the padded param tail steps by
    ``−lr·(0/c1)/(√(0/c2)+ε) = 0`` under every elementwise rule in this
    module. Truncating the tail therefore loses nothing and extending it
    appends the zeros a larger pad would have carried — the resized vector
    is bit-identical to the one an M-way ``_zero1_setup`` would have built
    from the same unpadded content. A non-zero truncated tail means the
    vector is NOT a zero-padded slice stack (layout bug or corrupted
    state), and silently dropping real data would poison the run — hard
    error instead."""
    vec = np.asarray(vec)
    if vec.ndim != 1:
        raise ValueError(f"resize_zero_padded wants a flat vector, got "
                         f"shape {vec.shape}")
    if new_len == vec.shape[0]:
        return vec
    if new_len < vec.shape[0]:
        tail = vec[new_len:]
        if tail.any():
            raise ValueError(
                f"cannot truncate {vec.shape[0]} -> {new_len}: tail is not "
                f"all-zero (max |tail| = {np.abs(tail).max()}) — not a "
                "zero-padded ZeRO-1 vector")
        return vec[:new_len]
    return np.concatenate([vec, np.zeros(new_len - vec.shape[0], vec.dtype)])


def apply_optimizer(optimizer, grads, opt_state, params):
    """One optimizer application: the duck-typed ``apply_gradients`` fast
    path when the optimizer provides it (ops.pallas_adam.FusedApplyAdam —
    one fused kernel pass over {p, m, v, g} instead of update + apply),
    else the plain optax update. Shared by every step factory that
    consumes averaged gradients (parallel/dp.py — including the ZeRO-1
    slice update, where the fast path runs on each replica's 1/N shard —
    and parallel/compress.py)."""
    if hasattr(optimizer, "apply_gradients"):
        return optimizer.apply_gradients(params, grads, opt_state)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state


class FusedAdamState(NamedTuple):
    count: jnp.ndarray   # [] int32
    mu: optax.Params
    nu: optax.Params


def adam_leaf_math(g, m, v, c1, c2, *, lr: float, b1: float, b2: float,
                   eps: float):
    """The per-leaf Adam recurrence, shared by every implementation here
    and by ops.pallas_adam's jnp fallback (the Pallas kernel mirrors this
    expression on Refs — keep the two in sync). Returns (update, m, v);
    the update is the signed step BEFORE it is added to the params."""
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    u = (-lr) * (m / c1) / (jnp.sqrt(v / c2) + eps)
    return u, m, v


def fused_adam(learning_rate: float, b1: float = 0.9, b2: float = 0.999,
               eps: float = 1e-8) -> optax.GradientTransformation:
    def init_fn(params):
        zeros = lambda p: jnp.zeros_like(p)
        return FusedAdamState(jnp.zeros((), jnp.int32),
                              jax.tree.map(zeros, params),
                              jax.tree.map(zeros, params))

    def update_fn(grads, state, params=None):
        del params
        count = state.count + 1
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)

        def leaf(g, m, v):
            u, m, v = adam_leaf_math(g, m, v, c1, c2, lr=learning_rate,
                                     b1=b1, b2=b2, eps=eps)
            return u.astype(g.dtype), m, v

        # Flatten-then-unflatten rather than a tree.map returning tuples:
        # grads trees may themselves contain tuple nodes, which an
        # is_leaf=isinstance(x, tuple) unzip would mistake for leaf triples.
        g_flat, treedef = jax.tree.flatten(grads)
        triples = [leaf(g, m, v) for g, m, v in
                   zip(g_flat, jax.tree.leaves(state.mu),
                       jax.tree.leaves(state.nu))]
        updates = jax.tree.unflatten(treedef, [t[0] for t in triples])
        mu = jax.tree.unflatten(treedef, [t[1] for t in triples])
        nu = jax.tree.unflatten(treedef, [t[2] for t in triples])
        return updates, FusedAdamState(count, mu, nu)

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer(opt_name: str, lr: float = 8e-4):
    """``TrainConfig.optimizer`` other than "adam" -> optimizer instance.
    "fused" = ``fused_adam`` above (same update as optax.adam, asserted
    ≤1e-6 in tests/test_core.py); "pallas" = the fully-fused Pallas apply
    (ops/pallas_adam.py — moments + param write in one kernel pass per
    leaf); "master" = fp32-master-weight Adam for bf16 params
    (ops/mixed_precision.py — pair with ``param_dtype="bfloat16"``). Which
    of them is fastest is not measured at published widths (PERF.md
    section 7)."""
    if opt_name == "pallas":
        from .pallas_adam import FusedApplyAdam
        return FusedApplyAdam(lr)
    if opt_name == "master":
        from .mixed_precision import master_weight_adam
        return master_weight_adam(lr)
    if opt_name != "fused":
        raise ValueError(
            f"unknown optimizer {opt_name!r}: expected one of "
            "'fused', 'pallas', 'master'")
    return fused_adam(lr)
