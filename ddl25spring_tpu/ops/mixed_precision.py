"""Mixed-precision training: bf16 parameters with fp32 master weights.

Capability target (parity-plus; absent in the reference, which trains fp32
torch modules end to end — lab/tutorial_1b/primer/intro.py): the standard
large-model recipe on TPU. The model's parameters live in bf16 — halving
their HBM footprint and the weight-read traffic of every matmul (the
canonical tiny-Llama re-casts fp32 weights to bf16 on every use;
models/llama.py's ``.astype(x.dtype)`` becomes a no-op when params are
already bf16) — while the optimizer accumulates in fp32 so tiny updates
are not rounded away (bf16 has ~8 bits of mantissa; an Adam step of
relative size < 2^-9 would vanish if applied in bf16).

``master_weight_adam`` is a plain ``optax.GradientTransformation``, so it
drops into every step factory here (dp/pp/zero1/compressed):

- state: (count, mu, nu, master) — master is the fp32 copy of the params,
  initialized by upcasting.
- update(grads, state, params): runs the shared Adam rule
  (ops.adam.adam_leaf_math) in fp32 against the master, then returns
  ``updates = master_new.astype(bf16) - params`` — so
  ``optax.apply_updates(params, updates)`` lands the params on the downcast
  master (exact under Sterbenz's lemma whenever consecutive values are
  within 2×, i.e. for Adam-sized steps; tests/test_mixed_precision.py).

The decode path composes: train in bf16+master, serve the bf16 params
directly (the serving cells' weights are bf16).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax

from .adam import adam_leaf_math


class MasterAdamState(NamedTuple):
    count: jnp.ndarray     # [] int32
    mu: optax.Params       # fp32
    nu: optax.Params       # fp32
    master: optax.Params   # fp32 master weights


def master_weight_adam(learning_rate: float, b1: float = 0.9,
                       b2: float = 0.999, eps: float = 1e-8
                       ) -> optax.GradientTransformation:
    def init_fn(params):
        f32 = lambda p: p.astype(jnp.float32)
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return MasterAdamState(jnp.zeros((), jnp.int32),
                               jax.tree.map(zeros, params),
                               jax.tree.map(zeros, params),
                               jax.tree.map(f32, params))

    def update_fn(grads, state, params):
        assert params is not None, (
            "master_weight_adam needs params (optax passes them in every "
            "step factory in this package)")
        count = state.count + 1
        cf = count.astype(jnp.float32)
        c1 = 1.0 - b1 ** cf
        c2 = 1.0 - b2 ** cf

        def leaf(g, m, v, master, p):
            u, m, v = adam_leaf_math(g.astype(jnp.float32), m, v, c1, c2,
                                     lr=learning_rate, b1=b1, b2=b2, eps=eps)
            master = master + u
            # The update is defined so apply_updates lands the params
            # EXACTLY on the downcast master (no drift between the two).
            return (master.astype(p.dtype) - p), m, v, master

        g_flat, treedef = jax.tree.flatten(grads)
        quads = [leaf(g, m, v, w, p) for g, m, v, w, p in
                 zip(g_flat, jax.tree.leaves(state.mu),
                     jax.tree.leaves(state.nu),
                     jax.tree.leaves(state.master),
                     jax.tree.leaves(params))]
        unflat = lambda i: jax.tree.unflatten(treedef,
                                              [q[i] for q in quads])
        return unflat(0), MasterAdamState(count, unflat(1), unflat(2),
                                          unflat(3))

    return optax.GradientTransformation(init_fn, update_fn)
