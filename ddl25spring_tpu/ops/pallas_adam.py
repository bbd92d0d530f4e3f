"""Fully-fused Adam step as a Pallas TPU kernel: params + moments in one pass.

Why another Adam: ``ops.adam.fused_adam`` collapses optax's multi-stage
update into one jnp expression per leaf, which XLA fuses into a single
elementwise kernel — but the *apply* (``p + u``) still lives outside the
optimizer contract, and XLA's fusion decisions over a 13-leaf tree are its
own. The optimizer leg is pure HBM bandwidth: seven parameter-sized fp32
streams ({p, m, v, g} read, {p, m, v} written) a step. The training cell
runs optax Adam, 22.4 ms of its step (PERF.md section 5); what this kernel
buys is not measured at published widths (PERF.md section 7). This module
commits the whole update rule

    m ← β1·m + (1−β1)·g
    v ← β2·v + (1−β2)·g²
    p ← p − lr · (m/(1−β1^t)) / (√(v/(1−β2^t)) + ε)

to one Pallas kernel per large leaf — seven HBM streams, nothing else — with
``input_output_aliases`` so p/m/v update in place.

Integration: ``FusedApplyAdam`` keeps the optax surface (``init`` /
``update`` — the latter the plain jnp rule, used by ZeRO-1 and anything else
that wants updates without params) and adds ``apply_gradients(params, grads,
state)``, the fused fast path. ``parallel.dp.make_grad_aggregation_step``
duck-types on ``apply_gradients`` and routes through it when present.

Leaf routing: fp32 leaves whose element count is a multiple of 512 and at
least 64 K go through the kernel reshaped to [N/512, 512] lanes-dense tiles;
everything else (norm vectors, odd shapes, non-fp32) falls back to the jnp
rule. At the canonical 288/6/6 config the kernel covers >99.9 % of the 24 M
parameters. Semantics match ``optax.adam`` within float re-association
(asserted in tests/test_pallas_adam.py, interpret mode on CPU; against
``ops.adam.fused_adam`` with the compiled kernel in ``chip_smoke.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .adam import FusedAdamState, adam_leaf_math, fused_adam
from .flash_attention import default_interpret

_LANES = 512          # flattened-leaf row width: 4 × the 128-lane vector
_ROW_BLOCK = 512      # rows per grid step → 1 MB fp32 per operand block
_MIN_PALLAS = 1 << 16  # leaves smaller than this stay on the jnp path


def _adam_kernel(c_ref, p_ref, m_ref, v_ref, g_ref, po_ref, mo_ref, vo_ref,
                 *, lr: float, b1: float, b2: float, eps: float):
    # Mirrors ops.adam.adam_leaf_math on Refs (the shared jnp rule can't be
    # called on Ref reads without materializing extra temporaries) — keep in
    # sync with it.
    # c_ref (SMEM, via scalar prefetch): [c1, c2] bias corrections for the
    # current step — traced values, so they ride in as data, not constants.
    c1 = c_ref[0]
    c2 = c_ref[1]
    g = g_ref[...]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * (g * g)
    mo_ref[...] = m
    vo_ref[...] = v
    po_ref[...] = p_ref[...] - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps",
                                             "interpret"))
def _adam_leaf_pallas(p, m, v, g, corrections, *, lr, b1, b2, eps,
                      interpret=False):
    """One leaf's fused update. p/m/v/g flat-reshaped to [rows, 512]."""
    shape = p.shape
    rows = p.size // _LANES
    p2, m2, v2, g2 = (x.reshape(rows, _LANES) for x in (p, m, v, g))
    block = min(rows, _ROW_BLOCK)
    kernel = functools.partial(_adam_kernel, lr=lr, b1=b1, b2=b2, eps=eps)
    # index_map under scalar prefetch receives (grid_idx, scalar_ref).
    spec = pl.BlockSpec((block, _LANES), lambda i, c: (i, 0))
    po, mo, vo = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, block),),
            in_specs=[spec] * 4,
            out_specs=[spec] * 3,
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)] * 3,
        # p/m/v update in place: input i (after the scalar arg) → output.
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
    )(corrections, p2, m2, v2, g2)
    return po.reshape(shape), mo.reshape(shape), vo.reshape(shape)


def _leaf_jnp(p, m, v, g, c1, c2, *, lr, b1, b2, eps):
    """Fallback: the shared rule (ops.adam.adam_leaf_math) + in-expression
    apply, fused by XLA into one elementwise kernel."""
    u, m, v = adam_leaf_math(g, m, v, c1, c2, lr=lr, b1=b1, b2=b2, eps=eps)
    return p + u, m, v


def _pallas_eligible(p, g) -> bool:
    return (p.dtype == jnp.float32 and g.dtype == jnp.float32
            and p.size >= _MIN_PALLAS and p.size % _LANES == 0)


class FusedApplyAdam:
    """Adam with a Pallas fused param+moment apply (see module docstring).

    optax-compatible: ``.init(params)`` / ``.update(grads, state, params)``
    behave exactly like ``ops.adam.fused_adam`` (one jnp expression per
    leaf). The fast path is ``.apply_gradients(params, grads, state)`` —
    used automatically by ``parallel.dp.make_grad_aggregation_step``.
    """

    def __init__(self, learning_rate: float, b1: float = 0.9,
                 b2: float = 0.999, eps: float = 1e-8,
                 interpret: Optional[bool] = None):
        self.lr, self.b1, self.b2, self.eps = learning_rate, b1, b2, eps
        # interpret=None follows the backend at trace time
        # (flash_attention.default_interpret): interpreted off TPU, which
        # keeps the CPU tests runnable. chip_smoke.py and
        # tests/test_tpu_compile.py pass False themselves.
        self.interpret = interpret
        self._fallback = fused_adam(learning_rate, b1, b2, eps)

    # ---- optax surface -------------------------------------------------
    def init(self, params) -> FusedAdamState:
        return self._fallback.init(params)

    def update(self, grads, state, params=None):
        return self._fallback.update(grads, state, params)

    # ---- fused fast path -----------------------------------------------
    def apply_gradients(self, params, grads, state: FusedAdamState):
        interpret = (default_interpret() if self.interpret is None
                     else self.interpret)
        count = state.count + 1
        cf = count.astype(jnp.float32)
        c1 = 1.0 - self.b1 ** cf
        c2 = 1.0 - self.b2 ** cf
        corrections = jnp.stack([c1, c2])

        hyper = dict(lr=self.lr, b1=self.b1, b2=self.b2, eps=self.eps)
        p_flat, treedef = jax.tree.flatten(params)
        g_flat = jax.tree.leaves(grads)
        m_flat = jax.tree.leaves(state.mu)
        v_flat = jax.tree.leaves(state.nu)
        new_p, new_m, new_v = [], [], []
        for p, m, v, g in zip(p_flat, m_flat, v_flat, g_flat):
            if _pallas_eligible(p, g):
                p2, m2, v2 = _adam_leaf_pallas(
                    p, m, v, g, corrections, interpret=interpret, **hyper)
            else:
                p2, m2, v2 = _leaf_jnp(p, m, v, g.astype(p.dtype), c1, c2,
                                       **hyper)
            new_p.append(p2)
            new_m.append(m2)
            new_v.append(v2)
        unflat = functools.partial(jax.tree.unflatten, treedef)
        return unflat(new_p), FusedAdamState(count, unflat(new_m),
                                             unflat(new_v))
