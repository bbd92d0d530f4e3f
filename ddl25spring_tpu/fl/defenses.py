"""Byzantine-robust aggregation rules — pure functions over the client axis.

Capability targets:
- Krum / Multi-Krum selection (attacks_and_defenses.ipynb cells 34, 37):
  score_i = Σ of the n−f−2 smallest squared L2 distances to other updates;
  Krum picks the argmin, Multi-Krum iterates k times removing each winner.
- coordinate-median / trimmed mean (cell 43, 46): per-coordinate stack over
  clients; median, or sort-trim-β then mean.
- majority-sign filtering (cell 49), norm clipping (cell 55).
- Bulyan (hw03 cell 15): Multi-Krum preselection → per-coordinate trimmed
  mean over survivors.
- SparseFed (hw03 cell 26): per-client norm clip → average → global top-k by
  magnitude, rest zeroed.

API note: the reference pre-scales client updates by sample weights and its
coordinate defenses multiply by ·20 (= clients/round) to undo that scaling
(cell 43). Here defenses receive the RAW per-client deltas ``[m, ...]`` plus
the normalized sample weights, so no magic rescale exists: selection rules
return indices (the server re-weights survivors), aggregation rules return
the aggregated delta directly. With equal sample counts the two formulations
are identical.

Everything is jnp over a stacked flat view [m, P] — jit/vmap friendly and
unit-testable against hand-computed cases.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import pytree as pt

PyTree = Any


# ------------------------------------------------------------ flat stacking

def stack_flat(deltas: PyTree) -> Tuple[jnp.ndarray, Callable[[jnp.ndarray], PyTree]]:
    """Stacked pytree (leading client axis m) -> (flat [m, P], unflatten for
    a single [P] vector)."""
    leaves = jax.tree.leaves(deltas)
    treedef = jax.tree.structure(deltas)
    m = leaves[0].shape[0]
    shapes = [l.shape[1:] for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = jnp.concatenate([l.reshape(m, -1) for l in leaves], axis=1)

    def unflatten(vec: jnp.ndarray) -> PyTree:
        parts = []
        off = 0
        for shape, size in zip(shapes, sizes):
            parts.append(vec[off:off + size].reshape(shape))
            off += size
        return jax.tree.unflatten(treedef, parts)

    return flat, unflatten


def unstack_flat(flat: jnp.ndarray, template: PyTree) -> PyTree:
    """Inverse of ``stack_flat`` for a whole [m, P] stack: rebuild the
    stacked pytree (leading client axis m) whose per-leaf trailing shapes
    come from ``template`` (a single un-stacked pytree, e.g. the params).

    The fleet engine (fl/fleet.py) streams per-client deltas off-device as
    flat rows and hands defenses the SAME stacked-tree shape the vmapped
    servers produce; round-tripping through stack_flat is pure
    reshape/concatenate, so the rebuilt stack is bitwise the original."""
    leaves = jax.tree.leaves(template)
    treedef = jax.tree.structure(template)
    m = flat.shape[0]
    parts = []
    off = 0
    for leaf in leaves:
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        parts.append(flat[:, off:off + size].reshape((m,) + leaf.shape))
        off += size
    return jax.tree.unflatten(treedef, parts)


# ------------------------------------------------------------ selection rules

def krum_scores(flat: jnp.ndarray, n_malicious: int) -> jnp.ndarray:
    """Per-client Krum score: sum of its n−f−2 smallest squared distances."""
    m = flat.shape[0]
    d2 = jnp.sum((flat[:, None, :] - flat[None, :, :]) ** 2, axis=-1)  # [m, m]
    d2 = d2 + jnp.diag(jnp.full((m,), jnp.inf))                       # exclude self
    k = max(m - n_malicious - 2, 1)
    nearest = jnp.sort(d2, axis=1)[:, :k]
    return nearest.sum(axis=1)


def krum(flat: jnp.ndarray, n_malicious: int) -> jnp.ndarray:
    """Index of the Krum winner (cell 34)."""
    return jnp.argmin(krum_scores(flat, n_malicious))


def multi_krum(flat: jnp.ndarray, n_malicious: int, k: int) -> jnp.ndarray:
    """k Krum winners, selected iteratively with removal (cell 37).

    Removal is emulated by masking: after each pick, the winner's distances
    are excluded from every later score. Returns [k] indices.
    """
    m = flat.shape[0]
    d2 = jnp.sum((flat[:, None, :] - flat[None, :, :]) ** 2, axis=-1)
    d2 = d2 + jnp.diag(jnp.full((m,), jnp.inf))

    def pick(carry, _):
        removed, d2m = carry
        n_remaining = m - removed.sum()
        kk = jnp.maximum(n_remaining - n_malicious - 2, 1)
        srt = jnp.sort(d2m, axis=1)
        ranks = jnp.arange(m)[None, :]
        scores = jnp.where(ranks < kk, srt, 0.0).sum(axis=1)
        scores = jnp.where(removed, jnp.inf, scores)
        winner = jnp.argmin(scores)
        removed = removed.at[winner].set(True)
        d2m = d2m.at[:, winner].set(jnp.inf)
        return (removed, d2m), winner

    (_, _), winners = jax.lax.scan(pick, (jnp.zeros(m, bool), d2), None, length=k)
    return winners


# ------------------------------------------------------------ coordinate rules

def coordinate_median(flat: jnp.ndarray) -> jnp.ndarray:
    """Per-coordinate median over clients (cell 43)."""
    return jnp.median(flat, axis=0)


def trimmed_mean(flat: jnp.ndarray, beta: float) -> jnp.ndarray:
    """Drop the β-fraction largest and smallest per coordinate, mean the rest
    (cell 46)."""
    m = flat.shape[0]
    t = int(beta * m)
    assert m - 2 * t > 0, f"beta={beta} trims all {m} clients"
    srt = jnp.sort(flat, axis=0)
    return srt[t:m - t].mean(axis=0)


def majority_sign(flat: jnp.ndarray) -> jnp.ndarray:
    """Keep only entries agreeing with the per-coordinate majority sign,
    average them (cell 49)."""
    signs = jnp.sign(flat)
    maj = jnp.sign(signs.sum(axis=0))
    agree = (signs == maj) & (maj != 0)
    # Mean over ALL clients with disagreeing entries zeroed — the reference's
    # formulation (cell 49: zeroed entries stay in the denominator).
    return jnp.where(agree, flat, 0.0).mean(axis=0)


def norm_clipping(flat: jnp.ndarray, ratio: float = 1.0) -> jnp.ndarray:
    """Scale each client update to ≤ mean-norm·ratio, then average (cell 55)."""
    norms = jnp.linalg.norm(flat, axis=1)
    bound = norms.mean() * ratio
    scale = jnp.minimum(1.0, bound / jnp.maximum(norms, 1e-12))
    return (flat * scale[:, None]).mean(axis=0)


def bulyan(flat: jnp.ndarray, n_malicious: int, k: int, beta: float) -> jnp.ndarray:
    """Multi-Krum preselect k survivors, then coordinate trimmed-mean over
    them (hw03 cell 15). When the trim would consume all survivors
    (k ≤ 2·int(β·k), e.g. every β=0.6 grid cell), the reference silently
    skips trimming and means the multi-krum winners as-is (cell 15's
    ``else: trimmed_updates = sorted_updates`` branch) — reproduced here,
    since the hw3 grid sweeps exactly those infeasible cells."""
    winners = multi_krum(flat, n_malicious, k)
    if k - 2 * int(beta * k) > 0:
        return trimmed_mean(flat[winners], beta)
    return flat[winners].mean(axis=0)


def sparse_fed(flat: jnp.ndarray, topk_fraction: float, *, clip_ratio: float = 1.0
               ) -> jnp.ndarray:
    """Per-client norm clip → average → keep the global top-k coordinates by
    magnitude, zero the rest (hw03 cell 26)."""
    avg = norm_clipping(flat, clip_ratio)
    p = avg.shape[0]
    k = max(1, int(topk_fraction * p))
    thresh = jnp.sort(jnp.abs(avg))[p - k]
    return jnp.where(jnp.abs(avg) >= thresh, avg, 0.0)


# ------------------------------------------------------------ server adapters
# FedAvgGradServer's hook signature: defense(deltas_tree [m,...], weights [m])
# -> aggregated delta tree. These adapters lift the rules above into it.

def selection_defense(rule: Callable[..., jnp.ndarray], **kw) -> Callable:
    """Wrap a selection rule (returns indices) — survivors are re-weighted by
    their sample counts, like FedAvgServerDefense (cell 34).

    The returned hook carries its flat [m, P] → [P] core as
    ``hook.flat_hook``: consumers that already hold the flat stack (the
    fleet engine streams per-client deltas off-device as flat rows) apply
    it directly instead of round-tripping through the stacked pytree —
    same ops, so both entry points agree bitwise. The survivors are summed
    by ``tree_weighted_fold``, whose order is the index order wherever the
    hook is traced: XLA associates (and contracts) ``(x * w).sum(0)`` one
    way inside the server's jitted round and another when the fleet engine
    calls the hook op by op (a few units in the last place on the CPU)."""

    def flat_hook(flat: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
        idx = jnp.atleast_1d(rule(flat, **kw))
        w = weights[idx]
        w = w / jnp.maximum(w.sum(), 1e-12)
        return pt.tree_weighted_fold(flat[idx], w)

    def hook(deltas: PyTree, weights: jnp.ndarray) -> PyTree:
        flat, unflatten = stack_flat(deltas)
        return unflatten(flat_hook(flat, weights))

    hook.flat_hook = flat_hook
    return hook


def coordinate_defense(rule: Callable[..., jnp.ndarray], **kw) -> Callable:
    """Wrap an aggregation rule operating on the flat [m, P] stack — the
    FedAvgServerDefenseCoordinate pattern (cell 43). Carries
    ``hook.flat_hook`` like ``selection_defense`` (weights unused — the
    coordinate rules replace the weighted mean entirely)."""

    def flat_hook(flat: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
        return rule(flat, **kw)

    def hook(deltas: PyTree, weights: jnp.ndarray) -> PyTree:
        flat, unflatten = stack_flat(deltas)
        return unflatten(flat_hook(flat, weights))

    hook.flat_hook = flat_hook
    return hook
