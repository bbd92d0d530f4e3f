"""Speculative decoding on the paged serving engine: draft-propose, verify.

Each generated token costs one full engine dispatch, which streams every
weight byte whatever the batch (PERF.md section 5: the dense part of a
decode step runs at 90% of weights-once) — so tokens-per-dispatch, not
FLOPs, is the decode lever. No cell runs speculation: what it buys is not
measured at published widths. Speculative decoding buys tokens per dispatch
(ROADMAP item 2b): a cheap DRAFT model proposes ``k`` tokens with ``k``
single-token decode steps over its OWN paged pool, then the target model
scores all ``k + 1`` window positions in ONE donated dispatch over the
block-table cache (``make_verify_step``) and accepts a prefix:

- **greedy** (``temperature == 0``): accept while ``argmax(target) ==
  draft``. Every accepted token IS the target's own argmax at that
  position, and the one correction/bonus token beyond the accepted prefix
  is too — so greedy speculative streams are BITWISE the streams
  ``generate()`` emits alone, at any ``k``, any acceptance rate, any
  draft (the house bar, pinned in tests/test_generate.py).
- **stochastic** (``temperature > 0``): standard rejection sampling —
  draft token ``d ~ q`` is accepted with probability ``min(1, p(d)/q(d))``
  and the first rejection resamples from the normalized residual
  ``max(p - q, 0)`` — which preserves the target distribution ``p``
  exactly (the classic speculative-sampling identity:
  ``Σ_x q(x)·min(1, p(x)/q(x)) + P[reject]·residual(x) = p(x)``), though
  NOT the same sample path as ``generate()``: rejection sampling consumes
  randomness differently, so the stochastic bar is distributional, not
  bitwise. Per-slot RNG discipline keeps the PR 6 invariant: INACTIVE
  slots' keys are untouched (``where``-select), and an active slot's key
  advances exactly once per verify dispatch.

Cache discipline (the part that makes paged speculation correct):

- The verify dispatch writes K/V for all ``k + 1`` window positions
  ``pos .. pos + k``. After accepting ``a`` draft tokens, positions
  ``pos .. pos + a`` hold K/V of accepted stream tokens (valid); positions
  beyond hold K/V of rejected drafts (garbage). The next window starts at
  ``pos + a + 1`` and rewrites every garbage position BEFORE any query can
  attend to it — in-window positions are scattered before the gather
  (engine._block_paged), and positions beyond a row's absolute position
  are masked, the same invariant that makes the trash block safe.
- The draft runs ``k + 1`` single-token dispatches per round: ``k``
  proposals plus one CACHE-FILL consuming its own last proposal, so the
  draft pool is valid through ``pos + k`` even on full acceptance (without
  the fill, an all-accepted round leaves a one-position hole the next
  round's attention would read). Rejected-draft K/V in the draft pool is
  overwritten by the next round exactly like the target's.
- Near the horizon, per-slot ``live = min(k + 1, remaining)`` masks window
  rows whose writes would spill past the slot's reservation to the trash
  block (a ``max_seq_len`` request's block table has no slack — an
  unmasked clamp would wrap onto its own last block).

The compile contract grows from two programs per engine to THREE (prefill
+ decode_step + verify_step; decode_step idles while speculation is on
but remains the non-speculative path) plus the draft's TWO (its own
prefill + decode) — all compiled once, zero retraces across any workload
and any ``k`` (CompileWatch-gated in experiments/serving_bench.py
``--speculate``). A weight hot-swap lands between ``step()`` calls, i.e.
at a VERIFY boundary: a round's draft proposals and its verification
always run under one generation of weights (the draft keeps its own
weights across target swaps — acceptance may drop, correctness cannot:
greedy verification re-derives every token from the target).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..config import LlamaConfig
from ..models import generate, llama
from .kvcache import TRASH_BLOCK, PagedKVConfig, init_pool


@dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knob for one engine: propose ``k`` tokens per
    round with a draft model holding ``draft_params`` (a separately
    weighted tiny-llama — smaller via ``draft_cfg``, or same-arch; a
    SAME-WEIGHTS draft makes greedy acceptance deterministically 1, the
    CPU bench's trick for a deterministic tokens-per-dispatch bar).
    ``draft_cfg=None`` means the target's config (same shapes, its own
    weights). The draft must share the target's vocabulary — proposals
    are token ids the target scores."""

    k: int
    draft_params: dict
    draft_cfg: Optional[LlamaConfig] = None

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"SpecConfig.k={self.k}: propose at least one "
                             "token per round")


# ------------------------------------------------------------ draft engine

class DraftEngine:
    """The draft half of speculation: its own block pool (same geometry as
    the target's, so the TARGET's block tables index it unchanged — one
    allocator serves both), its own prefill/decode programs, its own
    per-slot RNG keys. The parent Engine drives it with the same host-side
    slot state (tables / pos / temps) it feeds the target programs."""

    # Salt folded into a sampling request's key to derive the draft's
    # independent proposal stream (the target's own key must advance
    # exactly as generate()'s does, so the draft cannot share it).
    KEY_SALT = 0x5bec

    def __init__(self, spec: SpecConfig, target_cfg: LlamaConfig,
                 paged: PagedKVConfig, num_slots: int, *,
                 prefill_chunk: int, top_k: Optional[int],
                 top_p: Optional[float], engine_id: Optional[int] = None):
        from . import engine as _engine
        from ..telemetry import introspect

        self.cfg = spec.draft_cfg or target_cfg
        if self.cfg.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"draft vocab {self.cfg.vocab_size} != target vocab "
                f"{target_cfg.vocab_size}: proposals are token ids the "
                "target must be able to score")
        self.k = spec.k
        self.params = spec.draft_params
        self.fused = generate._fuse_blocks(self.params["blocks"])
        self.pool = init_pool(self.cfg, paged)
        self.keys = jnp.zeros((num_slots, 2), jnp.uint32)
        tag = "" if engine_id is None else f"[{engine_id}]"
        self._prefill = introspect.watch(
            _engine.make_prefill_chunk(self.cfg, paged, prefill_chunk,
                                       top_k, top_p),
            name=f"serving/draft_prefill{tag}", max_caches=1)
        # The TARGET's decode factory in its return_probs variant — one
        # paged-cache body serves both models, so cache-indexing fixes
        # can never drift between them (the bitwise bar depends on the
        # two pools agreeing op-for-op).
        self._decode = introspect.watch(
            _engine.make_decode_step(self.cfg, paged, num_slots, top_k,
                                     top_p, return_probs=True),
            name=f"serving/draft_decode{tag}", max_caches=1)

    def admit_key(self, s: int, temperature: float, key) -> None:
        """Seed slot ``s``'s draft proposal stream: an independent child of
        the request key for sampling requests (KEY_SALT), the placeholder
        for greedy ones (argmax never reads it)."""
        if temperature > 0 and key is not None:
            dkey = jax.random.fold_in(key, self.KEY_SALT)
        else:
            dkey = jax.random.PRNGKey(0)
        self.keys = self.keys.at[s].set(dkey)

    def prefill_chunk(self, table_row, chunk, off, n_valid, write_from,
                      temperature) -> None:
        """Mirror one prompt chunk into the draft pool. The sampled token
        and split key are ALWAYS discarded — the draft's first proposal
        comes from its decode program consuming the target's first emitted
        token, so prefill is purely a cache write here."""
        self.pool, _, _ = self._prefill(
            self.pool, self.params, self.fused, table_row, chunk,
            off, n_valid, write_from, self.keys[0],
            jnp.float32(temperature))

    def propose(self, tables, last_tok, pos, temps, active, live):
        """One proposal round: k single-token decode dispatches from the
        target's last emitted tokens, plus the cache-fill dispatch
        consuming the final proposal (module docstring). Rows beyond a
        slot's ``live`` window are masked inactive — their writes go to
        trash and their proposals are never accepted. Returns
        (draft_tokens [S, k], draft_probs [S, k, V])."""
        cur = last_tok
        toks, probs = [], []
        for j in range(self.k + 1):
            step_active = jnp.logical_and(active, j < live)
            self.pool, cur, q, self.keys = self._decode(
                self.pool, self.params, self.fused, tables, cur,
                pos + j, self.keys, temps, step_active)
            if j < self.k:             # the last dispatch is cache-fill
                toks.append(cur)
                probs.append(q)
        return jnp.stack(toks, axis=1), jnp.stack(probs, axis=1)


# ------------------------------------------------------------- verify step

def rejection_accept(sub: jnp.ndarray, p: jnp.ndarray, q: jnp.ndarray,
                     drafts: jnp.ndarray):
    """One slot's stochastic acceptance: standard speculative rejection
    sampling. ``p`` [k+1, V] is the target's sampling distribution at each
    window row, ``q`` [k, V] the draft's at each proposal, ``drafts`` [k]
    the proposals (each sampled from its ``q`` row). Accept proposal ``i``
    while ``u_i < min(1, p_i(d_i)/q_i(d_i))``; the first rejection
    resamples from the normalized residual ``max(p_i - q_i, 0)`` and full
    acceptance draws the bonus token from ``p_k``. Returns
    ``(accepted_count, correction_token)`` — the emitted window is the
    accepted drafts then the correction.

    This is the speculative-sampling identity — emitted tokens are
    distributed EXACTLY as ``p`` row by row
    (``q(x)·min(1, p(x)/q(x)) + (1 - Σ_y min(p, q)(y))·residual(x) =
    p(x)``) — kept standalone so the math is unit-testable against the
    analytic acceptance rate ``Σ_x min(p(x), q(x))`` without a model in
    the loop (tests/test_speculate.py). Randomness discipline: decision
    draws fold ``sub`` per position (2i accept, 2i+1 resample, 2k+1
    bonus) so consumption is fixed no matter where rejection lands —
    the verify program splits a slot's key exactly once per dispatch."""
    k = q.shape[0]
    idx = jnp.arange(k)
    p_tok = jnp.take_along_axis(p[:k], drafts[:, None], axis=-1)[:, 0]
    q_tok = jnp.take_along_axis(q, drafts[:, None], axis=-1)[:, 0]
    u = jax.vmap(lambda i: jax.random.uniform(
        jax.random.fold_in(sub, 2 * i)))(idx)
    accept = u * jnp.maximum(q_tok, 1e-30) < p_tok
    s_acc = jnp.cumprod(accept.astype(jnp.int32)).sum()
    # Residual resample at every candidate rejection row (only the row at
    # s_acc is ever emitted); an all-zero residual (p <= q everywhere,
    # numerically) falls back to p — there rejection has probability ~0,
    # so the fallback only guards against a -inf-everywhere categorical.
    resid = jnp.maximum(p[:k] - q, 0.0)                        # [k, V]
    ok = resid.sum(axis=-1, keepdims=True) > 0
    resid = jnp.where(ok, resid, p[:k])
    logr = jnp.where(resid > 0, jnp.log(jnp.maximum(resid, 1e-30)),
                     -jnp.inf)
    resampled = jax.vmap(
        lambda i: jax.random.categorical(
            jax.random.fold_in(sub, 2 * i + 1), logr[i]))(idx)
    bonus = jax.random.categorical(
        jax.random.fold_in(sub, 2 * k + 1),
        jnp.where(p[k] > 0, jnp.log(jnp.maximum(p[k], 1e-30)), -jnp.inf))
    corr = jnp.where(s_acc < k, resampled[jnp.minimum(s_acc, k - 1)], bonus)
    return s_acc, corr


def make_verify_step(cfg: LlamaConfig, paged: PagedKVConfig,
                     num_slots: int, k: int, top_k: Optional[int],
                     top_p: Optional[float]):
    """ONE compiled program scoring ``k + 1`` positions per slot over the
    block-table cache: the decode step widened to a multi-position window
    (the chunked-prefill scatter/gather machinery with per-slot live
    lengths), with a sampling head at EVERY position and the acceptance
    rule computed in-dispatch — so a speculative round costs exactly one
    target dispatch regardless of how many tokens it lands.

    Inputs: ``window`` [S, k+1] = (last emitted token, then the k draft
    proposals); ``draft_probs`` [S, k, V] = the draft's sampling
    distribution at each proposal (the ``q`` of the rejection test);
    ``live`` [S] masks window rows past a slot's remaining horizon.
    Returns (pool, out_tokens [S, k+1], accepted [S], new_keys): the host
    emits ``out_tokens[s, :min(accepted[s] + 1, remaining)]`` — accepted
    draft tokens re-derived by the target, then one correction (on
    rejection) or bonus (on full acceptance) token."""
    from .engine import _forward_paged  # import here to avoid a cycle

    bl = paged.block_len
    kp1 = k + 1

    @partial(jax.jit, donate_argnums=(0,))
    def verify_step(pool: dict, params: dict, fused: dict,
                    tables: jnp.ndarray, window: jnp.ndarray,
                    draft_probs: jnp.ndarray, pos: jnp.ndarray,
                    live: jnp.ndarray, keys: jnp.ndarray,
                    temps: jnp.ndarray, active: jnp.ndarray):
        mb = tables.shape[1]
        rows = jnp.arange(kp1, dtype=jnp.int32)
        positions = pos[:, None] + rows[None, :]               # [S, k+1]
        writable = jnp.logical_and(active[:, None], rows[None, :] < live[:, None])
        blk_idx = jnp.minimum(positions // bl, mb - 1)
        own = jnp.take_along_axis(tables, blk_idx, axis=1)     # [S, k+1]
        wblk = jnp.where(writable, own, TRASH_BLOCK)
        woff = positions % bl
        h, pool, _ = _forward_paged(params, fused, window, pool, tables,
                                 positions, wblk, woff, cfg)
        logits = llama.head(params, h, cfg)                    # [S, k+1, V]

        # Greedy: the target's argmax at every window position; accept the
        # longest prefix where it re-derives the draft. Each accepted
        # token — and the correction/bonus beyond it — is the token
        # generate() would have emitted, which is the bitwise bar.
        greedy_toks = jnp.argmax(logits, axis=-1)              # [S, k+1]
        drafts = window[:, 1:]                                 # [S, k]
        g_match = greedy_toks[:, :k] == drafts
        g_acc = jnp.cumprod(g_match.astype(jnp.int32), axis=1).sum(axis=1)

        # Stochastic: rejection sampling against the draft's q
        # (``rejection_accept`` — the unit-tested identity). One key
        # split per dispatch per active slot; per-position decision keys
        # fold from the sub-key, so randomness consumption is fixed at
        # one split regardless of where the rejection lands.
        safe_t = jnp.where(temps > 0, temps, 1.0)[:, None, None]
        p = jax.nn.softmax(
            generate.filter_logits(logits / safe_t, top_k, top_p), axis=-1)
        split = jax.vmap(jax.random.split)(keys)
        subs = split[:, 1]
        new_keys = jnp.where(active[:, None], split[:, 0], keys)
        s_acc, s_corr = jax.vmap(rejection_accept)(subs, p, draft_probs,
                                                   drafts)
        # Stochastic out tokens: accepted drafts verbatim, the
        # correction/bonus at row s_acc, bonus at row k on full accept.
        base = jnp.concatenate(
            [drafts, jnp.zeros((num_slots, 1), drafts.dtype)], axis=1)
        st_toks = jnp.where(rows[None, :] == s_acc[:, None],
                            s_corr[:, None], base)

        sampled = temps > 0
        out = jnp.where(sampled[:, None], st_toks, greedy_toks)
        accepted = jnp.where(sampled, s_acc, g_acc).astype(jnp.int32)
        return pool, out, accepted, new_keys

    return verify_step

