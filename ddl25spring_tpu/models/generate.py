"""Autoregressive decoding for the tiny-Llama: KV cache + sampling.

Parity-plus: the reference's training stack (simplellm surface, SURVEY.md
§2.9) never decodes — but a framework a reference user can *switch to* needs
inference. TPU-native shape of the problem:

- The KV cache is a pair of static-shape ``[L, B, max_len, H, Dh]`` arrays
  (stacked-layer layout, matching the model's scanned ``[L, ...]`` blocks).
  Static shapes mean one compile for prefill and one for the decode step —
  no per-length recompilation; position is a traced scalar.
- The whole generation loop is a single ``lax.scan`` over decode steps —
  one compiled program per (batch, prompt_len, max_new) shape, sampling
  included; nothing returns to Python between tokens.
- Cache updates are ``lax.dynamic_update_slice`` writes; with the step jitted
  and the cache donated, XLA performs them in place.
- Decode attention masks by absolute position (``kpos <= pos``), so the
  cache's unwritten tail is unread garbage, not a correctness hazard.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..config import LlamaConfig
from .. import nn
from . import llama


def init_cache(cfg: LlamaConfig, batch: int, max_len: int,
               kv_dtype: Optional[str] = None) -> dict:
    """Zeroed KV cache: {"k","v"} each [L, B, max_len, H, Dh]. ``max_len``
    bounds prompt + generated tokens. ``kv_dtype`` overrides the storage
    dtype (default: the compute dtype): serving decode re-reads the whole
    cache every step, so bf16 storage halves the per-step KV bytes (the
    serving cells run bf16 weights and cache; PERF.md section 5 has the
    decode step's parts). K is stored post-RoPE and
    attention runs fp32 softmax either way; the only precision change is
    the rounding of cached K/V."""
    dt = jnp.dtype(kv_dtype or cfg.dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.num_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _attend_cached(q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray,
                   q_positions: jnp.ndarray) -> jnp.ndarray:
    """Attention of q [B, Tq, H, Dh] over the full cache [B, Tmax, H, Dh],
    masked to ``kpos <= q_position`` per query row. fp32 softmax, heads
    folded into batch (the same layout as llama._xla_attention)."""
    b, tq, h, dh = q.shape
    tmax = ck.shape[1]
    scale = 1.0 / math.sqrt(dh)
    qm = q.transpose(0, 2, 1, 3).reshape(b * h, tq, dh)
    # Casts after the transpose/reshape fuse into the dots: the HBM read is
    # of the cache's storage dtype (bf16 when kv_dtype narrows it).
    km = ck.transpose(0, 2, 1, 3).reshape(b * h, tmax, dh).astype(q.dtype)
    vm = cv.transpose(0, 2, 1, 3).reshape(b * h, tmax, dh).astype(q.dtype)
    scores = lax.dot_general(qm, km, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32) * scale
    mask = q_positions[:, None] >= jnp.arange(tmax)[None, :]   # [Tq, Tmax]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = lax.dot_general(probs, vm, (((2,), (1,)), ((0,), (0,))))
    return out.reshape(b, h, tq, dh).transpose(0, 2, 1, 3)


def _fuse_blocks(blocks: dict) -> dict:
    """Pre-concatenate each layer's QKV and gate/up weights (leading [L] axis
    preserved). Training fuses these per call — fine there, the concat is
    noise next to a [B·T, D] matmul — but the decode loop runs matVECs, which
    are weight-bandwidth-bound: a per-token concat would read and re-write
    every weight byte it is about to stream, doubling traffic. Fusing once
    per generate() call keeps the hot loop at one read per weight byte."""
    return {
        "attn_norm": blocks["attn_norm"],
        "mlp_norm": blocks["mlp_norm"],
        "w_qkv": jnp.concatenate([blocks["wq"], blocks["wk"], blocks["wv"]],
                                 axis=-1),
        "wo": blocks["wo"],
        "w_gu": jnp.concatenate([blocks["w_gate"], blocks["w_up"]], axis=-1),
        "w_down": blocks["w_down"],
    }


def _block_with_cache(block: dict, ck: jnp.ndarray, cv: jnp.ndarray,
                      x: jnp.ndarray, positions: jnp.ndarray, start: jnp.ndarray,
                      cfg: LlamaConfig) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One pre-fused block over x [B, T, D] at absolute ``positions`` [T],
    writing this call's K/V into the cache at offset ``start`` and attending
    over the whole cache. Serves both prefill (T = prompt length, start = 0)
    and decode (T = 1, start = pos). Same math as llama.block_apply —
    asserted against llama.forward position-by-position in
    tests/test_generate.py."""
    b, t, d = x.shape
    dh = cfg.head_dim
    xn = nn.rmsnorm(block["attn_norm"], x, eps=cfg.norm_eps)
    qkv = xn @ block["w_qkv"].astype(x.dtype)
    dl = qkv.shape[-1] // 3
    h_local = dl // dh
    q = qkv[..., :dl].reshape(b, t, h_local, dh)
    k = qkv[..., dl:2 * dl].reshape(b, t, h_local, dh)
    v = qkv[..., 2 * dl:].reshape(b, t, h_local, dh)
    cos, sin = llama.rope_angles(positions, dh, cfg.rope_theta)
    q = llama.apply_rope(q, cos, sin)
    k = llama.apply_rope(k, cos, sin)          # cached K is stored post-RoPE
    ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, start, 0, 0))
    cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, start, 0, 0))
    out = _attend_cached(q, ck, cv, positions)
    x = x + out.reshape(b, t, h_local * dh) @ block["wo"].astype(x.dtype)
    xn = nn.rmsnorm(block["mlp_norm"], x, eps=cfg.norm_eps)
    gu = xn @ block["w_gu"].astype(x.dtype)
    f = gu.shape[-1] // 2
    x = x + (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ block["w_down"].astype(x.dtype)
    return x, ck, cv


def _forward_fused(params: dict, fused_blocks: dict, tokens: jnp.ndarray,
                   cache: dict, start, cfg: LlamaConfig
                   ) -> Tuple[jnp.ndarray, dict]:
    """Body of forward_cached, taking blocks already through _fuse_blocks."""
    t = tokens.shape[1]
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(t)
    h = llama.embed(params, tokens, cfg)

    def body(carry, layer):
        block, ck, cv = layer
        out, ck, cv = _block_with_cache(block, ck, cv, carry, positions,
                                        start, cfg)
        return out, (ck, cv)

    h, (ck, cv) = lax.scan(body, h, (fused_blocks, cache["k"], cache["v"]))
    logits = llama.head(params, h[:, -1:, :], cfg)[:, 0, :]
    return logits, {"k": ck, "v": cv}


def forward_cached(params: dict, tokens: jnp.ndarray, cache: dict,
                   start, cfg: LlamaConfig
                   ) -> Tuple[jnp.ndarray, dict]:
    """tokens [B, T] at absolute positions start..start+T → (logits of the
    LAST position [B, V] fp32, updated cache). One lax.scan over the stacked
    blocks, threading each layer's cache slice through the scanned axis."""
    return _forward_fused(params, _fuse_blocks(params["blocks"]), tokens,
                          cache, start, cfg)


def filter_logits(logits: jnp.ndarray, top_k: Optional[int],
                  top_p: Optional[float]) -> jnp.ndarray:
    """Apply the top_k / top_p (nucleus) filters to temperature-scaled
    logits [B, V]. The filters compose: k-truncation first, then the
    smallest prefix of the remaining distribution whose mass reaches p.

    The ONE implementation of the filter contract: the serving engine's
    per-slot sampler (serving/engine.py) calls this too, and its
    bitwise-parity bar means the two paths must stay the same ops — keep
    any change here."""
    if top_k is not None:
        kth = lax.top_k(logits, top_k)[0][..., -1:]    # [B, 1]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # Static-shape nucleus filter: one descending sort + cumsum, then a
        # per-row logit threshold — no gather/scatter back through sort
        # indices. A token is kept iff the mass of strictly-better tokens is
        # < p (so the top token always survives, and the boundary token that
        # crosses p is included, matching the usual nucleus definition).
        sorted_logits = -jnp.sort(-logits, axis=-1)            # descending
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        mass_before = jnp.cumsum(probs, axis=-1) - probs       # exclusive
        kept = mass_before < top_p                             # [B, V]
        thresh = jnp.min(jnp.where(kept, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)               # [B, 1]
        logits = jnp.where(logits < thresh, -jnp.inf, logits)
    return logits


def _sample(key, logits: jnp.ndarray, temperature: float,
            top_k: Optional[int], top_p: Optional[float]) -> jnp.ndarray:
    """logits [B, V] → token ids [B]. temperature 0 = greedy (argmax)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = filter_logits(logits / temperature, top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1)


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "temperature",
                                   "top_k", "top_p", "max_len", "kv_dtype"))
def generate(params: dict, prompt: jnp.ndarray, cfg: LlamaConfig,
             max_new_tokens: int, *, key: Optional[jax.Array] = None,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             max_len: Optional[int] = None,
             kv_dtype: Optional[str] = None) -> jnp.ndarray:
    """prompt [B, Tp] → generated ids [B, max_new_tokens].

    One compiled program: prefill over the prompt, then a lax.scan of
    single-token decode steps with in-place cache writes. Greedy by default;
    ``temperature``/``top_k``/``top_p`` enable sampling (``key`` required
    then). ``kv_dtype`` narrows the cache storage dtype (init_cache).
    """
    b, tp = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    assert top_p is None or 0.0 < top_p <= 1.0, \
        f"top_p must be in (0, 1], got {top_p}"  # p<=0 would mask every token
    if max_len is None:
        max_len = tp + max_new_tokens
    if max_len < tp + max_new_tokens:
        # Hard error, not an assert: an oversized request would silently
        # write K/V past the masked range (dynamic_update_slice clamps the
        # start index, so late positions OVERWRITE earlier cache entries)
        # and the tail tokens would be garbage — and `python -O` would
        # strip an assert entirely. Raised at trace time, so it fires on
        # the first call of each shape, jit or not.
        raise ValueError(
            f"prompt_len + max_new_tokens = {tp} + {max_new_tokens} = "
            f"{tp + max_new_tokens} exceeds max_len={max_len}: the KV cache "
            f"only holds max_len positions, so the request cannot fit — "
            f"raise max_len or shorten the request")
    if key is None:
        assert temperature == 0.0, "sampling (temperature>0) requires a key"
        key = jax.random.PRNGKey(0)   # unused by greedy argmax
    cache = init_cache(cfg, b, max_len, kv_dtype)
    fused = _fuse_blocks(params["blocks"])   # once, hoisted out of the scan
    logits, cache = _forward_fused(params, fused, prompt, cache, 0, cfg)
    key, sub = jax.random.split(key)
    first = _sample(sub, logits, temperature, top_k, top_p)

    def step(carry, _):
        cache, tok, pos, key = carry
        logits, cache = _forward_fused(params, fused, tok[:, None], cache,
                                       pos, cfg)
        key, sub = jax.random.split(key)
        nxt = _sample(sub, logits, temperature, top_k, top_p)
        return (cache, nxt, pos + 1, key), nxt

    carry = (cache, first, jnp.asarray(tp, jnp.int32), key)
    _, rest = lax.scan(step, carry, None, length=max_new_tokens - 1)
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def speculative_stream(params: dict, draft_params: dict,
                       prompt, cfg: LlamaConfig, max_new_tokens: int, *,
                       k: int, draft_cfg: Optional[LlamaConfig] = None):
    """REFERENCE greedy speculative decoding — the parity twin of the
    serving engine's draft-propose / verify round (serving/speculate.py),
    written as the obviously-correct O(T²) re-forward loop (the same
    style as tests/test_generate.py's greedy reference): the draft
    proposes ``k`` tokens by argmax over its own full forward, the target
    scores the whole window in one forward, and the accepted prefix plus
    one correction/bonus token extends the stream.

    Greedy speculative decoding emits EXACTLY the greedy stream — every
    accepted token is re-derived as the target's own argmax and so is the
    token beyond the accepted prefix — so the returned tokens equal
    ``generate(params, prompt, cfg, max_new_tokens)``'s bitwise at any
    ``k`` and any draft (pinned in tests/test_generate.py). Returns
    ``(tokens, stats)`` with ``stats`` counting proposed/accepted draft
    tokens and target rounds — the acceptance-rate accounting the
    engine's schema-v7 ``speculate`` events report per dispatch.

    Deliberately NOT a production path (each round re-runs full forwards;
    one compile per sequence length): it exists so the engine's
    one-dispatch verify program has an independent, hand-checkable
    reference for both the emitted stream and the acceptance counts."""
    dcfg = draft_cfg or cfg
    if k < 1 or max_new_tokens < 1:
        raise ValueError(f"k={k}, max_new_tokens={max_new_tokens}")
    seq = jnp.asarray(prompt, jnp.int32).reshape(1, -1)
    out = []
    stats = {"proposed": 0, "accepted": 0, "rounds": 0, "k": k}
    while len(out) < max_new_tokens:
        d_seq = seq
        drafts = []
        for _ in range(k):
            d_log = llama.forward(draft_params, d_seq, dcfg)[:, -1, :]
            d_tok = jnp.argmax(d_log, axis=-1)
            drafts.append(int(d_tok[0]))
            d_seq = jnp.concatenate([d_seq, d_tok[:, None]], axis=1)
        window = jnp.concatenate(
            [seq, jnp.asarray(drafts, jnp.int32)[None, :]], axis=1)
        t_log = llama.forward(params, window, cfg)[0]          # [T, V]
        base = seq.shape[1] - 1
        targets = [int(jnp.argmax(t_log[base + i])) for i in range(k + 1)]
        a = 0
        while a < k and targets[a] == drafts[a]:
            a += 1
        remaining = max_new_tokens - len(out)
        emit = targets[:a + 1][:remaining]
        # Horizon truncation never reads as rejection: proposals past
        # max_new could never be emitted, so — the engine's schema-v7
        # rule — only min(k, remaining) count as proposed (a same-weights
        # draft stays at acceptance exactly 1 at any max_new).
        stats["proposed"] += min(k, remaining)
        stats["accepted"] += min(a, len(emit))
        stats["rounds"] += 1
        out.extend(emit)
        seq = jnp.concatenate(
            [seq, jnp.asarray(emit, jnp.int32)[None, :]], axis=1)
    return out, stats
