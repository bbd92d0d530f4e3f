"""KV-cache decoding vs the full forward pass.

The cache path must be a pure re-arrangement of the same math: prefill+decode
logits are compared against `llama.forward` at every position, and greedy
generation must equal the O(T²) re-forward argmax loop.
"""

import jax
import jax.numpy as jnp
import pytest

from ddl25spring_tpu.config import LlamaConfig
from ddl25spring_tpu.models import generate, llama

CFG = LlamaConfig(vocab_size=97, dmodel=32, num_heads=4, n_layers=3,
                  ctx_size=32)


@pytest.fixture(scope="module")
def params():
    return llama.init_llama(jax.random.PRNGKey(0), CFG)


def test_prefill_matches_forward(params):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, CFG.vocab_size)
    full = llama.forward(params, tokens, CFG)            # [B, T, V]
    cache = generate.init_cache(CFG, 2, 16)
    logits, _ = generate.forward_cached(params, tokens, cache, 0, CFG)
    assert jnp.allclose(logits, full[:, -1, :], atol=1e-4)


def test_decode_steps_match_forward(params):
    """Feed tokens one at a time through the cache; every step's logits must
    equal the full forward's logits at that position."""
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, CFG.vocab_size)
    full = llama.forward(params, tokens, CFG)
    cache = generate.init_cache(CFG, 2, 8)
    for t in range(tokens.shape[1]):
        logits, cache = generate.forward_cached(
            params, tokens[:, t:t + 1], cache, t, CFG)
        assert jnp.allclose(logits, full[:, t, :], atol=1e-4), t


def test_prefill_then_decode_matches_forward(params):
    """Mixed mode: prefill 5 tokens, decode 3 more — each decode step must
    agree with the all-at-once forward over the concatenation."""
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, CFG.vocab_size)
    full = llama.forward(params, tokens, CFG)
    cache = generate.init_cache(CFG, 1, 8)
    logits, cache = generate.forward_cached(params, tokens[:, :5], cache, 0, CFG)
    assert jnp.allclose(logits, full[:, 4, :], atol=1e-4)
    for t in range(5, 8):
        logits, cache = generate.forward_cached(
            params, tokens[:, t:t + 1], cache, t, CFG)
        assert jnp.allclose(logits, full[:, t, :], atol=1e-4), t


def test_greedy_generate_matches_reforward_loop(params):
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 4), 0, CFG.vocab_size)
    out = generate.generate(params, prompt, CFG, 6)
    assert out.shape == (2, 6)
    # Reference: naive O(T²) loop re-running the full forward each step.
    seq = prompt
    want = []
    for _ in range(6):
        logits = llama.forward(params, seq, CFG)[:, -1, :]
        nxt = jnp.argmax(logits, axis=-1)
        want.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    assert jnp.array_equal(out, jnp.stack(want, axis=1))


def test_sampled_generate_respects_top_k(params):
    prompt = jnp.zeros((1, 2), jnp.int32)
    out = generate.generate(params, prompt, CFG, 5, key=jax.random.PRNGKey(7),
                            temperature=0.8, top_k=3)
    assert out.shape == (1, 5)
    # Replay with the cache to check every sampled id was inside the top-3
    # of its step's distribution.
    cache = generate.init_cache(CFG, 1, 7)
    logits, cache = generate.forward_cached(params, prompt, cache, 0, CFG)
    for i in range(5):
        top3 = set(jax.lax.top_k(logits[0], 3)[1].tolist())
        assert int(out[0, i]) in top3, i
        if i < 4:
            logits, cache = generate.forward_cached(
                params, out[:, i:i + 1], cache, 2 + i, CFG)


def test_nucleus_filter_keeps_smallest_covering_prefix():
    """_sample with top_p on a hand-built distribution: probs
    (0.5, 0.3, 0.15, 0.05) → p=0.6 keeps {0, 1} (token 1 crosses the
    boundary and is included), p=0.4 keeps only {0}, p=1.0 keeps all."""
    probs = jnp.array([[0.5, 0.3, 0.15, 0.05]])
    logits = jnp.log(probs)
    keys = jax.random.split(jax.random.PRNGKey(3), 200)

    def support(top_p):
        ids = [int(generate._sample(k, logits, 1.0, None, top_p)[0])
               for k in keys]
        return set(ids)

    assert support(0.4) == {0}
    assert support(0.6) <= {0, 1} and 1 in support(0.6)
    assert support(1.0) <= {0, 1, 2, 3} and len(support(1.0)) >= 3


def test_sampled_generate_respects_top_p(params):
    prompt = jnp.zeros((1, 2), jnp.int32)
    out = generate.generate(params, prompt, CFG, 4, key=jax.random.PRNGKey(9),
                            temperature=0.8, top_p=0.9)
    assert out.shape == (1, 4)
    # Replay: every sampled id must lie in the nucleus (smallest prefix of
    # the temperature-scaled distribution reaching 0.9) of its step.
    cache = generate.init_cache(CFG, 1, 6)
    logits, cache = generate.forward_cached(params, prompt, cache, 0, CFG)
    for i in range(4):
        p = jax.nn.softmax(logits[0] / 0.8)
        order = jnp.argsort(-p)
        mass_before = jnp.cumsum(p[order]) - p[order]
        nucleus = set(order[mass_before < 0.9].tolist())
        assert int(out[0, i]) in nucleus, i
        if i < 3:
            logits, cache = generate.forward_cached(
                params, out[:, i:i + 1], cache, 2 + i, CFG)


def test_padding_idx_zero_embedding_in_decode():
    cfg = LlamaConfig(vocab_size=97, dmodel=32, num_heads=4, n_layers=2,
                      ctx_size=16, padding_idx=0)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    tokens = jnp.array([[0, 5, 0, 7]], jnp.int32)
    full = llama.forward(params, tokens, cfg)
    cache = generate.init_cache(cfg, 1, 4)
    for t in range(4):
        logits, cache = generate.forward_cached(
            params, tokens[:, t:t + 1], cache, t, cfg)
        assert jnp.allclose(logits, full[:, t, :], atol=1e-4), t


def test_generate_with_sharded_params_and_batch(params, devices):
    """Distributed inference: params replicated / batch sharded over a
    ``data`` mesh axis must decode exactly what one device decodes —
    jit partitions the whole prefill+decode program via GSPMD."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ddl25spring_tpu.parallel import make_mesh

    prompt = jax.random.randint(jax.random.PRNGKey(9), (4, 5), 0,
                                CFG.vocab_size)
    want = generate.generate(params, prompt, CFG, 6)

    mesh = make_mesh({"data": 2}, devices=devices[:2])
    p_sh = jax.device_put(params, NamedSharding(mesh, P()))
    prompt_sh = jax.device_put(prompt, NamedSharding(mesh, P("data")))
    got = generate.generate(p_sh, prompt_sh, CFG, 6)
    assert jnp.array_equal(want, got)


def test_generate_oversized_request_raises(params):
    """prompt_len + max_new_tokens > max_len must be a clear ValueError,
    not a silent out-of-range cache write (dynamic_update_slice would clamp
    the start index and OVERWRITE earlier positions, producing garbage tail
    tokens)."""
    prompt = jnp.zeros((1, 6), jnp.int32)
    with pytest.raises(ValueError, match="exceeds max_len"):
        generate.generate(params, prompt, CFG, 4, max_len=8)   # needs 10
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate.generate(params, prompt, CFG, 0)
    # The boundary case fits exactly and must NOT raise.
    out = generate.generate(params, prompt, CFG, 4, max_len=10)
    assert out.shape == (1, 4)


# ----------------------------------------------------- serving-engine parity
# The slot-based prefill()/decode_step() engine (ddl25spring_tpu/serving)
# re-arranges this module's math over a paged block pool; these tests pin
# that it reproduces generate() TOKEN-FOR-TOKEN at equal seeds — the
# serving subsystem's correctness bar (ISSUE 6).

def _paged():
    from ddl25spring_tpu.serving import PagedKVConfig
    return PagedKVConfig(num_blocks=32, block_len=4, max_blocks_per_seq=8)


def _engine_streams(params, requests, *, num_slots, prefill_chunk,
                    top_k=None, top_p=None, speculate=None):
    """Run ragged ``(prompt, max_new, temperature, seed)`` requests in ONE
    slot batch; returns each slot's emitted tokens."""
    import numpy as np

    from ddl25spring_tpu.serving import Engine
    eng = Engine(params, CFG, _paged(), num_slots,
                 prefill_chunk=prefill_chunk, top_k=top_k, top_p=top_p,
                 speculate=speculate)
    slots = {}
    for i, (prompt, mx, temp, seed) in enumerate(requests):
        key = jax.random.PRNGKey(seed) if temp > 0 else None
        s = eng.admit(np.asarray(prompt, np.int32), mx, temperature=temp,
                      key=key)
        slots[i] = s
    toks = {s: [] for s in slots.values()}
    while eng.busy:
        for ev in eng.step():
            toks[ev.slot].append(ev.token)
    return [toks[slots[i]] for i in range(len(requests))]


def _generate_stream(params, prompt, mx, temp, seed, *, top_k=None,
                     top_p=None):
    # The ONE reference-construction helper (serving/frontend.py) — the
    # rules that make the parity bar valid (max_len/kv_dtype pinned to the
    # pool, key only when sampling) must not be re-derived here.
    from ddl25spring_tpu.serving import Request, reference_stream
    req = Request(rid="ref", prompt=tuple(int(t) for t in prompt),
                  max_new=mx, temperature=temp, seed=seed)
    return reference_stream(params, CFG, _paged(), req, top_k=top_k,
                            top_p=top_p)


def test_slot_engine_matches_generate_greedy_bitwise(params):
    """Ragged greedy prompts sharing one slot batch: each stream must be
    BITWISE the stream generate() emits for that request alone."""
    rng = jax.random.PRNGKey(21)
    reqs = []
    for i, (tp, mx) in enumerate([(3, 6), (9, 4), (5, 8)]):
        rng, sub = jax.random.split(rng)
        prompt = jax.random.randint(sub, (tp,), 0, CFG.vocab_size).tolist()
        reqs.append((prompt, mx, 0.0, 0))
    got = _engine_streams(params, reqs, num_slots=3, prefill_chunk=4)
    for (prompt, mx, temp, seed), stream in zip(reqs, got):
        assert stream == _generate_stream(params, prompt, mx, temp, seed)


def test_slot_engine_matches_generate_sampled_bitwise(params):
    """Temperature sampling at equal seeds, mixed with a greedy neighbor in
    the same batch: per-slot RNG keys must reproduce generate()'s exact
    split sequence regardless of batch company."""
    reqs = [([5, 17, 3], 6, 0.8, 13),
            ([2, 9, 41, 7, 30, 11, 4], 5, 0.6, 99),
            ([8, 8], 7, 0.0, 0)]
    got = _engine_streams(params, reqs, num_slots=3, prefill_chunk=4)
    for (prompt, mx, temp, seed), stream in zip(reqs, got):
        assert stream == _generate_stream(params, prompt, mx, temp, seed)


def test_slot_engine_chunked_prefill_matches_whole_prompt(params):
    """A prompt split over several prefill chunks (chunk < prompt_len) must
    emit the same stream as one-shot prefill — chunking is a latency
    decision, not a math change. Also pins the RNG discipline: the key
    splits ONCE per prefill no matter how many chunks carry it."""
    prompt = [int(x) for x in
              jax.random.randint(jax.random.PRNGKey(5), (11,), 0,
                                 CFG.vocab_size)]
    want_greedy = _generate_stream(params, prompt, 6, 0.0, 0)
    want_sampled = _generate_stream(params, prompt, 6, 0.9, 42)
    for chunk in (2, 3, 16):       # straddling, uneven, single-chunk
        got = _engine_streams(params, [(prompt, 6, 0.0, 0),
                                       (prompt, 6, 0.9, 42)],
                              num_slots=2, prefill_chunk=chunk)
        assert got[0] == want_greedy, chunk
        assert got[1] == want_sampled, chunk


def test_slot_engine_matches_generate_with_top_k_top_p(params):
    """The static top_k/top_p filters compose identically on both paths."""
    reqs = [([1, 2, 3], 5, 0.8, 3), ([4, 5], 4, 0.7, 8)]
    got = _engine_streams(params, reqs, num_slots=2, prefill_chunk=4,
                          top_k=7, top_p=0.9)
    for (prompt, mx, temp, seed), stream in zip(reqs, got):
        assert stream == _generate_stream(params, prompt, mx, temp, seed,
                                          top_k=7, top_p=0.9)


# -------------------------------------------------- speculative decoding
# Greedy speculative decoding must emit BITWISE the greedy stream: every
# accepted draft token is re-derived as the target's own argmax, and so
# is the correction/bonus token beyond the accepted prefix — for ANY
# draft, at any k (serving/speculate.py; the engine battery's scheduler-
# level and CoW twins live in tests/test_speculate.py).

def _spec(params_or_draft, k):
    from ddl25spring_tpu.serving import SpecConfig
    return SpecConfig(k=k, draft_params=params_or_draft)


def test_reference_speculative_stream_matches_generate(params):
    """The hand-checkable reference (models/generate.py): greedy
    draft-propose/verify over full re-forwards equals generate() token
    for token at k ∈ {1, 3} — for a same-weights draft (acceptance 1,
    every proposal used) AND a disagreeing one (acceptance < 1, every
    correction used)."""
    draft = llama.init_llama(jax.random.PRNGKey(9), CFG)
    prompt = [3, 5, 7, 2]
    want = generate.generate(params, jnp.asarray([prompt]), CFG,
                             7)[0].tolist()
    for k in (1, 3):
        for dp in (params, draft):
            got, stats = generate.speculative_stream(params, dp, prompt,
                                                     CFG, 7, k=k)
            assert got == want, (k, stats)
            assert stats["proposed"] > 0
            assert 0 <= stats["accepted"] <= stats["proposed"]
    # Same weights accept every usable proposal; the acceptance counter
    # is exact, not an estimate — INCLUDING at a max_new that is not a
    # multiple of the round size, where the final round's proposals are
    # horizon-truncated: only min(k, remaining) count as proposed (the
    # engine's schema-v7 rule), so truncation never reads as rejection.
    for mx in (7, 6):
        _, s_same = generate.speculative_stream(params, params, prompt,
                                                CFG, mx, k=3)
        assert s_same["accepted"] == s_same["proposed"] > 0, mx


def test_slot_engine_speculative_greedy_bitwise(params):
    """Ragged greedy prompts in one slot batch under speculation: each
    stream bitwise generate()'s for k ∈ {1, 3}, with a same-weights and
    a separately-weighted draft — acceptance rate is a throughput knob,
    never a token knob."""
    draft = llama.init_llama(jax.random.PRNGKey(9), CFG)
    reqs = []
    rng = jax.random.PRNGKey(23)
    for tp, mx in [(3, 6), (9, 4), (5, 8)]:
        rng, sub = jax.random.split(rng)
        prompt = jax.random.randint(sub, (tp,), 0, CFG.vocab_size).tolist()
        reqs.append((prompt, mx, 0.0, 0))
    want = [_generate_stream(params, p, mx, t, s) for p, mx, t, s in reqs]
    for k in (1, 3):
        for dp in (params, draft):
            got = _engine_streams(params, reqs, num_slots=3,
                                  prefill_chunk=4, speculate=_spec(dp, k))
            assert got == want, k


def test_speculative_acceptance_straddles_block_edge(params):
    """Verify windows whose accepted prefix crosses a block boundary
    (block_len=4; prompt lengths chosen so windows start mid-block and
    end in the next) write the straddling K/V correctly: streams stay
    bitwise through every crossing, including a max_seq_len request
    whose final window is horizon-clamped (the live mask — an unmasked
    tail write would wrap onto the slot's own last block)."""
    reqs = [([1, 2, 3], 10, 0.0, 0),       # windows at pos 3,7,11,...
            ([5, 6, 7, 8, 9, 10], 8, 0.0, 0),
            # 24+8-1 = 31 positions: the full 8-block reservation, so the
            # final window's tail rows clamp onto the slot's OWN last
            # block — only the live mask keeps them in the trash.
            ([4] * 24, 8, 0.0, 0)]
    want = [_generate_stream(params, p, mx, t, s) for p, mx, t, s in reqs]
    got = _engine_streams(params, reqs, num_slots=3, prefill_chunk=16,
                          speculate=_spec(params, 3))
    assert got == want


def test_speculative_greedy_neighbors_unperturbed_by_sampling(params):
    """A greedy stream sharing a speculative batch with sampling
    neighbors must stay bitwise — rejection sampling consumes the
    NEIGHBOR's key, never the greedy slot's tokens."""
    reqs = [([5, 17, 3], 6, 0.8, 13), ([8, 8], 7, 0.0, 0)]
    got = _engine_streams(params, reqs, num_slots=2, prefill_chunk=4,
                          speculate=_spec(params, 2))
    assert got[1] == _generate_stream(params, [8, 8], 7, 0.0, 0)
    assert len(got[0]) == 6


def test_bf16_kv_cache_close_to_fp32(params):
    """kv_dtype="bfloat16" halves cache storage (what the serving cells
    run); the decode must stay the same computation
    up to bf16 rounding of cached K/V: logits within bf16 tolerance, and
    greedy tokens identical for a short horizon at this scale."""
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, 6), 0,
                                CFG.vocab_size)
    out32 = generate.generate(params, prompt, CFG, 8)
    out16 = generate.generate(params, prompt, CFG, 8, kv_dtype="bfloat16")
    assert out16.dtype == out32.dtype
    assert (out16 == out32).mean() > 0.9  # rounding may flip a near-tie

    cache = generate.init_cache(CFG, 2, 8, "bfloat16")
    assert cache["k"].dtype == jnp.bfloat16
    logits16, _ = generate.forward_cached(params, prompt, cache, 0, CFG)
    full = llama.forward(params, prompt, CFG)[:, -1, :]
    assert jnp.allclose(logits16, full, atol=0.05)
