"""A decoder whose blocks run a Mamba-2 state-space mixer beside grouped-query
attention (``model_type`` ``falcon_h1``) through the serving engine, against
the plain float32 reference (benchmarks/references/state_space.py), at small
sizes on the CPU with seeded random weights. Logits, not tokens. The tiny
widths keep what the published ones have: 5 query heads a key/value head, 2
groups of B and C, a head size that is not ``dmodel / num_heads``.

(a) the chunked scan against the sequential recurrence, from a carried state
    and with a padded tail, and the one-step form against both;
(b) prefill in chunks, then decode, through the paged pool and the state
    store against the reference's one full pass, prompts that are no multiple
    of the chunk; the same with the recurrence in bfloat16 fails;
(c) the state store's contract: a slot that is not active and the padded
    positions of a last chunk leave state and tail bit for bit, a slot used
    again starts from zero;
(d) the paged-attention kernel with grouped heads (interpret mode) against the
    gather, and with a group of 1 the text it lowered to before it had groups;
(e) ``ModelDescription.from_published`` on the benchmark's configuration file.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(REPO, "benchmarks")]

from references import state_space as ref  # noqa: E402

from ddl25spring_tpu.config import ModelDescription  # noqa: E402
from ddl25spring_tpu.models import state_space  # noqa: E402
from ddl25spring_tpu.ops import paged_attention as pa  # noqa: E402
from ddl25spring_tpu.serving import engine as eng  # noqa: E402
from ddl25spring_tpu.serving.kvcache import (TRASH_BLOCK,  # noqa: E402
                                             PagedKVConfig, init_pool,
                                             init_state, pool_bytes,
                                             state_bytes_per_slot)

# Widths 96 and 64 in place of 5120 and 4096: the multipliers are set for
# them as muP sets the published ones for theirs, large enough that the
# state-space branch is a visible share of a logit (a fault in the state
# then shows).
CFG = dict(
    model_type="falcon_h1", hidden_size=96, intermediate_size=160,
    num_attention_heads=10, num_key_value_heads=2, head_dim=8,
    num_hidden_layers=3, vocab_size=256, rms_norm_eps=1e-5, rope_theta=1e11,
    rope_scaling=None, tie_word_embeddings=False, attention_bias=False,
    mlp_bias=False, projectors_bias=False, attn_layer_indices=None,
    mamba_d_ssm=64, mamba_n_heads=8, mamba_d_head=8, mamba_n_groups=2,
    mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=8,
    mamba_conv_bias=True, mamba_proj_bias=False, mamba_rms_norm=True,
    mamba_norm_before_gate=False, embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.125, attention_in_multiplier=1.0,
    attention_out_multiplier=0.3, key_multiplier=0.33,
    ssm_in_multiplier=1.0, ssm_out_multiplier=1.5,
    ssm_multipliers=[0.7, 1.0, 1.5, 2.0, 1.2], mlp_multipliers=[0.7, 0.18],
    initializer_range=0.2)
DIMS = ref.Dims.from_config(CFG)
PAGED = PagedKVConfig(num_blocks=40, block_len=4, max_blocks_per_seq=16)
CHUNK = 16                                  # two chunks of the scan
LENGTHS = ((45, 12), (9, 12), (23, 12))     # (prompt, decoded) a slot


def description(**over):
    return ModelDescription.from_published(
        dict(CFG, **over), ctx_size=PAGED.max_seq_len, dtype="float32",
        param_dtype="float32")


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(3, DIMS, "float32")


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(0)
    return [rng.integers(0, DIMS.vocab, p + n).astype(np.int32)
            for p, n in LENGTHS]


@pytest.fixture(scope="module")
def reference_logits(sequences):
    model = ref.Seeded(3, DIMS, "float32")
    return [np.asarray(model.logits(jnp.asarray(s))) for s in sequences]


# --------------------------------------------------------------- (a) the scan

def scan_inputs(t, seed=0):
    mx = description().mixer
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    x = f(t, mx.heads, mx.head_dim)
    dt = jax.nn.softplus(f(t, mx.heads))
    a = -jnp.exp(f(mx.heads) * 0.5)
    return mx, x, dt, a, f(t, mx.groups, mx.state), f(t, mx.groups, mx.state)


@pytest.mark.parametrize("carried", [0, 5, 16])
@pytest.mark.parametrize("n_valid", [24, 17, 1])
def test_chunked_scan_is_the_sequential_recurrence(carried, n_valid):
    """Three chunks of 8 after ``carried`` positions whose state is handed
    over, the last ``24 - n_valid`` positions padded (``dt`` 0): the outputs
    of the real positions and the state after the last of them are the
    sequential recurrence's over ``carried + n_valid`` positions. Float32
    sums in another order: 1e-5 of values of order 1 to 10."""
    t = 24
    mx, x, dt, a, b, c = scan_inputs(carried + t)
    want_y, want_s = ref.recurrence(*(m[:carried + n_valid]
                                      for m in (x, dt)), a,
                                    b[:carried + n_valid],
                                    c[:carried + n_valid])
    _, s0 = ref.recurrence(x[:carried], dt[:carried], a, b[:carried],
                           c[:carried])
    valid = jnp.arange(t) < n_valid
    got_y, got_s = state_space.scan_chunked(
        x[None, carried:], jnp.where(valid[:, None], dt[carried:], 0.0)[None],
        a, b[None, carried:], c[None, carried:], s0[None], mx)
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(got_y[0, :n_valid], want_y[carried:],
                               atol=1e-5 * scale, rtol=0)
    np.testing.assert_allclose(got_s[0], want_s, rtol=0,
                               atol=1e-5 * float(jnp.abs(want_s).max()))


def test_one_step_is_the_recurrence_and_dt_zero_carries_the_state():
    mx, x, dt, a, b, c = scan_inputs(7, seed=1)
    want_y, want_s = ref.recurrence(x, dt, a, b, c)
    _, s0 = ref.recurrence(x[:6], dt[:6], a, b[:6], c[:6])
    stay = jnp.asarray([1.0, 0.0])[:, None]             # slot 1 is not active
    y, s1 = state_space.scan_step(
        jnp.stack([x[6]] * 2), dt[6] * stay, a, jnp.stack([b[6]] * 2),
        jnp.stack([c[6]] * 2), jnp.stack([s0] * 2), mx)
    np.testing.assert_allclose(y[0], want_y[6], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s1[0], want_s, atol=1e-5, rtol=1e-5)
    assert np.asarray(s1[1]).tobytes() == np.asarray(s0).tobytes()


# ------------------------------------------ (b) engine against the reference

def paged_logits(params, desc, sequences, chunk, lengths=LENGTHS, pool=None):
    """Every sequence's logits through the engine's paged forward as the two
    programs drive it: each slot's prompt in chunks of `chunk` (one slot a
    call, the tail padded and written to trash, the slot's state carried),
    then decode steps over all slots at once, each at its own position, fed
    the sequence's own next token. Returns per slot the logits of rows
    0..len-2, and the pool with the state store."""
    head = {k: v for k, v in params.items() if k != "runs"}
    runs = tuple(params["runs"])
    bl, mb = PAGED.block_len, PAGED.max_blocks_per_seq
    if pool is None:
        pool = {**init_pool(desc, PAGED), **init_state(desc, len(sequences))}
    tables = np.full((len(sequences), mb), TRASH_BLOCK, np.int32)
    nxt = 1
    for s, seq in enumerate(sequences):
        n = -(-len(seq) // bl)
        tables[s, :n] = np.arange(nxt, nxt + n)
        nxt += n

    @jax.jit
    def forward(pool, tokens, tables, positions, wblk, woff, valid, slot=None):
        h, pool, _ = eng._forward_paged(head, runs, tokens, pool, tables,
                                        positions, wblk, woff, desc, valid,
                                        slot)
        return eng._head(head, h, desc), pool

    out = [[] for _ in sequences]
    for s, (seq, (p_len, _)) in enumerate(zip(sequences, lengths)):
        for off in range(0, p_len, chunk):
            n = min(chunk, p_len - off)
            toks = np.zeros(chunk, np.int32)
            toks[:n] = seq[off:off + n]
            pos = off + np.arange(chunk, dtype=np.int32)
            valid = np.arange(chunk) < n
            blk = np.minimum(pos // bl, mb - 1)
            wblk = np.where(valid, tables[s][blk], TRASH_BLOCK)
            lg, pool = forward(pool, toks[None], tables[s][None], pos[None],
                               wblk[None], (pos % bl)[None], valid[None],
                               jnp.int32(s))
            out[s].extend(np.asarray(lg[0, :n]))
    steps = max(n for _, n in lengths) - 1
    for i in range(steps):
        pos = np.array([p + i for p, _ in lengths], np.int32)
        active = np.array([i < n - 1 for _, n in lengths])
        toks = np.array([seq[min(q, len(seq) - 1)]
                         for seq, q in zip(sequences, pos)], np.int32)
        own = tables[np.arange(len(sequences)), np.minimum(pos // bl, mb - 1)]
        wblk = np.where(active, own, TRASH_BLOCK)
        lg, pool = forward(pool, toks[:, None], tables, pos[:, None],
                           wblk[:, None], (pos % bl)[:, None],
                           active[:, None])
        for s in np.nonzero(active)[0]:
            out[s].append(np.asarray(lg[s, 0]))
    return [np.stack(rows) if rows else np.zeros((0, DIMS.vocab))
            for rows in out], pool


# Float32 program against the float32 reference: the two differ in the order
# of their sums (the chunked scan against the sequential recurrence, grouped
# queries folded into rows, XLA's default float32 products against
# `highest`), which reads under 3e-6 of logits whose standard deviation is
# 0.24. With the state and the recurrence in bfloat16, everything else as it
# is, the same comparison reads 1.8e-2: a limit of 1e-4 passes the one with
# thirty times of room and fails the other by a factor of a hundred.
TOLERANCE = 1e-4


def worst_gap(got, want):
    return max(float(np.abs(g - w[:len(g)]).max()) for g, w in zip(got, want))


def test_prefill_then_decode_matches_the_reference(weights, sequences,
                                                   reference_logits):
    """Prompts of 45, 9 and 23 tokens in chunks of 16 (none a multiple), then
    11 decode steps with the slots at different lengths."""
    got, pool = paged_logits(weights, description(), sequences, CHUNK)
    assert [len(g) for g in got] == [len(s) - 1 for s in sequences]
    assert worst_gap(got, reference_logits) < TOLERANCE
    assert pool["s"].dtype == jnp.float32
    assert float(jnp.abs(pool["s"]).max()) > 0


def test_the_same_comparison_fails_with_the_recurrence_in_bfloat16(
        weights, sequences, reference_logits):
    desc = description(state_dtype="bfloat16")
    got, pool = paged_logits(weights, desc, sequences, CHUNK)
    assert pool["s"].dtype == jnp.bfloat16
    assert worst_gap(got, reference_logits) > 10 * TOLERANCE


def test_one_chunk_or_many_the_same_logits(weights, sequences):
    """The chunk a prompt is cut into is not part of the result: chunks of 8
    (one chunk of the scan) against chunks of 32 (four), to float32 sums."""
    a, _ = paged_logits(weights, description(), sequences, 8)
    b, _ = paged_logits(weights, description(), sequences, 32)
    assert worst_gap(a, b) < 1e-5


# -------------------------------------------------- (c) the store's contract

def test_the_store_is_sized_by_slots_and_the_pool_by_kv_heads():
    desc = description()
    pool, state = init_pool(desc, PAGED), init_state(desc, 5)
    assert pool["k"].shape == (3, 40, 4, 2, 8)          # 2 of 10 heads, of 8
    assert state["s"].shape == (3, 5, 8, 8, 16)
    assert state["tail"].shape == (3, 5, 3, 64 + 2 * 2 * 16)
    assert pool_bytes(desc, PAGED) == 2 * pool["k"].size * 4
    assert 5 * state_bytes_per_slot(desc) == sum(
        v.size * v.dtype.itemsize for v in state.values())
    from ddl25spring_tpu.config import LlamaConfig
    assert init_state(LlamaConfig(), 5) == {}
    assert state_bytes_per_slot(LlamaConfig()) == 0


def test_padded_positions_and_other_slots_leave_state_and_tail(weights,
                                                               sequences):
    """A last chunk's padded tail: whatever tokens stand there, the slot's
    state and tail come out the same bits. And a prefill chunk of one slot,
    or a decode step in which a slot is not active, leaves that slot's rows
    the same bits."""
    desc = description()
    _, pool = paged_logits(weights, desc, sequences, CHUNK)
    other = [s.copy() for s in sequences]
    for seq, (p_len, _) in zip(other, LENGTHS):
        seq[p_len:] = 7                     # what the padded tail reads
    # the decode steps feed positions p_len.., so compare after the prompts
    short = tuple((p, 1) for p, _ in LENGTHS)
    _, a = paged_logits(weights, desc, sequences, CHUNK, short)
    _, b = paged_logits(weights, desc, other, CHUNK, short)
    for k in ("s", "tail"):
        assert np.asarray(a[k]).tobytes() == np.asarray(b[k]).tobytes()
    # slots 0 and 2 then run a new prompt each and decode; slot 1 is in no
    # chunk and in no step active (a prompt of 0 in this harness)
    _, after = paged_logits(weights, desc, sequences, CHUNK,
                            ((20, 6), (0, 1), (11, 6)), pool)
    for k in ("s", "tail"):
        assert (np.asarray(after[k][:, 1]).tobytes()
                == np.asarray(pool[k][:, 1]).tobytes())
        assert (np.asarray(after[k][:, 0]).tobytes()
                != np.asarray(pool[k][:, 0]).tobytes())


def served(engine, requests):
    """Run ``requests`` [(prompt, max_new)] through ``engine`` one after the
    other's admission allows; the tokens of each by admission order."""
    out, by_slot, pending = {}, {}, list(enumerate(requests))
    while pending or engine.busy:
        while pending and engine.can_admit(len(pending[0][1][0]),
                                           pending[0][1][1]):
            i, (prompt, max_new) = pending.pop(0)
            by_slot[engine.admit(prompt, max_new)] = i
            out[i] = []
        for ev in engine.step():
            out[by_slot[ev.slot]].append(ev.token)
    return [out[i] for i in range(len(requests))]


def test_a_slot_used_again_starts_from_zero(weights, sequences):
    """One slot serves three requests in turn: each reads what it reads in
    an engine of its own, whatever state the one before left in the slot.
    Admission reckons a slot for the state beside the blocks of the pool."""
    desc = description()
    requests = [(seq[:p], n) for seq, (p, n) in zip(sequences, LENGTHS)]
    alone = [served(eng.Engine(weights, desc, PAGED, 1, prefill_chunk=CHUNK),
                    [r])[0] for r in requests]
    engine = eng.Engine(weights, desc, PAGED, 1, prefill_chunk=CHUNK)
    assert engine.state_bytes_in_use() == 0
    assert engine.can_admit(45, 12)
    engine.admit(*requests[0])
    assert engine.state_bytes_in_use() == state_bytes_per_slot(desc)
    assert not engine.can_admit(9, 12)              # blocks, but no slot
    engine.retire(0)
    assert engine.state_bytes_in_use() == 0
    assert served(engine, requests) == alone
    assert [len(t) for t in alone] == [n for _, n in LENGTHS]
    assert float(jnp.abs(engine.pool["s"]).max()) > 0


def test_what_the_engine_refuses(weights):
    desc = description()
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.Engine(weights, desc, PAGED, 2, prefill_chunk=CHUNK,
                   prefix_share=True)
    with pytest.raises(ValueError, match="whole chunks of 8"):
        eng.Engine(weights, desc, PAGED, 2, prefill_chunk=12)
    eng.Engine(weights, desc, PAGED, 2, prefill_chunk=4)    # one short chunk


def test_the_engine_is_built_where_the_backend_is_a_tpu(weights,
                                                        monkeypatch):
    """What ``Engine.__init__`` asks of the path functions for this model
    on a TPU (the first chip run of PR 36 ended here: the latent chunk
    kernel's path was asked about a model without latent attention)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine = eng.Engine(weights, description(), PAGED, 2, prefill_chunk=CHUNK)
    assert engine._chunk_key_block == 0
    assert not engine._decode_reads_live_blocks     # heads of 8: no kernel
    published = eng.paged_attention_path(1, 4, 128, jnp.dtype("bfloat16"), 16)
    assert published == {"impl": "pallas", "interpret": False}


def test_the_dispatch_spans_count_the_state(weights, sequences):
    desc = description()
    engine = eng.Engine(weights, desc, PAGED, 2, prefill_chunk=CHUNK)
    seen = {}

    class Recording:
        def __init__(self, inner):
            self.inner = inner

        def __call__(self, name, **counters):
            seen.setdefault(name, []).append(counters)
            return self.inner(name, **counters)

    engine.spans = Recording(engine.spans)
    served(engine, [(sequences[0][:20], 3), (sequences[1][:9], 3)])
    chunks = seen["engine.prefill.dispatch"]
    assert [c["scan_chunks"] for c in chunks] == [2, 1, 2]     # 16, 4; 9
    assert all(c["state_slots"] == 1 for c in chunks)
    assert [c["state_slots"] for c in seen["engine.decode.dispatch"]] \
        == [c["active"] for c in seen["engine.decode.dispatch"]]


# ------------------------------------------------- (d) grouped paged kernel

def test_grouped_kernel_against_the_gather():
    """20 query heads over 4 key/value heads of 128 (interpret mode), slots
    of lengths 0, 1, a whole block, and a ragged last block, against
    ``_attend_grouped`` over the gathered table; bf16 in, float32 sums."""
    s, hq, h, dh, bl, width, layers = 4, 20, 4, 128, 16, 4, 2
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(s, hq, dh)), jnp.bfloat16)
    pk, pv = (jnp.asarray(rng.normal(size=(layers, 1 + s * width, bl, h, dh)),
                          jnp.bfloat16) for _ in range(2))
    tables = jnp.asarray(1 + rng.permutation(s * width).reshape(s, width),
                         jnp.int32)
    lengths = jnp.asarray([0, 1, 32, 55], jnp.int32)
    walk = pa.plan(tables, lengths, bl, h, hq // h)
    got = pa.paged_attention(q, pk, pv, jnp.int32(1), walk, interpret=True)
    ck = pk[1, tables].reshape(s, -1, h, dh)
    cv = pv[1, tables].reshape(s, -1, h, dh)
    want = eng._attend_grouped(q[:, None], ck, cv,
                               (lengths - 1)[:, None])[:, 0]
    assert float(jnp.abs(got[0].astype(jnp.float32)).max()) == 0.0
    np.testing.assert_allclose(np.asarray(got[1:], np.float32),
                               np.asarray(want[1:], np.float32),
                               atol=2e-2, rtol=2e-2)
    assert pa.supported(h, dh, jnp.bfloat16, bl)
    assert not pa.supported(h, dh, jnp.bfloat16)


def test_engine_with_the_grouped_kernel_serves_what_the_gather_serves(
        weights, sequences, monkeypatch):
    """The decode program with the kernel as its attention (interpret mode;
    ``paged_attention_path`` replaced, as tests/test_paged_attention.py
    does): the same tokens as with the gather, and ``gathered_positions``
    counts live blocks."""
    desc = description()
    requests = [(seq[:p], 6) for seq, (p, _) in zip(sequences, LENGTHS)]
    want = served(eng.Engine(weights, desc, PAGED, 3, prefill_chunk=CHUNK),
                  requests)

    def kernel_path(t, h, dh, kv_dtype, block_len=1):
        assert (h, dh, block_len) == (2, 8, PAGED.block_len)
        return {"impl": "pallas" if t == 1 else "xla", "interpret": True}

    monkeypatch.setattr(eng, "paged_attention_path", kernel_path)
    engine = eng.Engine(weights, desc, PAGED, 3, prefill_chunk=CHUNK)
    assert engine._decode_reads_live_blocks
    assert served(engine, requests) == want


def test_a_group_of_one_lowers_to_what_it_did():
    """``head_columns`` and ``plan`` as they stood before the kernel had
    groups, word for word: with as many key/value heads as query heads the
    kernel's call lowers to the same text with them as with today's."""
    def head_columns(positions, h):
        cols = jnp.arange(positions * h, dtype=jnp.int32)
        return jnp.where(cols[None, :] % h == jnp.arange(h)[:, None],
                         cols[None, :] // h, pa._OTHER_HEAD).astype(jnp.int32)

    def plan(tables, lengths, block_len, h):
        s, width = tables.shape
        lengths = lengths.astype(jnp.int32)
        live = (jnp.arange(width, dtype=jnp.int32)[None, :] * block_len
                < lengths[:, None]).reshape(-1)
        steps = jnp.arange(s * width, dtype=jnp.int32)
        last_live = lax.cummax(jnp.where(live, steps, 0))
        fetch = tables.astype(jnp.int32).reshape(-1)[last_live]
        return lengths, fetch.reshape(s, width), head_columns(block_len, h)

    s, h, dh, bl, width = 3, 8, 128, 16, 4
    args = (jnp.zeros((s, h, dh), jnp.float32),
            jnp.zeros((2, 13, bl, h, dh), jnp.float32),
            jnp.zeros((2, 13, bl, h, dh), jnp.float32),
            jnp.zeros((s, width), jnp.int32), jnp.zeros((s,), jnp.int32))

    def call(plan_fn):
        def paged_attention(q, pk, pv, tables, lengths):
            return pa.paged_attention(q, pk, pv, jnp.int32(1),
                                      plan_fn(tables, lengths, bl, h),
                                      interpret=True)
        return jax.jit(paged_attention).lower(*args).as_text()

    assert call(pa.plan) == call(plan)


# -------------------------------------------- (e) the configuration's file

def test_from_published_reads_the_benchmarks_file():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "falcon-h1-34b.json")) as f:
        cfg = json.load(f)
    desc = ModelDescription.from_published(
        cfg, ctx_size=1024, dtype=cfg["compute_dtype"],
        param_dtype=cfg["weights_dtype"]["serve"])
    assert cfg["published"] == {"num_hidden_layers": 72}
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert desc.layer_kinds == ("parallel",) * 6 and not desc.plain
    assert (desc.vocab_size, desc.dmodel, desc.ffn_hidden) == (261120, 5120,
                                                               21504)
    assert (desc.num_heads, desc.num_kv_heads, desc.head_dim) == (20, 4, 128)
    assert desc.cache_row == 2 * 4 * 128 and desc.rope_theta == 1e11
    mx = desc.mixer
    assert (mx.d_inner, mx.heads, mx.head_dim, mx.groups, mx.state, mx.conv,
            mx.chunk, mx.state_dtype) == (4096, 32, 128, 2, 256, 4, 128,
                                          "float32")
    assert (mx.conv_dim, mx.proj_dim) == (5120, 9248)
    m = desc.multipliers
    # the twelve scalars and the two of the SwiGLU, as published
    assert (m.embedding, m.lm_head, m.attention_in, m.attention_out, m.key,
            m.ssm_in, m.ssm_out) == (
        5.656854249492381, 0.0078125, 1.0, 0.0375, 0.011048543456039804,
        0.25, 0.08838834764831845)
    assert m.ssm == (0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738)
    assert m.mlp == (0.1767766952966369, 0.011160714285714284)
    assert state_bytes_per_slot(desc) == 6 * (32 * 128 * 256 * 4
                                              + 3 * 5120 * 2)
    with pytest.raises(ValueError, match="grouped K/V heads"):
        ModelDescription.from_published(
            dict(cfg, model_type="llama"), ctx_size=1024, dtype="bfloat16",
            param_dtype="bfloat16")
    dims = ref.Dims.from_config(cfg)
    assert (dims.vocab, dims.layers, dims.proj_dim, dims.qkv_dim) == (
        261120, 6, 9248, 3584)
