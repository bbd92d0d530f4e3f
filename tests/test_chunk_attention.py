"""The prefill chunk's attention kernel (ops/chunk_attention.py) against the
XLA form it stands in for on a TPU: ``latent.attend_expanded``'s scores,
softmax and weighted sum over the same expanded K and V.

Everything runs on the CPU with the kernel in interpret mode. The float32
reference's bar is the XLA form's (tests/test_latent_experts.py runs what it
ran); the kernel is held to that form by the tolerances stated here, and to
the reference by ``served_logit_gap`` on the chip (PERF.md, PR 33).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.config import ModelDescription
from ddl25spring_tpu.models import latent
from ddl25spring_tpu.ops import chunk_attention as ca
from ddl25spring_tpu.serving import engine as eng
from ddl25spring_tpu.serving.kvcache import PagedKVConfig

# |kernel - XLA form| on outputs of order 1. float32: the order of the sums
# alone, inside the 1e-4 that tests/test_latent_experts.py holds the program
# to against the float32 reference. bf16: the kernel rounds the exponent to
# 8 bits before the division and the XLA form the quotient, so a value may
# land a unit or two of the output's last place off.
TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def description(nope=16, rope=8, v=16, heads=4, kv_rank=32, dtype="float32",
                ctx=2048):
    """tests/test_latent_experts.py's tiny model with these head sizes."""
    cfg = dict(
        model_type="axk1", hidden_size=64, intermediate_size=128,
        num_attention_heads=heads, num_key_value_heads=heads,
        num_hidden_layers=3, vocab_size=256, first_k_dense_replace=1,
        moe_layer_freq=1, kv_lora_rank=kv_rank, q_lora_rank=48,
        qk_nope_head_dim=nope, qk_rope_head_dim=rope, v_head_dim=v,
        rms_norm_eps=1e-6, rope_theta=10000,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=32, mscale=1,
                          mscale_all_dim=1,
                          original_max_position_embeddings=16, type="yarn"),
        n_routed_experts=4, published=dict(n_routed_experts=16),
        first_held_expert=4, num_experts_per_tok=4, moe_intermediate_size=32,
        n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True,
        scoring_func="sigmoid", topk_method="none")
    return ModelDescription.from_published(cfg, ctx_size=ctx, dtype=dtype,
                                           param_dtype="float32")


T, K, BQ, BK = 64, 256, 32, 64
SIZES = {"192|128": dict(nope=128, rope=64, v=128, heads=2, kv_rank=64),
         "24|16": dict(nope=16, rope=8, v=16, heads=4, kv_rank=32)}
# name -> (offset of the chunk, its real tokens)
CHUNKS = {
    "offset_0": (0, T),                     # live 64: one key block
    "mid_prompt": (64, T),                  # live 128
    "last_chunk": (192, T),                 # live 256: the table is full
    "live_not_a_block": (64, 40),           # live 104; 24 padding rows
    "one_real_token": (128, 1),             # live 129
    "unaligned_offset": (50, T),            # live 114, queries astride blocks
}


def operands(sizes, dtype, seed=0):
    """Random queries, gathered rows (``row_stride`` wide, the tail zero) and
    ``w_kvb`` for one slot."""
    desc = description(dtype=dtype, **SIZES[sizes])
    att, h = desc.attention, desc.num_heads
    rng = np.random.default_rng(seed)
    stride = -(-att.row_dim // 128) * 128
    rows = np.zeros((1, K, stride), np.float32)
    rows[..., :att.row_dim] = rng.normal(size=(1, K, att.row_dim))
    q = jnp.asarray(rng.normal(size=(1, T, h, att.qk_dim)), dtype)
    w_kvb = jnp.asarray(rng.normal(size=(att.kv_rank, h * (att.nope_dim
                                                           + att.v_dim))),
                        dtype) * att.kv_rank ** -0.5
    return desc, q, jnp.asarray(rows, dtype), w_kvb


def kernel_for(desc, live, seen=None):
    """``attend_expanded``'s ``fused`` with small blocks; ``seen`` keeps
    what it was handed."""
    def fused(q, kv, k_rope, q_positions):
        if seen is not None:
            seen.update(kv=kv, k_rope=k_rope)
        return ca.chunk_attention(
            q, kv, k_rope, q_positions, jnp.asarray([live], jnp.int32),
            nope_dim=desc.attention.nope_dim,
            scale=latent.softmax_scale(desc.attention), block_q=BQ,
            block_k=BK, interpret=True)
    return fused


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("chunk", sorted(CHUNKS))
def test_kernel_matches_the_expanded_attention(chunk, sizes, dtype):
    """The kernel over the key blocks that hold live positions against
    ``attend_expanded`` over the whole padded table, on the same expanded K
    and V. Every key block past the live ones holds NaN when the kernel
    reads: the result is bit for bit what it is without them. A final
    chunk's padding rows give finite numbers that nothing uses."""
    off, n_valid = CHUNKS[chunk]
    desc, q, rows, w_kvb = operands(sizes, dtype)
    pos = jnp.asarray(off + np.arange(T), jnp.int32)[None]
    live = off + n_valid
    want = latent.attend_expanded(w_kvb, q, rows, pos, desc)
    got = latent.attend_expanded(w_kvb, q, rows, pos, desc,
                                 fused=kernel_for(desc, live))
    dead = -(-live // BK) * BK
    poisoned = latent.attend_expanded(
        w_kvb, q, rows.at[:, dead:].set(jnp.nan), pos, desc,
        fused=kernel_for(desc, live))
    assert got.dtype == want.dtype and got.shape == want.shape == (
        1, T, desc.num_heads, desc.attention.v_dim)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(poisoned, np.float32))
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:, :n_valid], want[:, :n_valid], rtol=0,
                               atol=TOL[dtype])


@pytest.mark.parametrize("sizes", sorted(SIZES))
def test_the_kernel_is_handed_the_parents_keys_and_values(sizes):
    """What ``attend_expanded`` hands ``fused`` is, bit for bit, what its
    own keys and values are made of: the expansion's bf16 product, a head's
    un-rotated keys beside its values, and the rows' rotated part."""
    desc, q, rows, w_kvb = operands(sizes, "bfloat16")
    att, h = desc.attention, desc.num_heads
    seen = {}
    latent.attend_expanded(w_kvb, q, rows, jnp.arange(T)[None], desc,
                           fused=kernel_for(desc, T, seen))
    # the parent's lines, as `git show 669ff45:.../models/latent.py` has them
    kv = (rows[..., :att.kv_rank] @ w_kvb).reshape(1, K, h,
                                                   att.nope_dim + att.v_dim)
    k_rope = jnp.broadcast_to(rows[:, :, None, att.kv_rank:att.row_dim],
                              (1, K, h, att.rope_dim))
    keys = jnp.concatenate([kv[..., :att.nope_dim], k_rope], axis=-1)
    values = kv[..., att.nope_dim:]
    assert seen["kv"].dtype == seen["k_rope"].dtype == jnp.bfloat16
    handed = seen["kv"].reshape(1, K, h, att.nope_dim + att.v_dim)
    same = np.testing.assert_array_equal
    same(np.asarray(handed[..., :att.nope_dim], np.float32),
         np.asarray(keys[..., :att.nope_dim], np.float32))
    same(np.asarray(handed[..., att.nope_dim:], np.float32),
         np.asarray(values, np.float32))
    for head in range(h):
        same(np.asarray(seen["k_rope"], np.float32),
             np.asarray(keys[:, :, head, att.nope_dim:], np.float32))


def test_bounds_name_the_last_block_a_query_block_may_see():
    pos = jnp.asarray([np.arange(64, 128), np.arange(0, 64)], jnp.int32)
    # slot 0: live 100 cuts its second query block; slot 1: its own
    # positions bound both
    got = ca.bounds(pos, jnp.asarray([100, 256], jnp.int32), 32, 16)
    assert got.tolist() == [[(96 - 1) // 16, (100 - 1) // 16], [1, 3]]
    # nothing live: block 0, never an index before it
    assert ca.bounds(pos[:1], jnp.asarray([0], jnp.int32), 32, 16).tolist() \
        == [[0, 0]]


AXK1 = dict(nope=128, rope=64, v=128, heads=64, kv_rank=512)


@pytest.mark.parametrize("t,k_len,sizes,dtype,backend,impl", [
    (512, 8192, AXK1, "bfloat16", "tpu", "pallas"),     # the A.X-K1 cell
    (256, 8192, AXK1, "bfloat16", "tpu", "pallas"),
    (512, 8192, AXK1, "float32", "tpu", "pallas"),
    (1, 8192, AXK1, "bfloat16", "tpu", "xla"),          # a decode step folds
    (96, 8192, AXK1, "bfloat16", "tpu", "xla"),         # so do 96 rows a slot
    (512, 8192, AXK1, "bfloat16", "cpu", "xla"),        # the tier-1 tests
    (520, 8192, AXK1, "bfloat16", "tpu", "xla"),        # no block of queries
    (512, 8200, AXK1, "bfloat16", "tpu", "xla"),        # no block of keys
    (512, 8192, dict(AXK1, v=64), "bfloat16", "tpu", "xla"),   # part tiles
    (40, 64, SIZES["24|16"], "float32", "tpu", "xla"),  # the tiny model
])
def test_the_path_is_read_from_what_the_trace_can_see(
        monkeypatch, t, k_len, sizes, dtype, backend, impl):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    desc = description(dtype=dtype, **sizes)
    assert eng.chunk_attention_path(t, k_len, desc) == {
        "impl": impl, "interpret": False if impl == "pallas" else None}


# ------------------------------------------------------------- the engine

PAGED = PagedKVConfig(num_blocks=600, block_len=4, max_blocks_per_seq=512)
CHUNK = 40
PROMPTS = [(45, 5), (1050, 4), (23, 6)]      # (prompt, max_new); 2048 a slot


def kernel_path(t, k_len, desc):
    if not latent.expand_pays(t, desc.attention, desc.num_heads):
        return {"impl": "xla", "interpret": None}
    return {"impl": "pallas", "interpret": True}


def keep_prefill_dispatches(engine, into: list) -> None:
    """Every ``engine.prefill.dispatch`` span's counters, appended to
    ``into`` as the engine opens it."""
    real = engine.spans

    class Kept:
        def __call__(self, name, **kw):
            if name == "engine.prefill.dispatch":
                into.append(kw)
            return real(name, **kw)

        def __getattr__(self, name):
            return getattr(real, name)
    engine.spans = Kept()


def serve(path_fn, monkeypatch):
    """Three requests through two slots; returns each request's tokens, the
    counters of every ``engine.prefill.dispatch`` and the engine."""
    if path_fn is not None:
        monkeypatch.setattr(eng, "chunk_attention_path", path_fn)
    desc = description()
    params = latent.init_params(jax.random.key(3), desc)
    engine = eng.Engine(params, desc, PAGED, 2, prefill_chunk=CHUNK)
    rng = np.random.default_rng(1)
    waiting = [(i, rng.integers(1, desc.vocab_size, size=n), m)
               for i, (n, m) in enumerate(PROMPTS)]
    tokens, in_slot, counters = {i: [] for i, _, _ in waiting}, {}, []
    keep_prefill_dispatches(engine, counters)
    while waiting or engine.busy:
        while waiting and engine.can_admit(len(waiting[0][1]), waiting[0][2]):
            i, prompt, max_new = waiting.pop(0)
            in_slot[engine.admit(prompt, max_new)] = i
        for ev in engine.step():
            tokens[in_slot[ev.slot]].append(ev.token)
    return tokens, counters, engine


def test_engine_with_the_kernel_serves_what_the_xla_path_serves(monkeypatch):
    """One ``Engine`` run with ``chunk_attention_path`` replaced, so that its
    ``prefill_chunk`` holds the kernel (interpret mode; two key blocks of
    1024 over slots of 2048) and its ``decode_step`` does not, beside the
    same run on the XLA path: float32, greedy. The tokens are the same, the
    pools the two leave behind agree to 1e-5, and ``attended_positions`` is
    the live keys in whole key blocks against the table's full width."""
    want, xla_counts, xla_engine = serve(None, monkeypatch)
    got, counts, engine = serve(kernel_path, monkeypatch)
    for e, held in ((engine, True), (xla_engine, False)):
        chunk = str(jax.make_jaxpr(e._prefill)(
            e.pool, e._head, e.fused, jnp.array(e.tables[0]),
            jnp.zeros(CHUNK, jnp.int32), jnp.int32(0), jnp.int32(CHUNK),
            jnp.int32(0), e.keys[0], jnp.float32(0)))
        assert ("pallas_call" in chunk) == held
        step = str(jax.make_jaxpr(e._decode)(
            e.pool, e._head, e.fused, jnp.array(e.tables),
            jnp.array(e.last_tok), jnp.array(e.pos), e.keys,
            jnp.array(e.temps), jnp.zeros(2, bool)))
        assert "pallas_call" not in step
    assert got == want
    assert all(len(got[i]) == m for i, (_, m) in enumerate(PROMPTS))
    np.testing.assert_allclose(np.asarray(engine.pool["c"][:, 1:]),
                               np.asarray(xla_engine.pool["c"][:, 1:]),
                               rtol=0, atol=1e-5)

    assert len(counts) == len(xla_counts) == sum(
        -(-n // CHUNK) for n, _ in PROMPTS)
    block = ca.blocks(CHUNK, PAGED.max_seq_len)[1]
    assert block == 1024 and engine._chunk_key_block == block
    assert xla_engine._chunk_key_block == 0
    for c, x in zip(counts, xla_counts):
        live = c["off"] + c["n_valid"]
        assert (c["off"], c["n_valid"]) == (x["off"], x["n_valid"])
        assert c["attended_positions"] == -(-live // block) * block
        assert x["attended_positions"] == PAGED.max_seq_len
    assert {c["attended_positions"] for c in counts} == {1024, 2048}
    live = sum(c["off"] + c["n_valid"] for c in counts)
    assert live / sum(c["attended_positions"] for c in counts) \
        > live / sum(c["attended_positions"] for c in xla_counts)


def test_a_llama_models_prefill_dispatch_counts_the_tables_width():
    """``attended_positions`` is on every ``engine.prefill.dispatch`` span:
    a ``LlamaConfig`` model's chunk gathers its table whole."""
    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama

    cfg = LlamaConfig(vocab_size=97, dmodel=32, num_heads=4, n_layers=2,
                      ctx_size=64)
    paged = PagedKVConfig(num_blocks=33, block_len=4, max_blocks_per_seq=10)
    engine = eng.Engine(llama.init_llama(jax.random.key(0), cfg), cfg, paged,
                        2, prefill_chunk=8)
    seen = []
    keep_prefill_dispatches(engine, seen)
    engine.admit(np.arange(1, 12), 2)
    while engine.busy:
        engine.step()
    assert [c["attended_positions"] for c in seen] == [paged.max_seq_len] * 2
