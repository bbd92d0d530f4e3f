"""The latent-attention, routed-expert decoder through the serving engine,
against the plain float32 reference (benchmarks/references/latent_experts.py),
at small sizes on the CPU with seeded random weights. Logits, not tokens.

(a) prefill in chunks, then decode, through the latent paged cache against the
    reference's one full pass: both arithmetic forms of the attention, slots
    at different lengths;
(b) the expert layer against a loop over experts under skewed routing;
(c) the share: the parts that all ranges of held experts give, the shared
    expert counted once, add up to the uncut layer;
(d) the latent pool's size, write and gather, and a LlamaConfig model's pool
    and hidden states bit for bit what the parent commit's code gives.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(REPO, "benchmarks")]

from references import latent_experts as ref  # noqa: E402

from ddl25spring_tpu.config import (ExpertLayer, LlamaConfig,  # noqa: E402
                                    ModelDescription, describe)
from ddl25spring_tpu.models import experts, latent, llama  # noqa: E402
from ddl25spring_tpu.serving import engine as eng  # noqa: E402
from ddl25spring_tpu.serving.kvcache import (TRASH_BLOCK,  # noqa: E402
                                             PagedKVConfig, init_pool,
                                             kv_bytes_per_token, pool_bytes,
                                             row_stride)

CFG = dict(
    model_type="axk1", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3,
    vocab_size=256, first_k_dense_replace=1, moe_layer_freq=1,
    kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=32, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    n_routed_experts=4, published=dict(n_routed_experts=16),
    first_held_expert=4, num_experts_per_tok=4, moe_intermediate_size=32,
    n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="none")
DIMS = ref.Dims.from_config(CFG)
PAGED = PagedKVConfig(num_blocks=40, block_len=4, max_blocks_per_seq=16)
LENGTHS = ((45, 12), (9, 12), (23, 12))     # (prompt, decoded) a slot


def description(dtype="float32"):
    return ModelDescription.from_published(CFG, ctx_size=PAGED.max_seq_len,
                                           dtype=dtype, param_dtype="float32")


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


def paged_logits(params, desc, sequences, chunk, lengths=LENGTHS):
    """Every sequence's logits through the engine's paged forward as the two
    programs drive it: each slot's prompt in chunks of `chunk` (one slot a
    call, the tail padded and written to trash), then decode steps over all
    slots at once, each at its own position, fed the sequence's own next
    token. Returns per slot the logits of rows 0..len-2."""
    head = {k: v for k, v in params.items() if k != "runs"}
    runs = tuple(params["runs"])
    bl, mb = PAGED.block_len, PAGED.max_blocks_per_seq
    pool = init_pool(desc, PAGED)
    tables = np.full((len(sequences), mb), TRASH_BLOCK, np.int32)
    nxt = 1
    for s, seq in enumerate(sequences):
        n = -(-len(seq) // bl)
        tables[s, :n] = np.arange(nxt, nxt + n)
        nxt += n

    @jax.jit
    def forward(pool, tokens, tables, positions, wblk, woff, valid):
        h, pool, stats = eng._forward_paged(head, runs, tokens, pool, tables,
                                            positions, wblk, woff, desc,
                                            valid)
        return llama.head(head, h, desc), pool, stats

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
            lg, pool, _ = forward(pool, toks[None], tables[s][None],
                                  pos[None], wblk[None], (pos % bl)[None],
                                  valid[None])
            out[s].extend(np.asarray(lg[0, :n]))
    steps = max(n for _, n in lengths) - 1
    for i in range(steps):
        pos = np.array([p + i for p, _ in lengths], np.int32)
        active = np.array([i < n - 1 for _, n in lengths])
        toks = np.array([seq[min(q, len(seq) - 1)]
                         for seq, q in zip(sequences, pos)], np.int32)
        own = tables[np.arange(len(sequences)), np.minimum(pos // bl, mb - 1)]
        wblk = np.where(active, own, TRASH_BLOCK)
        lg, pool, _ = forward(pool, toks[:, None], tables, pos[:, None],
                              wblk[:, None], (pos % bl)[:, None],
                              active[:, None])
        for s in np.nonzero(active)[0]:
            out[s].append(np.asarray(lg[s, 0]))
    return [np.stack(rows) for rows in out], pool


# Float32 program against the float32 reference: the two differ in the order
# of their sums (folded against expanded attention, grouped against looped
# experts, XLA's default float32 products against `highest`), which reads
# under 2e-5 of the logits' spread here (their standard deviation is about
# 0.16). The same program computing in bfloat16 reads 2e-3 and more, so a
# limit of 1e-4 fails a precision below the stated one by a factor of 20.
TOLERANCE = 1e-4


@pytest.mark.parametrize("chunk", [8, 40])
def test_prefill_chunks_then_decode_match_the_reference(
        weights, sequences, reference_logits, chunk):
    desc = description()
    att = desc.attention
    # chunk 40 expands the rows to per-head K and V, chunk 8 and the decode
    # step fold: both forms are in the comparison
    assert latent.expand_pays(40, att, desc.num_heads)
    assert not latent.expand_pays(8, att, desc.num_heads)
    assert not latent.expand_pays(1, att, desc.num_heads)
    got, _ = paged_logits(weights, desc, sequences, chunk)
    for g, want, seq in zip(got, reference_logits, sequences):
        assert g.shape == want.shape == (len(seq) - 1, DIMS.vocab)
        assert np.abs(g - want).max() < TOLERANCE


def test_bfloat16_where_float32_is_stated_fails_the_tolerance(
        weights, sequences, reference_logits):
    got, _ = paged_logits(weights, description("bfloat16"), sequences, 40)
    worst = max(np.abs(g.astype(np.float32) - want).max()
                for g, want in zip(got, reference_logits))
    assert worst > 10 * TOLERANCE


def test_the_engine_serves_it_and_counts_its_routing(weights, sequences):
    """Engine.admit/step over the same model: every served token is the
    reference's first choice (float32 against float32), and the routing
    counters add up."""
    desc = description()
    engine = eng.Engine(weights, desc, PAGED, 3, prefill_chunk=40)
    prompts = [seq[:p] for seq, (p, _) in zip(sequences, LENGTHS)]
    served = {engine.admit(p, 8): [] for p in prompts}
    while engine.busy:
        for ev in engine.step():
            served[ev.slot].append(ev.token)
    model = ref.Seeded(3, DIMS, "float32")
    for s, p in enumerate(prompts):
        toks = jnp.asarray(np.concatenate([p, served[s]]), jnp.int32)
        gaps = np.asarray(ref.gap_below_best(model, toks, toks[1:]))
        assert gaps[len(p) - 1:].max() < TOLERANCE
    tokens = sum(len(p) for p in prompts) + 3 * 7
    r = engine.routing
    assert r["pairs_routed"] == tokens * DIMS.top_k * 2      # 2 expert layers
    assert 0 < r["pairs_held"] < r["pairs_routed"]
    assert r["experts_hit"] > 0 and r["max_pairs"] > 0
    # one copy of each weight: what the engine holds is the tree it was given
    held = jax.tree.leaves(engine.weights)
    assert {id(x) for x in held} == {id(x) for x in jax.tree.leaves(weights)}
    assert set(engine.pool) == {"c"}


def test_the_programs_weights_are_the_references(weights):
    mine = jax.jit(lambda k: latent.init_params(k, description()))(
        jax.random.key(3))
    assert jax.tree.structure(mine) == jax.tree.structure(weights)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(weights)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ the expert layer

SPEC = ExpertLayer(n_experts=192, top_k=8, width=16, n_shared=1, scale=2.5,
                   norm_topk=True, held_start=0, held_count=192)


def whole_layer(d=32, seed=0):
    return experts.init_layer(jax.random.key(seed), d, SPEC, 0.3, 0.3,
                              jnp.float32)


def held_part(block, spec: ExpertLayer):
    lo, hi = spec.held_start, spec.held_start + spec.held_count
    return dict(block, we_gu=block["we_gu"][lo:hi],
                we_down=block["we_down"][lo:hi])


def loop_over_experts(block, x, spec: ExpertLayer):
    """The held experts' part, one expert at a time over every token."""
    idx, w = experts.route(block["w_r"], x, spec)
    y = experts.swiglu(x, block["ws_gu"], block["ws_down"])
    for e in range(spec.held_count):
        we = jnp.sum(jnp.where(idx == spec.held_start + e, w, 0.0), axis=-1)
        y = y + we[:, None] * experts.swiglu(x, block["we_gu"][e],
                                             block["we_down"][e])
    return y


def test_the_expert_layer_drops_nothing_under_skewed_routing():
    """One held expert takes a pair of every token, one takes none."""
    spec = dataclasses.replace(SPEC, held_start=24, held_count=12)
    block = held_part(whole_layer(), spec)
    rng = np.random.default_rng(1)
    u = np.zeros(32, np.float32)
    u[0] = 1.0
    x = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32) * 0.1 + 3 * u)
    w_r = np.asarray(block["w_r"]).copy()
    w_r[:, 24 + 3] = 10 * u          # every token's first choice
    w_r[:, 24 + 7] = -10 * u         # no token's
    block = dict(block, w_r=jnp.asarray(w_r))
    y, stats = jax.jit(lambda b, x: experts.expert_layer(b, x, spec))(block, x)
    idx, _ = experts.route(block["w_r"], x, spec)
    here = (np.asarray(idx) >= 24) & (np.asarray(idx) < 36)
    counts = np.bincount(np.asarray(idx)[here] - 24, minlength=12)
    assert counts[3] == 64 and counts[7] == 0
    pairs_held, experts_hit, max_pairs = (int(v) for v in stats)
    assert pairs_held == here.sum() and max_pairs == 64
    assert experts_hit == (counts > 0).sum() < 12
    want = loop_over_experts(block, x, spec)
    # float32 sums in another order: 1e-5 of values of order 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    # rows that are no tokens route nowhere and are not counted
    valid = jnp.arange(64) < 40
    _, part = experts.expert_layer(block, x, spec, valid)
    assert int(part[0]) == here[:40].sum()


def test_the_shares_of_all_ranges_add_up_to_the_uncut_layer():
    """16 ranges of 12 experts: each chip's result less the shared expert,
    which every chip computes alike, summed, plus the shared expert once, is
    the layer that holds all 192."""
    block = whole_layer()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(48, 32)),
                    jnp.float32)
    whole, stats = experts.expert_layer(block, x, SPEC)
    assert int(stats[0]) == 48 * 8          # uncut, every pair is held
    shared = experts.swiglu(x, block["ws_gu"], block["ws_down"])
    total, pairs = shared, 0
    for r in range(16):
        spec = dataclasses.replace(SPEC, held_start=12 * r, held_count=12)
        y, st = experts.expert_layer(held_part(block, spec), x, spec)
        total = total + (y - shared)
        pairs += int(st[0])
    assert pairs == 48 * 8
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)


def test_the_reference_routes_as_the_program_does():
    """The reference's per-expert weights are the program's (ids, weights)."""
    block = whole_layer()
    x = jnp.asarray(np.random.default_rng(4).normal(size=(16, 32)),
                    jnp.float32)
    dims = ref.Dims.from_config({**CFG, "hidden_size": 32,
                                 "published": {"n_routed_experts": 192},
                                 "num_experts_per_tok": 8})
    weight = np.asarray(ref.routing(block["w_r"], x, dims, ref.REFERENCE))
    idx, w = experts.route(block["w_r"], x, SPEC)
    dense = np.zeros_like(weight)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(w), axis=1)
    np.testing.assert_allclose(weight, dense, atol=1e-6)


# -------------------------------------------------------------------- the pool

def test_the_latent_pool_holds_one_row_a_position_a_layer():
    desc = description("bfloat16")
    pool = init_pool(desc, PAGED)
    assert set(pool) == {"c"}
    assert desc.cache_row == desc.attention.row_dim == 40
    # whole vectors of 128 lanes a row: 40 values in 128, as 576 lie in 640
    assert row_stride(desc) == 128
    assert row_stride(desc.replace(attention=dataclasses.replace(
        desc.attention, kv_rank=512, rope_dim=64))) == 640
    assert pool["c"].shape == (3, 40, 4, 128) and pool["c"].dtype == jnp.bfloat16
    assert kv_bytes_per_token(desc) == 3 * 128 * 2
    assert pool_bytes(desc, PAGED) == pool["c"].nbytes
    # the serving preflight sizes the weights and the pool from the description
    from ddl25spring_tpu.telemetry.memory import preflight
    pre = preflight(desc, paged=PAGED)
    assert pre["kv_pool_bytes"] == pool["c"].nbytes
    tree = jax.eval_shape(lambda: latent.init_params(jax.random.key(0), desc))
    assert pre["params_bytes"] == sum(x.size * x.dtype.itemsize
                                      for x in jax.tree.leaves(tree))


def test_the_latent_row_is_written_after_norm_and_rotation_and_gathered(
        weights, sequences):
    """What the pool holds at a position is `latent_row` of that token at
    that position, in the slot's own blocks and nowhere else."""
    desc = description()
    _, pool = paged_logits(weights, desc, sequences[:1], 40, LENGTHS[:1])
    # the first layer's row of position 5, computed on its own
    block = jax.tree.map(lambda a: a[0], weights["runs"][0])
    x = weights["embed"][sequences[0][:8]][None]
    from ddl25spring_tpu import nn
    xn = nn.rmsnorm(block["attn_norm"], x, eps=desc.norm_eps)
    cos, sin = latent.rope_tables(jnp.arange(8)[None], desc.attention,
                                  desc.rope_theta)
    row = latent.latent_row(block, xn, cos, sin, desc)[0, 5]
    stored = pool["c"][0, 1 + 5 // 4, 5 % 4]
    np.testing.assert_allclose(np.asarray(stored[:40]), np.asarray(row),
                               atol=1e-6)
    assert not np.asarray(stored[40:]).any()
    used = -(-len(sequences[0]) // 4)
    assert not np.asarray(pool["c"][:, 1 + used:]).any()


def parent_forward_paged(params, fused_blocks, tokens, pool, tables,
                         positions, wblk, woff, cfg):
    """`_forward_paged` as commit 87aef57 has it, word for word."""
    h = llama.embed(params, tokens, cfg)
    layers = jnp.arange(pool["k"].shape[0], dtype=jnp.int32)

    def body(carry, layer_block):
        x, pk, pv = carry
        layer, block = layer_block
        return eng._block_paged(block, layer, pk, pv, x, positions, tables,
                                wblk, woff, cfg), None

    with jax.named_scope("layers"):
        (h, pk, pv), _ = lax.scan(body, (h, pool["k"], pool["v"]),
                                  (layers, fused_blocks))
    return h, {"k": pk, "v": pv}


def test_a_llama_models_pool_and_hidden_states_are_the_parents_bit_for_bit():
    from ddl25spring_tpu.models import generate

    cfg = LlamaConfig(vocab_size=128, dmodel=32, num_heads=2, n_layers=2,
                      ctx_size=32)
    assert describe(cfg).plain and describe(cfg).cache_row == 2 * 32
    paged = PagedKVConfig(num_blocks=9, block_len=4, max_blocks_per_seq=4)
    pool = init_pool(cfg, paged)
    assert set(pool) == {"k", "v"} and pool["k"].shape == (2, 9, 4, 2, 16)
    assert kv_bytes_per_token(cfg) == 2 * 2 * 2 * 16 * 4
    params = llama.init_llama(jax.random.key(0), cfg)
    fused = generate._fuse_blocks(params["blocks"])
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 6)),
                       jnp.int32)
    tables = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32), (2, 6))
    wblk = jnp.take_along_axis(tables, pos // 4, axis=1)
    h0, p0 = jax.jit(lambda pool: parent_forward_paged(
        params, fused, toks, pool, tables, pos, wblk, pos % 4, cfg))(pool)
    h1, p1, stats = jax.jit(lambda pool: eng._forward_paged(
        params, fused, toks, pool, tables, pos, wblk, pos % 4, cfg))(pool)
    assert stats is None
    np.testing.assert_array_equal(np.asarray(h0), np.asarray(h1))
    for k in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(p0[k]), np.asarray(p1[k]))
    # and the engine keeps one copy: the fused layers, the rest as given
    engine = eng.Engine(params, cfg, paged, 2, prefill_chunk=4)
    assert engine.weights[0]["embed"] is params["embed"]
    assert engine.weights[1]["wo"] is params["blocks"]["wo"]
    assert not any(x is params["blocks"]["wq"]
                   for x in jax.tree.leaves(engine.weights))
    for a, b in zip(jax.tree.leaves(engine.params), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- loud refusals

def test_speculation_prefix_sharing_the_fleet_and_the_trainer_refuse_it(
        weights):
    from ddl25spring_tpu.config import TrainConfig
    from ddl25spring_tpu.serving.fleet import ServingFleet
    from ddl25spring_tpu.serving.speculate import SpecConfig
    from ddl25spring_tpu.train.llm import train_llm_dp

    desc = description()
    with pytest.raises(NotImplementedError, match="LlamaConfig"):
        eng.Engine(weights, desc, PAGED, 2, prefix_share=True)
    with pytest.raises(NotImplementedError, match="LlamaConfig"):
        eng.Engine(weights, desc, PAGED, 2,
                   speculate=SpecConfig(draft_params=weights, k=2))
    with pytest.raises(NotImplementedError, match="LlamaConfig"):
        ServingFleet(weights, desc, PAGED, num_engines=2, num_slots=1)
    with pytest.raises(NotImplementedError, match="LlamaConfig"):
        train_llm_dp(desc, TrainConfig(iters=1))
