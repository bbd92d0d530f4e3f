"""A decoder whose layers mix by a gated short convolution or by grouped-query
attention with per-head q/k norms, a mixer a layer, with routed experts
chosen through a selection bias (``model_type`` ``lfm2_moe``), through the
serving engine against the plain float32 reference
(benchmarks/references/conv_experts.py), at small sizes on the CPU with
seeded random weights. Logits, not tokens. The tiny widths keep what the
published ones have: heads of 64 (two to a row of the pool), 2 query heads a
key/value head, the top 4 of 16 experts, a leading dense layer, whole
periods of (attention, conv, conv) and what is left of one.

(a) prefill in chunks, then decode, through the pool of the attention layers
    and the store of the conv layers' tails, against the reference's one full
    pass; the same in bfloat16 fails; one chunk or three, the same logits;
(b) the selection bias chooses and does not weigh;
(c) the pool holds the attention layers alone, the store the conv layers
    alone; a model of no attention layer has no pool and runs;
(d) the decode kernel at heads of 64 (interpret mode) in the engine;
(e) ``ModelDescription.from_published`` on the benchmark's configuration file.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(REPO, "benchmarks")]

from references import conv_experts as ref  # noqa: E402

from ddl25spring_tpu.config import ModelDescription  # noqa: E402
from ddl25spring_tpu.models import experts  # noqa: E402
from ddl25spring_tpu.serving import engine as eng  # noqa: E402
from ddl25spring_tpu.serving.kvcache import (TRASH_BLOCK,  # noqa: E402
                                             PagedKVConfig, init_pool,
                                             init_state, pool_bytes,
                                             state_bytes_per_slot)

CFG = dict(
    model_type="lfm2_moe", hidden_size=256, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=2,
    num_hidden_layers=8, vocab_size=512,
    layer_types=["conv", "full_attention", "conv", "conv", "full_attention",
                 "conv", "conv", "full_attention"],
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    initializer_range=0.1)
DIMS = ref.Dims.from_config(CFG)
PAGED = PagedKVConfig(num_blocks=40, block_len=4, max_blocks_per_seq=16)
CHUNK = 8
LENGTHS = ((21, 9), (9, 9), (13, 9))     # (prompt, decoded) a slot


def description(dtype="float32"):
    return ModelDescription.from_published(
        CFG, ctx_size=PAGED.max_seq_len, dtype=dtype, param_dtype="float32")


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


# ------------------------------------------------ (a) against the reference

def paged_logits(params, desc, sequences, chunk, lengths=LENGTHS):
    """Each prompt through ``_forward_paged`` in chunks of ``chunk`` (one slot
    a call, the last chunk padded), then decode steps over all slots at once,
    each at its own position, fed the sequence's own next token. Returns per
    slot the logits of rows 0..len-2, and the pool with the store."""
    head = {k: v for k, v in params.items() if k != "runs"}
    runs = tuple(params["runs"])
    bl, mb = PAGED.block_len, PAGED.max_blocks_per_seq
    pool = {**init_pool(desc, PAGED), **init_state(desc, len(sequences))}
    tables = np.full((len(sequences), mb), TRASH_BLOCK, np.int32)
    nxt = 1
    for s, seq in enumerate(sequences):
        n = -(-len(seq) // bl)
        tables[s, :n] = np.arange(nxt, nxt + n)
        nxt += n

    @jax.jit
    def forward(pool, tokens, tables, positions, wblk, woff, valid, slot=None):
        h, pool, stats = eng._forward_paged(head, runs, tokens, pool, tables,
                                            positions, wblk, woff, desc,
                                            valid, slot)
        return eng._head(head, h, desc), pool, stats

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
                                  valid[None], jnp.int32(s))
            out[s].extend(np.asarray(lg[0, :n], np.float32))
    for i in range(max(n for _, n in lengths) - 1):
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
            out[s].append(np.asarray(lg[s, 0], np.float32))
    return [np.stack(rows) for rows in out], pool


# Float32 program against the float32 reference: they differ in the order of
# their sums (grouped queries folded into rows, the experts grouped by pair,
# XLA's default float32 products against `highest`), which reads 7e-6 of
# logits whose standard deviation is 1.6. With everything but the weights in
# bfloat16 the same comparison reads 1.15 (a near-tie among the experts falls
# another way): a limit of 1e-4 passes the one with fourteen times of room and
# fails the other by four orders.
TOLERANCE = 1e-4


def worst_gap(got, want):
    return max(float(np.abs(g - w[:len(g)]).max()) for g, w in zip(got, want))


def test_prefill_then_decode_matches_the_reference(weights, sequences,
                                                   reference_logits):
    """Prompts of 21, 9 and 13 tokens in chunks of 8 (none a multiple), then
    8 decode steps with the slots at different lengths, through a dense
    layer, two whole periods of (attention, conv, conv) with experts and an
    attention layer left over."""
    desc = description()
    assert desc.periods() == (
        ("dense", ("conv",), 1, 0),
        ("experts", ("attention", "conv", "conv"), 2, 1),
        ("experts", ("attention",), 1, 7))
    got, pool = paged_logits(weights, desc, sequences, CHUNK)
    assert [len(g) for g in got] == [len(s) - 1 for s in sequences]
    assert np.std(reference_logits[0]) > 1.0
    assert worst_gap(got, reference_logits) < TOLERANCE
    assert float(jnp.abs(pool["tail"]).max()) > 0


def test_the_same_comparison_fails_in_bfloat16(weights, sequences,
                                               reference_logits):
    got, _ = paged_logits(weights, description(dtype="bfloat16"), sequences,
                          CHUNK)
    assert worst_gap(got, reference_logits) > 10 * TOLERANCE


def test_one_chunk_or_three_the_same_logits(weights, sequences):
    """The chunk a prompt is cut into is not part of the result: the tail of
    each conv layer is carried from chunk to chunk."""
    one, _ = paged_logits(weights, description(), sequences, 24)
    three, _ = paged_logits(weights, description(), sequences, CHUNK)
    assert worst_gap(one, three) < 1e-5


# --------------------------------------------------- (b) the selection bias

def test_the_selection_bias_chooses_and_does_not_weigh():
    spec = description().experts
    assert spec.n_shared == 0
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    w_r = jnp.asarray(rng.normal(size=(256, 16)) * 0.1, jnp.float32)
    bias = jnp.asarray(rng.normal(size=16) * 0.2, jnp.float32)
    plain_idx, _ = experts.route(w_r, x, spec)
    idx, w = experts.route(w_r, x, spec, bias)
    scores = np.asarray(jax.nn.sigmoid(x @ w_r))
    # the chosen are the top 4 of scores + bias, and another set than the
    # top 4 of the scores alone for many tokens
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :4]
    assert (np.sort(np.asarray(idx), 1) == np.sort(want, 1)).all()
    changed = np.mean([set(a) != set(b) for a, b in
                       zip(np.asarray(idx), np.asarray(plain_idx))])
    assert changed > 0.25
    # their weights are the scores alone, normalised
    top = np.take_along_axis(scores, np.asarray(idx), 1)
    np.testing.assert_allclose(
        np.asarray(w), top / (top.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)


def test_the_cells_bias_changes_a_quarter_of_the_choices():
    """At the published widths (2048 over 64 experts, the router and the
    bias as the reference draws them), the bias changes more than a quarter
    of the top-4 choices (about 29%): a program that ignored it would read
    wrong."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    dims = ref.Dims.from_config(cfg)
    spec = ModelDescription.from_published(
        cfg, ctx_size=1024, dtype="bfloat16", param_dtype="bfloat16").experts
    # the router and the bias alone: the experts' weights are dead code here
    w = jax.jit(lambda key: {k: v for k, v in ref.layer_weights(
        key, dims, 2, jnp.bfloat16).items() if k in ("w_r", "expert_bias")})(
            jax.random.key(5))
    x = jax.random.normal(jax.random.key(6), (256, dims.d), jnp.float32)
    a, _ = experts.route(w["w_r"], x, spec, w["expert_bias"])
    b, _ = experts.route(w["w_r"], x, spec)
    changed = np.mean([len(set(p) - set(q)) / 4 for p, q in
                       zip(np.asarray(a), np.asarray(b))])
    assert changed > 0.25


# ----------------------------------------------------- (c) pool and store

def test_the_pool_holds_attention_layers_and_the_store_conv_layers():
    desc = description()
    pool, state = init_pool(desc, PAGED), init_state(desc, 5)
    # 3 attention layers of 8; 2 heads of 64 side by side in a row of 128
    assert pool["k"].shape == pool["v"].shape == (3, 40, 4, 1, 128)
    assert set(state) == {"tail"}
    assert state["tail"].shape == (5, 5, 2, 256)        # 5 conv layers
    assert pool_bytes(desc, PAGED) == 2 * pool["k"].size * 4
    assert state_bytes_per_slot(desc) == 5 * 2 * 256 * 4
    assert 5 * state_bytes_per_slot(desc) == state["tail"].size * 4


def test_a_model_of_no_attention_layer_has_no_pool_and_serves(weights):
    """The benchmark's rehearsal shrinks the depth to the two leading dense
    conv layers: no attention layer, so no pool; admission still reckons
    blocks, and the engine serves."""
    cfg = dict(CFG, num_hidden_layers=2, num_dense_layers=2,
               layer_types=["conv", "conv"])
    desc = ModelDescription.from_published(cfg, ctx_size=64, dtype="float32",
                                           param_dtype="float32")
    assert init_pool(desc, PAGED) == {}
    dims = ref.Dims.from_config(cfg)
    engine = eng.Engine(ref.make_weights(1, dims, "float32"), desc, PAGED, 2,
                        prefill_chunk=CHUNK)
    assert set(engine.pool) == {"tail"}
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 512, 11).astype(np.int32)
    got = served(engine, [(prompt, 5)])[0]
    model = ref.Seeded(1, dims, "float32")
    full = np.concatenate([prompt, got])
    want = np.argmax(np.asarray(model.logits(jnp.asarray(full))), -1)
    assert list(want[len(prompt) - 1:]) == got


def served(engine, requests):
    """Run ``requests`` [(prompt, max_new)] through ``engine`` as admission
    allows; the tokens of each by admission order."""
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


def test_a_slot_used_again_starts_from_a_zero_tail(weights, sequences):
    desc = description()
    requests = [(seq[:p], n) for seq, (p, n) in zip(sequences, LENGTHS)]
    alone = [served(eng.Engine(weights, desc, PAGED, 1, prefill_chunk=CHUNK),
                    [r])[0] for r in requests]
    engine = eng.Engine(weights, desc, PAGED, 1, prefill_chunk=CHUNK)
    assert served(engine, requests) == alone
    assert engine.state_bytes_in_use() == 0


def test_the_dispatch_spans_count_slots_and_pairs(weights, sequences):
    engine = eng.Engine(weights, description(), PAGED, 2, prefill_chunk=CHUNK)
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
    assert all(c["state_slots"] == 1 for c in chunks)
    # 4 experts a token in each of the 7 expert layers
    assert [c["pairs_routed"] for c in chunks] == [
        28 * c["n_valid"] for c in chunks]
    steps = seen["engine.decode.dispatch"]
    assert [c["state_slots"] for c in steps] == [c["active"] for c in steps]
    assert [c["pairs_routed"] for c in steps] == [28 * c["active"]
                                                  for c in steps]
    assert engine.routing["pairs_held"] == engine.routing["pairs_routed"]


def test_what_the_engine_refuses(weights):
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.Engine(weights, description(), PAGED, 2, prefill_chunk=CHUNK,
                   prefix_share=True)


# ---------------------------------------------- (d) the kernel at heads of 64

def kernel_path(t, h, dh, kv_dtype, block_len=1):
    assert (h, dh) == (1, 128)              # two heads of 64 a row
    return {"impl": "pallas" if t == 1 else "xla", "interpret": True}


def test_engine_with_the_kernel_at_heads_of_64_serves_what_the_gather_serves(
        weights, sequences, monkeypatch):
    desc = description()
    requests = [(seq[:p], 6) for seq, (p, _) in zip(sequences, LENGTHS)]
    want = served(eng.Engine(weights, desc, PAGED, 3, prefill_chunk=CHUNK),
                  requests)
    monkeypatch.setattr(eng, "paged_attention_path", kernel_path)
    engine = eng.Engine(weights, desc, PAGED, 3, prefill_chunk=CHUNK)
    assert engine._walk_chunk == PAGED.max_blocks_per_seq
    jaxpr = str(jax.make_jaxpr(engine._decode)(
        engine.pool, engine._head, engine.fused, jnp.array(engine.tables),
        jnp.array(engine.last_tok), jnp.array(engine.pos), engine.keys,
        jnp.array(engine.temps), jnp.zeros(3, bool)))
    assert "pallas_call" in jaxpr
    assert served(engine, requests) == want


def test_the_engine_is_built_where_the_backend_is_a_tpu(weights,
                                                        monkeypatch):
    """What ``Engine.__init__`` asks of the path functions for this model on
    a TPU: the kernel for the decode step (rows of 128, blocks of 16), no
    chunk kernel (no latent attention)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paged = PagedKVConfig(num_blocks=40, block_len=16, max_blocks_per_seq=4)
    engine = eng.Engine(weights, description(), paged, 2, prefill_chunk=CHUNK)
    assert engine._chunk_key_block == 0
    assert engine._walk_chunk == 4


# ------------------------------------------- (e) the configuration's file

def test_from_published_reads_the_benchmarks_file():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    desc = ModelDescription.from_published(
        cfg, ctx_size=1024, dtype=cfg["compute_dtype"],
        param_dtype=cfg["weights_dtype"]["serve"])
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:10]
    assert desc.layer_kinds == ("dense",) * 2 + ("experts",) * 8
    assert desc.mixers == ("conv", "conv") + ("attention", "conv", "conv",
                                              "conv") * 2
    assert desc.periods() == (("dense", ("conv",), 2, 0),
                              ("experts", ("attention", "conv", "conv",
                                           "conv"), 2, 2))
    assert (desc.vocab_size, desc.dmodel, desc.ffn_hidden) == (65536, 2048,
                                                               11776)
    assert (desc.num_heads, desc.num_kv_heads, desc.head_dim) == (32, 8, 64)
    assert desc.rope_theta == 1e6 and desc.norm_eps == 1e-5
    assert desc.tie_embeddings and desc.conv.taps == 3
    ex = desc.experts
    assert (ex.n_experts, ex.top_k, ex.width, ex.n_shared, ex.held_count,
            ex.norm_topk, ex.scale, ex.norm_eps) == (
        64, 4, 1536, 0, 64, True, 1.0, 1e-6)
    paged = PagedKVConfig(num_blocks=16385, block_len=16,
                          max_blocks_per_seq=64, kv_dtype="bfloat16")
    pool = jax.eval_shape(lambda: init_pool(desc, paged))
    assert pool["k"].shape == (2, 16385, 16, 4, 128)
    # a position: 2 attention layers x K and V x 8 heads x 64 x 2 bytes
    assert pool_bytes(desc, paged) == 16385 * 16 * 4096
    # a slot's tails: 8 conv layers x 2 x 2048 x 2 bytes
    assert state_bytes_per_slot(desc) == 65_536
    whole = ModelDescription.from_published(
        dict(cfg, **cfg["published"]), ctx_size=1024, dtype="bfloat16",
        param_dtype="bfloat16")
    assert whole.periods()[1:] == (
        ("experts", ("attention", "conv", "conv", "conv"), 9, 2),
        ("experts", ("attention", "conv"), 1, 38))
    assert (whole.count_mixer("attention"), whole.count_mixer("conv")) == (
        10, 30)
