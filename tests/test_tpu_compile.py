"""Compile the main path's kernels and step programs for a described TPU v5e.

The TPU compiler is installed without a chip: it compiles for a topology
that is described and not attached (/opt/skills/guides/on-chip-measurement,
section 2). These tests hand it the Pallas kernels at the canonical widths
with ``interpret=False``, the whole DP train step, and the ZeRO-1 step on a
four-device mesh, and read the compiled text. A compile that passes here is
a compile, not a chip run: ``chip_smoke.py`` is the run.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU's library, so a worker that merely
imports or collects this file must not touch it, and everything built from
the topology is built in a fixture or a test. All of it lives in this one
file, which xdist's ``--dist loadfile`` gives to one worker.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ddl25spring_tpu.config import LlamaConfig
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.ops.flash_attention import flash_attention
from ddl25spring_tpu.ops.pallas_adam import _adam_leaf_pallas
from ddl25spring_tpu.parallel import dp

CANONICAL = LlamaConfig(dtype="bfloat16")     # 288 / 6 x 48 / 6 layers / 32000
BATCH, SEQ = 64, CANONICAL.ctx_size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache and
    # cannot be read back without the chip (the next one warns and compiles
    # again): keep the cache off around these tests.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _qkv(shape, sharding):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)] * 3


def _flash_program(dh_major: bool, block: int, backward: bool):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block, dh_major=dh_major,
                               interpret=False)
    if not backward:
        return jax.jit(fwd)
    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fwd(q, k, v).astype(jnp.float32)),
        (0, 1, 2)))


# Blocks: ``llama.attention`` asks for min(T, flash_block=512) = 256 at the
# canonical T; 128 is the kernel's own default.
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("block", [256, 128])
@pytest.mark.parametrize("dh_major", [False, True],
                         ids=["row_major", "dh_major"])
def test_flash_canonical_shape(one_chip, dh_major, block, backward):
    program = _flash_program(dh_major, block, backward)
    text = program.lower(*_qkv((BATCH, SEQ, 6, 48), one_chip)) \
        .compile().as_text()
    # fwd is one kernel; the backward adds the dQ and the dK/dV kernels.
    assert text.count("tpu_custom_call") >= (3 if backward else 1)


@pytest.mark.parametrize("dh_major", [False, True],
                         ids=["row_major", "dh_major"])
def test_flash_long_sequence_widest_block(one_chip, dh_major):
    """T=2048 at the widest block the model config asks for (512): the
    shape at which a kernel would outgrow the chip's fast memory first."""
    program = _flash_program(dh_major, 512, backward=True)
    compiled = program.lower(*_qkv((8, 2048, 6, 48), one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_pallas_adam_lm_head_leaf(one_chip):
    """The fused Adam apply on the [288, 32000] ``lm_head`` leaf: scalar
    prefetch, a ragged last grid step (18000 rows of 512 in blocks of 512)
    and p/m/v aliased in place."""
    leaf = jax.ShapeDtypeStruct((288, 32000), jnp.float32, sharding=one_chip)
    corrections = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip)
    compiled = _adam_leaf_pallas.lower(
        leaf, leaf, leaf, leaf, corrections, lr=8e-4, b1=0.9, b2=0.999,
        eps=1e-8, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _abstract_state(make, sharding):
    """``make()``'s pytree as ShapeDtypeStructs with ``sharding`` (nothing
    can be put on a described device, so nothing is built)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(make))


def test_dp_train_step_holds_the_flash_kernel(topo, monkeypatch):
    """The whole jitted DP train step at canonical width, batch 64. The
    step asks ``jax.default_backend()`` which attention to build, and here
    that still says cpu: the test steers it, the program has no option."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert llama.attention_path(CANONICAL, SEQ) == {"impl": "pallas",
                                                    "interpret": False}
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    opt = optax.adam(8e-4)
    step = dp.make_grad_aggregation_step(
        lambda p, b: llama.forward_loss(p, b, CANONICAL), opt, mesh)
    state = _abstract_state(
        lambda: dp.init_state(
            llama.init_llama(jax.random.key(0), CANONICAL), opt),
        NamedSharding(mesh, P()))
    batch = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32,
                                 sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(state, batch).compile()
    # The scanned layer body holds the forward kernel and its transpose
    # the two backward kernels.
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_zero1_step_on_four_chips_has_its_collectives(topo, monkeypatch):
    """The ZeRO-1 step over a 4-device mesh of the described chips: the
    program asks for a reduce-scatter of the gradients and an all-gather of
    the fresh parameter slices, and the compiled text holds collectives
    over all four devices. Which ones is the compiler's choice: on v5e:2x2
    it turns both into an ``all-reduce`` of the whole flat vector (PERF.md,
    PR 21), so the text is asked for any of the three.

    ``dp.make_zero1_step`` places its initial state on the mesh, which a
    described device cannot hold, so the step is assembled here from the
    same parts. Width 288 with depth and vocabulary cut: the compile time
    grows with the length of the flat vector (47 s at the canonical
    26.4 M), and the collectives are what this test is about."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = CANONICAL.replace(n_layers=2, vocab_size=2048)
    mesh = Mesh(np.array(topo.devices), ("data",))
    opt = optax.adam(8e-4)
    params = jax.eval_shape(
        lambda: llama.init_llama(jax.random.key(0), cfg))
    n, pad, local, total = dp._flat_geometry(mesh, params)
    assert n == 4
    opt_specs = jax.tree.map(
        lambda x: P("data") if x.ndim >= 1 else P(),
        jax.eval_shape(opt.init,
                       jax.ShapeDtypeStruct((local,), jnp.float32)))
    specs = dp.TrainState(P(), opt_specs, P())
    step = jax.jit(shard_map(
        dp._make_zero1_local_step(
            lambda p, b: llama.forward_loss(p, b, cfg), opt, n, pad, local,
            total),
        mesh=mesh, in_specs=(specs, P("data")), out_specs=(specs, P()),
        check_vma=False), donate_argnums=(0,))
    state = dp.TrainState(
        params,
        jax.eval_shape(opt.init,
                       jax.ShapeDtypeStruct((n * local,), jnp.float32)),
        jax.ShapeDtypeStruct((), jnp.int32))
    state = jax.tree.map(    # ``specs`` is a prefix tree of ``state``
        lambda spec, sub: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), sub),
        specs, state, is_leaf=lambda x: isinstance(x, P))
    batch = jax.ShapeDtypeStruct((n * BATCH, SEQ), jnp.int32,
                                 sharding=NamedSharding(mesh, P("data")))
    lowered = step.lower(state, batch)
    asked = lowered.as_text()
    assert "reduce_scatter" in asked and "all_gather" in asked
    text = lowered.compile().as_text()
    over_all_four = [
        line for line in text.splitlines()
        if re.search(r" (all-reduce|reduce-scatter|all-gather)(-start)?\(",
                     line) and "replica_groups={{0,1,2,3}}" in line]
    # The gradient's sync and the parameters' (which may carry the loss).
    assert len(over_all_four) >= 2, over_all_four
    assert "tpu_custom_call" in text


def test_decode_step_at_the_7b_cells_shapes_holds_the_paged_kernel(
        one_chip, monkeypatch):
    """``decode_step`` at the dense serving cells' shapes (8 layers of
    deepseek-llm-7b, 16 slots, 1281 blocks of 16, tables 128 wide, bf16),
    with ``jax.default_backend`` steered as above so that
    ``engine.paged_attention_path`` names the kernel: the compiled text
    holds the kernel's call and no value of the padded gather's shapes
    (2048 positions x 16 slots, either way round), and the pool is never
    copied. ``prefill_chunk``, 256 queries a slot, keeps the gather: its
    lowered text is the same with and without the steering."""
    from ddl25spring_tpu.models import generate
    from ddl25spring_tpu.serving import engine as eng
    from ddl25spring_tpu.serving.kvcache import PagedKVConfig, init_pool

    cfg = LlamaConfig(vocab_size=102400, dmodel=4096, num_heads=32,
                      n_layers=8, ffn_hidden=11008, ctx_size=2048,
                      rope_theta=10000.0, norm_eps=1e-6, dtype="bfloat16",
                      param_dtype="bfloat16")
    paged = PagedKVConfig(num_blocks=1281, block_len=16,
                          max_blocks_per_seq=128, kv_dtype="bfloat16")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    params = _abstract_state(
        lambda: llama.init_llama(jax.random.key(0), cfg), one_chip)
    fused = _abstract_state(
        lambda: generate._fuse_blocks(
            llama.init_llama(jax.random.key(0), cfg)["blocks"]), one_chip)
    pool = _abstract_state(lambda: init_pool(cfg, paged), one_chip)
    assert pool["k"].shape == (8, 1281, 16, 32, 128)
    s, mb, tc = 16, 128, 256
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    chunk_args = (pool, params, fused, sds((mb,), i32), sds((tc,), i32),
                  sds((), i32), sds((), i32), sds((), i32), sds((2,), u32),
                  sds((), f32))
    on_cpu = eng.make_prefill_chunk(cfg, paged, tc, None, None).lower(
        *chunk_args).as_text()

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert eng.paged_attention_path(1, 32, 128, jnp.dtype("bfloat16")) == {
        "impl": "pallas", "interpret": False}
    compiled = eng.make_decode_step(cfg, paged, s, None, None).lower(
        pool, params, fused, sds((s, mb), i32), sds((s,), i32),
        sds((s,), i32), sds((s, 2), u32), sds((s,), f32),
        sds((s,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text
    assert "[2048,16,32,128]" not in text and "[16,2048,32,128]" not in text
    assert not re.search(r"= bf16\[8,1281,16,32,128\]\S* copy\(", text)
    # the padded gather and its float32 copy were 0.81 GB of temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9
    assert eng.make_prefill_chunk(cfg, paged, tc, None, None).lower(
        *chunk_args).as_text() == on_cpu
    assert "tpu_custom_call" not in on_cpu


def test_latent_expert_serving_programs_at_published_widths(one_chip,
                                                            monkeypatch):
    """The engine's two programs for a described model (latent attention,
    routed experts) at A.X-K1's published widths, two layers (one dense,
    one of experts), 4 slots of 1024 positions. In the compiled
    ``decode_step`` no array holds a key or a value per head (positions x
    64 heads x 128, 192 or 256): the step attends over the latent rows as
    they lie; ``prefill_chunk`` expands them. Neither program copies the
    whole pool (declared 576 wide instead of ``row_stride``'s 640, both
    did, in and out), and the softmax's maximum is no row-wide
    ``reduce-window``. With ``jax.default_backend`` steered as above so
    that ``engine.chunk_attention_path`` names the kernel, ``prefill_chunk``
    holds it over the expansion's product as it lies and no float32 scores,
    and ``decode_step``'s lowered text is what it is without."""
    import json

    from ddl25spring_tpu.config import ModelDescription
    from ddl25spring_tpu.models import latent
    from ddl25spring_tpu.serving import engine as eng
    from ddl25spring_tpu.serving.kvcache import PagedKVConfig, init_pool

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs", "a.x-k1.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=2)
    desc = ModelDescription.from_published(
        cfg, ctx_size=1024, dtype="bfloat16", param_dtype="bfloat16")
    assert desc.runs() == (("dense", 0, 1), ("experts", 1, 1))
    assert (desc.attention.row_dim, desc.experts.n_experts,
            desc.experts.held_count) == (576, 192, 12)
    paged = PagedKVConfig(num_blocks=257, block_len=16, max_blocks_per_seq=64,
                          kv_dtype="bfloat16")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    params = _abstract_state(
        lambda: latent.init_params(jax.random.key(0), desc), one_chip)
    head = {k: v for k, v in params.items() if k != "runs"}
    pool = _abstract_state(lambda: init_pool(desc, paged), one_chip)
    assert pool["c"].shape == (2, 257, 16, 640)
    s, mb, tc = 4, 64, 512
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    step_args = (pool, head, params["runs"], sds((s, mb), i32),
                 sds((s,), i32), sds((s,), i32), sds((s, 2), u32),
                 sds((s,), f32), sds((s,), jnp.bool_))
    chunk_args = (pool, head, params["runs"], sds((mb,), i32),
                  sds((tc,), i32), sds((), i32), sds((), i32), sds((), i32),
                  sds((2,), u32), sds((), f32))
    lowered = eng.make_decode_step(desc, paged, s, None, None).lower(
        *step_args)
    decode = lowered.compile().as_text()
    prefill = eng.make_prefill_chunk(desc, paged, tc, None, None).lower(
        *chunk_args).compile().as_text()
    per_head = re.compile(r"\[(?:\d+,)*1024,64,(?:128|192|256)\]")
    assert not per_head.search(decode)
    assert per_head.search(prefill)
    # the kernel's call, not the bare name: the compiled text lists the
    # source files of its operations' stack frames, and under xdist's
    # ``--dist loadfile`` a worker that ran tests/test_chunk_attention.py
    # first has cached jaxprs of jax's own helpers traced from that file
    assert not re.search(r"chunk_attention\S* = ", prefill)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert eng.chunk_attention_path(tc, mb * 16, desc) == {
        "impl": "pallas", "interpret": False}
    assert eng.make_decode_step(desc, paged, s, None, None).lower(
        *step_args).as_text() == lowered.as_text()
    compiled = eng.make_prefill_chunk(desc, paged, tc, None, None).lower(
        *chunk_args).compile()
    fused = compiled.as_text()
    assert re.search(r"chunk_attention\S* = bf16\[1,512,8192\]\S* custom-call",
                     fused)
    # the kernel reads the product [positions, 64 heads x 256] as it lies;
    # no scores [64 heads, queries, 1024 keys] are left, in any order
    assert re.search(r"bf16\[1,1024,16384\]", fused)
    assert not per_head.search(fused)
    assert not re.search(r"f32\[(?:\d+,)*1024\]", fused)
    assert not re.search(r"f32\[(?:\d+,)*64,(?:\d+,)*1024", fused)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9
    for text in (decode, prefill, fused):
        assert not re.search(r"= bf16\[2,257,16,640\]\S* copy\(", text)
        assert "reduce-window" not in text
        assert "ragged-dot" in text


def test_state_space_serving_programs_at_published_widths(one_chip,
                                                          monkeypatch):
    """The engine's two programs for a model of state-space mixers beside
    grouped-query attention at Falcon-H1-34B's published widths, two layers,
    8 slots of 1024 positions, with ``jax.default_backend`` steered so that
    the decode step's attention is the kernel (20 query heads over 4
    key/value heads). Pool and state store are donated and aliased to the
    results, and neither program holds a temporary of the state's size, the
    whole store's or one layer's: the decode step updates every slot's state
    in place, the prefill chunk one slot's. The scan's products are float32
    (the state's type), the carried inputs of the convolution bf16."""
    import json

    from ddl25spring_tpu.config import ModelDescription
    from ddl25spring_tpu.models import state_space
    from ddl25spring_tpu.serving import engine as eng
    from ddl25spring_tpu.serving.kvcache import (PagedKVConfig, init_pool,
                                                 init_state)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs",
                           "falcon-h1-34b.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=2)
    desc = ModelDescription.from_published(
        cfg, ctx_size=1024, dtype="bfloat16", param_dtype="bfloat16")
    paged = PagedKVConfig(num_blocks=513, block_len=16, max_blocks_per_seq=64,
                          kv_dtype="bfloat16")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    params = _abstract_state(
        lambda: state_space.init_params(jax.random.key(0), desc), one_chip)
    head = {k: v for k, v in params.items() if k != "runs"}
    s, mb, tc = 8, 64, 256
    pool = _abstract_state(
        lambda: {**init_pool(desc, paged), **init_state(desc, s)}, one_chip)
    assert pool["k"].shape == (2, 513, 16, 4, 128)
    assert pool["s"].shape == (2, s, 32, 128, 256)
    assert pool["s"].dtype == jnp.float32
    assert pool["tail"].shape == (2, s, 3, 5120)
    donated = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert eng.paged_attention_path(1, 4, 128, jnp.dtype("bfloat16"), 16) == {
        "impl": "pallas", "interpret": False}
    decode = eng.make_decode_step(desc, paged, s, None, None).lower(
        pool, head, params["runs"], sds((s, mb), i32), sds((s,), i32),
        sds((s,), i32), sds((s, 2), u32), sds((s,), f32),
        sds((s,), jnp.bool_)).compile()
    prefill = eng.make_prefill_chunk(desc, paged, tc, None, None).lower(
        pool, head, params["runs"], sds((mb,), i32), sds((tc,), i32),
        sds((), i32), sds((), i32), sds((), i32), sds((2,), u32),
        sds((), f32), sds((), i32)).compile()
    layer_state = s * 32 * 128 * 256 * 4           # one layer's, every slot
    for compiled in (decode, prefill):
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes >= donated
        assert m.temp_size_in_bytes < layer_state // 2
        text = compiled.as_text()
        assert not re.search(r"= f32\[2,8,32,128,256\]\S* copy\(", text)
        assert not re.search(r"= bf16\[2,513,16,4,128\]\S* copy\(", text)
    text = decode.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text
    # the gather of the padded table is gone from the decode step
    assert "[8,1024,4,128]" not in text
    assert "tpu_custom_call" not in prefill.as_text()
