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


def test_latent_expert_serving_programs_at_published_widths(one_chip):
    """The engine's two programs for a described model (latent attention,
    routed experts) at A.X-K1's published widths, two layers (one dense,
    one of experts), 4 slots of 1024 positions. In the compiled
    ``decode_step`` no array holds a key or a value per head (positions x
    64 heads x 128, 192 or 256): the step attends over the latent rows as
    they lie; ``prefill_chunk`` expands them. Neither program copies the
    whole pool (declared 576 wide instead of ``row_stride``'s 640, both
    did, in and out), and the softmax's maximum is no row-wide
    ``reduce-window``."""
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
    decode = eng.make_decode_step(desc, paged, s, None, None).lower(
        pool, head, params["runs"], sds((s, mb), i32), sds((s,), i32),
        sds((s,), i32), sds((s, 2), u32), sds((s,), f32),
        sds((s,), jnp.bool_)).compile().as_text()
    prefill = eng.make_prefill_chunk(desc, paged, tc, None, None).lower(
        pool, head, params["runs"], sds((mb,), i32), sds((tc,), i32),
        sds((), i32), sds((), i32), sds((), i32), sds((2,), u32),
        sds((), f32)).compile().as_text()
    per_head = re.compile(r"\[(?:\d+,)*1024,64,(?:128|192|256)\]")
    assert not per_head.search(decode)
    assert per_head.search(prefill)
    for text in (decode, prefill):
        assert not re.search(r"= bf16\[2,257,16,640\]\S* copy\(", text)
        assert "reduce-window" not in text
        assert "ragged-dot" in text
