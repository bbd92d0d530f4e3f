"""Pallas flash attention vs the XLA reference.

These tests run in interpret mode on the CPU test mesh. The Mosaic lowering
is compiled for a described v5e in tests/test_tpu_compile.py, and the
compiled kernels are run against the same reference on the chip by
chip_smoke.py's kernels phase.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.config import LlamaConfig
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.ops.flash_attention import flash_attention


def _ref_attention(q, k, v, causal=True):
    return llama._xla_attention(q, k, v, causal=causal)


@pytest.mark.parametrize("t,dh", [(256, 48), (128, 64), (100, 32)])
def test_flash_matches_xla_causal(t, dh):
    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    b, h = 2, 3
    q = jax.random.normal(kq, (b, t, h, dh), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, dh), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, dh), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = _ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_noncausal_padded_tail():
    """Non-block-multiple t: padded tail keys must get zero softmax mass."""
    key = jax.random.key(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 100, 2, 32), jnp.float32)
    k = jax.random.normal(kk, (1, 100, 2, 32), jnp.float32)
    v = jax.random.normal(kv, (1, 100, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = _ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_mismatched_blocks():
    """block_q != block_k with t not a multiple of either: no dropped keys."""
    key = jax.random.key(4)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 100, 2, 32), jnp.float32)
    k = jax.random.normal(kk, (1, 100, 2, 32), jnp.float32)
    v = jax.random.normal(kv, (1, 100, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
    ref = _ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_noncausal():
    key = jax.random.key(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 128, 2, 32), jnp.float32)
    k = jax.random.normal(kk, (1, 128, 2, 32), jnp.float32)
    v = jax.random.normal(kv, (1, 128, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = _ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16_path():
    key = jax.random.key(2)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 256, 2, 48), jnp.bfloat16)
    k = jax.random.normal(kk, (1, 256, 2, 48), jnp.bfloat16)
    v = jax.random.normal(kv, (1, 256, 2, 48), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = _ref_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2)


def test_llama_forward_with_pallas_attention():
    """attention_impl='pallas' end-to-end through the model forward."""
    cfg = LlamaConfig(vocab_size=128, dmodel=64, num_heads=2, n_layers=2,
                      ctx_size=64, attention_impl="pallas")
    cfg_ref = LlamaConfig(vocab_size=128, dmodel=64, num_heads=2, n_layers=2,
                          ctx_size=64)
    params = llama.init_llama(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 128)
    out = llama.forward(params, tokens, cfg)
    ref = llama.forward(params, tokens, cfg_ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-2, rtol=5e-2)


# ------------------------------------------------------- dh-major variant

@pytest.mark.parametrize("t,dh,causal", [(256, 48, True), (128, 64, False),
                                         (100, 32, True), (100, 32, False)])
def test_flash_dh_major_matches_xla(t, dh, causal):
    """The [BH, Dh, T] dense-layout kernels are the same math — including
    padded tails (non-block-multiple t) on the lane axis."""
    kq, kk, kv = jax.random.split(jax.random.key(5), 3)
    b, h = 2, 3
    q = jax.random.normal(kq, (b, t, h, dh), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, dh), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, dh), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          dh_major=True)
    ref = _ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t", [256, 100])
def test_flash_dh_major_wide_block_matches_xla(t):
    """The production-default TPU path: dh-major with whole-sequence blocks
    (block_q = block_k = min(T, 512) — a single k-block, so the
    online-softmax recurrence never runs). LlamaConfig defaults route every
    T<=512 TPU training step through exactly this configuration
    (config.flash_block); cover fwd and grads, incl. a non-block-multiple T
    where the wide block equals the unpadded length."""
    kq, kk, kv, kw = jax.random.split(jax.random.key(11), 4)
    b, h, dh = 2, 2, 48
    blk = min(t, 512)
    q = jax.random.normal(kq, (b, t, h, dh), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, dh), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, dh), jnp.float32)
    w = jax.random.normal(kw, (b, t, h, dh), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=blk, block_k=blk,
                          dh_major=True)
    ref = _ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss(impl):
        def f(q, k, v):
            o = (flash_attention(q, k, v, causal=True, block_q=blk,
                                 block_k=blk, dh_major=True)
                 if impl == "pallas" else
                 _ref_attention(q, k, v, causal=True))
            return jnp.sum(o.astype(jnp.float32) * w)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for gf, gr, name in zip(loss("pallas"), loss("xla"), "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name}")


def test_flash_dh_major_bf16():
    kq, kk, kv = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(kq, (1, 256, 2, 48), jnp.bfloat16)
    k = jax.random.normal(kk, (1, 256, 2, 48), jnp.bfloat16)
    v = jax.random.normal(kv, (1, 256, 2, 48), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, dh_major=True)
    ref = _ref_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("t,causal", [(128, True), (100, False)])
def test_flash_dh_major_grad_matches_xla(t, causal):
    """dQ/dK/dV through the dh-major backward kernels, incl. padded query
    lanes (must backprop zeros)."""
    kq, kk, kv, kw = jax.random.split(jax.random.key(8), 4)
    b, h, dh = 2, 2, 48
    q = jax.random.normal(kq, (b, t, h, dh), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, dh), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, dh), jnp.float32)
    w = jax.random.normal(kw, (b, t, h, dh), jnp.float32)

    def loss(impl):
        def f(q, k, v):
            if impl == "pallas":
                o = flash_attention(q, k, v, causal=causal, block_q=64,
                                    block_k=64, dh_major=True)
            else:
                o = _ref_attention(q, k, v, causal=causal)
            return jnp.sum(o.astype(jnp.float32) * w)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for gf, gr, name in zip(loss("pallas"), loss("xla"), "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4, err_msg=f"d{name}")


# ------------------------------------------------------------ backward pass

def _loss_pair(t, dh, causal, dtype=jnp.float32, seed=7):
    kq, kk, kv, kw = jax.random.split(jax.random.key(seed), 4)
    b, h = 2, 2
    q = jax.random.normal(kq, (b, t, h, dh), dtype)
    k = jax.random.normal(kk, (b, t, h, dh), dtype)
    v = jax.random.normal(kv, (b, t, h, dh), dtype)
    w = jax.random.normal(kw, (b, t, h, dh), jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        return jnp.sum(o.astype(jnp.float32) * w)

    def loss_ref(q, k, v):
        o = _ref_attention(q, k, v, causal=causal)
        return jnp.sum(o.astype(jnp.float32) * w)

    return (q, k, v), loss_flash, loss_ref


@pytest.mark.parametrize("t,causal", [(128, True), (128, False),
                                      (100, True), (100, False)])
def test_flash_grad_matches_xla(t, causal):
    """dQ/dK/dV from the Pallas backward vs autodiff through the XLA path,
    including non-block-multiple t (padded query rows must backprop zeros)."""
    (q, k, v), loss_flash, loss_ref = _loss_pair(t, 32, causal)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4, err_msg=f"d{name}")


def test_flash_grad_mismatched_blocks():
    kq, kk, kv = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(kq, (1, 100, 2, 32), jnp.float32)
    k = jax.random.normal(kk, (1, 100, 2, 32), jnp.float32)
    v = jax.random.normal(kv, (1, 100, 2, 32), jnp.float32)

    def f(impl):
        def loss(q, k, v):
            if impl == "pallas":
                o = flash_attention(q, k, v, causal=True, block_q=128,
                                    block_k=64)
            else:
                o = _ref_attention(q, k, v, causal=True)
            return jnp.sum(o ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for gf, gr in zip(f("pallas"), f("xla")):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


def test_train_step_with_pallas_attention():
    """A full value_and_grad train step through the model with
    attention_impl='pallas' (the path round-1 shipped broken)."""
    import optax
    from ddl25spring_tpu.ops import causal_lm_loss

    cfg = LlamaConfig(vocab_size=128, dmodel=64, num_heads=2, n_layers=2,
                      ctx_size=64, attention_impl="pallas")
    params = llama.init_llama(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 128)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, tokens):
        def loss_fn(p):
            return causal_lm_loss(llama.forward(p, tokens, cfg), tokens)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2, loss

    params2, opt_state, loss = step(params, opt_state, tokens)
    assert jnp.isfinite(loss)
    # Params actually moved, and a second step also runs.
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()) > 0,
                         params, params2)
    assert any(jax.tree.leaves(moved))
    _, _, loss2 = step(params2, opt_state, tokens)
    assert jnp.isfinite(loss2)
