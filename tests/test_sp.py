"""Sequence parallelism: ring attention and the SP train step.

Checks that sharding the sequence over the 8-device virtual mesh is
numerically equivalent to the single-device reference — same logits, same
loss, same training trajectory.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ddl25spring_tpu.config import LlamaConfig
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.ops import causal_lm_loss
from ddl25spring_tpu.parallel import make_mesh, sp


def _cfg(ctx=64):
    return LlamaConfig(vocab_size=128, dmodel=32, num_heads=4, n_layers=2,
                       ctx_size=ctx)


def test_ring_attention_matches_full():
    """ring_attention over 4 shards == full causal attention."""
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    b, t, h, dh = 2, 64, 4, 16
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (b, t, h, dh), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, dh), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, dh), jnp.float32)

    ring = jax.jit(shard_map(
        lambda q, k, v: sp.ring_attention(q, k, v, "seq", causal=True),
        mesh=mesh, in_specs=P(None, "seq"), out_specs=P(None, "seq"),
        check_vma=False))
    out = ring(q, k, v)
    ref = llama._xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_sp_forward_matches_single_device():
    cfg = _cfg()
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    params = llama.init_llama(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.ctx_size), 0,
                                cfg.vocab_size)
    out = sp.sp_forward(params, tokens, cfg, mesh)
    ref = llama.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-3)


def test_sp_train_step_matches_single_device():
    """One SP train step == one single-device step: same loss, same params."""
    cfg = _cfg()
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    params = llama.init_llama(jax.random.key(0), cfg)
    # SGD, not Adam: the param check must be linear in the gradients, or
    # m/sqrt(v) normalization amplifies float accumulation-order noise on
    # near-zero coordinates into percent-level param differences.
    opt = optax.sgd(0.1)
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.ctx_size), 0,
                                cfg.vocab_size)

    # Reference first: the SP step donates its input state, which would
    # invalidate `params` buffers aliased into it.
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: causal_lm_loss(llama.forward(p, tokens, cfg), tokens))(params)
    updates, _ = opt.update(ref_grads, opt.init(params), params)
    ref_params = optax.apply_updates(params, updates)

    state = sp.init_state(mesh, params, opt)
    step = sp.make_sp_train_step(cfg, opt, mesh)
    state, loss = step(state, sp.shard_batch(mesh, tokens))

    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_sp_composes_with_dp():
    """(data=2, seq=4) mesh: DP×SP step matches single-device on the same
    global batch."""
    cfg = _cfg()
    mesh = make_mesh({"data": 2, "seq": 4})
    params = llama.init_llama(jax.random.key(0), cfg)
    opt = optax.adam(1e-3)
    tokens = jax.random.randint(jax.random.key(1), (4, cfg.ctx_size), 0,
                                cfg.vocab_size)

    ref_loss = causal_lm_loss(llama.forward(params, tokens, cfg), tokens)

    state = sp.init_state(mesh, params, opt)
    step = sp.make_sp_train_step(cfg, opt, mesh)
    state, loss = step(state, sp.shard_batch(mesh, tokens))

    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5, rtol=1e-5)
