"""Tensor parallelism: Megatron-sharded blocks vs the single-device model."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from ddl25spring_tpu.config import LlamaConfig
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.ops import causal_lm_loss
from ddl25spring_tpu.parallel import make_mesh, tp


def _cfg():
    return LlamaConfig(vocab_size=128, dmodel=32, num_heads=4, n_layers=2,
                       ctx_size=32)


def test_tp_forward_matches_single_device():
    cfg = _cfg()
    mesh = make_mesh({"model": 4}, devices=jax.devices()[:4])
    params = llama.init_llama(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.ctx_size), 0,
                                cfg.vocab_size)
    out = tp.tp_forward(tp.shard_params(mesh, params), tokens, cfg, mesh)
    ref = llama.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-3)


def test_tp_params_actually_sharded():
    cfg = _cfg()
    mesh = make_mesh({"model": 4}, devices=jax.devices()[:4])
    params = tp.shard_params(mesh, llama.init_llama(jax.random.key(0), cfg))
    wq_spec = params["blocks"]["wq"].sharding.spec
    wo_spec = params["blocks"]["wo"].sharding.spec
    assert wq_spec == P(None, None, "model"), wq_spec
    # Trailing-None-free on purpose: XLA normalizes output shardings, and
    # an unnormalized input spec would be a different jit cache signature
    # (one spurious re-lowering per driver — see tp.param_specs).
    assert wo_spec == P(None, "model"), wo_spec
    assert params["embed"].sharding.spec == P()


def test_tp_train_step_matches_single_device():
    cfg = _cfg()
    mesh = make_mesh({"model": 4}, devices=jax.devices()[:4])
    params = llama.init_llama(jax.random.key(0), cfg)
    opt = optax.sgd(0.1)  # linear in grads; see test_sp for why not Adam
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.ctx_size), 0,
                                cfg.vocab_size)

    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: causal_lm_loss(llama.forward(p, tokens, cfg), tokens))(params)
    updates, _ = opt.update(ref_grads, opt.init(params), params)
    ref_params = optax.apply_updates(params, updates)

    state = tp.init_state(mesh, params, opt)
    step = tp.make_tp_train_step(cfg, opt, mesh)
    state, loss = step(state, tp.shard_batch(mesh, tokens))

    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5, rtol=1e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(state.params)[0],
            jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4,
            err_msg=jax.tree_util.keystr(path))


def test_tp_composes_with_dp():
    cfg = _cfg()
    mesh = make_mesh({"data": 2, "model": 4})
    params = llama.init_llama(jax.random.key(0), cfg)
    opt = optax.sgd(0.1)
    tokens = jax.random.randint(jax.random.key(1), (4, cfg.ctx_size), 0,
                                cfg.vocab_size)

    ref_loss = causal_lm_loss(llama.forward(params, tokens, cfg), tokens)

    state = tp.init_state(mesh, params, opt)
    step = tp.make_tp_train_step(cfg, opt, mesh)
    state, loss = step(state, tp.shard_batch(mesh, tokens))

    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------- PSA column
#
# The ISSUE-18 composition column: partially-synchronized activations
# (TrainConfig.psa), the fused K-scan TP dispatch, and the DP×TP ring.
# The golden checks: psa off/"full" are BITWISE the legacy path, the
# relaxed modes hold a pinned convergence bar against the exact path on
# the tiny-llama fixture, and every driver variant is bitwise-reproducible
# under the K-scan / preempt-resume / numerics levers.


def _host_params(cfg, seed=0):
    """numpy leaves: jax.device_put may ALIAS a same-device jax.Array into
    the donated state, and the donated step would then delete the caller's
    buffers (the dp.replicate donation hazard) — numpy forces a copy."""
    return jax.tree.map(np.asarray, llama.init_llama(jax.random.key(seed), cfg))


def _tokens(cfg, batch=4, seed=1):
    return np.asarray(jax.random.randint(jax.random.key(seed),
                                         (batch, cfg.ctx_size), 0,
                                         cfg.vocab_size))


def _run_steps(step, state, batch, n):
    losses = []
    for _ in range(n):
        state, l = step(state, batch)
        losses.append(float(l))
    return state, losses


def test_tp_psa_off_and_full_bitwise_vs_legacy(devices):
    """psa="" (raw in-model psums) and psa="full" (the same sync positions
    through the telemetry comm wrappers) are BITWISE the legacy
    make_tp_train_step path — losses and params — over 3 adam steps.
    (One shared legacy reference: the factory compiles dominate this
    file's tier-1 cost.)"""
    cfg = _cfg()
    mesh = make_mesh({"model": 4}, devices=devices[:4])
    params = _host_params(cfg)
    tokens = _tokens(cfg)
    opt = optax.adam(1e-3)

    ref_state = tp.init_state(mesh, params, opt)
    legacy = tp.make_tp_train_step(cfg, opt, mesh)
    ref_state, ref_losses = _run_steps(legacy, ref_state,
                                       tp.shard_batch(mesh, tokens), 3)
    ref_leaves = jax.tree.leaves(jax.device_get(ref_state.params))

    for psa in ("", "full"):
        state, step = tp.make_tp_step(cfg, opt, mesh, params, psa=psa)
        state, losses = _run_steps(step, state,
                                   tp.shard_batch(mesh, tokens), 3)
        assert losses == ref_losses, psa
        for a, b in zip(ref_leaves,
                        jax.tree.leaves(jax.device_get(state.params))):
            np.testing.assert_array_equal(a, b)


def test_tp_psa_relaxed_convergence_bar(devices):
    """The relaxed sync modes on the tiny-llama fixture: losses finite and
    descending, and the 5-step trajectory tracks the exact path within the
    pinned bar — deferred sync's boundary correction and int8 EF's
    residual compensation keep the relaxation principled, not drifting.
    (One shared exact reference across the modes; defer:1 is subsumed by
    defer:2 — more deferral, same machinery.)"""
    cfg = _cfg()
    mesh = make_mesh({"model": 4}, devices=devices[:4])
    params = _host_params(cfg)
    tokens = _tokens(cfg)
    opt = optax.adam(1e-3)

    exact_state, exact_step = tp.make_tp_step(cfg, opt, mesh, params)
    _, exact_losses = _run_steps(exact_step, exact_state,
                                 tp.shard_batch(mesh, tokens), 5)

    for psa in ("defer:2", "int8_ef"):
        state, step = tp.make_tp_step(cfg, opt, mesh, params, psa=psa,
                                      batch_shape=(tokens.shape[0],
                                                   cfg.ctx_size))
        _, losses = _run_steps(step, state, tp.shard_batch(mesh, tokens), 5)

        assert all(np.isfinite(losses)), (psa, losses)
        assert losses[-1] < losses[0], (psa, losses)
        np.testing.assert_allclose(losses, exact_losses, atol=2e-2, rtol=0,
                                   err_msg=psa)


def test_tp_psa_int8_error_feedback_property(devices):
    """The EF residual contract of _psa_int8_sync on a quadratic-sized
    fixture: the residual carries exactly the quantization error
    (c − s·q per shard), so consecutive syncs TELESCOPE — out1 + out2 =
    2·exact − psum(res2), i.e. the CUMULATIVE error after two syncs is
    bounded by ONE quantization step, not two. (The per-step error is
    allowed to wobble — EF compensates cumulatively, it is not a
    per-step contraction.)"""
    from jax import shard_map

    mesh = make_mesh({"model": 4}, devices=devices[:4])
    y = np.linspace(-1.0, 1.0, 4 * 8 * 16, dtype=np.float32).reshape(4, 8, 16)

    def body(y_shard):
        y0 = y_shard[0]
        out1, res1 = tp._psa_int8_sync(y0, jnp.zeros_like(y0), 1)
        out2, res2 = tp._psa_int8_sync(y0, res1, 1)
        exact = jax.lax.psum(y0, "model")
        return out1[None], out2[None], res1[None], res2[None], exact[None]

    out1, out2, res1, res2, exact = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("model"),),
        out_specs=(P("model"),) * 5, check_vma=False))(y)
    e1 = np.abs(np.asarray(out1) - np.asarray(exact)).max()
    # int8 quantization error bound: each shard contributes ≤ s/2 ≈
    # max|c|/254; 4 shards of values in [-1, 1] (+ residual headroom).
    assert e1 <= 4 * 2.0 / 254 + 1e-6, e1
    # telescoping: out1 + out2 = 2·exact − psum(res2), so the two-sync
    # cumulative error is bounded by ONE sync's quantization error.
    cum = np.abs((np.asarray(out1) + np.asarray(out2))
                 - 2 * np.asarray(exact)).max()
    assert cum <= 4 * 2.0 / 254 + 1e-6, cum
    # the residual really is the per-shard quantization error: applying
    # it once must not leave a residual larger than one quantization step.
    assert np.abs(np.asarray(res2)).max() <= 2.0 / 254 + 1e-6


@pytest.mark.parametrize("psa", ["", "int8_ef"])
def test_tp_multi_step_bitwise_matches_per_step(devices, psa):
    """tp.make_tp_multi_step reproduces K per-step calls BITWISE at
    K∈{1,4} — the shared-body factory promise; int8_ef additionally
    proves the activation EF residual tree threads the scan carry.
    One 4-step per-step reference trajectory serves both K values
    (snapshotted after step 1 and step 4) to keep tier-1 cost down."""
    cfg = _cfg()
    mesh = make_mesh({"model": 4}, devices=devices[:4])
    tokens = _tokens(cfg)
    opt = optax.adam(1e-3)
    bshape = (tokens.shape[0], cfg.ctx_size)

    state1, step1 = tp.make_tp_step(cfg, opt, mesh, _host_params(cfg),
                                    psa=psa, batch_shape=bshape)
    batch = tp.shard_batch(mesh, tokens)
    ref = {}
    state1, l1 = _run_steps(step1, state1, batch, 1)
    ref[1] = (l1, jax.tree.leaves(jax.device_get(state1.params)))
    state1, l4 = _run_steps(step1, state1, batch, 3)
    ref[4] = (l1 + l4, jax.tree.leaves(jax.device_get(state1.params)))

    for k in (1, 4):
        state2, step2 = tp.make_tp_multi_step(
            cfg, opt, mesh, _host_params(cfg), psa=psa, batch_shape=bshape)
        window = tp.shard_batch_window(
            mesh, np.broadcast_to(tokens, (k,) + tokens.shape))
        state2, losses = step2(state2, window)

        ref_losses, ref_leaves = ref[k]
        assert [float(x) for x in losses] == ref_losses, k
        for a, b in zip(ref_leaves,
                        jax.tree.leaves(jax.device_get(state2.params))):
            np.testing.assert_array_equal(a, b)


def test_tp_numerics_on_off_bitwise(devices):
    """Arming make_tp_numerics adds OUTPUTS only: losses and params are
    bitwise identical on vs off, and the summary is model-axis
    psum-agreed (replicated — every shard returns the same stats)."""
    cfg = _cfg()
    mesh = make_mesh({"model": 4}, devices=devices[:4])
    tokens = _tokens(cfg)
    opt = optax.adam(1e-3)

    state1, step1 = tp.make_tp_step(cfg, opt, mesh, _host_params(cfg))
    state1, l1 = _run_steps(step1, state1, tp.shard_batch(mesh, tokens), 2)

    numerics = tp.make_tp_numerics(_host_params(cfg), mesh)
    state2, step2 = tp.make_tp_step(cfg, opt, mesh, _host_params(cfg),
                                    numerics=numerics)
    l2 = []
    summary = None
    for _ in range(2):
        state2, (loss, summary) = step2(state2, tp.shard_batch(mesh, tokens))
        l2.append(float(loss))

    assert l1 == l2
    for a, b in zip(jax.tree.leaves(jax.device_get(state1.params)),
                    jax.tree.leaves(jax.device_get(state2.params))):
        np.testing.assert_array_equal(a, b)
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(
        jax.device_get(summary)))


def test_tp_dp_overlap_replicas_bitwise_in_sync(devices):
    """DP×TP int8 ring + zero1: after 3 steps every replica of every
    param holds bitwise-identical values — data replicas because the int8
    delta gather applies the same quantized deltas everywhere (the
    compress.py zero1 rule), and MODEL replicas of the replicated leaves
    (norm scales) because the int8 scales are model-agreed
    (compress._int8_encode scale_sync_axis; without it each model cell's
    scale couples to its own col/row shard values and the replicated
    entries decode differently per cell)."""
    cfg = _cfg()
    mesh = make_mesh({"data": 2, "model": 4}, devices=devices[:8])
    tokens = _tokens(cfg, batch=8, seed=2)
    opt = optax.adam(1e-3)

    state, step = tp.make_tp_overlap_step(
        cfg, opt, mesh, _host_params(cfg), aggregation="zero1",
        wire="int8_ef", overlap_microbatches=2)
    state, losses = _run_steps(step, state, tp.shard_batch(mesh, tokens), 3)
    assert all(np.isfinite(losses))

    # embed is replicated over BOTH axes: all 8 addressable shards must
    # agree bitwise. Sharded leaves replicate over data only — the
    # per-device comparison below covers them via the full-array gather.
    embed = state.params["embed"]
    shards = [np.asarray(s.data) for s in embed.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
    for leaf in jax.tree.leaves(state.params):
        by_index = {}
        for s in leaf.addressable_shards:
            # s.index is a tuple of slice objects (unhashable) — key on
            # the (start, stop) pairs instead.
            key = tuple((sl.start, sl.stop) for sl in s.index)
            by_index.setdefault(key, []).append(np.asarray(s.data))
        for group in by_index.values():
            for g in group[1:]:
                np.testing.assert_array_equal(group[0], g)


@pytest.mark.parametrize("driver", ["psa_step", "overlap"])
def test_tp_preempt_resume_bitwise_through_ef_residuals(devices, driver):
    """A host snapshot/restore mid-run (the preempt/resume cycle) is
    BITWISE invisible: the activation EF residuals (TPActState) and the
    ring/gather EF residuals (OverlapEFState) live in the state tree, so
    4 straight steps == 2 steps + snapshot + restore + 2 steps."""
    cfg = _cfg()
    opt = optax.adam(1e-3)
    if driver == "psa_step":
        mesh = make_mesh({"model": 4}, devices=devices[:4])
        tokens = _tokens(cfg)
        make = lambda: tp.make_tp_step(  # noqa: E731
            cfg, opt, mesh, _host_params(cfg), psa="int8_ef",
            batch_shape=(tokens.shape[0], cfg.ctx_size))
        batch = tp.shard_batch(mesh, tokens)
    else:
        mesh = make_mesh({"data": 2, "model": 4}, devices=devices[:8])
        tokens = _tokens(cfg, batch=8, seed=2)
        make = lambda: tp.make_tp_overlap_step(  # noqa: E731
            cfg, opt, mesh, _host_params(cfg), aggregation="zero1",
            wire="int8_ef", overlap_microbatches=1)
        batch = tp.shard_batch(mesh, tokens)

    state, step = make()
    state, straight = _run_steps(step, state, batch, 4)
    straight_params = jax.device_get(state.params)

    state2, step2 = make()
    state2, first = _run_steps(step2, state2, batch, 2)
    snapshot = jax.device_get(state2)          # host round-trip (orbax shape)
    template, step3 = make()                   # fresh program, fresh buffers
    restored = jax.tree.map(
        lambda h, t: jax.device_put(np.asarray(h), t.sharding),
        snapshot, template)
    restored, rest = _run_steps(step3, restored, batch, 2)

    assert first + rest == straight
    for a, b in zip(jax.tree.leaves(straight_params),
                    jax.tree.leaves(jax.device_get(restored.params))):
        np.testing.assert_array_equal(a, b)


def test_tp_psa_named_errors(devices):
    """Unsupported PSA spellings and combinations die with NAMED errors,
    not shape mismatches deep in a trace."""
    cfg = _cfg()
    mesh = make_mesh({"model": 4}, devices=devices[:4])
    opt = optax.adam(1e-3)
    with pytest.raises(ValueError, match="divisible"):
        tp.make_tp_step(cfg, opt, mesh, _host_params(cfg), psa="defer:3")
    with pytest.raises(ValueError, match="psa"):
        tp.make_tp_step(cfg, opt, mesh, _host_params(cfg), psa="bogus")
    with pytest.raises(ValueError, match="batch_shape"):
        tp.make_tp_step(cfg, opt, mesh, _host_params(cfg), psa="int8_ef")
    mesh2 = make_mesh({"data": 2, "model": 4})
    with pytest.raises(ValueError, match="int8_ef"):
        tp.make_tp_overlap_step(cfg, opt, mesh2, _host_params(cfg),
                                aggregation="zero1", wire="int8_ef",
                                overlap_microbatches=1, psa="int8_ef")
    mesh3 = make_mesh({"data": 4}, devices=devices[:4])
    with pytest.raises(ValueError, match="model"):
        tp.make_tp_overlap_step(cfg, opt, mesh3, _host_params(cfg),
                                aggregation="zero1", wire="fp32",
                                overlap_microbatches=1)


def test_train_llm_tp_rejects_unsupported_levers(devices):
    """The TP trainer's validation wall (the test_train_llm_pp_rejects_
    dp_only_levers precedent): every combination the docs list as
    unsupported must hard-error at config time with a NAMED reason.
    PSA × elastic is no longer on the list (the remesh path resizes the
    activation EF residual trees now — tests/test_elastic.py); what
    remains named-unsupported is elastic × the DP×TP ring driver and
    elastic × numerics."""
    from ddl25spring_tpu.config import ResilienceConfig, TrainConfig
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train.llm import train_llm_tp

    cfg = _cfg()
    base = dict(batch_size=4, seq_len=16, iters=2, lr=3e-3, model=4)
    kw = dict(mesh=make_mesh({"model": 4}, devices=devices[:4]),
              tokenizer=ByteTokenizer(), log_every=0)
    with pytest.raises(ValueError, match="accum_steps"):
        train_llm_tp(cfg, TrainConfig(**base, accum_steps=4), **kw)
    with pytest.raises(ValueError, match="DP-trainer-only"):
        train_llm_tp(cfg, TrainConfig(**base, dcn=2, wire_dcn="int8_ef"),
                     **kw)
    with pytest.raises(ValueError, match="overlap_microbatches"):
        train_llm_tp(cfg, TrainConfig(**base, wire="int8_ef"), **kw)
    with pytest.raises(ValueError, match="ring driver"):
        train_llm_tp(cfg, TrainConfig(**base), aggregation="zero1", **kw)
    with pytest.raises(ValueError, match="ring driver"):
        train_llm_tp(cfg, TrainConfig(**base, overlap_microbatches=1),
                     aggregation="zero1",
                     resilience=ResilienceConfig(elastic=True), **kw)
    with pytest.raises(ValueError, match="numerics_every"):
        train_llm_tp(cfg, TrainConfig(**base, psa="int8_ef",
                                      numerics_every=1),
                     resilience=ResilienceConfig(elastic=True), **kw)
    with pytest.raises(ValueError, match="scale_hook"):
        train_llm_tp(cfg, TrainConfig(**base),
                     scale_hook=lambda *a: None, **kw)
    with pytest.raises(ValueError, match="injit_guard"):
        train_llm_tp(cfg, TrainConfig(**base),
                     resilience=ResilienceConfig(injit_guard=True), **kw)
