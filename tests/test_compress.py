"""Compressed gradient all-reduce (parallel/compress.py).

Pins: (1) the bf16-wire step tracks the uncompressed step closely; (2) the
collective really runs in the compressed dtype (jaxpr evidence — the test
that would catch a silent decay to an fp32 wire); (3) int8+error-feedback
converges where naive int8 stalls, and its residual is exactly the
quantization remainder; (4) both steps train a real model end to end on the
virtual mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.parallel import compress, dp, make_mesh


def _mesh2():
    return make_mesh({"data": 2})


def _quadratic_setup(key, dim=64):
    # Convex problem with a known optimum at w*: loss = mean((x@w - y)^2).
    k1, k2, k3 = jax.random.split(key, 3)
    w_star = jax.random.normal(k1, (dim,))
    x = jax.random.normal(k2, (256, dim))
    y = x @ w_star
    params = {"w": jnp.zeros((dim,))}

    def loss_fn(p, batch):
        xb, yb = batch[..., :-1], batch[..., -1]
        return jnp.mean((xb @ p["w"] - yb) ** 2)

    batch = jnp.concatenate([x, y[:, None]], axis=-1)
    return params, loss_fn, batch, w_star


def test_bf16_step_tracks_uncompressed():
    mesh = _mesh2()
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(0))
    opt = optax.sgd(0.05)

    s_ref = dp.replicate(mesh, dp.init_state(
        jax.tree.map(jnp.copy, params), opt))
    s_bf = dp.replicate(mesh, dp.init_state(
        jax.tree.map(jnp.copy, params), opt))
    step_ref = dp.make_grad_aggregation_step(loss_fn, opt, mesh)
    step_bf = compress.make_bf16_grad_step(loss_fn, opt, mesh)
    sb = dp.shard_batch(mesh, batch)
    for _ in range(20):
        s_ref, l_ref = step_ref(s_ref, sb)
        s_bf, l_bf = step_bf(s_bf, sb)
    # bf16 has ~3 decimal digits; over 20 steps the trajectories stay close.
    np.testing.assert_allclose(float(l_bf), float(l_ref), rtol=0.05)
    np.testing.assert_allclose(np.asarray(s_bf.params["w"]),
                               np.asarray(s_ref.params["w"]), atol=0.02)


def test_wire_dtypes_in_compiled_program():
    """The compressed collectives must actually move compressed elements:
    the bf16 step's gradient pmean operand is bf16, and the int8 step's one
    gradient collective is an all_gather whose operand is int8 (the int32
    sum is local arithmetic, not a collective) — not fp32 gradients."""
    mesh = _mesh2()
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(1))
    opt = optax.sgd(0.05)

    jaxpr = str(jax.make_jaxpr(
        lambda s, b: compress.make_bf16_grad_step(loss_fn, opt, mesh)(s, b))(
            dp.replicate(mesh, dp.init_state(params, opt)),
            dp.shard_batch(mesh, batch)))
    assert "bf16[65]" in jaxpr.replace("bfloat16", "bf16") or \
        "bf16[64]" in jaxpr.replace("bfloat16", "bf16"), \
        "no bf16 gradient collective found in the bf16-wire step"

    # Two leaves: the payload must ride ONE concatenated all_gather, not
    # one collective per leaf.
    params = {**params, "extra": jnp.zeros((32,))}
    state = compress.init_ef_state(mesh, params, opt)
    jaxpr8 = str(jax.make_jaxpr(
        lambda s, b: compress.make_int8_ef_grad_step(loss_fn, opt, mesh)(s, b))(
            state, dp.shard_batch(mesh, batch)))
    import re
    n_gathers = len(re.findall(r"= all_gather\[", jaxpr8))
    assert n_gathers == 1, \
        f"expected one concatenated all_gather eqn, found {n_gathers}"
    # The gradient's collective is an all_gather of an i8 operand...
    assert re.search(r"all_gather\S*\s[a-z]+:i8\[", jaxpr8) or \
        re.search(r":i8\[64\][^\n]*\n[^\n]*all_gather", jaxpr8) or \
        ("all_gather" in jaxpr8 and "i8[64]" in jaxpr8), \
        "no int8 all_gather found in the int8-EF step"
    # ...and no gradient-sized int32 (or fp32-gradient) psum exists: the
    # only psum operands are the scalar loss / scale reductions.
    for m in re.finditer(r"(psum|pmax|pmin)[^\n]*", jaxpr8):
        assert "i32[64]" not in m.group(0) and "f32[64]" not in m.group(0), \
            f"gradient-sized reduction on the wire: {m.group(0)}"


def test_int8_ef_residual_is_quantization_remainder():
    mesh = _mesh2()
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(2))
    opt = optax.sgd(0.0)  # lr 0: params frozen, residual pure quantization
    state = compress.init_ef_state(mesh, params, opt)
    step = compress.make_int8_ef_grad_step(loss_fn, opt, mesh)
    state, _ = step(state, dp.shard_batch(mesh, batch))
    # |residual| <= s/2 elementwise, s = pmax|c|/127: remainder of rounding.
    res = np.asarray(jax.device_get(state.residual["w"]))
    assert res.shape[0] == 2
    # Reconstruct the SHARED scale (pmax over both shards' c = g + 0).
    grads = []
    for shard in range(2):
        sb = np.asarray(batch).reshape(2, -1, batch.shape[-1])[shard]
        xb, yb = sb[:, :-1], sb[:, -1]
        grads.append(2 * xb.T @ (xb @ np.zeros(64) - yb) / len(sb))
    s = max(np.abs(g).max() for g in grads) / 127.0
    assert np.abs(res).max() <= s * 0.51 + 1e-12


def test_int8_ef_converges_on_quadratic():
    mesh = _mesh2()
    params, loss_fn, batch, w_star = _quadratic_setup(jax.random.key(3))
    opt = optax.sgd(0.05)
    state = compress.init_ef_state(mesh, params, opt)
    step = compress.make_int8_ef_grad_step(loss_fn, opt, mesh)
    sb = dp.shard_batch(mesh, batch)
    losses = []
    for _ in range(60):
        state, loss = step(state, sb)
        losses.append(float(loss))
    assert losses[-1] < 1e-2 * losses[0], (losses[0], losses[-1])


@pytest.mark.parametrize("maker", ["bf16", "int8"])
def test_llm_end_to_end(maker):
    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama

    mesh = _mesh2()
    cfg = LlamaConfig(vocab_size=64, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=8)
    params = llama.init_llama(jax.random.key(0), cfg)
    opt = optax.adam(1e-3)

    def loss_fn(p, b):
        return llama.forward_loss(p, b, cfg)

    if maker == "bf16":
        state = dp.replicate(mesh, dp.init_state(params, opt))
        step = compress.make_bf16_grad_step(loss_fn, opt, mesh)
    else:
        state = compress.init_ef_state(mesh, params, opt)
        step = compress.make_int8_ef_grad_step(loss_fn, opt, mesh)
    toks = jax.random.randint(jax.random.key(1), (4, 8), 0, 64)
    sb = dp.shard_batch(mesh, toks)
    first = None
    for _ in range(10):
        state, loss = step(state, sb)
        first = first if first is not None else float(loss)
    assert float(loss) < first


# ---------------------------------------------------------------------------
# Overlapped, compressed gradient sync (the ACCO-style microbatch ring).
#
# Pins: (1) the ppermute ring reduce-scatter is bitwise-equal to its
# documented ring-order spec and to lax.psum_scatter wherever the addition
# is exact (the two associate differently, so general floats match to
# re-association tolerance); (2) wire dtypes really ride the ppermute hops
# (jaxpr evidence); (3) the K-step scanned driver is bitwise the per-step
# driver at any K and M, for every wire format; (4) M=1 f32 matches the
# existing fused paths to fp32 tolerance; (5) int8+EF converges where the
# ring quantization alone would stall, and the EF residuals survive a
# preempt/resume cycle EXACTLY (bitwise trajectory across the restart) —
# on the new driver and on the legacy per-step int8 path.

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


def _mesh4(devices):
    return make_mesh({"data": 4}, devices=devices[:4])


def _ring_spec_reference(cols, owner, n):
    """Host-side spec of the ring order: chunk ``owner``'s partial starts
    at rank owner+1 and accumulates one rank per hop, the owner last."""
    c = cols[0].shape[0] // n
    sl = slice(owner * c, (owner + 1) * c)
    order = [(owner + 1 + i) % n for i in range(n)]
    s = cols[order[0]][sl].copy()
    for i in order[1:]:
        s = s + cols[i][sl]
    return s


def test_ring_reduce_scatter_matches_spec_order_bitwise(devices):
    """The f32 ring is bitwise its documented summation order — chunk c
    associates as (((g_{c+1} + g_{c+2}) + ...) + g_c) — on every shard."""
    n = 4
    mesh = _mesh4(devices)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n * 6)).astype(np.float32)

    def f(v):
        out, _ = compress.ring_reduce_scatter(v, "data", wire="fp32")
        return out

    g = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), check_vma=False))
    out = np.asarray(g(jax.device_put(
        x.reshape(-1), NamedSharding(mesh, P("data"))))).reshape(n, 6)
    for r in range(n):
        np.testing.assert_array_equal(
            out[r], _ring_spec_reference(list(x), r, n))


def test_ring_reduce_scatter_vs_psum_scatter(devices):
    """Satellite pin: vs ``lax.psum_scatter``. XLA CPU's scatter associates
    rank-linearly while the ring associates ring-order (a ring cannot
    produce the linear order for every chunk without serializing through
    rank 0), so the contract is: BITWISE equality wherever the addition is
    exact — integer-valued gradients, where association cannot matter —
    and re-association tolerance on general floats."""
    from jax import lax
    n = 4
    mesh = _mesh4(devices)
    rng = np.random.default_rng(1)

    def f_ring(v):
        out, _ = compress.ring_reduce_scatter(v, "data", wire="fp32")
        return out

    def f_ref(v):
        return lax.psum_scatter(v, "data", scatter_dimension=0, tiled=True)

    ring = jax.jit(shard_map(f_ring, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"), check_vma=False))
    ref = jax.jit(shard_map(f_ref, mesh=mesh, in_specs=P("data"),
                            out_specs=P("data"), check_vma=False))

    exact = jax.device_put(
        rng.integers(-1000, 1000, size=n * n * 8).astype(np.float32),
        NamedSharding(mesh, P("data")))
    np.testing.assert_array_equal(np.asarray(ring(exact)),
                                  np.asarray(ref(exact)))
    floats = jax.device_put(
        rng.standard_normal(n * n * 8).astype(np.float32),
        NamedSharding(mesh, P("data")))
    np.testing.assert_allclose(np.asarray(ring(floats)),
                               np.asarray(ref(floats)),
                               rtol=1e-6, atol=1e-6)


def test_overlap_wire_dtypes_ride_the_ppermute_hops():
    """jaxpr evidence that the ring's in-flight chunks are COMPRESSED: the
    int8 driver's ppermutes carry i8 chunk payloads (plus f32 scalar
    scales) and no gradient-sized f32 ppermute exists; the bf16 driver's
    carry bf16."""
    mesh = _mesh2()
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(1))
    opt = optax.sgd(0.05)

    state8, step8 = compress.make_overlap_step(
        loss_fn, opt, mesh, params, microbatches=2, wire="int8_ef",
        aggregation="zero1")
    jx8 = str(jax.make_jaxpr(lambda s, b: step8(s, b))(
        state8, dp.shard_batch(mesh, batch)))
    hops = [ln for ln in jx8.splitlines() if "ppermute" in ln]
    assert any(":i8[32]" in ln or "i8[32]" in ln for ln in hops), \
        f"no int8 chunk hop in: {hops}"
    for ln in hops:
        # f32 ppermutes may carry only the scalar scale sidecars (f32[]).
        assert "f32[32]" not in ln, \
            f"gradient-sized f32 hop on the wire: {ln}"

    stateb, stepb = compress.make_overlap_step(
        loss_fn, opt, mesh, params, microbatches=1, wire="bf16",
        aggregation="gradient")
    jxb = str(jax.make_jaxpr(lambda s, b: stepb(s, b))(
        stateb, dp.shard_batch(mesh, batch))).replace("bfloat16", "bf16")
    hops = [ln for ln in jxb.splitlines() if "ppermute" in ln]
    assert any("bf16[32]" in ln for ln in hops), \
        f"no bf16 chunk hop in: {hops}"


@pytest.mark.parametrize("wire", ["fp32", "bf16", "int8_ef"])
def test_overlap_multi_step_bitwise_matches_per_step(devices, wire):
    """The fused K-step overlap driver reproduces the per-step driver's
    loss sequence AND final state bitwise at K=4, M=2 — the scanned body
    is the shared local step, so drift is a bug (the make_multi_step
    contract carried to the ring driver; for int8 this additionally
    proves the EF residuals thread the scan carry exactly)."""
    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama

    mesh = _mesh4(devices)
    cfg = LlamaConfig(vocab_size=64, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=8)

    def loss_fn(p, b):
        return llama.forward_loss(p, b, cfg)

    ks = jax.random.split(jax.random.key(2), 4)
    batches = [jax.random.randint(k, (8, 8), 0, 64) for k in ks]

    s1, step1 = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh,
        llama.init_llama(jax.random.key(0), cfg),
        microbatches=2, wire=wire, aggregation="zero1")
    ref = []
    for b in batches:
        s1, l = step1(s1, dp.shard_batch(mesh, b))
        ref.append(float(l))

    sK, stepK = compress.make_overlap_multi_step(
        loss_fn, optax.adam(1e-3), mesh,
        llama.init_llama(jax.random.key(0), cfg),
        microbatches=2, wire=wire, aggregation="zero1")
    sK, losses = stepK(sK, dp.shard_batch_window(mesh, np.stack(batches)))
    assert [float(x) for x in np.asarray(losses)] == ref
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(sK)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_overlap_f32_matches_existing_paths(devices):
    """M=1 f32 ring vs the existing fused paths: same math, ring-vs-linear
    reduction order only — fp32-tolerance equality for both aggregations
    (the overlap restructuring itself must not touch the numerics)."""
    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama

    mesh = _mesh4(devices)
    cfg = LlamaConfig(vocab_size=64, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=8)

    def loss_fn(p, b):
        return llama.forward_loss(p, b, cfg)

    batches = [jax.random.randint(k, (8, 8), 0, 64)
               for k in jax.random.split(jax.random.key(3), 3)]

    z_state, z_step = dp.make_zero1_step(
        loss_fn, optax.adam(1e-3), mesh,
        llama.init_llama(jax.random.key(0), cfg))
    o_state, o_step = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh,
        llama.init_llama(jax.random.key(0), cfg),
        microbatches=1, wire="fp32", aggregation="zero1")
    for b in batches:
        z_state, zl = z_step(z_state, dp.shard_batch(mesh, b))
        o_state, ol = o_step(o_state, dp.shard_batch(mesh, b))
        np.testing.assert_allclose(float(ol), float(zl), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(z_state.params),
                    jax.tree.leaves(o_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6, rtol=1e-5)

    g_state = dp.replicate(mesh, dp.init_state(
        llama.init_llama(jax.random.key(0), cfg), optax.adam(1e-3)))
    g_step = dp.make_grad_aggregation_step(loss_fn, optax.adam(1e-3), mesh)
    og_state, og_step = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh,
        llama.init_llama(jax.random.key(0), cfg),
        microbatches=1, wire="fp32", aggregation="gradient")
    for b in batches:
        g_state, gl = g_step(g_state, dp.shard_batch(mesh, b))
        og_state, ogl = og_step(og_state, dp.shard_batch(mesh, b))
        np.testing.assert_allclose(float(ogl), float(gl), rtol=1e-6)


def test_overlap_int8_converges_on_quadratic():
    """int8 in-flight ring chunks + int8 second leg with EF converge on
    the convex problem (the existing int8 path's bar), at M=2 where the
    microbatch pipeline and the per-hop quantization are both live."""
    mesh = _mesh2()
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(3))
    for agg in ("gradient", "zero1"):
        state, step = compress.make_overlap_step(
            loss_fn, optax.sgd(0.05), mesh,
            jax.tree.map(jnp.copy, params), microbatches=2,
            wire="int8_ef", aggregation=agg)
        sb = dp.shard_batch(mesh, batch)
        losses = []
        for _ in range(60):
            state, loss = step(state, sb)
            losses.append(float(loss))
        assert losses[-1] < 1e-2 * losses[0], (agg, losses[0], losses[-1])


def test_overlap_replicas_stay_bitwise_identical(devices):
    """Every wire format broadcasts ONE payload all shards apply
    identically, so the replicated params must stay bitwise in sync —
    the invariant that makes the quantized second leg sound."""
    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama

    mesh = _mesh4(devices)
    cfg = LlamaConfig(vocab_size=64, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=8)

    def loss_fn(p, b):
        return llama.forward_loss(p, b, cfg)

    batch = jax.random.randint(jax.random.key(1), (8, 8), 0, 64)
    for wire in ("fp32", "bf16", "int8_ef"):
        for agg in ("gradient", "zero1"):
            state, step = compress.make_overlap_step(
                loss_fn, optax.adam(1e-3), mesh,
                llama.init_llama(jax.random.key(0), cfg),
                microbatches=2, wire=wire, aggregation=agg)
            for _ in range(2):
                state, _ = step(state, dp.shard_batch(mesh, batch))
            for leaf in jax.tree.leaves(state.params):
                shards = [np.asarray(s.data)
                          for s in leaf.addressable_shards]
                for s in shards[1:]:
                    np.testing.assert_array_equal(shards[0], s)


def test_overlap_ef_residual_exact_through_preempt_resume(devices):
    """The acceptance bar: an int8+EF overlap run (zero1, K=2) interrupted
    at a chunk edge and resumed from its checkpoint walks BITWISE the
    uninterrupted trajectory — possible only if both EF residual trees
    restore exactly (a zeroed residual would shift every loss after the
    resume point)."""
    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train import train_llm_dp

    cfg = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=16)
    base = dict(batch_size=2, seq_len=16, lr=3e-3, data=2, wire="int8_ef",
                overlap_microbatches=2, steps_per_dispatch=2)
    mesh = lambda: make_mesh({"data": 2}, devices=devices[:2])  # noqa: E731

    ref = train_llm_dp(cfg, TrainConfig(**base, iters=6),
                       tokenizer=ByteTokenizer(), aggregation="zero1",
                       mesh=mesh(), log_every=0)
    import tempfile
    d = tempfile.mkdtemp()
    a = train_llm_dp(cfg, TrainConfig(**base, iters=4),
                     tokenizer=ByteTokenizer(), aggregation="zero1",
                     mesh=mesh(), log_every=0, checkpoint_dir=d,
                     checkpoint_every=100)
    b = train_llm_dp(cfg, TrainConfig(**base, iters=6),
                     tokenizer=ByteTokenizer(), aggregation="zero1",
                     mesh=mesh(), log_every=0, checkpoint_dir=d,
                     checkpoint_every=100)
    assert a.losses + b.losses == ref.losses


def test_int8_ef_legacy_resume_preserves_residual(devices):
    """Satellite pin: the legacy per-step int8+EF path's residual IS part
    of checkpointed state (EFTrainState rides the checkpointer whole) —
    a mid-run preemption must not silently drop accumulated quantization
    error, proven by bitwise trajectory equality across a resume."""
    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train import train_llm_dp

    cfg = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=16)
    base = dict(batch_size=2, seq_len=16, lr=3e-3, data=2, wire="int8_ef")
    mesh = lambda: make_mesh({"data": 2}, devices=devices[:2])  # noqa: E731

    ref = train_llm_dp(cfg, TrainConfig(**base, iters=6),
                       tokenizer=ByteTokenizer(), mesh=mesh(), log_every=0)
    import tempfile
    d = tempfile.mkdtemp()
    a = train_llm_dp(cfg, TrainConfig(**base, iters=3),
                     tokenizer=ByteTokenizer(), mesh=mesh(), log_every=0,
                     checkpoint_dir=d, checkpoint_every=100)
    b = train_llm_dp(cfg, TrainConfig(**base, iters=6),
                     tokenizer=ByteTokenizer(), mesh=mesh(), log_every=0,
                     checkpoint_dir=d, checkpoint_every=100)
    assert a.losses + b.losses == ref.losses


def test_overlap_trainer_composition_and_guards(devices):
    """Trainer-level composition: overlap_microbatches=2 + bf16 wire +
    zero1 + steps_per_dispatch=2 trains finite and matches its own
    per-step-dispatch run bitwise; invalid compositions fail loudly."""
    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train import train_llm_dp

    cfg = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=16)
    base = dict(batch_size=2, seq_len=16, iters=4, lr=3e-3, data=2,
                wire="bf16", overlap_microbatches=2)
    mesh = lambda: make_mesh({"data": 2}, devices=devices[:2])  # noqa: E731
    ref = train_llm_dp(cfg, TrainConfig(**base), tokenizer=ByteTokenizer(),
                       aggregation="zero1", mesh=mesh(), log_every=0)
    got = train_llm_dp(cfg, TrainConfig(**base, steps_per_dispatch=2),
                       tokenizer=ByteTokenizer(), aggregation="zero1",
                       mesh=mesh(), log_every=0)
    assert got.losses == ref.losses
    assert all(np.isfinite(ref.losses))

    with pytest.raises(ValueError, match="zero1 aggregation only"):
        train_llm_dp(cfg, TrainConfig(**base), tokenizer=ByteTokenizer(),
                     aggregation="weight", mesh=mesh(), log_every=0)
    with pytest.raises(ValueError, match="accum_steps"):
        train_llm_dp(cfg, TrainConfig(**base, accum_steps=2),
                     tokenizer=ByteTokenizer(), mesh=mesh(), log_every=0)
    # numerics_every now COMPOSES with the ring driver (PR 12 satellite —
    # was a hard error): same trajectory bitwise, instrumentation on.
    instr = train_llm_dp(cfg, TrainConfig(**base, numerics_every=2),
                         tokenizer=ByteTokenizer(), aggregation="zero1",
                         mesh=mesh(), log_every=0)
    assert instr.losses == ref.losses


# ---------------------------------------------------------------------------
# Bucketed backward (comm_buckets > 1): sub-1/n ring chunking that starts
# the first hop before the full gradient materializes (ISSUE 19).
# ---------------------------------------------------------------------------


def _llama_setup(key=0):
    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama

    cfg = LlamaConfig(vocab_size=64, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=8)

    def loss_fn(p, b):
        return llama.forward_loss(p, b, cfg)

    return cfg, loss_fn, llama.init_llama(jax.random.key(key), cfg)


def test_bucket_map_covers_in_vjp_emission_order():
    """The BucketMap partitions the padded flat space exactly once, with
    lm_head first and the embedding last (top-of-network buckets first —
    the VJP emission order that makes early rings independent of late
    grads), blocks layers walked top-down, and the global pad riding the
    LAST bucket's tail."""
    _, _, params = _llama_setup()
    n = 4
    for B in (1, 2, 3, 8):
        bm = compress.make_bucket_map(params, n, B)
        assert bm.nbuckets == B
        assert sum(bm.sizes) == bm.local
        assert bm.n * bm.local == bm.total + bm.pad
        # pieces tile [0, n·local) exactly once, in order
        pos = 0
        for _, start, size in [pc for b in bm.pieces for pc in b]:
            del start
            pos += size
        assert pos + bm.pad == bm.n * bm.local
    bm = compress.make_bucket_map(params, n, 8)
    leaf_order = [pc[0] for b in bm.pieces for pc in b]
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    first_key = paths[leaf_order[0]]
    last_key = paths[leaf_order[-1]]
    assert "lm_head" in first_key, first_key
    assert "embed" in last_key, last_key
    with pytest.raises(ValueError, match="exceeds the per-shard slice"):
        compress.make_bucket_map(params, n, 10 ** 9)


def test_bucketed_fp32_ring_bitwise_at_every_bucket_count(devices):
    """THE house bar, at the ring level: on exact-arithmetic inputs
    (small integers — every fp32 sum is exact regardless of association)
    the per-bucket rings and the unbucketed ``ring_reduce_scatter``
    BITWISE agree with the exact cross-shard sum — hence with each other
    — at every bucket count. Bucketing re-chunks the ring and reorders
    coordinates, which can only reassociate sums; exact sums don't
    care."""
    mesh = _mesh4(devices)
    n, local = 4, 16
    params = {"w": jnp.zeros((n * local,))}   # single leaf: no pad
    xs = np.asarray(jax.random.randint(jax.random.key(9),
                                       (n, n * local), -50, 50),
                    dtype=np.float32)
    exact = xs.sum(axis=0)                    # integer sums: exact in fp32

    for B in (1, 2, 3, 8):
        bm = compress.make_bucket_map(params, n, B)

        def body(x):
            v = x.reshape(-1)
            outs = []
            for b in range(bm.nbuckets):
                o = bm.n * bm.offsets[b]
                red, _ = compress.ring_reduce_scatter(
                    v[o:o + bm.n * bm.sizes[b]], "data", wire="fp32",
                    residual=None, label=f"ring_grad_b{b}")
                outs.append(red)
            return jnp.concatenate(outs)[None]

        got = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False))(jnp.asarray(xs))
        got = np.asarray(got)                 # [n, local] owned concats
        for r in range(n):
            want = np.concatenate([
                exact[bm.n * bm.offsets[b] + r * bm.sizes[b]:
                      bm.n * bm.offsets[b] + (r + 1) * bm.sizes[b]]
                for b in range(bm.nbuckets)])
            np.testing.assert_array_equal(got[r], want)


def test_bucketed_driver_fp32_matches_unbucketed(devices):
    """Driver level: the first step from w=0 on integer data is exact
    arithmetic end-to-end (integer gradients, dyadic lr) — losses AND
    params bitwise across bucket counts; further steps accumulate only
    reassociation-level float noise (losses stay equal, params to fp32
    tolerance), for both aggregations."""
    mesh = _mesh4(devices)
    dim = 64
    k1, k2 = jax.random.split(jax.random.key(7))
    w_star = jnp.round(jax.random.normal(k1, (dim,)) * 3)
    x = jnp.round(jax.random.normal(k2, (64, dim)) * 2)
    y = x @ w_star
    batch = jnp.concatenate([x, y[:, None]], axis=-1)

    def loss_fn(p, b):
        xb, yb = b[..., :-1], b[..., -1]
        return jnp.mean((xb @ p["w"] - yb) ** 2)

    def run(B, agg, steps):
        state, step = compress.make_overlap_step(
            loss_fn, optax.sgd(2. ** -4), mesh, {"w": jnp.zeros((dim,))},
            microbatches=2, wire="fp32", aggregation=agg, comm_buckets=B)
        losses = []
        for _ in range(steps):
            state, l = step(state, dp.shard_batch(mesh, batch))
            losses.append(float(l))
        return losses, np.asarray(state.params["w"])

    for agg in ("gradient", "zero1"):
        ref1_l, ref1_w = run(1, agg, 1)
        ref4_l, ref4_w = run(1, agg, 4)
        for B in (2, 3, 8):
            got_l, got_w = run(B, agg, 1)
            assert got_l == ref1_l, (agg, B, ref1_l, got_l)
            np.testing.assert_array_equal(got_w, ref1_w)
            got_l, got_w = run(B, agg, 4)
            assert got_l == ref4_l, (agg, B, ref4_l, got_l)
            np.testing.assert_allclose(got_w, ref4_w, atol=1e-6, rtol=0)


def test_bucketed_int8_converges_on_quadratic():
    """int8 wire × comm_buckets=4: per-bucket quantization + per-bucket EF
    residual tuples hold the PR 10 convergence bound on the convex
    problem, for both aggregations."""
    mesh = _mesh2()
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(3))
    for agg in ("gradient", "zero1"):
        state, step = compress.make_overlap_step(
            loss_fn, optax.sgd(0.05), mesh,
            jax.tree.map(jnp.copy, params), microbatches=2,
            wire="int8_ef", aggregation=agg, comm_buckets=4)
        sb = dp.shard_batch(mesh, batch)
        losses = []
        for _ in range(60):
            state, loss = step(state, sb)
            losses.append(float(loss))
        assert losses[-1] < 1e-2 * losses[0], (agg, losses[0], losses[-1])


@pytest.mark.parametrize("wire", ["fp32", "int8_ef"])
def test_bucketed_multi_step_bitwise_matches_per_step(devices, wire):
    """K-scan at a FIXED bucket count is bitwise vs per-step dispatch —
    the per-bucket EF residual tuples and per-bucket ZeRO-1 moments
    thread the scan carry exactly (the make_multi_step contract carried
    to the bucketed ring)."""
    from ddl25spring_tpu.models import llama

    mesh = _mesh4(devices)
    cfg, loss_fn, _ = _llama_setup()
    ks = jax.random.split(jax.random.key(2), 4)
    batches = [jax.random.randint(k, (8, 8), 0, 64) for k in ks]

    s1, step1 = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh,
        llama.init_llama(jax.random.key(0), cfg),
        microbatches=2, wire=wire, aggregation="zero1", comm_buckets=2)
    ref = []
    for b in batches:
        s1, l = step1(s1, dp.shard_batch(mesh, b))
        ref.append(float(l))

    sK, stepK = compress.make_overlap_multi_step(
        loss_fn, optax.adam(1e-3), mesh,
        llama.init_llama(jax.random.key(0), cfg),
        microbatches=2, wire=wire, aggregation="zero1", comm_buckets=2)
    sK, losses = stepK(sK, dp.shard_batch_window(mesh, np.stack(batches)))
    assert [float(x) for x in np.asarray(losses)] == ref
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(sK)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucketed_preempt_resume_bitwise(devices):
    """The acceptance bar at comm_buckets=8: an int8+EF bucketed run
    (zero1, K=2) interrupted at a chunk edge and resumed from checkpoint
    walks BITWISE the uninterrupted trajectory — the per-bucket EF
    residual tuples ride the checkpointed state tree whole."""
    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train import train_llm_dp

    cfg = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=16)
    base = dict(batch_size=2, seq_len=16, lr=3e-3, data=2, wire="int8_ef",
                overlap_microbatches=2, steps_per_dispatch=2,
                comm_buckets=8)
    mesh = lambda: make_mesh({"data": 2}, devices=devices[:2])  # noqa: E731

    ref = train_llm_dp(cfg, TrainConfig(**base, iters=6),
                       tokenizer=ByteTokenizer(), aggregation="zero1",
                       mesh=mesh(), log_every=0)
    import tempfile
    d = tempfile.mkdtemp()
    a = train_llm_dp(cfg, TrainConfig(**base, iters=4),
                     tokenizer=ByteTokenizer(), aggregation="zero1",
                     mesh=mesh(), log_every=0, checkpoint_dir=d,
                     checkpoint_every=100)
    b = train_llm_dp(cfg, TrainConfig(**base, iters=6),
                     tokenizer=ByteTokenizer(), aggregation="zero1",
                     mesh=mesh(), log_every=0, checkpoint_dir=d,
                     checkpoint_every=100)
    assert a.losses + b.losses == ref.losses
    assert all(np.isfinite(ref.losses))


def test_ring_overlap_evidence_positive_and_negative(devices):
    """The PR 10 evidence standard, applied within the backward: at B=1
    the first ring hop depends on the WHOLE backward scan (overlap
    fraction 0, first hop waits); at B=8 M=1 the lm_head bucket's hops
    are dataflow-independent of the blocks' VJP scan — first hop starts
    before the full gradient materializes. Asserted on the jaxpr, not on
    timings."""
    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama

    mesh = _mesh4(devices)
    cfg = LlamaConfig(vocab_size=259, dmodel=32, num_heads=2, n_layers=2,
                      ctx_size=16)

    def loss_fn(p, b):
        return llama.forward_loss(p, b, cfg)

    def evidence(B, M):
        state, step = compress.make_overlap_step(
            loss_fn, optax.adam(1e-3), mesh,
            llama.init_llama(jax.random.key(0), cfg),
            microbatches=M, wire="int8_ef", aggregation="zero1",
            comm_buckets=B)
        batch = dp.shard_batch(
            mesh, jax.random.randint(jax.random.key(1),
                                     (4 * M, 16), 0, 259))
        return compress.ring_overlap_evidence(step, state, batch)

    ev1 = evidence(1, 1)
    assert ev1["overlap_fraction"] == 0.0
    assert not ev1["first_hop_independent"]

    ev8 = evidence(8, 1)
    assert ev8["first_hop_independent"], ev8
    assert ev8["overlap_fraction"] > 0.0, ev8
    assert ev8["n_ring_hops"] == 8 * ev1["n_ring_hops"]


def test_bucketed_zero_retraces_across_grid(devices):
    """Zero retraces across the comm_buckets × wire × K grid: every
    config compiles exactly ONE program across repeated dispatches
    (max_caches=1 — a second trace is a hard failure)."""
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.telemetry import introspect

    mesh = _mesh4(devices)
    cfg, loss_fn, _ = _llama_setup()
    batches = [np.asarray(jax.random.randint(k, (8, 8), 0, 64))
               for k in jax.random.split(jax.random.key(5), 3)]
    for B in (2, 8):
        for wire in ("fp32", "int8_ef"):
            for K in (1, 2):
                if K == 1:
                    state, step = compress.make_overlap_step(
                        loss_fn, optax.adam(1e-3), mesh,
                        llama.init_llama(jax.random.key(0), cfg),
                        microbatches=2, wire=wire, aggregation="zero1",
                        comm_buckets=B)
                    step = introspect.watch(
                        step, name=f"grid-b{B}-{wire}-k1", max_caches=1)
                    for b in batches:
                        state, _ = step(state, dp.shard_batch(mesh, b))
                else:
                    state, step = compress.make_overlap_multi_step(
                        loss_fn, optax.adam(1e-3), mesh,
                        llama.init_llama(jax.random.key(0), cfg),
                        microbatches=2, wire=wire, aggregation="zero1",
                        comm_buckets=B)
                    step = introspect.watch(
                        step, name=f"grid-b{B}-{wire}-k2", max_caches=1)
                    w = dp.shard_batch_window(mesh, np.stack(batches[:2]))
                    for _ in range(2):
                        state, _ = step(state, w)
