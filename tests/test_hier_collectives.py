"""Topology-aware two-level collectives on the hybrid DCN/ICI mesh
(parallel/compress.py hier_reduce_scatter + the hierarchical overlap
drivers, parallel/distributed.py hier_data_mesh).

Pins, in the house style:
(1) the two-level fp32 reduction bitwise-equals the flat ring at EVERY
    (islands × island_size) factorization of the 8-device CPU mesh on
    exact-arithmetic (integer-valued) inputs — the association-free
    regime where any correct schedule must agree to the bit — and
    bitwise-equals its documented chain-of-chains spec on general floats;
(2) at the DEGENERATE factorizations (1×n, n×1) one of the two rings is
    the identity and the two-level driver IS the flat ring — losses and
    params bitwise through real training; at interior factorizations the
    same sum re-associates (island-parenthesized vs single chain), so the
    contract is fp32 tolerance, exactly the ring-vs-psum_scatter
    precedent of PR 10;
(3) int8+EF across the DCN axis only converges on the convex quadratic
    at the PR 10 EF bound, the EF residuals ride the scan carry (K-step
    bitwise) and checkpoints (preempt/resume bitwise), and replicas stay
    bitwise in sync;
(4) the telemetry comm profile attributes bytes PER MESH AXIS exactly
    (the DCN budget the smoke gates);
(5) the satellite fixes: in-jit numerics summaries compose with the ring
    driver (losses bitwise on/off), and the in-jit guard_nonfinite
    select-back skips without leaving jit, counted in ResilienceStats.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ddl25spring_tpu.parallel import compress, dp, make_mesh
from ddl25spring_tpu.parallel.distributed import hier_data_mesh

FACTORIZATIONS = [(1, 8), (2, 4), (4, 2), (8, 1)]


def _quadratic_setup(key, dim=64):
    k1, k2, _ = jax.random.split(key, 3)
    w_star = jax.random.normal(k1, (dim,))
    x = jax.random.normal(k2, (256, dim))
    y = x @ w_star

    def loss_fn(p, batch):
        xb, yb = batch[..., :-1], batch[..., -1]
        return jnp.mean((xb @ p["w"] - yb) ** 2)

    batch = jnp.concatenate([x, y[:, None]], axis=-1)
    return {"w": jnp.zeros((dim,))}, loss_fn, batch, w_star


def _tiny_llama():
    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama

    cfg = LlamaConfig(vocab_size=64, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=8)

    def loss_fn(p, b):
        return llama.forward_loss(p, b, cfg)

    return cfg, loss_fn, (lambda: llama.init_llama(jax.random.key(0), cfg))


def _run_hier_rs(mesh, x_flat, wire_ici="fp32", wire_dcn="fp32"):
    """x_flat [n·cols] sharded over the hier mesh → per-rank owned chunks
    [n, cols] in RANK order (rank r = d·S + s holds slice s·D + d)."""
    from ddl25spring_tpu.parallel.dp import data_partition

    def f(v):
        out, _ = compress.hier_reduce_scatter(v, wire_ici=wire_ici,
                                              wire_dcn=wire_dcn)
        return out

    spec = P(data_partition(mesh))
    g = jax.jit(shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec,
                          check_vma=False))
    out = np.asarray(g(jax.device_put(x_flat, NamedSharding(mesh, spec))))
    return out.reshape(mesh.devices.size, -1)


def test_hier_rs_bitwise_flat_ring_at_every_factorization(devices):
    """Acceptance pin: the two-level fp32 reduction == the flat ring to
    the BIT at every factorization of the 8-device mesh, on
    integer-valued inputs where fp32 addition is exact (association
    cannot matter, so any dropped/doubled contribution or mis-routed
    chunk would show). Ownership map: rank d·S+s holds slice s·D+d."""
    n, cols = 8, 6
    rng = np.random.default_rng(0)
    x = rng.integers(-1000, 1000, size=(n, n * cols)).astype(np.float32)
    flat = x.reshape(-1)

    mesh_f = make_mesh({"data": n}, devices=devices)

    def f_flat(v):
        out, _ = compress.ring_reduce_scatter(v, "data", wire="fp32")
        return out

    ring = jax.jit(shard_map(f_flat, mesh=mesh_f, in_specs=P("data"),
                             out_specs=P("data"), check_vma=False))
    flat_out = np.asarray(
        ring(jax.device_put(flat, NamedSharding(mesh_f, P("data"))))
    ).reshape(n, cols)
    # Ground truth: the plain sum (exact on these inputs).
    np.testing.assert_array_equal(
        flat_out, x.sum(axis=0).reshape(n, cols))

    for D, S in FACTORIZATIONS:
        mesh_h = hier_data_mesh(D, S, devices=devices)
        out = _run_hier_rs(mesh_h, flat)
        for d in range(D):
            for s in range(S):
                np.testing.assert_array_equal(
                    out[d * S + s], flat_out[s * D + d],
                    err_msg=f"factorization {D}x{S}, rank ({d},{s})")


def test_hier_rs_matches_spec_reference_bitwise(devices):
    """General floats: the two-level reduction is bitwise its documented
    chain-of-chains spec — chunk s·D+d = the dcn-ring-order chain over
    island partials (owner island last), each island partial the
    ici-ring-order chain of its members (owner rank last)."""
    D, S = 2, 4
    n, cols = D * S, 5
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, n * cols)).astype(np.float32)
    mesh_h = hier_data_mesh(D, S, devices=devices)
    out = _run_hier_rs(mesh_h, x.reshape(-1))

    chunk = cols                       # one owned chunk, in elements

    def island_partial(d, s):
        """Superchunk s's island-d partial: the ICI-ring chain (start
        s+1, owner s last) over island d's members, on superchunk s's
        D·chunk elements."""
        sl = slice(s * (D * chunk), (s + 1) * (D * chunk))
        order = [(s + 1 + i) % S for i in range(S)]
        acc = x[d * S + order[0]][sl].copy()
        for s2 in order[1:]:
            acc = acc + x[d * S + s2][sl]
        return acc

    for d in range(D):
        for s in range(S):
            order = [(d + 1 + i) % D for i in range(D)]
            acc = island_partial(order[0], s)
            for d2 in order[1:]:
                acc = acc + island_partial(d2, s)
            want = acc[d * chunk:(d + 1) * chunk]
            np.testing.assert_array_equal(out[d * S + s], want,
                                          err_msg=f"rank ({d},{s})")


def test_hier_wire_dtypes_ride_the_right_axes():
    """jaxpr evidence: in int8-across-DCN mode the DCN ring's ppermutes
    carry i8 chunks while the ICI ring's carry full fp32 superchunks —
    compression exactly where the topology says, nowhere else."""
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(1))
    mesh = hier_data_mesh(2, 2, devices=jax.devices()[:4])
    state, step = compress.make_overlap_step(
        loss_fn, optax.sgd(0.05), mesh, params, microbatches=1,
        wire={"ici": "fp32", "dcn": "int8_ef"}, aggregation="zero1")
    jx = str(jax.make_jaxpr(lambda s, b: step(s, b))(
        state, dp.shard_batch(mesh, batch)))
    hops = [ln for ln in jx.splitlines() if "ppermute" in ln]
    # dim=64, n=4: local chunk 16, ici superchunk 32.
    assert any("i8[16]" in ln for ln in hops), f"no i8 DCN hop in {hops}"
    assert any("f32[32]" in ln for ln in hops), \
        f"no fp32 ICI superchunk hop in {hops}"
    # No gradient-sized fp32 crosses as a DCN *chunk* hop: the only f32
    # ppermutes are the [32] ICI superchunks and scalar scale sidecars.
    for ln in hops:
        assert "f32[16]" not in ln, f"uncompressed DCN chunk hop: {ln}"


@pytest.mark.parametrize("DS", [(1, 4), (4, 1)])
def test_hier_driver_degenerate_factorizations_bitwise_flat(devices, DS):
    """1×n and n×1 factorizations: one ring is the identity, so the
    two-level fp32 driver must reproduce the flat ring driver's losses
    AND params bitwise through real training (zero1, M=2)."""
    D, S = DS
    cfg, loss_fn, fresh = _tiny_llama()
    batch = jax.random.randint(jax.random.key(1), (8, 8), 0, 64)

    mesh_f = make_mesh({"data": 4}, devices=devices[:4])
    fs, fstep = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh_f, fresh(), microbatches=2,
        wire="fp32", aggregation="zero1")
    mesh_h = hier_data_mesh(D, S, devices=devices[:4])
    hs, hstep = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh_h, fresh(), microbatches=2,
        wire={"ici": "fp32", "dcn": "fp32"}, aggregation="zero1")
    for _ in range(3):
        fs, fl = fstep(fs, dp.shard_batch(mesh_f, batch))
        hs, hl = hstep(hs, dp.shard_batch(mesh_h, batch))
        assert float(fl) == float(hl)
    for a, b in zip(jax.tree.leaves(fs.params), jax.tree.leaves(hs.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hier_driver_interior_factorization_tracks_flat(devices):
    """2×2 vs the flat 4-ring: same sum, island-parenthesized vs single
    chain — fp32 re-association tolerance, the documented contract."""
    cfg, loss_fn, fresh = _tiny_llama()
    batch = jax.random.randint(jax.random.key(1), (8, 8), 0, 64)
    mesh_f = make_mesh({"data": 4}, devices=devices[:4])
    fs, fstep = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh_f, fresh(), microbatches=1,
        wire="fp32", aggregation="zero1")
    mesh_h = hier_data_mesh(2, 2, devices=devices[:4])
    hs, hstep = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh_h, fresh(), microbatches=1,
        wire={"ici": "fp32", "dcn": "fp32"}, aggregation="zero1")
    for _ in range(3):
        fs, fl = fstep(fs, dp.shard_batch(mesh_f, batch))
        hs, hl = hstep(hs, dp.shard_batch(mesh_h, batch))
        np.testing.assert_allclose(float(hl), float(fl), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(fs.params), jax.tree.leaves(hs.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6, rtol=1e-5)


def test_hier_multi_step_bitwise_matches_per_step(devices):
    """K-scan composition on the hierarchical driver: the fused K=3
    window reproduces 3 per-step calls bitwise — for int8-across-DCN this
    additionally proves the DCN EF residuals thread the scan carry
    exactly (the make_multi_step contract carried to the two-level
    topology)."""
    cfg, loss_fn, fresh = _tiny_llama()
    mesh = hier_data_mesh(2, 2, devices=devices[:4])
    wire = {"ici": "fp32", "dcn": "int8_ef"}
    ks = jax.random.split(jax.random.key(2), 3)
    batches = [jax.random.randint(k, (8, 8), 0, 64) for k in ks]

    s1, step1 = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh, fresh(), microbatches=2,
        wire=wire, aggregation="zero1")
    ref = []
    for b in batches:
        s1, l = step1(s1, dp.shard_batch(mesh, b))
        ref.append(float(l))

    sK, stepK = compress.make_overlap_multi_step(
        loss_fn, optax.adam(1e-3), mesh, fresh(), microbatches=2,
        wire=wire, aggregation="zero1")
    sK, losses = stepK(sK, dp.shard_batch_window(mesh, np.stack(batches)))
    assert [float(x) for x in np.asarray(losses)] == ref
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(sK)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hier_int8_dcn_converges_on_quadratic(devices):
    """int8+EF on the DCN axis only: converges on the convex quadratic at
    the PR 10 EF bound (100x loss drop), both aggregations, with the
    microbatch pipeline live (M=2) — the compressed-hop bias really is
    compensated by the per-(shard, chunk) error feedback."""
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(3))
    mesh = hier_data_mesh(2, 2, devices=jax.devices()[:4])
    for agg in ("gradient", "zero1"):
        state, step = compress.make_overlap_step(
            loss_fn, optax.sgd(0.05), mesh,
            jax.tree.map(jnp.copy, params), microbatches=2,
            wire={"ici": "fp32", "dcn": "int8_ef"}, aggregation=agg)
        sb = dp.shard_batch(mesh, batch)
        losses = []
        for _ in range(60):
            state, loss = step(state, sb)
            losses.append(float(loss))
        assert losses[-1] < 1e-2 * losses[0], (agg, losses[0], losses[-1])


def test_hier_replicas_stay_bitwise_identical(devices):
    """Every broadcast leg delivers ONE payload all shards apply
    identically — across islands too — so replicated params must stay
    bitwise in sync in every per-axis wire combination."""
    cfg, loss_fn, fresh = _tiny_llama()
    mesh = hier_data_mesh(2, 2, devices=devices[:4])
    batch = jax.random.randint(jax.random.key(1), (8, 8), 0, 64)
    for wire in ({"ici": "fp32", "dcn": "int8_ef"},
                 {"ici": "bf16", "dcn": "bf16"}):
        for agg in ("gradient", "zero1"):
            state, step = compress.make_overlap_step(
                loss_fn, optax.adam(1e-3), mesh, fresh(), microbatches=2,
                wire=wire, aggregation=agg)
            for _ in range(2):
                state, _ = step(state, dp.shard_batch(mesh, batch))
            for leaf in jax.tree.leaves(state.params):
                shards = [np.asarray(s.data)
                          for s in leaf.addressable_shards]
                for s in shards[1:]:
                    np.testing.assert_array_equal(shards[0], s)


def test_hier_ef_residual_exact_through_preempt_resume(devices):
    """Acceptance bar: a hierarchical int8-DCN run (dcn=2 × data=2,
    zero1, K=2, M=2) interrupted at a chunk edge and resumed from its
    checkpoint walks BITWISE the uninterrupted trajectory — the DCN EF
    residual trees restore exactly through the checkpointed state."""
    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train import train_llm_dp

    cfg = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=16)
    base = dict(batch_size=2, seq_len=16, lr=3e-3, data=2, dcn=2,
                wire="fp32", wire_dcn="int8_ef",
                overlap_microbatches=2, steps_per_dispatch=2)
    mesh = lambda: hier_data_mesh(2, 2, devices=devices[:4])  # noqa: E731

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


def test_hier_per_axis_byte_attribution_exact(devices):
    """The telemetry comm profile attributes bytes per MESH AXIS, and the
    DCN entry reproduces the analytic two-level formula exactly: ring
    (D−1)·chunk int8 + (D−1)·4 scales, delta gather (D−1)·chunk int8 +
    (D−1)·4 scales, loss pmean 2(D−1)/D·4 — per device per step."""
    from ddl25spring_tpu.telemetry import measure_comm

    cfg, loss_fn, fresh = _tiny_llama()
    D, S = 2, 2
    mesh = hier_data_mesh(D, S, devices=devices[:4])
    state, step = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh, fresh(), microbatches=1,
        wire={"ici": "fp32", "dcn": "int8_ef"}, aggregation="zero1")
    batch_sds = jax.ShapeDtypeStruct((8, 8), jnp.int32)
    prof = measure_comm(step, state, batch_sds)
    assert prof is not None and prof.records

    _, _, local, _ = dp._flat_geometry(mesh, fresh())
    by_axis = prof.by_axis()
    assert set(by_axis) == {"data", "dcn"}
    want_dcn = ((D - 1) * local        # int8 ring chunks
                + (D - 1) * 4          # ring scale sidecars
                + (D - 1) * local      # int8 delta gather
                + (D - 1) * 4          # delta scale gather
                + 2 * (D - 1) / D * 4)  # loss pmean's DCN leg
    assert by_axis["dcn"]["wire_bytes_per_device"] == want_dcn, \
        (by_axis["dcn"], want_dcn)
    # The per-axis view survives into the manifest shape (as_dict).
    d = prof.as_dict(steps_per_dispatch=2)
    assert set(d["axes"]) == {"data", "dcn"}
    assert d["axes"]["dcn"]["wire_bytes_per_device_per_train_step"] == \
        want_dcn / 2

    # Flat driver control: a single-axis mesh attributes everything to
    # ``data`` — no phantom axes.
    mesh_f = make_mesh({"data": 4}, devices=devices[:4])
    fstate, fstep = compress.make_overlap_step(
        loss_fn, optax.adam(1e-3), mesh_f, fresh(), microbatches=1,
        wire="int8_ef", aggregation="zero1")
    fprof = measure_comm(fstep, fstate, batch_sds)
    assert set(fprof.by_axis()) == {"data"}


def test_shard_batch_hier_layout(devices):
    """dp.shard_batch on the hierarchical mesh places batch rows
    island-major: replica (d, s) = device d·S + s reads block d·S + s —
    the same order a flat ``data=n`` mesh gives the same devices."""
    mesh = hier_data_mesh(2, 2, devices=devices[:4])
    batch = np.arange(8, dtype=np.int32).reshape(8, 1)  # 2 rows per shard
    sharded = dp.shard_batch(mesh, batch)
    got = {}
    for s in sharded.addressable_shards:
        got[s.device.id] = np.asarray(s.data).ravel().tolist()
    flat_devices = [d.id for d in mesh.devices.flatten()]
    for i, dev_id in enumerate(flat_devices):
        assert got[dev_id] == [2 * i, 2 * i + 1], (i, got)


def test_numerics_composes_with_ring_driver_bitwise(devices):
    """Satellite (was a hard error): in-jit numerics summaries ride the
    overlap driver's scan — losses and params bitwise identical with the
    summary on or off, and the finite mask reports clean gradients."""
    from ddl25spring_tpu.telemetry import introspect

    cfg, loss_fn, fresh = _tiny_llama()
    mesh = make_mesh({"data": 4}, devices=devices[:4])
    window = np.stack([np.asarray(jax.random.randint(k, (8, 8), 0, 64))
                       for k in jax.random.split(jax.random.key(5), 2)])

    s0, step0 = compress.make_overlap_multi_step(
        loss_fn, optax.adam(1e-3), mesh, fresh(), microbatches=2,
        wire="int8_ef", aggregation="zero1")
    s0, l0 = step0(s0, dp.shard_batch_window(mesh, window))

    handle = introspect.make_summarizer(fresh(), psum_axis="data")
    s1, step1 = compress.make_overlap_multi_step(
        loss_fn, optax.adam(1e-3), mesh, fresh(), microbatches=2,
        wire="int8_ef", aggregation="zero1", numerics=handle)
    s1, out = step1(s1, dp.shard_batch_window(mesh, window))
    l1, summary = introspect.split_step_output(out)
    assert summary is not None
    assert np.asarray(l0).tolist() == np.asarray(l1).tolist()
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert bool(np.asarray(summary.grad_finite).all())
    # The stacked [K] summary renders into event fields (chunk's last).
    fields = handle.event_fields(summary, index=-1)
    assert np.isfinite(fields["grad_norm"])


def test_hier_numerics_trainer_end_to_end(devices):
    """numerics_every through the hierarchical trainer: summaries
    psum-agree over BOTH mesh axes, losses bitwise on/off."""
    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train import train_llm_dp

    cfg = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=16)
    base = dict(batch_size=2, seq_len=16, iters=4, lr=3e-3, data=2, dcn=2,
                wire_dcn="int8_ef", overlap_microbatches=1)
    mesh = lambda: hier_data_mesh(2, 2, devices=devices[:4])  # noqa: E731
    a = train_llm_dp(cfg, TrainConfig(**base), tokenizer=ByteTokenizer(),
                     aggregation="zero1", mesh=mesh(), log_every=0)
    b = train_llm_dp(cfg, TrainConfig(**base, numerics_every=2),
                     tokenizer=ByteTokenizer(), aggregation="zero1",
                     mesh=mesh(), log_every=0)
    assert a.losses == b.losses
    assert all(np.isfinite(a.losses))


def test_injit_guard_ring_driver_skips_in_jit(devices):
    """Satellite (was a hard error): guard_nonfinite fused into the ring
    driver body — a poisoned shard's NaN makes the psum-agreed verdict
    reject the step WITHOUT leaving jit: the whole state (params,
    moments, both EF residual trees) select-backs bitwise and the step
    counter freezes; a clean batch then trains normally."""
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(7))
    mesh = make_mesh({"data": 2}, devices=devices[:2])
    state, step = compress.make_overlap_step(
        loss_fn, optax.sgd(0.05), mesh, params, microbatches=2,
        wire="int8_ef", aggregation="zero1", guard_nonfinite=True)

    # One clean step first (a nonzero residual makes the select-back
    # claim strong: skipped steps must not zero OR update EF state).
    state, l0 = step(state, dp.shard_batch(mesh, batch))
    snapshot = [np.asarray(x) for x in jax.tree.leaves(state)]

    poisoned = np.asarray(batch).copy()
    poisoned[0, 0] = np.nan          # shard 0's rows carry the NaN
    state, l1 = step(state, dp.shard_batch(mesh, poisoned))
    assert not np.isfinite(float(l1))     # fault visible to the host
    for a, b in zip(snapshot, jax.tree.leaves(state)):
        np.testing.assert_array_equal(a, np.asarray(b))  # true no-op

    state, l2 = step(state, dp.shard_batch(mesh, batch))
    assert np.isfinite(float(l2))
    assert int(np.asarray(state.step)) == 2   # 2 good steps, 1 skipped


def test_injit_guard_hier_driver_skips_in_jit(devices):
    """The fused guard's verdict agreement extends over BOTH axes of the
    hierarchical mesh: a NaN on one island skips the step everywhere
    (replicas would otherwise diverge island-by-island)."""
    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(8))
    mesh = hier_data_mesh(2, 2, devices=devices[:4])
    state, step = compress.make_overlap_step(
        loss_fn, optax.sgd(0.05), mesh, params, microbatches=1,
        wire={"ici": "fp32", "dcn": "int8_ef"}, aggregation="zero1",
        guard_nonfinite=True)
    state, _ = step(state, dp.shard_batch(mesh, batch))
    snapshot = [np.asarray(x) for x in jax.tree.leaves(state)]
    poisoned = np.asarray(batch).copy()
    poisoned[-1, 3] = np.inf         # last shard (island 1) poisoned
    state, l1 = step(state, dp.shard_batch(mesh, poisoned))
    assert not np.isfinite(float(l1))
    for a, b in zip(snapshot, jax.tree.leaves(state)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert int(np.asarray(state.step)) == 1


def test_injit_guard_trainer_counts_in_resilience_stats(devices):
    """ResilienceConfig.injit_guard through the DP trainer on the ring
    driver: a blow-up (lr chosen to overflow fp32 after the first
    update) makes every subsequent step's loss/grads non-finite — the
    fused guard skips them in-jit and the loop's end-of-run sync counts
    exactly those non-advances into ResilienceStats.skipped_steps."""
    from ddl25spring_tpu.config import (LlamaConfig, ResilienceConfig,
                                        TrainConfig)
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train import train_llm_dp

    cfg = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=16)
    mesh = lambda: make_mesh({"data": 2}, devices=devices[:2])  # noqa: E731
    r = train_llm_dp(
        cfg, TrainConfig(batch_size=2, seq_len=16, iters=4, lr=1e35,
                         data=2, wire="int8_ef", overlap_microbatches=1),
        tokenizer=ByteTokenizer(), aggregation="zero1", mesh=mesh(),
        log_every=0,
        resilience=ResilienceConfig(guard=False, injit_guard=True))
    # Step 0's update is finite (huge but representable) and applied;
    # every later step sees non-finite loss/grads and skips in-jit.
    assert r.resilience.skipped_steps == 3, r.resilience.as_dict()
    assert np.isfinite(r.losses[0]) and not np.isfinite(r.losses[-1])

    # Mutual exclusion with the host StepGuard is a hard error.
    with pytest.raises(ValueError, match="mutually exclusive"):
        train_llm_dp(
            cfg, TrainConfig(batch_size=2, seq_len=16, iters=2,
                             data=2, overlap_microbatches=1),
            tokenizer=ByteTokenizer(), aggregation="zero1", mesh=mesh(),
            log_every=0,
            resilience=ResilienceConfig(guard=True, injit_guard=True))


def test_hier_validation_errors(devices):
    """Invalid compositions fail loudly, each with the pointer to the
    right path."""
    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train import train_llm_dp

    params, loss_fn, batch, _ = _quadratic_setup(jax.random.key(9))
    mesh_h = hier_data_mesh(2, 2, devices=devices[:4])
    with pytest.raises(ValueError, match="full-precision tier"):
        compress.make_overlap_step(
            loss_fn, optax.sgd(0.05), mesh_h, params,
            wire={"ici": "int8_ef", "dcn": "int8_ef"})
    with pytest.raises(ValueError, match="per-axis wire"):
        compress.make_overlap_step(loss_fn, optax.sgd(0.05), mesh_h,
                                   params, wire="int8_ef")
    with pytest.raises(ValueError, match="hierarchical mesh"):
        compress.make_overlap_step(
            loss_fn, optax.sgd(0.05),
            make_mesh({"data": 2}, devices=devices[:2]), params,
            wire={"ici": "fp32", "dcn": "int8_ef"})
    # The flat dp factories refuse the hierarchical mesh outright.
    with pytest.raises(ValueError, match="two-level ring driver"):
        dp.make_zero1_step(loss_fn, optax.sgd(0.05), mesh_h, params)
    with pytest.raises(ValueError, match="two-level ring driver"):
        dp.make_grad_aggregation_step(loss_fn, optax.sgd(0.05), mesh_h)

    cfg = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                      ctx_size=16)
    tc = dict(batch_size=2, seq_len=16, iters=2, data=2)
    with pytest.raises(ValueError, match="two-level ring driver"):
        train_llm_dp(cfg, TrainConfig(**tc, dcn=2),
                     tokenizer=ByteTokenizer(), aggregation="zero1",
                     mesh=hier_data_mesh(2, 2, devices=devices[:4]),
                     log_every=0)
    with pytest.raises(ValueError, match="wire_dcn"):
        train_llm_dp(cfg, TrainConfig(**tc, wire_dcn="int8_ef",
                                      overlap_microbatches=1),
                     tokenizer=ByteTokenizer(), aggregation="zero1",
                     log_every=0)
    # dcn > 1 with an explicit FLAT mesh must error too (same bar as
    # wire_dcn): silently training the flat ring would fake a
    # hierarchical measurement.
    with pytest.raises(ValueError, match="no 'dcn' axis"):
        train_llm_dp(cfg, TrainConfig(**tc, dcn=2, overlap_microbatches=1),
                     tokenizer=ByteTokenizer(), aggregation="zero1",
                     mesh=make_mesh({"data": 4}, devices=devices[:4]),
                     log_every=0)
