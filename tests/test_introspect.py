"""Run-health introspection tests (telemetry/introspect.py, ISSUE 9).

Pins the tentpole's contracts: in-jit numerics instrumentation is
bitwise-invisible to losses/params (gradient + zero1, per-step and fused
K>1 dispatch), NaN-leaf attribution names the faulted tree path all the
way into a flight-recorder bundle, the CompileWatch retrace detector
fires exactly on compile-budget violations, bundles round-trip under
their size cap, schema v5 validates with v1–v4 back-compat, and the new
MFU-floor / grad-norm SLOs and bench_compare's derived attainment rows
behave.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.parallel import dp, make_mesh
from ddl25spring_tpu.telemetry import introspect
from ddl25spring_tpu.telemetry.events import (EventLog, SCHEMA_VERSION,
                                              read_events, validate_event)
from ddl25spring_tpu.telemetry.introspect import (CompileWatch,
                                                  FlightRecorder,
                                                  load_bundle,
                                                  split_step_output, watch)


def _toy_params():
    # A stacked "blocks" leaf (per-layer grouping) plus plain top-level
    # leaves — the llama tree's shape in miniature.
    return {
        "embed": jnp.ones((8, 4)),
        "blocks": {"w": jnp.full((3, 4, 4), 0.1), "b": jnp.zeros((3, 4))},
        "head": jnp.full((4, 8), 0.2),
    }


def _toy_loss(p, batch):
    x = batch @ p["embed"]
    x, _ = jax.lax.scan(
        lambda c, l: (jnp.tanh(c @ l["w"] + l["b"][None]), None),
        x, p["blocks"])
    return jnp.mean((x @ p["head"]) ** 2)


def _batches(n=4, b=8):
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.standard_normal((b, 8)).astype(np.float32))
            for _ in range(n)]


@pytest.fixture(scope="module")
def mesh(devices):
    return make_mesh({"data": 4})


# ------------------------------------------------- bitwise invariance


def _params_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert (np.asarray(x) == np.asarray(y)).all()


def test_numerics_bitwise_invariance_gradient_per_step(mesh):
    """K=1 gradient path: losses and params identical with the in-jit
    summary on vs off — extra outputs never perturb existing ones."""
    params, opt = _toy_params(), optax.adam(1e-2)
    nh = introspect.make_summarizer(params)

    def run(numerics):
        step = dp.make_grad_aggregation_step(_toy_loss, opt, mesh,
                                             numerics=numerics)
        st = dp.replicate(mesh, dp.init_state(params, opt))
        losses = []
        for b in _batches():
            st, out = step(st, dp.shard_batch(mesh, b))
            loss, aux = split_step_output(out)
            losses.append(np.asarray(loss))
            assert (aux is None) == (numerics is None)
        return st, losses

    st_off, l_off = run(None)
    st_on, l_on = run(nh)
    assert all((a == b).all() for a, b in zip(l_off, l_on))
    _params_equal(st_off.params, st_on.params)


@pytest.mark.parametrize("zero1", [False, True])
def test_numerics_bitwise_invariance_chunked_k4(mesh, zero1):
    """Fused K=4 dispatch, gradient AND zero1: the scan-stacked summary
    rides along without touching the loss sequence or the final state."""
    params, opt = _toy_params(), optax.adam(1e-2)
    nh = introspect.make_summarizer(
        params, psum_axis="data" if zero1 else None)
    window = dp.shard_batch_window(mesh, jnp.stack(_batches(4)))

    def run(numerics):
        if zero1:
            st, step = dp.make_zero1_multi_step(_toy_loss, opt, mesh,
                                                params, numerics=numerics)
        else:
            step = dp.make_multi_step(_toy_loss, opt, mesh,
                                      numerics=numerics)
            st = dp.replicate(mesh, dp.init_state(params, opt))
        st, out = step(st, window)
        return st, split_step_output(out)

    st_off, (l_off, aux_off) = run(None)
    st_on, (l_on, aux_on) = run(nh)
    assert aux_off is None and aux_on is not None
    assert (np.asarray(l_off) == np.asarray(l_on)).all()
    _params_equal(st_off.params, st_on.params)
    # The stacked summary covers each of the K steps.
    assert np.asarray(aux_on.grad_sq).shape[0] == 4


def test_summarizer_groups_and_finite_mask():
    """Per-layer groups from the stacked blocks leaf; a NaN planted in
    one gradient leaf flips exactly that leaf's finite bit, and
    event_fields names its path."""
    params = _toy_params()
    nh = introspect.make_summarizer(params)
    assert nh.groups == ["blocks/0", "blocks/1", "blocks/2", "embed",
                        "head"]
    assert nh.paths == ["blocks/b", "blocks/w", "embed", "head"]

    grads = jax.tree.map(jnp.ones_like, params)
    grads["blocks"]["w"] = grads["blocks"]["w"].at[1, 0, 0].set(jnp.nan)
    new_params = jax.tree.map(lambda x: x * 1.5, params)
    summary = jax.jit(nh.summarize)(params, grads, new_params)
    finite = np.asarray(summary.grad_finite)
    assert finite.tolist() == [True, False, True, True]  # blocks/w only
    fields = nh.event_fields(summary)
    assert fields["nonfinite_grads"] == ["blocks/w"]
    assert set(fields) >= {"grad_norm", "worst_group",
                           "worst_update_ratio", "groups"}
    # A uniform 1.5x scale: ||Δ|| / ||new|| = 0.5/1.5 everywhere (the
    # ratio's denominator is the POST-update param norm).
    for g in fields["groups"].values():
        assert g["update_ratio"] == pytest.approx(1 / 3, rel=1e-5)


# ------------------------------------------- NaN attribution end-to-end


def test_guard_trip_bundle_names_faulted_leaf(tmp_path, devices):
    """A targeted nan_grad FaultPlan (leaf #2 = blocks/mlp_norm/scale in
    the llama tree) under StepGuard + telemetry: the fault event carries
    the leaf-path attribution and the flight recorder dumps a bundle
    naming it — the acceptance bar for "a StepGuard trip names the
    offending tree path"."""
    from ddl25spring_tpu.config import (LlamaConfig, ResilienceConfig,
                                        TrainConfig)
    from ddl25spring_tpu.telemetry import Telemetry
    from ddl25spring_tpu.train.llm import train_llm_dp

    cfg = LlamaConfig(dmodel=16, num_heads=2, n_layers=2, ctx_size=16,
                      vocab_size=64)
    tc = TrainConfig(iters=8, batch_size=2, seq_len=16, data=2,
                     numerics_every=4)
    tel = Telemetry(str(tmp_path / "obs"), step_every=4)
    report = train_llm_dp(
        cfg, tc, telemetry=tel, log_every=0,
        resilience=ResilienceConfig(guard=True, faults="nan_grad@5:2"))
    tel.close()
    assert report.resilience.skipped_steps == 1

    events = read_events(str(tmp_path / "obs" / "events.jsonl"))
    faults = [e for e in events if e["type"] == "fault"]
    assert faults and faults[0]["attribution"]["nonfinite_params"]
    leaf = faults[0]["attribution"]["nonfinite_params"][0]

    bundles = glob.glob(str(tmp_path / "obs" / "postmortem" / "*.json"))
    assert len(bundles) == 1
    bundle = load_bundle(bundles[0])
    assert bundle["reason"] == "fault"
    assert bundle["attribution"]["nonfinite_params"] == [leaf]
    # Self-contained: manifest + a numerics sample + the compile record
    # ride inside the bundle, not as pointers.
    assert bundle["manifest"]["trainer"] == "dp"
    assert bundle["last_numerics"]["it"] == 5   # forced sample at the trip
    assert bundle["compiles"] and bundle["compiles"][0]["name"].startswith(
        "train/dp-gradient")

    # The postmortem renderer's self-check mode agrees.
    from experiments.postmortem import main as pm_main
    assert pm_main([str(tmp_path / "obs"), "--expect-leaf", leaf]) == 0
    assert pm_main([str(tmp_path / "obs"),
                    "--expect-leaf", "no/such/leaf"]) == 1


# ------------------------------------------------- compile watch


def test_compile_watch_retrace_detector(tmp_path):
    log = EventLog(str(tmp_path / "events.jsonl"), run_id="r")
    f = watch(jax.jit(lambda x: x * 2), name="toy", max_caches=1,
              events=log)
    f(jnp.ones(4))                      # compile #1 — within budget
    f(jnp.ones(4))                      # cache hit — no event
    f(jnp.ones(5))                      # compile #2 — budget broken
    log.close()
    assert [c.retrace for c in f.compiles] == [False, True]
    assert f.retraces == 1
    events = read_events(str(tmp_path / "events.jsonl"),
                         types=("compile",))
    assert [e["retrace"] for e in events] == [False, True]
    assert all(e["name"] == "toy" and e["seconds"] > 0 for e in events)
    # hlo flops costed for the compiled program (this jaxlib supports it).
    assert events[0]["flops"] and events[0]["flops"] > 0
    # Delegation: the wrapper is transparent to jit-object users.
    assert f._cache_size() == 2
    assert jax.eval_shape(f, jnp.ones(4)).shape == (4,)
    # Re-watching re-binds instead of stacking.
    assert watch(f, name="toy2", max_caches=None) is f
    assert f.name == "toy2" and f.max_caches is None


def test_compile_watch_without_events_is_silent():
    f = watch(jax.jit(lambda x: x + 1), name="quiet", max_caches=1)
    f(jnp.ones(3))
    assert len(f.compiles) == 1 and f.retraces == 0
    # No events bound -> no hlo costing (no second compile paid).
    assert f.compiles[0].flops is None


# ------------------------------------------------- flight recorder


def _mk_event(i, etype="step", **fields):
    return {"schema": SCHEMA_VERSION, "run_id": "r", "seq": i, "t": float(i),
            "type": etype, **fields}


def test_flight_recorder_roundtrip_and_size_cap(tmp_path):
    rec = FlightRecorder(str(tmp_path), capacity=64, max_bytes=8192,
                         max_bundles=2)
    rec.observe(_mk_event(0, "manifest", trainer="dp", platform="cpu"))
    blob = "x" * 512
    for i in range(1, 60):
        rec.observe(_mk_event(i, "step", it=i, loss=1.0, pad=blob))
    rec.observe(_mk_event(60, "numerics", it=60, grad_norm=2.0,
                          worst_group="blocks/0"))
    rec.observe(_mk_event(61, "fault", counters={"skipped_steps": 1},
                          attribution={"nonfinite_params": ["blocks/w"]}))
    assert len(rec.bundles) == 1
    bundle = load_bundle(rec.bundles[0])
    assert os.path.getsize(rec.bundles[0]) <= 8192
    assert bundle["dropped_events"] > 0           # cap actually evicted
    assert bundle["reason"] == "fault"
    assert bundle["attribution"] == {"nonfinite_params": ["blocks/w"]}
    # Pinned context survives ring eviction.
    assert bundle["manifest"]["trainer"] == "dp"
    assert bundle["last_numerics"]["worst_group"] == "blocks/0"
    # The ring's newest events survive; the trigger is the last one.
    assert bundle["recent_events"][-1]["type"] == "fault"

    # Bundle-count cap: the third trigger is suppressed, counted.
    rec.observe(_mk_event(62, "remesh", old_world=4, new_world=3))
    rec.observe(_mk_event(63, "slo_violation", slo="mfu"))
    assert len(rec.bundles) == 2 and rec.suppressed == 1
    names = sorted(os.path.basename(p) for p in rec.bundles)
    assert names == ["postmortem-000-fault.json",
                     "postmortem-001-remesh.json"]


def test_telemetry_bundle_arms_flight_recorder(tmp_path):
    from ddl25spring_tpu.telemetry import Telemetry
    tel = Telemetry(str(tmp_path / "t"))
    tel.events.fault(counters={"skipped_steps": 2}, it=3)
    tel.close()
    assert tel.flight is not None
    bundles = glob.glob(str(tmp_path / "t" / "postmortem" / "*.json"))
    assert len(bundles) == 1
    assert load_bundle(bundles[0])["trigger"]["it"] == 3
    # Opt-out stays silent.
    tel2 = Telemetry(str(tmp_path / "t2"), flight=False)
    tel2.events.fault(counters={"skipped_steps": 1}, it=1)
    tel2.close()
    assert tel2.flight is None
    assert not glob.glob(str(tmp_path / "t2" / "postmortem" / "*.json"))


# ------------------------------------------------- schema v5


def test_schema_v5_validation_and_backcompat():
    base = {"schema": SCHEMA_VERSION, "run_id": "r", "seq": 1, "t": 0.0}
    ok_numerics = {**base, "type": "numerics", "it": 10, "grad_norm": 1.0}
    ok_compile = {**base, "type": "compile", "name": "train/dp",
                  "seconds": 0.5, "retrace": False}
    assert validate_event(ok_numerics) == []
    assert validate_event(ok_compile) == []
    assert any("it" in p for p in
               validate_event({**base, "type": "numerics"}))
    assert any("seconds" in p for p in
               validate_event({**base, "type": "compile", "name": "x"}))
    # v1–v4 streams stay valid under the v5 reader.
    for schema, etype, fields in (
            (1, "step", {"it": 1}),
            (2, "request_done", {"req": "r1", "tokens": 3}),
            (3, "fl_cohort", {"round": 0, "tier": "edge", "cohort": 0}),
            (4, "span", {"name": "a", "trace_id": "t", "span_id": "s",
                         "start_ns": 0, "dur_ns": 1})):
        assert validate_event({**base, "schema": schema, "type": etype,
                               **fields}) == []
    # The future-schema rule still names the offender.
    problems = validate_event({**base, "schema": SCHEMA_VERSION + 1,
                               "type": "numerics", "it": 1})
    assert problems and "numerics" in problems[0]


# ------------------------------------------------------- roofline peaks

@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", 197e12),
    ("cpu", "cpu", "calibrated"),
    ("tpu", "TPU v4", ValueError),
    ("gpu", "NVIDIA H100", ValueError),
])
def test_device_peaks_keyed_by_device_kind(platform, kind, want):
    """One table keyed by device_kind: v5e's published peaks for a v5e, the
    labelled calibrated baseline for the CPU the tests run on, and an error
    — never another chip's peaks or the CPU's — for any other accelerator."""
    from types import SimpleNamespace

    device = SimpleNamespace(platform=platform, device_kind=kind)
    if want is ValueError:
        with pytest.raises(ValueError, match=kind):
            introspect.device_peaks(device)
    elif want == "calibrated":
        assert introspect.device_peaks(device)["source"].startswith(want)
    else:
        peaks = introspect.device_peaks(device)
        assert peaks["flops_per_sec"] == want
        assert peaks["hbm_bytes_per_sec"] == 819e9


# ------------------------------------------------- slo monitor (v5 SLOs)


def test_slo_monitor_mfu_normalizes_tail_chunk_programs():
    """Chunked runs compile a smaller tail-chunk program LAST; per-step
    normalization (each compile event's flops / its own
    steps_per_dispatch) keeps the MFU floor from reading the tail's
    smaller flops as a throughput collapse."""
    from experiments.slo_monitor import SLOConfig, SLOMonitor

    m = SLOMonitor(SLOConfig(window_s=30.0, min_mfu=0.05))
    m.feed([_mk_event(0, "manifest", peaks={"flops_per_sec": 1e9})])
    # Full-K program then the tail: both 1e8 flops/STEP.
    m.feed([_mk_event(1, "compile", name="k4", seconds=1.0, flops=4e8,
                      steps_per_dispatch=4),
            _mk_event(2, "compile", name="tail", seconds=1.0, flops=2e8,
                      steps_per_dispatch=2)])
    for i in range(3, 13):
        m.feed([_mk_event(i, "step", it=i, steps=1, dt_s=1.0)])
    # 1e8 flops/step x 10 steps / 10 s / 1e9 peak = MFU 0.1 > 0.05 floor.
    assert all(v["slo"] != "mfu" for v in m.evaluate(13.0))


def test_slo_monitor_sidecar_breach_dumps_bundle(tmp_path):
    """An SLO breach detected OUT of process still produces a postmortem:
    the monitor arms its own slo_violation-only recorder over the tailed
    stream (the run's in-process recorder can't see a sidecar's
    emission)."""
    from experiments.slo_monitor import main as slo_main

    log = EventLog(str(tmp_path / "events.jsonl"), run_id="r")
    log.manifest(jax_version="0", platform="cpu",
                 peaks={"flops_per_sec": 1e9})
    log.emit("compile", name="train/dp", seconds=1.0, flops=1e6,
             steps_per_dispatch=1)
    for i in range(12):
        log.step(it=i, steps=1, dt_s=1.0, loss=1.0)
    log.close()
    rc = slo_main([str(tmp_path), "--check", "--emit", "--slo-mfu", "0.5"])
    assert rc == 1                      # breach -> nonzero in --check
    bundles = glob.glob(str(tmp_path / "postmortem" / "*.json"))
    assert len(bundles) == 1 and "slo_violation" in bundles[0]
    bundle = load_bundle(bundles[0])
    assert bundle["trigger"]["slo"] == "mfu"
    # Tailed-stream context rode into the ring (manifest pinned too).
    assert bundle["manifest"]["platform"] == "cpu"
    assert any(e["type"] == "step" for e in bundle["recent_events"])


def test_slo_monitor_mfu_floor_and_gradnorm_spikes():
    from experiments.slo_monitor import SLOConfig, SLOMonitor

    cfg = SLOConfig(window_s=30.0, min_mfu=0.5,
                    max_gradnorm_spike_rate=0.2,
                    gradnorm_spike_factor=5.0)
    m = SLOMonitor(cfg)
    # Peak 1 GFLOP/s; program 1e8 flops/dispatch at 1 dispatch/s = MFU 0.1.
    m.feed([_mk_event(0, "manifest", peaks={"flops_per_sec": 1e9})])
    m.feed([_mk_event(1, "compile", name="train/dp", seconds=1.0,
                      flops=1e8, steps_per_dispatch=1)])
    for i in range(2, 12):
        m.feed([_mk_event(i, "step", it=i, steps=1, dt_s=1.0)])
    fresh = m.evaluate(12.0)
    slos = {v["slo"] for v in fresh}
    assert "mfu" in slos
    mfu = next(v for v in fresh if v["slo"] == "mfu")
    assert mfu["value"] == pytest.approx(0.1, rel=1e-6)

    # Grad-norm spikes: 2 of 8 samples at 100x the median -> rate 0.25.
    m2 = SLOMonitor(cfg)
    norms = [1.0] * 6 + [100.0, 100.0]
    m2.feed([_mk_event(i, "numerics", it=i, grad_norm=g)
             for i, g in enumerate(norms)])
    fresh = m2.evaluate(8.0)
    spike = next(v for v in fresh if v["slo"] == "gradnorm_spike_rate")
    assert spike["value"] == pytest.approx(0.25)
    # Healthy norms: no violation (and a prior breach recovers).
    m2.feed([_mk_event(i, "numerics", it=i, grad_norm=1.0)
             for i in range(8, 40)])
    assert all(v["slo"] != "gradnorm_spike_rate"
               for v in m2.evaluate(40.0))
    assert "gradnorm_spike_rate" not in m2.active


# ------------------------------------------------- bench_compare


def test_bench_compare_mfu_rows_same_platform_only(tmp_path):
    from experiments.bench_compare import compare, parse_rows

    tpu = {"metric": "tok_s", "value": 563695.0, "mfu": 0.310,
           "platform": "tpu", "variant": "flash-dhm"}
    cpu_old = {"metric": "tok_s", "value": 343.0, "mfu": 0.0002,
               "platform": "cpu-fallback", "variant": "f32"}
    cpu_new = {"metric": "tok_s", "value": 350.0, "mfu": 0.00019,
               "platform": "cpu-fallback", "variant": "f32"}
    untagged = {"metric": "tok_s", "value": 1.0, "mfu": 0.9}
    files = []
    for name, row in (("a.json", tpu), ("b.json", cpu_old),
                      ("u.json", untagged)):
        path = tmp_path / name
        path.write_text(json.dumps(row))
        files.append(str(path))
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(cpu_new))

    rows = parse_rows(files[0])
    assert {"metric": "mfu", "value": 0.310, "platform": "tpu",
            "variant": "flash-dhm"} in rows
    # No platform tag -> no derived row (never lands in a shared bucket).
    assert all(r["metric"] != "mfu" for r in parse_rows(files[2]))

    # The CPU candidate's mfu is judged against the CPU history ONLY:
    # 0.00019 vs 0.0002 is a 5% dip (ok at 20%), NOT a 99.9% regression
    # vs the TPU 0.310.
    lines, regressions = compare(files, str(cand), 20.0)
    assert not [r for r in regressions if r.startswith("mfu")]
    mfu_cpu = [ln for ln in lines if ln.startswith("mfu [cpu-fallback")]
    assert mfu_cpu, lines
    # And a genuine same-platform collapse still gates.
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({**cpu_new, "mfu": 0.00001}))
    _, regressions = compare(files, str(bad), 20.0)
    assert any(r.startswith("mfu [cpu-fallback") for r in regressions)
