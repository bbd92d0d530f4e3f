"""Resilience layer: fault injection vs self-healing, end to end.

The acceptance matrix from the resilience design: for each injected fault —
NaN gradient at step k, SIGTERM at step k, corrupted latest checkpoint, FL
client dropout mid-round — the guarded run completes, the fault shows up in
the emitted counters, and the final result matches a fault-free run within
tolerance (exactly for the pure resume cases).
"""

import csv
import os
import signal
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.checkpoint import Checkpointer, save_best
from ddl25spring_tpu.config import FLConfig, LlamaConfig, ResilienceConfig, TrainConfig
from ddl25spring_tpu.metrics import ResilienceStats
from ddl25spring_tpu.parallel import dp, make_mesh
from ddl25spring_tpu.resilience import (FaultPlan, PreemptionHandler,
                                        StepGuard, backoff_schedule,
                                        corrupt_latest_checkpoint, parse_spec,
                                        retry_call)
from ddl25spring_tpu.tokenizers import ByteTokenizer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                   ctx_size=16)


# --------------------------------------------------------------- fault plans

def test_fault_plan_parse_and_queries():
    plan = FaultPlan.from_spec(
        " nan_grad@3, spike_grad@5:50 ,preempt@7,drop_client@2:2", seed=9)
    assert plan.grad_fault_at(3).kind == "nan_grad"
    assert plan.grad_fault_at(5).arg == 50.0
    assert plan.grad_fault_at(4) is None
    assert plan.preempt_at(7) and not plan.preempt_at(6)
    assert bool(plan) and not bool(FaultPlan.from_spec(""))
    with pytest.raises(ValueError):
        parse_spec("nan_grad")          # missing @step
    with pytest.raises(ValueError):
        parse_spec("warp_core@3")       # unknown kind


def test_fault_plan_client_choice_deterministic():
    plan = FaultPlan.from_spec("drop_client@1:2,delay_client@1:1", seed=4)
    idx = np.arange(10)
    m1, d1, s1 = plan.surviving_clients(1, idx)
    m2, d2, s2 = plan.surviving_clients(1, idx)
    assert (m1 == m2).all() and (d1, s1) == (2, 1) == (d2, s2)
    assert m1.sum() == 7
    # Unfaulted rounds lose nobody.
    m3, d3, s3 = plan.surviving_clients(0, idx)
    assert m3.all() and d3 == 0 and s3 == 0
    # A different seed picks a different victim set (10 choose 3 makes a
    # collision across all three picks vanishingly unlikely for these seeds).
    m4, _, _ = FaultPlan.from_spec("drop_client@1:2,delay_client@1:1",
                                   seed=5).surviving_clients(1, idx)
    assert not (m1 == m4).all()


# -------------------------------------------------------------------- retry

def test_backoff_schedule_deterministic_and_shaped():
    s1 = backoff_schedule(5, base=0.1, max_delay=0.5, jitter=0.25, seed=3)
    s2 = backoff_schedule(5, base=0.1, max_delay=0.5, jitter=0.25, seed=3)
    assert s1 == s2
    # Exponential up to the cap, within the jitter band.
    for i, d in enumerate(s1):
        nominal = min(0.1 * 2 ** i, 0.5)
        assert 0.75 * nominal <= d <= 1.25 * nominal


def test_retry_call_retries_then_succeeds_and_raises():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return 42

    slept = []
    retried = []
    assert retry_call(flaky, attempts=5, sleep=slept.append,
                      on_retry=lambda i, e: retried.append(i)) == 42
    assert calls["n"] == 3 and len(slept) == 2 and retried == [0, 1]

    def always():
        calls["n"] += 1
        raise ValueError("permanent")

    calls["n"] = 0
    with pytest.raises(ValueError):
        retry_call(always, attempts=3, sleep=lambda s: None)
    assert calls["n"] == 3  # the budget was spent before surfacing


# ---------------------------------------------------------------- StepGuard

def _tiny_dp(devices, guard_nonfinite=False, lr=1e-2):
    mesh = make_mesh({"data": 2}, devices=devices[:2])
    params = {"w": jnp.arange(4, dtype=jnp.float32) / 4, "b": jnp.zeros((2,))}
    opt = optax.adam(lr)

    def loss_fn(p, batch):
        return jnp.mean((batch @ p["w"].reshape(2, 2) + p["b"]) ** 2)

    step = dp.make_grad_aggregation_step(loss_fn, opt, mesh,
                                         guard_nonfinite=guard_nonfinite)
    state = dp.replicate(mesh, dp.init_state(params, opt))
    rng = np.random.default_rng(0)
    batch = dp.shard_batch(
        mesh, rng.normal(size=(4, 2)).astype(np.float32))
    return mesh, state, step, batch


def test_guarded_fault_free_run_bit_identical(devices):
    """A StepGuard around a fault-free step must change NOTHING: the final
    params are bit-identical to the unguarded run's and every counter is 0."""
    _, state_a, step, batch = _tiny_dp(devices)
    _, state_b, _, _ = _tiny_dp(devices)
    stats = ResilienceStats()
    guard = StepGuard(step, stats=stats)
    for _ in range(6):
        state_a, loss_a = step(state_a, batch)
        state_b, loss_b = guard(state_b, batch)
    for a, b in zip(jax.tree.leaves(state_a), jax.tree.leaves(state_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(loss_a) == np.asarray(loss_b)
    assert stats.total_faults_handled == 0


def test_stepguard_skips_nan_step(devices):
    """A NaN-injected step is skipped: params unchanged across it, the skip
    counter increments, and training continues finitely afterwards."""
    _, state, step, batch = _tiny_dp(devices)
    stats = ResilienceStats()
    plan = FaultPlan.from_spec("nan_grad@2")
    guard = StepGuard(plan.wrap_step(step), stats=stats)
    params_before_fault = None
    for it in range(5):
        if it == 2:
            params_before_fault = jax.tree.map(np.asarray, state.params)
        state, loss = guard(state, batch)
        if it == 2:
            for a, b in zip(jax.tree.leaves(params_before_fault),
                            jax.tree.leaves(state.params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert stats.skipped_steps == 1 and stats.rollbacks == 0
    assert all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree.leaves(state.params))
    assert bool(jnp.isfinite(loss))


def test_stepguard_ema_catches_spike(devices):
    """A finite-but-exploded update (spike_grad) trips the EMA update-norm
    detector and is skipped as an anomaly."""
    _, state, step, batch = _tiny_dp(devices)
    stats = ResilienceStats()
    plan = FaultPlan.from_spec("spike_grad@6:1000")
    guard = StepGuard(plan.wrap_step(step), stats=stats,
                      ema_warmup=3, anomaly_factor=8.0)
    before = None
    for it in range(8):
        if it == 6:
            before = jax.tree.map(np.asarray, state.params)
        state, loss = guard(state, batch)
    assert stats.anomalies == 1 and stats.skipped_steps == 0
    # The spiked update was rejected wholesale.
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(state.params)):
        assert np.max(np.abs(np.asarray(a) - np.asarray(b))) < 1.0


def test_stepguard_rollback_after_k_bad(devices, tmp_path):
    """K consecutive bad steps roll the state back to the last good
    checkpoint (restored through Checkpointer's fallback machinery)."""
    _, state, step, batch = _tiny_dp(devices)
    stats = ResilienceStats()
    with Checkpointer(str(tmp_path / "ck"), stats=stats) as ckpt:
        # Two good steps, checkpoint, then a permanent NaN fault.
        for _ in range(2):
            state, _ = step(state, batch)
        ckpt.save(2, state)
        ckpt.wait()
        good = jax.tree.map(np.asarray, state)

        plan = FaultPlan.from_spec("nan_grad@0,nan_grad@1,nan_grad@2")
        guard = StepGuard(plan.wrap_step(step), ckpt=ckpt, stats=stats,
                          max_consecutive_bad=3)
        for _ in range(3):
            state, _ = guard(state, batch)
    assert stats.skipped_steps == 3 and stats.rollbacks == 1
    for a, b in zip(jax.tree.leaves(good), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dp_in_jit_guard_skips_nonfinite(devices):
    """The fused guard_nonfinite path: a poisoned batch yields a non-finite
    loss but the params/opt state/step are a select-back no-op."""
    mesh, state, step, batch = _tiny_dp(devices, guard_nonfinite=True)
    state, loss = step(state, batch)
    assert int(state.step) == 1 and bool(jnp.isfinite(loss))
    before = jax.tree.map(np.asarray, state.params)
    poisoned = dp.shard_batch(mesh, np.full((4, 2), np.nan, np.float32))
    state, loss = step(state, poisoned)
    assert not bool(jnp.isfinite(loss))
    assert int(state.step) == 1  # did not advance
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------- checkpoints

def test_restore_falls_back_past_corrupt_latest(tmp_path, devices):
    """Corrupt the newest orbax step on disk; restore must fall back to the
    previous valid step and say so in the counters."""
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    stats = ResilienceStats()
    with Checkpointer(str(tmp_path / "ck"), stats=stats) as ckpt:
        for s in (1, 2, 3):
            ckpt.save(s, {"w": tree["w"] * s})
        ckpt.wait()
        corrupt_latest_checkpoint(str(tmp_path / "ck"))
        restored = ckpt.restore(tree)
        assert ckpt.restored_step == 2
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(8, dtype=np.float32) * 2)
    assert stats.ckpt_fallbacks >= 1


def test_save_overwrite_replaces_stale_step_after_fallback(tmp_path, devices):
    """After a corrupt-latest fallback, a run re-treading the corrupt step's
    index must be able to re-save it: ``overwrite=True`` replaces the stale
    entry (a blind save would be an orbax StepAlreadyExistsError), and the
    replacement restores cleanly as the new latest."""
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    with Checkpointer(str(tmp_path / "ck")) as ckpt:
        for s in (1, 2, 3):
            ckpt.save(s, {"w": tree["w"] * s})
        ckpt.wait()
        corrupt_latest_checkpoint(str(tmp_path / "ck"))
        ckpt.restore(tree)
        assert ckpt.restored_step == 2
        ckpt.save(3, {"w": tree["w"] * 30}, force=True, overwrite=True)
        ckpt.wait()
        restored = ckpt.restore(tree)
        assert ckpt.restored_step == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(8, dtype=np.float32) * 30)
        # The stale entry may still be landing: a periodic save, then a
        # re-mesh that persists the new layout under the same index
        # (tests/test_elastic.py's preempt-remesh-resume pair failed now and
        # then on exactly this: the delete raced the commit's rename). The
        # overwrite deletes only once no save is in flight, and the
        # replacement is what restores.
        in_flight = []
        delete = ckpt._mgr.delete
        ckpt._mgr.delete = lambda s: (
            in_flight.append(ckpt._mgr.is_saving_in_progress()), delete(s))
        ckpt.save(4, {"w": tree["w"] * 4})
        ckpt.save(4, {"w": tree["w"] * 40}, force=True, overwrite=True)
        ckpt.wait()
        assert in_flight == [False]
        restored = ckpt.restore(tree)
        assert ckpt.restored_step == 4
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(8, dtype=np.float32) * 40)


def test_checkpoint_digest_catches_silent_bitflip(tmp_path):
    """A single flipped bit in a saved shard — invisible to orbax, which
    would hand the poisoned bytes back bit-exactly — fails the save-time
    digest manifest, so restore counts a ``ckpt_fallbacks`` and falls back
    to the previous step BEFORE any poisoned weights reach the run."""
    import pathlib

    tree = {"w": jnp.arange(64, dtype=jnp.float32)}
    stats = ResilienceStats()
    with Checkpointer(str(tmp_path / "ck"), stats=stats) as ckpt:
        ckpt.save(1, {"w": tree["w"]})
        ckpt.save(2, {"w": tree["w"] * 2})
        ckpt.wait()                       # digest manifests land here
        step_dir = pathlib.Path(tmp_path / "ck" / "2")
        # Flip one bit mid-file in the largest file (the array bytes);
        # size and structure are untouched — the silent-corruption case
        # truncation-style faults (corrupt_latest_checkpoint) don't model.
        victim = max((p for p in step_dir.rglob("*") if p.is_file()),
                     key=lambda p: p.stat().st_size)
        raw = bytearray(victim.read_bytes())
        raw[len(raw) // 2] ^= 0x01
        victim.write_bytes(raw)
        restored = ckpt.restore(tree)
        assert ckpt.restored_step == 1
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64, dtype=np.float32))
    assert stats.ckpt_fallbacks >= 1


def test_restore_all_corrupt_raises(tmp_path):
    tree = {"w": jnp.ones((4,))}
    with Checkpointer(str(tmp_path / "ck"), max_to_keep=2) as ckpt:
        ckpt.save(1, tree)
        ckpt.wait()
        corrupt_latest_checkpoint(str(tmp_path / "ck"))
        with pytest.raises(FileNotFoundError):
            ckpt.restore(tree)


def test_save_best_atomic_preserves_previous_on_failure(tmp_path, monkeypatch):
    """A failing write never clobbers the existing best file, and no temp
    litter survives."""
    path = str(tmp_path / "best.npz")
    save_best(path, {"w": jnp.ones((3,))})
    good = open(path, "rb").read()

    def boom(*a, **kw):
        raise OSError("disk full")

    monkeypatch.setattr(np, "savez", boom)
    with pytest.raises(OSError):
        save_best(path, {"w": jnp.zeros((3,))})
    assert open(path, "rb").read() == good
    assert [f for f in os.listdir(tmp_path) if f != "best.npz"] == []


# --------------------------------------------------------------- preemption

def test_preemption_handler_catches_and_restores():
    prev = signal.getsignal(signal.SIGTERM)
    with PreemptionHandler() as pre:
        assert not pre.requested
        signal.raise_signal(signal.SIGTERM)
        assert pre.requested
    assert signal.getsignal(signal.SIGTERM) is prev


def _train(tmp_path, name, *, iters, fault_plan=None, sink_rows=None,
           resilience=None):
    from ddl25spring_tpu.train.llm import train_llm_dp

    sink = None
    if sink_rows is not None:
        sink = lambda it, loss: sink_rows.append((it, loss))
    return train_llm_dp(
        model_cfg=TINY,
        train_cfg=TrainConfig(batch_size=2, seq_len=16, iters=iters, lr=3e-3),
        mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]),
        tokenizer=ByteTokenizer(),
        log_every=0,
        checkpoint_dir=str(tmp_path / name),
        checkpoint_every=4,
        loss_sink=sink, sink_every=1,
        fault_plan=fault_plan,
        resilience=resilience,
    )


def test_simulated_preemption_resumes_exactly(tmp_path, devices):
    """The resume half of the acceptance matrix, in-process: a simulated
    SIGTERM preemption force-saves, the rerun resumes with exact stream
    replay, and the stitched loss record equals an uninterrupted run's
    EXACTLY, with a contiguous iteration record."""
    rows_ref = []
    ref = _train(tmp_path, "ref", iters=10, sink_rows=rows_ref)
    assert not ref.preempted

    rows1 = []
    r1 = _train(tmp_path, "pre", iters=10, sink_rows=rows1,
                fault_plan=FaultPlan.from_spec("preempt@5"))
    assert r1.preempted and r1.resilience.preemptions == 1
    assert len(r1.losses) < 10

    rows2 = []
    r2 = _train(tmp_path, "pre", iters=10, sink_rows=rows2)
    assert not r2.preempted

    stitched = dict(rows1)
    stitched.update(dict(rows2))
    assert sorted(stitched) == list(range(10))       # contiguous record
    for it, loss in dict(rows_ref).items():
        assert stitched[it] == loss, f"iter {it} diverged after resume"
    assert r2.losses[-1] == ref.losses[-1]


def test_nan_fault_guarded_trainer_completes(tmp_path, devices):
    """NaN-grad at step k through the full DP trainer with the guard on: the
    run completes, the skip is counted, and the final loss lands within
    tolerance of the fault-free run's (one missing update on a smooth
    curve)."""
    ref = _train(tmp_path, "ref2", iters=10)
    got = _train(tmp_path, "nan", iters=10,
                 fault_plan=FaultPlan.from_spec("nan_grad@4"),
                 resilience=ResilienceConfig(guard=True, ema_warmup=100))
    assert got.resilience.skipped_steps == 1
    assert not np.isfinite(got.losses[4])  # the fault is visible...
    finite = [l for l in got.losses if np.isfinite(l)]
    assert len(finite) == 9                # ...and contained
    assert abs(got.losses[-1] - ref.losses[-1]) < 0.25 * abs(ref.losses[-1])


def test_unguarded_nan_fault_poisons_run(tmp_path, devices):
    """Negative control: without the guard the same NaN fault destroys the
    rest of the run — the counters prove the guard is what saved it above."""
    got = _train(tmp_path, "nanfree", iters=8,
                 fault_plan=FaultPlan.from_spec("nan_grad@3"))
    assert not np.isfinite(got.losses[-1])


# -------------------------------------------------- SIGTERM subprocess test

_TRAIN_SCRIPT = textwrap.dedent("""
    import os, signal, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    from ddl25spring_tpu.config import LlamaConfig, TrainConfig
    from ddl25spring_tpu.parallel import make_mesh
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train.llm import train_llm_dp

    out_dir = sys.argv[1]
    csv_path = os.path.join(out_dir, "loss.csv")

    def sink(it, loss):
        with open(csv_path, "a") as f:
            f.write(f"{it},{loss}\\n")
            f.flush()

    # the trainer's handler stands in for this one while its loop runs, and
    # restores it after: a SIGTERM that lands once the loop has ended finds
    # the run completed and is ignored, where the default would kill it
    signal.signal(signal.SIGTERM, signal.SIG_IGN)
    report = train_llm_dp(
        model_cfg=LlamaConfig(vocab_size=259, dmodel=16, num_heads=2,
                              n_layers=2, ctx_size=16),
        train_cfg=TrainConfig(batch_size=2, seq_len=16, iters=16, lr=3e-3),
        mesh=make_mesh({"data": 1}),
        tokenizer=ByteTokenizer(),
        log_every=0,
        checkpoint_dir=os.path.join(out_dir, "ck"),
        checkpoint_every=4,
        loss_sink=sink, sink_every=1,
    )
    print("PREEMPTED" if report.preempted else "COMPLETED", flush=True)
""")


def test_sigterm_subprocess_resumes_to_completion(tmp_path):
    """Real SIGTERM against a real training subprocess mid-loop: the child
    force-saves and exits cleanly; rerunning the identical command resumes
    and completes with a contiguous loss record.

    Race-tolerant by design: the 16-iter tiny child can legitimately
    OUTRUN the parent's 0.5 s progress poll and finish before the signal
    lands, in which case it honestly reports COMPLETED (this was a known
    flake when the assertion demanded PREEMPTED). Either outcome is a
    correct run; what this test actually pins is resume correctness, and
    the evidence for that is the stitched loss record — contiguous,
    finite, later rows winning the resume overlap — not the exit state."""
    script = tmp_path / "train_script.py"
    script.write_text(_TRAIN_SCRIPT)
    csv_path = tmp_path / "loss.csv"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}

    proc = subprocess.Popen([sys.executable, str(script), str(tmp_path)],
                            cwd=REPO, env=env, stdout=subprocess.PIPE,
                            text=True)
    deadline = time.time() + 240
    while time.time() < deadline:
        if csv_path.exists() and len(csv_path.read_text().splitlines()) >= 3:
            break
        if proc.poll() is not None and proc.poll() != 0:
            pytest.fail(f"trainer exited early rc={proc.returncode}")
        if proc.poll() == 0:
            break                # won the race: completed before the poll
        time.sleep(0.5)
    else:
        proc.kill()
        pytest.fail("trainer never made progress")
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == 0, out
    assert ("PREEMPTED" in out) or ("COMPLETED" in out), out
    preempted = "PREEMPTED" in out

    proc2 = subprocess.run([sys.executable, str(script), str(tmp_path)],
                           cwd=REPO, env=env, capture_output=True, text=True,
                           timeout=300)
    assert proc2.returncode == 0, proc2.stderr[-2000:]
    # The rerun either resumes-and-completes or finds the finished
    # checkpoint ("nothing to train") — both print COMPLETED.
    assert "COMPLETED" in proc2.stdout

    rows = [r for r in csv.reader(csv_path.read_text().splitlines()) if r]
    recorded = {}
    first_seen = {}
    for it, loss in rows:     # later rows win: the resume's overlap re-write
        it = int(it)
        recorded[it] = float(loss)
        first_seen.setdefault(it, float(loss))
    assert sorted(recorded) == list(range(16))   # contiguous 0..15
    assert all(np.isfinite(v) for v in recorded.values())
    if preempted:
        # Resume correctness, not just coverage: wherever the rerun
        # re-trod an iteration the first run already recorded, the
        # deterministic replay must reproduce the identical loss.
        assert all(first_seen[i] == recorded[i] for i in recorded)


# ----------------------------------------------------------- FL dropout

@pytest.fixture(scope="module")
def fl_setup():
    from ddl25spring_tpu.data import mnist
    from ddl25spring_tpu.fl import federate
    from ddl25spring_tpu.models import mnist_cnn

    x_raw, y, xt_raw, yt = mnist.load_mnist(n_train=400, n_test=100, seed=0)
    x = mnist.normalize(x_raw)
    xt = mnist.normalize(xt_raw)
    cfg = FLConfig(nr_clients=8, client_fraction=0.5, batch_size=50,
                   epochs=1, lr=0.05, rounds=2, seed=10)
    subsets = mnist.split(y, cfg.nr_clients, iid=True, seed=cfg.seed)
    data = federate(x, y.astype(np.int32), subsets)
    params = mnist_cnn.init(jax.random.key(0))
    apply_fn = mnist_cnn.apply
    return params, apply_fn, data, xt, yt.astype(np.int32), cfg


def test_fl_round_tolerates_client_dropout(fl_setup):
    """Clients vanishing mid-round: the round completes by re-weighting over
    survivors, deterministically under the plan seed, with the loss of
    coverage visible in the counters."""
    from ddl25spring_tpu.fl import FedAvgServer

    params, apply_fn, data, xt, yt, cfg = fl_setup
    plan = FaultPlan.from_spec("drop_client@0:2,delay_client@1:1", seed=3)

    a = FedAvgServer(params, apply_fn, data, xt, yt, cfg, fault_plan=plan)
    b = FedAvgServer(params, apply_fn, data, xt, yt, cfg, fault_plan=plan)
    ra = a.run(2)
    rb = b.run(2)
    assert a.resilience.dropped_clients == 2
    assert a.resilience.straggler_clients == 1
    assert a.resilience.skipped_rounds == 0
    # Deterministic under seed: identical servers walk identical paths.
    for pa, pb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    assert ra.test_accuracy == rb.test_accuracy
    # And the run still learned: accuracy is sane, not collapsed.
    fault_free = FedAvgServer(params, apply_fn, data, xt, yt, cfg)
    rf = fault_free.run(2)
    assert abs(ra.test_accuracy[-1] - rf.test_accuracy[-1]) < 0.25


def test_fl_all_clients_lost_round_is_skipped(fl_setup):
    """A round in which EVERY sampled client drops is skipped outright:
    counted in skipped_rounds, and the next round proceeds normally."""
    from ddl25spring_tpu.fl import FedAvgServer

    params, apply_fn, data, xt, yt, cfg = fl_setup
    plan = FaultPlan.from_spec("drop_client@0:99", seed=1)
    s = FedAvgServer(params, apply_fn, data, xt, yt, cfg, fault_plan=plan)
    before = jax.tree.map(np.asarray, s.params)
    # One run of 2 rounds: round 0 loses everyone, round 1 is fault-free.
    # (run() always iterates from round index 0, so two run(1) calls would
    # both hit the faulted round and never exercise the recovery.)
    s.run(2)
    assert s.resilience.skipped_rounds == 1
    assert s.result.rounds == 2
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(s.params)))
    assert changed, "round 1 (fault-free) must train past the skipped round"


def test_fl_survivor_reweighting_matches_direct_subset(fl_setup):
    """Re-weighted aggregation over survivors is EXACTLY the round the
    server would have run had it sampled only the survivors: the dropout
    path adds no numerics of its own. Since the padded-round refactor the
    dropout path keeps the dropped entries as zero-weight duplicates —
    tree_weighted_fold selects around weight-0 rows, so the padded round
    still equals the filtered one bitwise."""
    from ddl25spring_tpu.fl import FedAvgServer
    from ddl25spring_tpu.fl.servers import _round_weights

    params, apply_fn, data, xt, yt, cfg = fl_setup
    plan = FaultPlan.from_spec("drop_client@0:2", seed=3)
    s = FedAvgServer(params, apply_fn, data, xt, yt, cfg, fault_plan=plan)
    idx = s._sample(0)
    mask, _, _ = plan.surviving_clients(0, idx)
    survivors = idx[mask]
    dropped_params = s._round(s.params, 0)

    t = FedAvgServer(params, apply_fn, data, xt, yt, cfg)
    keys = jax.vmap(jax.random.key)(
        jnp.asarray(t.client_seeds(0, survivors)))
    survivors = jnp.asarray(survivors)
    w = _round_weights(data.sample_counts[survivors], None)
    direct_params = t._round_step(t.params, survivors, keys, w)
    for a, b in zip(jax.tree.leaves(dropped_params),
                    jax.tree.leaves(direct_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fl_dropout_rounds_reuse_one_compiled_round_step(fl_setup):
    """The satellite fix for the per-round retrace: rounds with DIFFERENT
    survivor counts pad back to the full sampled width with zero-weight
    masks, so the compiled round step serves every dropout pattern at ONE
    trace (the old filtering path recompiled once per distinct count)."""
    from ddl25spring_tpu.fl import FedAvgServer

    params, apply_fn, data, xt, yt, cfg = fl_setup
    # Distinct survivor counts in rounds 0/1/2: 2 dropped, 1, none.
    plan = FaultPlan.from_spec("drop_client@0:2,drop_client@1:1", seed=5)
    s = FedAvgServer(params, apply_fn, data, xt, yt, cfg, fault_plan=plan)
    s.run(3)
    assert s.resilience.dropped_clients == 3
    assert s._round_step._cache_size() == 1
