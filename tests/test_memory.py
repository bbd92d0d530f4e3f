"""Memory observability (ISSUE 17): unified device/host byte accounting.

The tentpole's acceptance bars, pinned: the schema-v9 ``memory`` event
validates (and v1-v8 streams stay valid); the ``memory_analysis`` reader
drops what a backend does not account; ``preflight``'s config-only per-device
budget lands within 10% of the MEASURED compiled argument bytes across
aggregation modes and dispatch widths (and its ZeRO-1 moments at ~1/n of
replicated — the memory-parity claim as a number); the MemoryMeter is
bitwise-invisible to losses and served streams (zero extra dispatches);
the BlockAllocator's fragmentation census is exact at its edge cases and
CoW prefix sharing cuts occupancy WITHOUT inflating fragmentation; and
the headroom SLO chain (meter -> slo_monitor ``--slo-headroom`` ->
autoscaler veto) fires end to end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.config import LlamaConfig, TrainConfig
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.parallel import compress, dp, make_mesh
from ddl25spring_tpu.serving import (BlockAllocator, Engine, PagedKVConfig,
                                     Request, Scheduler, reference_stream)
from ddl25spring_tpu.telemetry import SCHEMA_VERSION, Telemetry
from ddl25spring_tpu.telemetry.events import (EventLog, read_events,
                                              validate_event)
from ddl25spring_tpu.telemetry.memory import (MemoryMeter, allocator_census,
                                              host_rss_bytes, np_tree_bytes,
                                              preflight, program_memory,
                                              tree_state_bytes)

TINY = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                   ctx_size=16)
SRV_CFG = LlamaConfig(vocab_size=97, dmodel=32, num_heads=4, n_layers=2,
                      ctx_size=32)
SRV_PAGED = PagedKVConfig(num_blocks=24, block_len=4, max_blocks_per_seq=8)


# ----------------------------------------------------- schema v9 contract

def test_memory_event_emitter_roundtrip(tmp_path):
    """The typed v9 emitter produces strictly-valid events carrying the
    open field set the meter writes (bytes, census, cadence tags)."""
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="m") as log:
        log.memory(source="train", it=4, params_bytes=1000,
                   opt_state_bytes=2000, device_bytes=3000.0,
                   rss_bytes=4096)
        log.memory(source="serve", tick=8, blocks_in_use=5, holes=2,
                   largest_run=3, pool_used_bytes=640)
    events = read_events(path, strict=True)
    assert [e["type"] for e in events] == ["memory", "memory"]
    assert all(e["schema"] == SCHEMA_VERSION for e in events)
    assert events[0]["source"] == "train" and events[0]["device_bytes"] == 3000.0
    assert events[1]["holes"] == 2


def test_validate_memory_required_fields_and_backcompat():
    """``memory`` requires ``source``; every pre-v9 type stays valid at
    its own schema version under this reader — the bump is additive."""
    base = {"run_id": "r", "seq": 1, "t": 0.0}
    ok = {**base, "schema": SCHEMA_VERSION, "type": "memory",
          "source": "host"}
    assert validate_event(ok) == []
    assert validate_event({**base, "schema": SCHEMA_VERSION,
                           "type": "memory"}) != []     # missing source
    # One representative per prior schema version, v1..v8.
    for schema, ev in ((1, {"type": "step", "it": 0}),
                       (2, {"type": "request_done", "req": "a", "tokens": 2}),
                       (3, {"type": "fl_cohort", "round": 0, "tier": "edge",
                            "cohort": 1}),
                       (4, {"type": "span", "name": "a", "trace_id": "t",
                            "span_id": "s", "start_ns": 0, "dur_ns": 1}),
                       (5, {"type": "compile", "name": "step",
                            "seconds": 0.5}),
                       (6, {"type": "numerics", "it": 0}),
                       (7, {"type": "speculate", "req": "a", "proposed": 4,
                            "accepted": 2}),
                       (8, {"type": "scale", "direction": "train_to_serve",
                            "train_world": 3, "serve_engines": 2}),
                       (8, {"type": "remesh", "old_world": 4,
                            "new_world": 2})):
        assert validate_event({**base, "schema": schema, **ev}) == [], ev
    # A v8 stream must not know the v9 type — but an unknown type is only
    # flagged at/below the reader's version with the version it claimed.
    assert validate_event({**base, "schema": SCHEMA_VERSION, "type": "memory",
                           "source": "fleet", "rss_bytes": 1}) == []


# ------------------------------------------------ memory_analysis reader

def test_normalize_stats_variants():
    from types import SimpleNamespace as Stats

    from ddl25spring_tpu.telemetry.memory import _normalize_stats
    # device_bytes sums the components minus the donated alias.
    got = _normalize_stats(Stats(argument_size_in_bytes=100,
                                 output_size_in_bytes=40,
                                 temp_size_in_bytes=60,
                                 alias_size_in_bytes=30))
    assert got["argument_bytes"] == 100 and got["device_bytes"] == 170.0
    # Nothing usable reported -> None, never a zero-filled dict.
    assert _normalize_stats(Stats()) is None
    assert _normalize_stats(None) is None
    # Negative sentinel values are dropped field-wise.
    got = _normalize_stats(Stats(argument_size_in_bytes=100,
                                 temp_size_in_bytes=-1))
    assert got["argument_bytes"] == 100 and "temp_bytes" not in got


def test_program_memory_guard_and_this_jaxlib():
    """The one shared reader (CompileWatch, pp_schedules): a
    non-jitted callable gives None; a jitted program accounts real
    bytes."""
    assert program_memory(lambda x: x, 1) is None
    f = jax.jit(lambda a, b: a @ b)
    a = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((64, 16), jnp.float32)
    mem = program_memory(f, a, b)
    assert mem["argument_bytes"] == (32 * 64 + 64 * 16) * 4
    assert mem["output_bytes"] == 32 * 16 * 4
    assert mem["device_bytes"] >= mem["argument_bytes"]


# ------------------------------------------------- host-side byte helpers

def test_host_rss_and_np_tree_bytes():
    rss = host_rss_bytes()
    assert rss is None or rss > 2**20          # a python process is >1 MiB
    tree = {"a": np.zeros((4, 4), np.float32),
            "b": [np.zeros(8, np.int8), (np.zeros(2, np.float64),)],
            "c": None, "d": "not-an-array"}
    assert np_tree_bytes(tree) == 64 + 8 + 16
    assert np_tree_bytes(None) == 0
    # jax trees via shape metadata (never a device sync).
    assert tree_state_bytes({"w": jnp.zeros((3, 5), jnp.float32)}) == 60


def test_meter_accumulates_merges_and_peaks(tmp_path):
    """events=None keeps the meter a pure accumulator; static note()-d
    figures merge into every sample; device_bytes sums the device-resident
    components when the sampler didn't total them; peaks track maxima."""
    m = MemoryMeter(source="host")
    m.note(params_bytes=1000, opt_state_bytes=500, skipped=None)
    rec = m.sample(pool_used_bytes=200, it=1)
    assert rec["device_bytes"] == 1700.0
    assert "skipped" not in rec
    m.sample(pool_used_bytes=800, it=2)
    assert m.peaks["pool_used_bytes"] == 800.0
    assert m.peaks["device_bytes"] == 2300.0
    assert m.samples == 2
    # An explicit device_bytes wins over the component sum.
    assert m.sample(device_bytes=42.0)["device_bytes"] == 42.0
    # Bound to a log, every sample is one strictly-valid memory event.
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="m") as log:
        mm = MemoryMeter(log, source="fleet")
        mm.sample(phase="before")
        mm.sample(phase="after", rss_bytes=123)   # explicit beats setdefault
    events = read_events(path, strict=True)
    assert [e["source"] for e in events] == ["fleet", "fleet"]
    assert events[1]["rss_bytes"] == 123


def test_meter_emission_never_sinks_host():
    class Broken:
        def memory(self, **kw):
            raise OSError("disk full")
    m = MemoryMeter(Broken(), source="train")
    rec = m.sample(params_bytes=10)              # must not raise
    assert rec["params_bytes"] == 10 and m.samples == 1


# -------------------------------------------- preflight vs measured bytes

def test_preflight_zero1_moments_one_over_n():
    """The ZeRO-1 memory-parity claim (arXiv 2004.13336) as a number:
    sharded adam moments land at ~1/n of replicated (exact up to the
    flat-vector padding), and the replicated figure is ~2x params."""
    tc = TrainConfig(batch_size=2, seq_len=16, iters=1, data=4)
    pre = preflight(TINY, tc, aggregation="zero1")
    assert pre is not None and pre["n_data"] == 4
    ratio = pre["opt_state_bytes"] / pre["opt_state_replicated_bytes"]
    assert ratio == pytest.approx(0.25, rel=0.05)
    assert pre["opt_state_replicated_bytes"] == pytest.approx(
        2 * pre["params_bytes"], rel=0.05)       # adam: mu + nu
    # gradient aggregation replicates the moments: no 1/n.
    rep = preflight(TINY, tc, aggregation="gradient")
    assert rep["opt_state_bytes"] == rep["opt_state_replicated_bytes"]
    # The serving pool lands in the budget when a paged config is given.
    srv = preflight(SRV_CFG, paged=SRV_PAGED)
    from ddl25spring_tpu.serving import pool_bytes
    assert srv["kv_pool_bytes"] == pool_bytes(SRV_CFG, SRV_PAGED)
    assert srv["device_bytes"] >= srv["kv_pool_bytes"]


@pytest.mark.parametrize("mode,K", [("gradient", 1), ("gradient", 4),
                                    ("zero1", 1), ("zero1", 4)])
def test_preflight_matches_measured_footprint(devices, mode, K):
    """The fit estimator's acceptance bar: the config-only per-device
    prediction of the PERSISTENT footprint (state + batch window) agrees
    with the measured ``memory_analysis`` argument bytes of the real
    compiled step within 10%. memory_analysis reports per-device figures
    (replicated args full-size, sharded args their shard), so the
    comparison needs no world scaling; the measured total's only
    unmodeled argument is the 4-byte step counter."""
    n, B = 4, 2
    mesh = make_mesh({"data": n}, devices=devices[:n])
    tc = TrainConfig(batch_size=B, seq_len=TINY.ctx_size, iters=1, data=n,
                     steps_per_dispatch=K)
    pre = preflight(TINY, tc, mesh=mesh, aggregation=mode)
    assert pre is not None

    opt = optax.adam(tc.lr)

    def loss_fn(p, b):
        return llama.forward_loss(p, b, TINY)

    params = llama.init_llama(jax.random.key(0), TINY)
    if mode == "gradient":
        state = dp.replicate(mesh, dp.init_state(params, opt))
        if K == 1:
            step = dp.make_grad_aggregation_step(loss_fn, opt, mesh)
            batch = jax.ShapeDtypeStruct((n * B, TINY.ctx_size), jnp.int32)
        else:
            step = dp.make_multi_step(loss_fn, opt, mesh)
            batch = jax.ShapeDtypeStruct((K, n * B, TINY.ctx_size),
                                         jnp.int32)
    else:
        if K == 1:
            state, step = dp.make_zero1_step(loss_fn, opt, mesh, params)
            batch = jax.ShapeDtypeStruct((n * B, TINY.ctx_size), jnp.int32)
        else:
            state, step = dp.make_zero1_multi_step(loss_fn, opt, mesh,
                                                   params)
            batch = jax.ShapeDtypeStruct((K, n * B, TINY.ctx_size),
                                         jnp.int32)
    mem = program_memory(step, state, batch)
    assert mem is not None
    predicted = pre["state_bytes"] + pre["window_bytes"]
    assert pre["window_bytes"] == K * B * TINY.ctx_size * 4
    assert abs(mem["argument_bytes"] - predicted) / predicted < 0.10, \
        (predicted, mem["argument_bytes"])


def test_preflight_overlap_residuals_measured(devices):
    """The int8+EF overlap driver's residual trees are IN the budget:
    preflight's residual_bytes models OverlapEFState (one padded ring
    vector + a 1/n gather shard), and the full predicted state+window
    still lands within 10% of the measured argument bytes."""
    n, B, K, M = 4, 2, 2, 2
    mesh = make_mesh({"data": n}, devices=devices[:n])
    tc = TrainConfig(batch_size=B, seq_len=TINY.ctx_size, iters=1, data=n,
                     steps_per_dispatch=K, overlap_microbatches=M,
                     wire="int8_ef")
    pre = preflight(TINY, tc, mesh=mesh, aggregation="zero1")
    assert pre is not None and pre["residual_bytes"] > 0

    def loss_fn(p, b):
        return llama.forward_loss(p, b, TINY)

    state, step = compress.make_overlap_multi_step(
        loss_fn, optax.adam(tc.lr), mesh,
        llama.init_llama(jax.random.key(0), TINY),
        microbatches=M, wire="int8_ef", aggregation="zero1")
    window = jax.ShapeDtypeStruct((K, n * B, TINY.ctx_size), jnp.int32)
    mem = program_memory(step, state, window)
    assert mem is not None
    predicted = pre["state_bytes"] + pre["window_bytes"]
    assert abs(mem["argument_bytes"] - predicted) / predicted < 0.10, \
        (predicted, mem["argument_bytes"])


# ------------------------------------- allocator census + CoW interaction

def test_allocator_fragmentation_census_edges():
    a = BlockAllocator(8)                        # 7 allocatable: 1..7
    # Fully free: exactly one hole spanning capacity.
    assert a.fragmentation() == {"holes": 1, "largest_run": 7}
    got = a.alloc(7)
    # Empty free list: 0 holes, 0 run (not 1/0 or a crash).
    assert a.fragmentation() == {"holes": 0, "largest_run": 0}
    # Free alternating blocks: maximal shatter — each free block its own
    # hole of run 1.
    a.free([b for i, b in enumerate(got) if i % 2 == 0])
    assert a.fragmentation() == {"holes": 4, "largest_run": 1}
    assert a.holes == 4 and a.largest_run == 1
    # Heal two neighbors: holes drop, largest run grows.
    a.free([got[1]])                             # blocks 1,2,3 now free
    frag = a.fragmentation()
    assert frag["holes"] == 3 and frag["largest_run"] == 3


def test_allocator_census_bytes():
    a = BlockAllocator(6)
    a.alloc(2)
    c = allocator_census(a, bytes_per_block=100)
    assert c["blocks_in_use"] == 2 and c["free_blocks"] == 3
    assert c["pool_used_bytes"] == 200
    assert c["pool_capacity_bytes"] == 500
    assert c["peak_pool_used_bytes"] == 200
    assert c["holes"] == 1 and c["largest_run"] == 3
    # Without bytes_per_block the byte fields stay absent, never zero-lie.
    assert "pool_used_bytes" not in allocator_census(a)


def test_cow_prefix_share_cuts_occupancy_not_fragmentation():
    """The satellite's acceptance bar: two concurrent requests with an
    identical prompt prefix occupy FEWER physical blocks with CoW sharing
    on than off, while the fragmentation census is no worse — sharing
    dedupes whole block chains, it does not shatter the free list. And a
    drained pool returns to the pristine single-hole census either way."""
    params = llama.init_llama(jax.random.PRNGKey(0), SRV_CFG)
    prompt = tuple(range(2, 10))                 # 8 tokens = 2 full blocks

    def drive(prefix_share):
        eng = Engine(params, SRV_CFG, SRV_PAGED, 2, prefill_chunk=8,
                     prefix_share=prefix_share)
        sched = Scheduler(eng)
        sched.submit(Request(rid="a", prompt=prompt, max_new=4), now=0.0)
        sched.tick()                             # a prefills + registers
        sched.submit(Request(rid="b", prompt=prompt, max_new=4), now=0.0)
        mid = None
        while sched.outstanding:
            sched.tick()
            if mid is None and len(sched.records["b"].tokens) > 0:
                mid = allocator_census(eng.allocator)
        return sched, eng, mid

    shared, eng_s, mid_s = drive(True)
    plain, eng_p, mid_p = drive(False)
    # Streams are bitwise the per-request references regardless.
    ref = reference_stream(params, SRV_CFG, SRV_PAGED,
                           Request(rid="r", prompt=prompt, max_new=4))
    for sched in (shared, plain):
        assert sched.records["a"].tokens == ref
        assert sched.records["b"].tokens == ref
    # Occupancy: sharing held fewer physical blocks at peak.
    assert eng_s.allocator.peak_in_use < eng_p.allocator.peak_in_use
    # Fragmentation while both were live: no worse under sharing.
    assert mid_s["holes"] <= mid_p["holes"]
    assert mid_s["blocks_in_use"] < mid_p["blocks_in_use"]
    # Drained: both pools return to one pristine hole spanning capacity.
    for eng in (eng_s, eng_p):
        assert eng.allocator.in_use == 0
        assert eng.allocator.fragmentation() == {
            "holes": 1, "largest_run": eng.allocator.capacity}


def test_scheduler_memory_sampling_bitwise_and_events(tmp_path):
    """memory_every armed: the served stream is BITWISE the unmetered
    run's, and every Nth busy tick lands one strictly-valid ``memory``
    event carrying the pool census in blocks AND bytes plus the engine's
    static params bytes."""
    from ddl25spring_tpu.serving.kvcache import kv_bytes_per_token
    params = llama.init_llama(jax.random.PRNGKey(0), SRV_CFG)
    prompt = tuple(range(3, 9))

    def drive(memory_every, events=None):
        eng = Engine(params, SRV_CFG, SRV_PAGED, 2, prefill_chunk=8)
        sched = Scheduler(eng, events=events, memory_every=memory_every)
        sched.submit(Request(rid="a", prompt=prompt, max_new=5), now=0.0)
        sched.submit(Request(rid="b", prompt=prompt[:4], max_new=3),
                     now=0.0)
        while sched.outstanding:
            sched.tick()
        return sched

    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="srv") as log:
        metered = drive(2, events=log)
    plain = drive(0)
    for rid in ("a", "b"):
        assert metered.records[rid].tokens == plain.records[rid].tokens
    assert plain.memory_meter is None            # default off: no meter at all
    mems = [e for e in read_events(path, strict=True)
            if e["type"] == "memory"]
    assert mems and all(e["source"] == "serve" for e in mems)
    bpb = SRV_PAGED.block_len * kv_bytes_per_token(SRV_CFG,
                                                   SRV_PAGED.kv_dtype)
    for e in mems:
        assert e["params_bytes"] == tree_state_bytes(params)
        assert e["pool_used_bytes"] == e["blocks_in_use"] * bpb
        assert "holes" in e and "largest_run" in e
        assert e["device_bytes"] >= e["params_bytes"]
    assert metered.memory_meter.samples == len(mems)
    assert metered.memory_meter.peaks["blocks_in_use"] >= 1


# ------------------------------------------------- headroom SLO chain

def test_autoscaler_headroom_veto_then_release():
    """The guard rail: sustained TTFT pressure normally scales train ->
    serve, but a pool below the headroom floor vetoes the move; the hot
    streak keeps accumulating, so the move fires the FIRST tick headroom
    recovers — latency pressure never scales serving into a pool that
    can't fit it."""
    from ddl25spring_tpu.resilience.autoscale import (AutoscalePolicy,
                                                      Autoscaler)
    policy = AutoscalePolicy(ttft_slo_s=1.0, max_train_world=8,
                             max_serve_engines=4, sustain=2, cooldown=0,
                             min_headroom_frac=0.2)
    asc = Autoscaler(policy, train_world=4, serve_engines=2, log_fn=None)
    hot = 0.9                                    # above 0.8 * SLO
    assert asc.tick(hot, headroom_frac=0.5) is None   # streak 1 < sustain
    # Streak satisfied but the pool is starved: vetoed, allocation frozen.
    assert asc.tick(hot, headroom_frac=0.05) is None
    assert asc.tick(hot, headroom_frac=0.1) is None
    assert (asc.train_world, asc.serve_engines) == (4, 2)
    # Pool drains: the accumulated streak fires immediately.
    d = asc.tick(hot, headroom_frac=0.6)
    assert d is not None and d.direction == "train_to_serve"
    assert (asc.train_world, asc.serve_engines) == (3, 3)
    # No headroom feed (None) never vetoes; floor 0 disarms the rail.
    asc2 = Autoscaler(AutoscalePolicy(ttft_slo_s=1.0, max_train_world=8,
                                      max_serve_engines=4, sustain=1,
                                      cooldown=0),
                      train_world=4, serve_engines=2, log_fn=None)
    assert asc2.tick(hot, headroom_frac=0.0) is not None
    with pytest.raises(ValueError, match="min_headroom_frac"):
        AutoscalePolicy(ttft_slo_s=1.0, max_train_world=8,
                        max_serve_engines=4, min_headroom_frac=1.0)


def test_slo_monitor_headroom_breach(tmp_path):
    """The OOM-headroom SLO end to end: ``memory`` events' device_bytes
    against a --device-bytes budget — the WINDOW PEAK judges (a transient
    spike breaches even if the latest sample recovered), breach emits one
    strictly-valid slo_violation, and a healthy stream stays quiet."""
    from experiments.slo_monitor import SLOConfig, SLOMonitor

    def mem(seq, t, device_bytes):
        return {"schema": SCHEMA_VERSION, "run_id": "r", "seq": seq, "t": t,
                "type": "memory", "source": "serve",
                "device_bytes": device_bytes}

    cfg = SLOConfig(window_s=100.0, min_headroom_frac=0.2,
                    device_budget_bytes=1000.0)
    m = SLOMonitor(cfg)
    m.feed([mem(1, 0.0, 500.0), mem(2, 1.0, 700.0)])
    assert m.evaluate(2.0) == []                 # 30% free >= 20% floor
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="slo") as log:
        m2 = SLOMonitor(cfg, emit=log)
        m2.feed([mem(1, 0.0, 950.0), mem(2, 1.0, 600.0)])  # peak judges
        viols = m2.evaluate(2.0)
    assert [v["slo"] for v in viols] == ["headroom_frac"]
    assert viols[0]["value"] == pytest.approx(0.05)
    events = read_events(path, strict=True)
    assert [e["type"] for e in events] == ["slo_violation"]
    assert events[0]["slo"] == "headroom_frac"
    # Without a budget the objective never arms (the CLI enforces the
    # pairing; the config level simply stays quiet).
    m3 = SLOMonitor(SLOConfig(window_s=100.0, min_headroom_frac=0.2))
    m3.feed([mem(1, 0.0, 1e12)])
    assert m3.evaluate(1.0) == []


def test_slo_monitor_cli_requires_budget(tmp_path):
    from experiments.slo_monitor import main as slo_main
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="r") as log:
        log.memory(source="serve", device_bytes=100.0)
    with pytest.raises(SystemExit):
        slo_main([path, "--check", "--slo-headroom", "0.2", "--no-emit"])
    # Paired correctly: a roomy budget passes the check (exit 0).
    assert slo_main([path, "--check", "--slo-headroom", "0.2",
                     "--device-bytes", "1e9", "--no-emit"]) == 0


# ------------------------------------------------- trainer integration

def test_trainer_meter_bitwise_invariance_and_stream(tmp_path, devices):
    """The zero-overhead bar AND the stream contract in one run pair:
    train_llm_dp with telemetry (meter armed at chunk cadence) emits a
    preflight-stamped manifest plus per-cadence ``memory`` events, and
    the loss trajectory is BITWISE the bare run's — the meter is host
    bookkeeping only, zero extra dispatches."""
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train.llm import train_llm_dp
    n = 2
    tc = TrainConfig(batch_size=2, seq_len=16, iters=6, lr=3e-3, data=n,
                     steps_per_dispatch=2)

    def run(tel):
        return train_llm_dp(
            model_cfg=TINY, train_cfg=tc,
            mesh=make_mesh({"data": n}, devices=devices[:n]),
            tokenizer=ByteTokenizer(), log_every=0, telemetry=tel)

    bare = run(None)
    with Telemetry(str(tmp_path / "run"), step_every=2) as tel:
        metered = run(tel)
        events = read_events(tel.events_path, strict=True)
    assert metered.losses == bare.losses         # bitwise, not approx
    manifest = [e for e in events if e["type"] == "manifest"][0]
    pre = manifest["preflight"]
    assert pre["n_data"] == n and pre["params_bytes"] > 0
    mems = [e for e in events if e["type"] == "memory"]
    assert mems and all(e["source"] == "train" for e in mems)
    # Chunk-edge cadence: memory samples ride the step-event cadence.
    steps = [e for e in events if e["type"] == "step"]
    assert [e["it"] for e in mems] == [e["it"] for e in steps]
    for e in mems:
        assert e["params_bytes"] == pre["params_bytes"]
        assert e["device_bytes"] >= pre["params_bytes"]
    # Zero extra compiles: every compile event is the step program's.
    compiles = [e for e in events if e["type"] == "compile"]
    assert all(not c.get("retrace") for c in compiles)
    # The renderer consumes the new section (acceptance criterion).
    from experiments.obs_report import main as report_main
    assert report_main([str(tmp_path / "run")]) == 0
