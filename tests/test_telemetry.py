"""Telemetry layer (ddl25spring_tpu/telemetry) + observability satellites.

Pins the ISSUE-2 contracts: event-schema round-trip (incl. torn-final-line
crash tolerance and concurrent writers), EXACT static comm-volume bytes for
known DP configs (fp32 vs the compressed wire formats), heartbeat-based
stall detection in the watchdog's LivenessMonitor, cost_analysis guard
behavior on this jaxlib, thread-safe ResultSink header widening,
ResilienceStats.merge field completeness, and StepTimer misuse raising.
"""

import dataclasses
import json
import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.config import LlamaConfig, TrainConfig
from ddl25spring_tpu.metrics import ResilienceStats
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.parallel import compress, dp, make_mesh
from ddl25spring_tpu.telemetry import (EventLog, Heartbeat, MetricsRegistry,
                                       SCHEMA_VERSION, Telemetry, hlo_cost,
                                       measure_comm, read_events,
                                       read_heartbeat, validate_event)
from ddl25spring_tpu.tokenizers import ByteTokenizer
from ddl25spring_tpu.utils.tracing import ResultSink, StepTimer

TINY = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                   ctx_size=16)


# ----------------------------------------------------------- event stream

def test_eventlog_schema_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="r1") as log:
        log.manifest(jax_version=jax.__version__, platform="cpu")
        log.step(it=0, loss=2.5, dt_s=0.1)
        log.fault(counters={"skipped_steps": 1}, it=3)
        log.fl_round(round=0, wall_s=0.2, test_accuracy=0.5)
        log.run_end(steps=10, metrics={"counters": {}})
    events = read_events(path, strict=True)  # strict: validates every event
    assert [e["type"] for e in events] == [
        "manifest", "step", "fault", "fl_round", "run_end"]
    assert [e["seq"] for e in events] == [1, 2, 3, 4, 5]
    assert all(e["run_id"] == "r1" for e in events)
    assert all(e["schema"] == SCHEMA_VERSION for e in events)
    assert events[1]["loss"] == 2.5 and events[1]["it"] == 0
    # type filter
    assert [e["it"] for e in read_events(path, types=("step",))] == [0]


def test_eventlog_torn_final_line_and_corruption(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="r1") as log:
        log.step(it=0, loss=1.0)
        log.step(it=1, loss=2.0)
    with open(path, "ab") as f:
        f.write(b'{"schema": 1, "run_id": "r1", "seq": 3, "t": 0, "ty')
    # A torn FINAL line is a crash artifact, dropped even under strict.
    assert [e["it"] for e in read_events(path, strict=True)] == [0, 1]
    # Mid-file garbage is corruption: skipped lax, raised strict.
    with open(path, "ab") as f:
        f.write(b'rbage\n')
        f.write(json.dumps({"schema": 1, "run_id": "r1", "seq": 4, "t": 0,
                            "type": "step", "it": 2}).encode() + b"\n")
    assert [e["it"] for e in read_events(path)] == [0, 1, 2]
    with pytest.raises(ValueError):
        read_events(path, strict=True)
    # Valid JSON that is not an object (`null`, a number) is the same
    # corruption class: skipped lax (with a types filter too), raised
    # strict — never leaked to crash a consumer's `.get`.
    path2 = str(tmp_path / "nondict.jsonl")
    with open(path2, "w") as f:
        f.write('null\n')
        f.write(json.dumps({"schema": 1, "run_id": "r", "seq": 1, "t": 0,
                            "type": "step", "it": 0}) + "\n")
    assert [e["it"] for e in read_events(path2, types=("step",))] == [0]
    with pytest.raises(ValueError):
        read_events(path2, strict=True)


def test_eventlog_reopen_heals_torn_fragment(tmp_path):
    """A relaunch reusing the telemetry dir truncates a crashed
    predecessor's torn final line instead of appending onto it — the
    fragment must not become mid-file corruption that strict readers
    raise on."""
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="r1") as log:
        log.step(it=0, loss=1.0)
    with open(path, "ab") as f:
        f.write(b'{"schema": 1, "run_id": "r1", "seq": 2, "t": 0, "ty')
    with EventLog(path, run_id="r2") as log:
        log.manifest(jax_version="test", platform="cpu")
    events = read_events(path, strict=True)
    assert [e["run_id"] for e in events] == ["r1", "r2"]


def test_eventlog_emit_never_raises(tmp_path):
    """IO failure drops the event and counts (same never-sink-a-trainer
    policy as Heartbeat.beat) — including emits after close()."""
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path, run_id="r1")
    log.step(it=0, loss=1.0)
    log.close()
    record = log.emit("step", it=1, loss=2.0)   # must not raise
    assert record["it"] == 1 and log.write_errors == 1
    assert [e["it"] for e in read_events(path)] == [0]
    # Serialization failures count too: _json_fallback can't save
    # non-string dict keys, and json.dumps' TypeError must not escape.
    log2 = EventLog(path, run_id="r2")
    log2.emit("custom", data={(0, 1): "tuple-keyed"})
    assert log2.write_errors == 1
    log2.step(it=2, loss=3.0)                   # stream still usable
    log2.close()
    assert [e["it"] for e in read_events(path, strict=True)] == [0, 2]


def test_eventlog_heal_scans_backwards_across_chunks(tmp_path):
    """The reopen-heal finds the last newline by scanning backwards in
    64 KiB chunks — a fragment longer than one chunk (a crash mid-way
    through a huge manifest) must still truncate to the right offset."""
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="r1") as log:
        log.step(it=0, loss=1.0)
    with open(path, "ab") as f:
        f.write(b'{"pad": "' + b"x" * (200 * 1024))  # 200 KiB torn line
    with EventLog(path, run_id="r2") as log:
        log.step(it=1, loss=2.0)
    assert [e["it"] for e in read_events(path, strict=True)] == [0, 1]


def test_eventlog_partial_write_seals_torn_tail(tmp_path, monkeypatch):
    """ENOSPC mid-line: os.write lands SOME bytes then fails. The failed
    event counts as a write error, and the next successful emit seals the
    fragment with a newline so it stays ONE skippable malformed line
    instead of merging into (and corrupting) the next event."""
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path, run_id="r1")
    log.step(it=0, loss=1.0)

    real_write = os.write
    calls = []

    # POSIX write(2) semantics for a disk filling mid-line: the first call
    # writes what fits and returns SHORT; the retry gets ENOSPC.
    def short_then_fail(fd, data):
        if fd == log._fd:
            calls.append(len(data))
            if len(calls) == 1:
                return real_write(fd, bytes(data)[:10])
            raise OSError(28, "No space left on device")
        return real_write(fd, data)

    monkeypatch.setattr(os, "write", short_then_fail)
    log.step(it=1, loss=2.0)                  # partially lands, counted
    monkeypatch.setattr(os, "write", real_write)
    log.step(it=2, loss=3.0)                  # must seal, then append
    log.close()
    assert log.write_errors == 1
    assert [e["it"] for e in read_events(path)] == [0, 2]
    with pytest.raises(ValueError):           # the fragment IS corruption
        read_events(path, strict=True)


def test_eventlog_nonfinite_floats_stay_strict_json(tmp_path):
    """An unguarded chaos run can hand emit() loss=nan — the stream must
    stay STRICT JSON (the CI artifact is consumed by jq/non-Python
    readers), so non-finite floats land as their str(), never as the
    NaN/Infinity tokens json.dumps writes by default."""
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="r1") as log:
        log.step(it=0, loss=float("nan"),
                 extra=[float("inf"), np.float32("nan")])
    raw = open(path).read()
    assert "NaN" not in raw and "Infinity" not in raw
    assert log.write_errors == 0
    (event,) = read_events(path, strict=True)
    assert event["loss"] == "nan" and event["extra"][0] == "inf"


def test_telemetry_step_every_floor(tmp_path):
    """step_every=0 ('disable step events') must not arm a
    ZeroDivisionError inside the training loop's `it % step_every`."""
    tel = Telemetry(str(tmp_path / "run"), step_every=0)
    assert tel.step_every == 1
    tel.close()


def test_validate_event_contract():
    base = {"schema": SCHEMA_VERSION, "run_id": "r", "seq": 1, "t": 0.0}
    assert validate_event({**base, "type": "step", "it": 3}) == []
    # Per-type required fields.
    assert validate_event({**base, "type": "step"}) != []
    # The type set is CLOSED per schema version: an unknown type at/below
    # the reader's version is a typo, not forward compat — and the problem
    # names it.
    problems = validate_event({**base, "type": "novel_event"})
    assert problems and "novel_event" in problems[0]
    # A FUTURE schema version is a problem; missing base fields are too.
    assert validate_event({**base, "schema": SCHEMA_VERSION + 1,
                           "type": "step", "it": 0}) != []
    assert validate_event({"type": "step", "it": 0}) != []


def test_validate_event_forward_version_names_offender():
    """A vN+1 writer against this reader used to fail with only 'schema N+1
    is newer' — the message must now NAME the event type that carried the
    future version, and an unknown type riding a future schema must be
    reported as the version skew it is, not double-flagged as a typo."""
    base = {"run_id": "r", "seq": 1, "t": 0.0}
    problems = validate_event({**base, "schema": SCHEMA_VERSION + 1,
                               "type": "hologram"})
    assert len(problems) == 1
    assert "hologram" in problems[0]
    assert str(SCHEMA_VERSION + 1) in problems[0]
    # Same unknown type AT the reader's version: flagged as unknown, with
    # the version it claimed.
    problems = validate_event({**base, "schema": SCHEMA_VERSION,
                               "type": "hologram"})
    assert len(problems) == 1 and "unknown event type" in problems[0]


def test_request_event_emitters_roundtrip(tmp_path):
    """Schema v2: the serving lifecycle's four typed emitters produce
    valid, strictly-readable events carrying their required fields."""
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="srv") as log:
        log.request_enqueue(req="r-1", prompt_len=8, max_new=4,
                            temperature=0.8, queued=1)
        log.request_prefill(req="r-1", slot=2, blocks=3, queue_wait_s=0.01,
                            blocks_in_use=3)
        log.request_token(req="r-1", i=0, tok=17, slot=2)
        log.request_done(req="r-1", tokens=4, queue_wait_s=0.01,
                         ttft_s=0.05, tokens_per_sec=80.0, blocks_freed=3,
                         blocks_in_use=0)
    events = read_events(path, strict=True)    # strict = validate_event
    assert [e["type"] for e in events] == [
        "request_enqueue", "request_prefill", "request_token",
        "request_done"]
    assert all(e["schema"] == SCHEMA_VERSION for e in events)
    assert events[1]["slot"] == 2 and events[3]["tokens"] == 4


def test_validate_event_request_required_fields():
    """request_* events missing their per-type required fields must be
    flagged — the schema bump added real rows, not just names."""
    base = {"schema": SCHEMA_VERSION, "run_id": "r", "seq": 1, "t": 0.0}
    assert validate_event({**base, "type": "request_enqueue",
                           "req": "a"}) == []
    assert validate_event({**base, "type": "request_enqueue"}) != []
    assert validate_event({**base, "type": "request_prefill",
                           "req": "a"}) != []        # missing slot
    assert validate_event({**base, "type": "request_token",
                           "req": "a"}) != []        # missing i
    assert validate_event({**base, "type": "request_done",
                           "req": "a"}) != []        # missing tokens
    assert validate_event({**base, "type": "request_done", "req": "a",
                           "tokens": 3}) == []
    # v1 streams (all pre-serving types) remain valid under the v2 reader.
    assert validate_event({**base, "schema": 1, "type": "step",
                           "it": 0}) == []


def test_fleet_event_emitters_roundtrip(tmp_path):
    """Schema v3: the fleet FL emitters (fl_cohort / fl_tier) produce
    valid, strictly-readable events carrying their required fields."""
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="fleet") as log:
        log.fl_cohort(round=0, tier="edge", cohort=3, edge=1, clients=64,
                      payload_bytes=64 * 1320)
        log.fl_tier(round=0, tier="edge", edges=4, clients=256,
                    payload_bytes=256 * 1320, wire="float32")
        log.fl_tier(round=0, tier="server", inputs=4,
                    payload_bytes=4 * 1320)
    events = read_events(path, strict=True)
    assert [e["type"] for e in events] == ["fl_cohort", "fl_tier",
                                           "fl_tier"]
    assert all(e["schema"] == SCHEMA_VERSION for e in events)
    assert events[0]["clients"] == 64
    assert events[2]["tier"] == "server"


def test_validate_event_fleet_required_fields():
    """fl_cohort / fl_tier events missing their per-type required fields
    must be flagged, and pre-v3 streams stay valid under the v3 reader."""
    base = {"schema": SCHEMA_VERSION, "run_id": "r", "seq": 1, "t": 0.0}
    assert validate_event({**base, "type": "fl_cohort", "round": 0,
                           "tier": "edge", "cohort": 0}) == []
    assert validate_event({**base, "type": "fl_cohort", "round": 0,
                           "tier": "edge"}) != []      # missing cohort
    assert validate_event({**base, "type": "fl_tier", "round": 0,
                           "tier": "server"}) == []
    assert validate_event({**base, "type": "fl_tier", "round": 0}) != []
    # v2 streams (serving lifecycle) remain valid under the v3 reader.
    assert validate_event({**base, "schema": 2, "type": "request_done",
                           "req": "a", "tokens": 3}) == []


def test_eventlog_concurrent_writers(tmp_path):
    """10 threads x 50 events through one log: every event lands intact
    (one write() under the lock), seq is a permutation of 1..500."""
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path, run_id="r1")

    def emit(tid):
        for i in range(50):
            log.emit("step", it=i, thread=tid)

    threads = [threading.Thread(target=emit, args=(t,)) for t in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    log.close()
    events = read_events(path, strict=True)
    assert len(events) == 500
    assert sorted(e["seq"] for e in events) == list(range(1, 501))


# ------------------------------------------------- comm-volume accounting

def _param_bytes(params, itemsize):
    return sum(math.prod(l.shape) for l in jax.tree.leaves(params)) * itemsize


def test_comm_exact_bytes_dp_fp32(devices):
    """The known-config contract: a data=2 DP gradient-aggregation step
    moves EXACTLY n_params fp32 elements through grad_allreduce plus one
    scalar loss, with ring wire factor 2*(n-1)/n = 1.0 at n=2."""
    n = 2
    mesh = make_mesh({"data": n}, devices=devices[:n])
    params = llama.init_llama(jax.random.key(0), TINY)
    opt = optax.adam(1e-3)
    state = dp.replicate(mesh, dp.init_state(params, opt))
    step = dp.make_grad_aggregation_step(
        lambda p, b: llama.forward_loss(p, b, TINY), opt, mesh)
    batch = jax.ShapeDtypeStruct((n * 2, TINY.ctx_size), jnp.int32)
    profile = measure_comm(step, state, batch)
    assert profile is not None and profile.records
    by = profile.by_label()
    expected = _param_bytes(params, 4)                 # fp32 wire
    assert by["grad_allreduce"]["payload_bytes"] == expected
    assert by["grad_allreduce"]["axis_size"] == n
    assert by["loss_allreduce"]["payload_bytes"] == 4  # one fp32 scalar
    # Ring allreduce at n=2: 2*(n-1)/n = 1.0 -> wire == payload.
    assert by["grad_allreduce"]["wire_bytes_per_device"] == expected
    assert profile.payload_bytes_per_step == expected + 4


def test_comm_bf16_wire_halves_payload(devices):
    """The compression lever the accounting exists to measure: the bf16
    wire format's grad collective carries exactly HALF the fp32 bytes."""
    n = 2
    mesh = make_mesh({"data": n}, devices=devices[:n])
    params = llama.init_llama(jax.random.key(0), TINY)
    opt = optax.adam(1e-3)
    state = dp.replicate(mesh, dp.init_state(params, opt))
    step = compress.make_bf16_grad_step(
        lambda p, b: llama.forward_loss(p, b, TINY), opt, mesh)
    batch = jax.ShapeDtypeStruct((n * 2, TINY.ctx_size), jnp.int32)
    profile = measure_comm(step, state, batch)
    by = profile.by_label()
    assert by["grad_allreduce_bf16"]["payload_bytes"] == _param_bytes(params, 2)


def test_comm_scale_multiplies_scan_trips():
    """A record's ``scale`` (scan trip count) multiplies the per-step
    aggregate — the mechanism the PP/SP ring call sites rely on."""
    from ddl25spring_tpu.telemetry.comm import CommProfile, CommRecord
    r = CommRecord(op="ppermute", label="hop", axis="stage", axis_size=4,
                   payload_bytes=100, scale=6)
    p = CommProfile([r])
    assert p.payload_bytes_per_step == 600
    assert p.by_label()["hop"]["calls"] == 6
    assert r.wire_bytes_per_device == 100.0      # one neighbor send per exec


def test_measure_comm_handles_cached_trace():
    """A step whose trace is already cached must still produce records
    (the one-retry-after-clear_caches path in measure_comm)."""
    @jax.jit
    def f(x):
        from ddl25spring_tpu.telemetry import comm
        return comm.psum(x, "i", label="row_sum")

    vx = jax.ShapeDtypeStruct((8, 4), jnp.float32)

    def mapped(x):
        return jax.vmap(f, axis_name="i")(x)

    first = measure_comm(mapped, vx)
    second = measure_comm(mapped, vx)      # cache-warm path
    # Accounting is per-participant: the operand INSIDE the mapped axis is
    # the [4] f32 local row, and the axis resolves to its 8 participants.
    assert first.by_label()["row_sum"]["payload_bytes"] == 4 * 4
    assert first.by_label()["row_sum"]["axis_size"] == 8
    assert second.by_label()["row_sum"]["payload_bytes"] == 4 * 4


# ------------------------------------------------------- HLO cost guard

def test_hlo_cost_on_this_jaxlib():
    """The lower→compile→cost_analysis chain works on the installed jax,
    and a single matmul's count matches 2*M*N*K within a tenth."""
    m, k, n = 32, 64, 16
    f = jax.jit(lambda a, b: a @ b)
    a = jax.ShapeDtypeStruct((m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((k, n), jnp.float32)
    hlo = hlo_cost(f, a, b)
    analytic = 2.0 * m * k * n
    assert hlo is not None and hlo["flops"] > 0
    assert abs(hlo["flops"] - analytic) / analytic < 0.10


def test_hlo_cost_unavailable_paths():
    assert hlo_cost(lambda x: x, 1) is None          # not jitted: no .lower
    # A program that does not lower (shapes that cannot be multiplied)
    # gives None, never an exception: the callers are observers.
    f = jax.jit(lambda a, b: a @ b)
    bad = jax.ShapeDtypeStruct((3, 5), jnp.float32)
    assert hlo_cost(f, bad, bad) is None


def test_hlo_cost_normalize_variants():
    from ddl25spring_tpu.telemetry.costs import _normalize
    assert _normalize({"flops": 10.0}) == {"flops": 10.0,
                                           "bytes_accessed": None}
    assert _normalize({"flops": 10.0, "bytes accessed": 5.0}) == {
        "flops": 10.0, "bytes_accessed": 5.0}
    assert _normalize({"flops": -1}) is None          # some backends' "n/a"
    assert _normalize({}) is None
    assert _normalize(None) is None


# -------------------------------------------- heartbeat + watchdog stall

def test_heartbeat_roundtrip_and_seq(tmp_path):
    path = str(tmp_path / "heartbeat.json")
    hb = Heartbeat(path)
    assert hb.beat(step=3)
    assert hb.beat(step=4, phase="train")
    got = read_heartbeat(path)
    assert got["step"] == 4 and got["seq"] == 2 and got["phase"] == "train"
    assert got["pid"] == os.getpid()
    # Unreadable/missing/torn files degrade to None, never raise.
    assert read_heartbeat(str(tmp_path / "missing.json")) is None
    with open(path, "w") as f:
        f.write('{"torn')
    assert read_heartbeat(path) is None


def test_liveness_monitor_heartbeat_stall_detection(tmp_path):
    """The watchdog's first-class heartbeat signal: seq advancing proves
    life with zero progress-file growth; neither signal moving is a stall;
    a NEW WRITER (pid change, seq restart) is life, not a stall."""
    from experiments.watchdog import LivenessMonitor
    progress = tmp_path / "progress.csv"
    progress.write_text("iter,loss\n")
    hb_path = str(tmp_path / "heartbeat.json")
    hb = Heartbeat(hb_path)
    hb.beat(step=0)

    mon = LivenessMonitor(str(progress), hb_path)
    assert mon.poll() is False                  # nothing moved since init
    hb.beat(step=1)                             # heartbeat only, no CSV row
    assert mon.poll() is True
    assert mon.poll() is False                  # stalled again
    progress.write_text("iter,loss\n0,2.5\n")   # CSV only, no beat
    assert mon.poll() is True
    # Relaunch: a fresh writer's seq restarts at 1 with a different pid —
    # that must register as movement even though 1 < the old seq.
    with open(hb_path, "w") as f:
        json.dump({"schema": 1, "pid": os.getpid() + 1, "step": 0, "seq": 1,
                   "time": 0.0, "monotonic": 0.0}, f)
    assert mon.poll() is True
    # Heartbeat file vanishing is "no signal", not movement.
    os.unlink(hb_path)
    assert mon.poll() is False


def test_liveness_monitor_without_heartbeat(tmp_path):
    """No --heartbeat: exactly the legacy growth-only behavior."""
    from experiments.watchdog import LivenessMonitor
    progress = tmp_path / "progress.csv"
    mon = LivenessMonitor(str(progress))        # file doesn't exist yet
    assert mon.poll() is False
    progress.write_text("a\n")
    assert mon.poll() is True
    assert mon.poll() is False


# ----------------------------------------------------- metrics registry

def test_registry_percentiles_and_snapshot():
    reg = MetricsRegistry()
    for v in range(1, 101):                     # 1..100
        reg.observe("t", float(v))
    pcts = reg.percentiles("t")
    assert pcts["p50"] == pytest.approx(50.5)
    assert pcts["p95"] == pytest.approx(95.05)
    assert pcts["p99"] == pytest.approx(99.01)
    reg.counter_inc("n", 2)
    reg.gauge_set("g", 7.0)
    with pytest.raises(ValueError):
        reg.counter_inc("n", -1)
    snap = reg.snapshot()
    assert snap["counters"]["n"] == 2.0 and snap["gauges"]["g"] == 7.0
    h = snap["histograms"]["t"]
    assert h["count"] == 100 and h["max"] == 100.0
    assert reg.percentiles("missing") == {}


def test_registry_absorbs_resilience_completely():
    """The adapter iterates the stats object's own fields: EVERY counter —
    including any future one — lands in the registry."""
    reg = MetricsRegistry()
    stats = ResilienceStats(skipped_steps=2, preemptions=1)
    reg.absorb_resilience(stats)
    for name in stats.as_dict():
        assert reg.counter(f"faults/{name}") == getattr(stats, name)


def test_resilience_stats_merge_field_completeness():
    """A newly added counter must not be silently dropped by merge/as_dict:
    both walk the dataclass's own fields, pinned here field-by-field."""
    fields = [f.name for f in dataclasses.fields(ResilienceStats)]
    a = ResilienceStats(**{f: i + 1 for i, f in enumerate(fields)})
    b = ResilienceStats(**{f: 100 * (i + 1) for i, f in enumerate(fields)})
    a.merge(b)
    for i, f in enumerate(fields):
        assert getattr(a, f) == 101 * (i + 1), f"merge dropped {f!r}"
    assert set(a.as_dict()) == set(fields)
    assert a.total_faults_handled == sum(101 * (i + 1)
                                         for i in range(len(fields)))
    # delta walks the same fields: every moved counter appears, none else.
    assert a.delta(b.as_dict()) == {f: i + 1
                                    for i, f in enumerate(fields)}
    assert a.delta(a.as_dict()) == {}


# ------------------------------------------------- tracing satellites

def test_step_timer_tick_before_start_raises():
    t = StepTimer()
    with pytest.raises(RuntimeError):
        t.tick()
    t.start()
    assert t.tick() >= 0.0 and len(t.times) == 1


def test_resultsink_concurrent_header_widening(tmp_path):
    """8 threads append records with PROGRESSIVELY WIDER field sets into one
    sink: no row may be lost to a widening rewrite racing an append, and
    the final header must be the union of all fields."""
    path = str(tmp_path / "out.csv")
    sink = ResultSink(path)

    def writer(tid):
        for i in range(25):
            row = {"iter": i, "thread": tid}
            if i >= 10:
                row[f"extra_{tid}"] = i       # per-thread widening field
            sink.write(row)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    import csv as _csv
    with open(path, newline="") as f:
        rows = list(_csv.DictReader(f))
    assert len(rows) == 8 * 25                     # zero rows dropped
    header = rows[0].keys()
    assert {"iter", "thread", *{f"extra_{t}" for t in range(8)}} <= set(header)
    for t in range(8):                             # every thread's tail rows
        tail = [r for r in rows
                if r["thread"] == str(t) and r[f"extra_{t}"] != ""]
        assert len(tail) == 15


# ------------------------------------------------- end-to-end integration

def test_trainer_telemetry_end_to_end(tmp_path, devices):
    """train_llm_dp with a Telemetry attached: valid JSONL stream (manifest
    with EXACT static comm bytes, step cadence, run_end snapshot) plus a
    live heartbeat — the acceptance flow obs_report renders."""
    n = 2
    with Telemetry(str(tmp_path / "run"), step_every=2) as tel:
        from ddl25spring_tpu.train.llm import train_llm_dp
        report = train_llm_dp(
            model_cfg=TINY,
            train_cfg=TrainConfig(batch_size=2, seq_len=16, iters=5,
                                  lr=3e-3, data=n),
            mesh=make_mesh({"data": n}, devices=devices[:n]),
            tokenizer=ByteTokenizer(), log_every=0, telemetry=tel)
        events = read_events(tel.events_path, strict=True)
    by_type = {}
    for e in events:
        by_type.setdefault(e["type"], []).append(e)
    manifest = by_type["manifest"][0]
    assert manifest["trainer"] == "dp" and manifest["mesh"] == {"data": n}
    # The manifest names the device and the attention path the step was
    # built with: on the CPU mesh "auto" means XLA, no Pallas mode, and the
    # peaks are the calibrated ones, labelled so.
    assert manifest["platform"] == "cpu" and manifest["device_kind"] == "cpu"
    assert manifest["attention"] == {"impl": "xla", "interpret": None}
    assert manifest["peaks"]["source"].startswith("calibrated")
    params = llama.init_llama(jax.random.key(0), TINY)
    comm = manifest["comm"]["collectives"]
    assert comm["grad_allreduce"]["payload_bytes"] == _param_bytes(params, 4)
    assert [e["it"] for e in by_type["step"]] == [0, 2, 4]
    run_end = by_type["run_end"][0]
    assert run_end["steps"] == report.steps == 5
    snap = run_end["metrics"]
    assert snap["histograms"]["host_iter_s"]["count"] == 5
    assert snap["gauges"]["phase/dispatch_s"] > 0
    hb = read_heartbeat(tel.heartbeat_path)
    assert hb["step"] == 5 and hb["phase"] == "done"
    # The renderer consumes what the trainers emit (acceptance criterion).
    from experiments.obs_report import main as report_main
    assert report_main([str(tmp_path / "run")]) == 0


def test_trainer_telemetry_chunked_dispatch(tmp_path, devices):
    """Chunked mode (steps_per_dispatch=2): the manifest's comm profile
    covers one DISPATCH with the per-train-step normalization alongside
    (CommProfile.as_dict), step events land on chunk edges carrying the
    window size, and obs_report still renders the run."""
    n = 2
    with Telemetry(str(tmp_path / "run"), step_every=2) as tel:
        from ddl25spring_tpu.train.llm import train_llm_dp
        report = train_llm_dp(
            model_cfg=TINY,
            train_cfg=TrainConfig(batch_size=2, seq_len=16, iters=6,
                                  lr=3e-3, data=n, steps_per_dispatch=2),
            mesh=make_mesh({"data": n}, devices=devices[:n]),
            tokenizer=ByteTokenizer(), log_every=0, telemetry=tel)
        events = read_events(tel.events_path, strict=True)
    by_type = {}
    for e in events:
        by_type.setdefault(e["type"], []).append(e)
    comm = by_type["manifest"][0]["comm"]
    assert comm["steps_per_dispatch"] == 2
    # One dispatch = 2 recorded steps of traffic; the normalization halves.
    assert comm["payload_bytes_per_train_step"] == pytest.approx(
        comm["payload_bytes_per_step"] / 2)
    params = llama.init_llama(jax.random.key(0), TINY)
    assert comm["collectives"]["grad_allreduce"]["payload_bytes"] == \
        2 * _param_bytes(params, 4)
    steps = by_type["step"]
    assert [e["it"] for e in steps] == [1, 3, 5]   # chunk edges
    assert all(e["steps_per_dispatch"] == 2 for e in steps)
    assert steps[0].get("warmup") is True          # compile chunk flagged
    assert by_type["run_end"][0]["steps"] == report.steps == 6
    assert len(report.losses) == 6
    from experiments.obs_report import main as report_main
    assert report_main([str(tmp_path / "run")]) == 0


def test_fl_server_emits_round_events(tmp_path):
    """FL servers report through the same stream: one fl_round per round
    with accuracy/wall/messages, plus manifest and run_end."""
    from ddl25spring_tpu.config import FLConfig
    from ddl25spring_tpu.data import mnist
    from ddl25spring_tpu.fl import FedAvgServer, federate
    from ddl25spring_tpu.models import mnist_cnn
    x_raw, y, xt_raw, yt = mnist.load_mnist(n_train=300, n_test=100, seed=0)
    x, xt = mnist.normalize(x_raw), mnist.normalize(xt_raw)
    cfg = FLConfig(nr_clients=6, client_fraction=0.5, batch_size=50,
                   epochs=1, lr=0.05, rounds=2, seed=3)
    data = federate(x, y.astype(np.int32),
                    mnist.split(y, cfg.nr_clients, iid=True, seed=cfg.seed))
    with Telemetry(str(tmp_path / "fl")) as tel:
        server = FedAvgServer(mnist_cnn.init(jax.random.key(0)),
                              mnist_cnn.apply, data, xt,
                              yt.astype(np.int32), cfg, telemetry=tel)
        result = server.run(2)
        events = read_events(tel.events_path, strict=True)
    rounds = [e for e in events if e["type"] == "fl_round"]
    assert [r["round"] for r in rounds] == [0, 1]
    assert rounds[-1]["test_accuracy"] == result.test_accuracy[-1]
    assert rounds[-1]["messages"] == result.message_count[-1]
    end = [e for e in events if e["type"] == "run_end"][-1]
    assert end["final_accuracy"] == result.test_accuracy[-1]
    assert read_heartbeat(tel.heartbeat_path)["seq"] == 2


# ------------------------------------------------- span layer (schema v4)

def test_span_context_propagation_roundtrip(tmp_path):
    """The tentpole contract: explicit parent propagation reconstructs the
    exact tree — trace/span/parent ids round-trip through the stream
    (strict-valid under schema v4), SpanContext survives as_dict/from_dict
    across a process boundary, and the reassembled tree has one root,
    zero orphans, children in start order."""
    from ddl25spring_tpu.telemetry.trace import (SpanContext, Tracer,
                                                 trace_trees, tree_check)
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="t") as log:
        tr = Tracer(log)
        with tr.span("round", trace="round-0", round=0) as root:
            # Simulate crossing a process/function boundary: the context
            # travels as a dict, not an object.
            wire = root.ctx.as_dict()
            handed = SpanContext.from_dict(wire)
            assert handed == root.ctx
            with tr.span("tier", parent=handed, tier="edge") as tier:
                with tr.span("cohort", parent=tier.ctx, cohort=0):
                    pass
                with tr.span("cohort", parent=tier.ctx, cohort=1):
                    pass
    events = read_events(path, strict=True)       # v4-valid
    assert all(e["type"] == "span" for e in events)
    trees = trace_trees(events)
    assert set(trees) == {"round-0"}
    t = trees["round-0"]
    assert tree_check(t) == {"roots": 1, "orphans": 0, "imbalanced": 0}
    root_ev = t["roots"][0]
    assert root_ev["name"] == "round" and root_ev["round"] == 0
    (tier_ev,) = t["children"][root_ev["span_id"]]
    cohorts = t["children"][tier_ev["span_id"]]
    assert [c["cohort"] for c in cohorts] == [0, 1]   # start-ns order
    # Parenting is by id, not nesting order of emission (children emit
    # BEFORE their parent closes).
    assert [e["name"] for e in events] == ["cohort", "cohort", "tier",
                                           "round"]


def test_span_orphan_detection(tmp_path):
    """A span naming a never-closed parent must surface as an orphan, not
    silently reattach — that is the self-check obs_report renders."""
    from ddl25spring_tpu.telemetry.trace import trace_trees, tree_check
    base = {"schema": SCHEMA_VERSION, "run_id": "r", "seq": 1, "t": 0.0,
            "type": "span", "trace_id": "x", "start_ns": 0, "dur_ns": 1}
    events = [{**base, "name": "root", "span_id": "s1"},
              {**base, "name": "lost", "span_id": "s9",
               "parent_span_id": "s404"}]
    t = trace_trees(events)["x"]
    assert tree_check(t)["orphans"] == 1
    assert t["orphans"][0]["name"] == "lost"


def test_tracer_phases_adapter_and_opt_out():
    """Tracer(phases=Spans()) is the absorption path: every completed span
    feeds the accumulator (under its phase alias when given), umbrella
    spans opt out with phase=False, and events=None still accumulates —
    un-telemetered runs keep phase accounting through the one path."""
    from ddl25spring_tpu.telemetry.trace import Spans, Tracer
    acc = Spans()
    tr = Tracer(None, phases=acc)
    with tr.span("dispatch", trace="train", phase=False) as root:
        with tr.span("compute", parent=root.ctx, phase="dispatch"):
            pass
        with tr.span("stage", parent=root.ctx, phase="data"):
            pass
    assert acc.count("dispatch") == 1 and acc.count("data") == 1
    assert acc.count("compute") == 0          # filed under the alias
    assert acc.total("dispatch") >= 0.0
    # The umbrella span itself must NOT have double-counted anything.
    assert set(acc.as_dict()) == {"dispatch", "data"}


def test_span_schema_v4_validation_and_v3_backcompat():
    """span/slo_violation are v4 types with real required fields; a v3
    stream (old types at schema 3) stays strictly valid under this
    reader — the bump is additive."""
    base = {"run_id": "r", "seq": 1, "t": 0.0}
    ok = {**base, "schema": SCHEMA_VERSION, "type": "span", "name": "a",
          "trace_id": "t", "span_id": "s1", "start_ns": 0, "dur_ns": 1}
    assert validate_event(ok) == []
    for missing in ("name", "trace_id", "span_id", "start_ns", "dur_ns"):
        bad = {k: v for k, v in ok.items() if k != missing}
        assert validate_event(bad) != [], missing
    assert validate_event({**base, "schema": SCHEMA_VERSION,
                           "type": "slo_violation", "slo": "ttft"}) == []
    assert validate_event({**base, "schema": SCHEMA_VERSION,
                           "type": "slo_violation"}) != []
    # v3 (and v1) streams: every pre-v4 type validates unchanged.
    for schema, ev in ((3, {"type": "fl_cohort", "round": 0, "tier": "edge",
                            "cohort": 1}),
                       (3, {"type": "fl_tier", "round": 0, "tier": "edge"}),
                       (1, {"type": "step", "it": 0}),
                       (2, {"type": "request_done", "req": "a",
                            "tokens": 2})):
        assert validate_event({**base, "schema": schema, **ev}) == []


def test_trace_export_golden():
    """Tiny stream -> EXACT Chrome trace JSON: metadata rows for the
    process (run) and thread (trace), one complete event per span at
    tracer-clock microseconds, and the flat fault event anchored as an
    instant marker via the first span's epoch-vs-ns offset."""
    from experiments.trace_export import chrome_trace
    events = [
        {"schema": 4, "run_id": "r", "seq": 1, "t": 100.0, "type": "span",
         "name": "queue", "trace_id": "req-0", "span_id": "s2",
         "parent_span_id": "s1", "start_ns": 1000, "dur_ns": 2000},
        {"schema": 4, "run_id": "r", "seq": 2, "t": 100.5, "type": "span",
         "name": "request", "trace_id": "req-0", "span_id": "s1",
         "start_ns": 1000, "dur_ns": 6000, "tokens": 3},
        {"schema": 4, "run_id": "r", "seq": 3, "t": 101.0, "type": "fault",
         "counters": {"skipped_steps": 2}, "it": 7},
    ]
    # Instants anchor via the NEAREST span in epoch time — here the
    # "request" span at t=100.5, whose end (start+dur ns) calibrates the
    # epoch->span-clock offset.
    anchor = 100.5 - (1000 + 6000) / 1e9
    assert chrome_trace(events) == {
        "traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
             "args": {"name": "run r"}},
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
             "args": {"name": "req-0"}},
            {"ph": "i", "name": "fault", "cat": "event", "s": "p",
             "ts": (101.0 - anchor) * 1e6, "pid": 1, "tid": 0,
             "args": {"counters": {"skipped_steps": 2}, "it": 7}},
            {"ph": "X", "name": "queue", "cat": "span", "ts": 1.0,
             "dur": 2.0, "pid": 1, "tid": 1,
             "args": {"span_id": "s2", "parent_span_id": "s1"}},
            {"ph": "X", "name": "request", "cat": "span", "ts": 1.0,
             "dur": 6.0, "pid": 1, "tid": 1,
             "args": {"tokens": 3, "span_id": "s1"}},
        ],
        "displayTimeUnit": "ms",
    }
    # --no-instants drops the marker but not the spans.
    spans_only = chrome_trace(events, instants=False)
    assert [e["ph"] for e in spans_only["traceEvents"]] == ["M", "M",
                                                            "X", "X"]


# ------------------------------------------------- slo monitor

def _mk(seq, t, type, **fields):
    return {"schema": SCHEMA_VERSION, "run_id": "r", "seq": seq, "t": t,
            "type": type, **fields}


def test_slo_monitor_flags_stalled_stream():
    """The acceptance bar: a stream that goes silent with work
    outstanding is flagged within ONE rolling window — the final
    evaluation runs at the heartbeat's last beat, a window past the last
    token, where the sustained-rate floor breaks."""
    from experiments.slo_monitor import SLOConfig, check_stream
    events = [_mk(1, 0.0, "request_enqueue", req="a"),
              _mk(2, 0.2, "request_enqueue", req="b"),
              _mk(3, 0.5, "request_token", req="a", i=0),
              _mk(4, 1.0, "request_token", req="a", i=1),
              _mk(5, 1.5, "request_token", req="a", i=2)]
    cfg = SLOConfig(window_s=10.0, min_tokens_per_sec=0.1)
    # Healthy read: the stream's own horizon still has tokens in window.
    assert check_stream(events, cfg) == []
    # Stall: the writer's heartbeat kept beating for one more window with
    # zero tokens and both requests still outstanding.
    violations = check_stream(events, cfg, heartbeat={"time": 12.0})
    assert [v["slo"] for v in violations] == ["tokens_per_sec"]
    assert violations[0]["value"] == 0.0
    # Same silence with NOTHING outstanding is idleness, not a stall.
    done = events + [_mk(6, 1.6, "request_done", req="a", tokens=3),
                     _mk(7, 1.7, "request_done", req="b", tokens=0)]
    assert check_stream(done, cfg, heartbeat={"time": 12.0}) == []


def test_slo_monitor_ttft_and_transitions():
    """p99 TTFT over the window; one incident per ok->breached transition
    (a sustained breach must not spam one event per poll)."""
    from experiments.slo_monitor import SLOConfig, SLOMonitor
    cfg = SLOConfig(window_s=10.0, ttft_p99_s=1.0)
    m = SLOMonitor(cfg)
    m.feed([_mk(1, 0.0, "request_enqueue", req="a"),
            _mk(2, 5.0, "request_done", req="a", tokens=2, ttft_s=4.0)])
    assert [v["slo"] for v in m.evaluate(5.0)] == ["ttft_p99_s"]
    assert m.evaluate(6.0) == []            # still breached: no re-fire
    assert m.evaluate(20.0) == []           # window drained: recovered
    assert not m.active
    m.feed([_mk(3, 21.0, "request_done", req="b", tokens=1, ttft_s=9.0)])
    assert [v["slo"] for v in m.evaluate(21.0)] == ["ttft_p99_s"]
    assert len(m.violations) == 2


def test_slo_monitor_guard_skip_rate():
    from experiments.slo_monitor import SLOConfig, SLOMonitor
    cfg = SLOConfig(window_s=100.0, max_skip_rate=0.2)
    m = SLOMonitor(cfg)
    m.feed([_mk(1, 1.0, "step", it=9, steps=10),
            _mk(2, 2.0, "fault", counters={"skipped_steps": 5})])
    viols = m.evaluate(3.0)
    assert [v["slo"] for v in viols] == ["guard_skip_rate"]
    # Skipped steps still consume their batches, so they are IN the step
    # events' counts: rate = skips / steps.
    assert viols[0]["value"] == pytest.approx(5 / 10)


def test_slo_monitor_emits_events(tmp_path):
    """Violations land in the stream as schema-v4 slo_violation events a
    strict reader accepts — and obs_report renders them."""
    from experiments.slo_monitor import SLOConfig, SLOMonitor
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="slo") as log:
        m = SLOMonitor(SLOConfig(window_s=5.0, queue_p99_s=0.1), emit=log)
        m.feed([_mk(1, 0.0, "request_done", req="a", tokens=1,
                    queue_wait_s=3.0)])
        m.evaluate(0.5)
    events = read_events(path, strict=True)
    assert [e["type"] for e in events] == ["slo_violation"]
    assert events[0]["slo"] == "queue_p99_s"
    from experiments.obs_report import main as report_main
    assert report_main([path]) == 0


def test_stream_tailer_incremental_and_torn_lines(tmp_path):
    """The live tailer: picks up appends incrementally, buffers a torn
    final line until its newline lands (never misparses a mid-write
    line), and survives a shrink (healed fragment) by re-reading."""
    from experiments.slo_monitor import StreamTailer
    path = str(tmp_path / "events.jsonl")
    t = StreamTailer(path)
    assert t.poll() == []                       # no file yet: no signal
    with open(path, "wb") as f:
        f.write(b'{"type": "step", "it": 0}\n{"type": "st')
        f.flush()
        assert [e["it"] for e in t.poll()] == [0]   # torn tail buffered
        f.write(b'ep", "it": 1}\n')
        f.flush()
        assert [e["it"] for e in t.poll()] == [1]   # seam healed exactly
    os.truncate(path, 0)                        # recycled stream
    with open(path, "ab") as f:
        f.write(b'{"type": "step", "it": 7}\n')
    assert [e["it"] for e in t.poll()] == [7]       # reset + re-read


def test_two_tracers_one_trace_no_span_id_collision(tmp_path):
    """The elastic wiring: the training loop's tracer and the controller's
    tracer BOTH emit on trace 'train'. Independent per-tracer counters
    must not collide (trace_trees keys spans by id — a collision silently
    overwrites spans and corrupts the reassembled tree)."""
    from ddl25spring_tpu.telemetry.trace import Tracer, trace_trees
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="r") as log:
        loop_tr, ctrl_tr = Tracer(log), Tracer(log)
        with loop_tr.span("dispatch", trace="train", it=0):
            pass
        with ctrl_tr.span("remesh", trace="train", it=0) as rroot:
            with ctrl_tr.span("restore", parent=rroot.ctx):
                pass
        with loop_tr.span("dispatch", trace="train", it=2):
            pass
    events = read_events(path, strict=True)
    t = trace_trees(events)["train"]
    assert len(t["spans"]) == len(events) == 4     # nothing overwritten
    assert len(t["roots"]) == 3 and not t["orphans"]
    ids = [e["span_id"] for e in events]
    assert len(set(ids)) == 4


def test_stream_tailer_from_end_skips_existing(tmp_path):
    """from_end=True (the watchdog's relaunch attach): pre-existing events
    — a dead run's outstanding request_enqueues — are never re-fed."""
    from experiments.slo_monitor import StreamTailer
    path = str(tmp_path / "events.jsonl")
    with open(path, "wb") as f:
        f.write(b'{"type": "request_enqueue", "req": "dead"}\n')
    t = StreamTailer(path, from_end=True)
    assert t.poll() == []
    with open(path, "ab") as f:
        f.write(b'{"type": "request_enqueue", "req": "alive"}\n')
    assert [e["req"] for e in t.poll()] == ["alive"]


def test_slo_monitor_partial_first_window_rate():
    """A healthy just-started stream must not read as a stall: during the
    first partial window the rate divisor is the observed span, not the
    full window (compile pushing the first token late would otherwise
    deflate a true 12 tok/s below a 10 tok/s floor)."""
    from experiments.slo_monitor import SLOConfig, SLOMonitor
    m = SLOMonitor(SLOConfig(window_s=30.0, min_tokens_per_sec=10.0))
    m.feed([_mk(1, 20.0, "request_enqueue", req="a")]
           + [_mk(2 + i, 20.0 + i * 0.08, "request_token", req="a", i=i)
              for i in range(120)])          # 12 tok/s from the start
    # Evaluated at t=30 the stream has existed for 10s: dividing its 120
    # tokens by the full 30s window would read 4 < 10 and cry stall at a
    # healthy server — the observed span is what the floor judges.
    assert m.evaluate(30.0) == []


def test_sidecar_eventlog_never_truncates_live_stream(tmp_path):
    """heal=False (the slo_monitor sidecar): attaching to a stream whose
    final line is mid-write must NOT truncate it — the live writer's
    O_APPEND continuation still lands after the fragment, and the
    sidecar's first emit seals it with a leading newline instead."""
    path = str(tmp_path / "events.jsonl")
    with EventLog(path, run_id="live") as live:
        live.step(it=0, loss=1.0)
    size_before = os.path.getsize(path)
    with open(path, "ab") as f:
        f.write(b'{"schema": 4, "run_id": "live", "seq": 2, "t": 0, "ty')
    frag_size = os.path.getsize(path)
    sidecar = EventLog(path, run_id="slo", heal=False)
    assert os.path.getsize(path) == frag_size     # nothing truncated
    sidecar.slo_violation(slo="ttft_p99_s", value=2.0, threshold=1.0)
    sidecar.close()
    # The fragment stays one skippable malformed line; both real events
    # survive; the default (heal=True) path in the same state would have
    # truncated back to size_before.
    events = read_events(path)
    assert [(e["run_id"], e["type"]) for e in events] == [
        ("live", "step"), ("slo", "slo_violation")]
    assert size_before < frag_size


def test_trace_trees_partitions_by_run_id():
    """Relaunches share a file, a trace name AND a span-id sequence (each
    process's first tracer is instance 1): trace_trees must keep the runs'
    trees apart instead of silently overwriting spans."""
    from ddl25spring_tpu.telemetry.trace import trace_trees, tree_check
    def span(run, sid, name, parent=None, start=0):
        e = {"schema": SCHEMA_VERSION, "run_id": run, "seq": 1, "t": 0.0,
             "type": "span", "trace_id": "train", "name": name,
             "span_id": sid, "start_ns": start, "dur_ns": 1}
        if parent:
            e["parent_span_id"] = parent
        return e
    events = [span("run1", "s1.2", "compute", "s1.1"),
              span("run1", "s1.1", "dispatch"),
              span("run2", "s1.2", "compute", "s1.1", start=5),
              span("run2", "s1.1", "dispatch", start=5)]
    trees = trace_trees(events)
    assert set(trees) == {"train", "run2/train"}
    for t in trees.values():
        assert tree_check(t) == {"roots": 1, "orphans": 0, "imbalanced": 0}
        assert len(t["spans"]) == 2


def test_slo_monitor_counts_done_tokens_without_token_events():
    """Scheduler(token_events=False) streams carry throughput only at
    completion granularity; the tok/s floor must read it there instead of
    declaring every such server stalled."""
    from experiments.slo_monitor import SLOConfig, SLOMonitor
    cfg = SLOConfig(window_s=10.0, min_tokens_per_sec=1.0)
    m = SLOMonitor(cfg)
    m.feed([_mk(1, 0.0, "request_enqueue", req="a"),
            _mk(2, 1.0, "request_enqueue", req="b"),
            _mk(3, 5.0, "request_done", req="a", tokens=40)])
    assert m.evaluate(8.0) == []            # 40 tokens/8s, healthy
    # A stream WITH token events never double-counts the done totals.
    m2 = SLOMonitor(cfg)
    m2.feed([_mk(1, 0.0, "request_enqueue", req="a"),
             _mk(2, 0.5, "request_enqueue", req="b")]
            + [_mk(3 + i, 1.0 + i, "request_token", req="a", i=i)
               for i in range(4)]
            + [_mk(9, 5.0, "request_done", req="a", tokens=4)])
    assert sum(n for _, n in m2._tokens) == 4


def test_slo_monitor_cold_start_grace_then_stall():
    """No token has EVER arrived: that is startup (XLA compile), not a
    throughput deficit — the floor stays quiet for one full window from
    the stream's birth, then a still-token-less stream IS a stall."""
    from experiments.slo_monitor import SLOConfig, SLOMonitor
    m = SLOMonitor(SLOConfig(window_s=30.0, min_tokens_per_sec=0.5))
    m.feed([_mk(1, 0.0, "request_enqueue", req="a")])
    assert m.evaluate(10.0) == []           # compiling, within grace
    assert m.evaluate(29.0) == []
    viols = m.evaluate(31.0)                # a window with zero tokens
    assert [v["slo"] for v in viols] == ["tokens_per_sec"]
    assert viols[0]["value"] == 0.0


def test_stream_tailer_from_end_survives_heal_shrink(tmp_path):
    """A relaunched writer's EventLog heals a torn fragment by TRUNCATING
    a few bytes; a from_end tailer must re-attach at the new end, not
    reset to 0 and replay the dead run's history (whose never-completed
    enqueues would poison the fresh monitor's outstanding counters)."""
    from experiments.slo_monitor import StreamTailer
    path = str(tmp_path / "events.jsonl")
    with open(path, "wb") as f:
        f.write(b'{"type": "request_enqueue", "req": "dead"}\n')
        f.write(b'{"type": "st')                    # torn fragment
    t = StreamTailer(path, from_end=True)
    assert t.poll() == []
    with open(path, "r+b") as f:                    # the relaunch heals...
        f.truncate(len(b'{"type": "request_enqueue", "req": "dead"}\n'))
    with open(path, "ab") as f:                     # ...and writes anew
        f.write(b'{"type": "request_enqueue", "req": "alive"}\n')
    assert [e["req"] for e in t.poll()] == ["alive"]


# ------------------------------------- overlap ring accounting (ISSUE 10)

def test_comm_ring_accounting_matches_analytic(devices):
    """The ring driver's comm profile is EXACT: ppermute trip counts ×
    chunk payloads reproduce the analytic K·M·(n−1)·chunk_bytes wire
    formula to the byte per wire format (ppermute ring factor 1 — one
    neighbor send per trip), and the int8 scale sidecars account
    K·M·(n−1)·4 bytes."""
    import optax

    from ddl25spring_tpu.parallel.dp import _flat_geometry

    n, K, M = 4, 2, 2
    mesh = make_mesh({"data": n}, devices=devices[:n])
    params = llama.init_llama(jax.random.key(0), TINY)
    _, _, local, _ = _flat_geometry(mesh, params)
    window = jax.ShapeDtypeStruct((K, n * 2, TINY.ctx_size), jnp.int32)

    def loss_fn(p, b):
        return llama.forward_loss(p, b, TINY)

    for wire, itemsize in (("fp32", 4), ("bf16", 2), ("int8_ef", 1)):
        state, step = compress.make_overlap_multi_step(
            loss_fn, optax.adam(1e-3), mesh,
            llama.init_llama(jax.random.key(0), TINY),
            microbatches=M, wire=wire, aggregation="zero1")
        profile = measure_comm(step, state, window)
        assert profile is not None and profile.records
        by = profile.by_label()
        suffix = {"fp32": "f32", "bf16": "bf16", "int8_ef": "int8"}[wire]
        ring = by[f"ring_grad_{suffix}"]
        want = K * M * (n - 1) * local * itemsize
        assert ring["payload_bytes"] == want, (wire, ring)
        assert ring["calls"] == K * M * (n - 1)
        # ppermute ring factor is exactly 1: wire bytes == payload bytes.
        assert ring["wire_bytes_per_device"] == want
        if wire == "int8_ef":
            scales = by["ring_grad_scale"]
            assert scales["payload_bytes"] == K * M * (n - 1) * 4
            # The compressed second leg (delta gather) is int8 too.
            assert by["overlap_delta_gather_int8"]["payload_bytes"] == \
                K * local * 1


def test_as_dict_overlap_normalization_rule():
    """The normalization rule, pinned once so future drivers can't
    double-count: per-TRAIN-STEP figures divide the per-dispatch totals
    by steps_per_dispatch ONLY — an overlap step's M microbatch rings are
    that step's traffic, so dividing by M too would under-count M×. The
    per-microbatch-ring view is an ADDITIONAL field (÷M on top)."""
    from ddl25spring_tpu.telemetry.comm import CommProfile, CommRecord
    K, M = 4, 2
    # One ring hop traced per microbatch (unrolled), each executing K
    # times per dispatch: 2 records at scale=K.
    records = [CommRecord(op="ppermute", label="ring_grad_f32",
                          axis="data", axis_size=2, payload_bytes=100,
                          scale=K)
               for _ in range(M)]
    p = CommProfile(records)
    d = p.as_dict(steps_per_dispatch=K, overlap_microbatches=M)
    assert d["wire_bytes_per_device_per_step"] == K * M * 100
    assert d["wire_bytes_per_device_per_train_step"] == M * 100   # ÷K only
    assert d["wire_bytes_per_device_per_microbatch"] == 100       # ÷K÷M
    assert d["overlap_microbatches"] == M
    # M = 1 adds nothing: the legacy dict shape is unchanged.
    d1 = p.as_dict(steps_per_dispatch=K)
    assert "overlap_microbatches" not in d1
    assert "wire_bytes_per_device_per_microbatch" not in d1
