"""The program's own spans, counters and scope names on the profiler's clock
(telemetry/trace.py; the names are listed in docs/COMPONENTS.md).

Everything runs on the CPU. A trace is started here with
``jax.profiler.start_trace`` and read back with ``jax.profiler.ProfileData``:
the program must be on the timeline whoever started the profiler, and the
numbers these traces hold are never speeds.
"""

import glob
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl25spring_tpu.config import LlamaConfig, TrainConfig
from ddl25spring_tpu.models import generate, llama
from ddl25spring_tpu.parallel import dp, make_mesh
from ddl25spring_tpu.serving import engine as engine_mod
from ddl25spring_tpu.serving.engine import (Engine, make_decode_step,
                                            make_prefill_chunk)
from ddl25spring_tpu.serving.kvcache import PagedKVConfig, init_pool
from ddl25spring_tpu.serving.scheduler import Request, Scheduler
from ddl25spring_tpu.serving.speculate import make_verify_step
from ddl25spring_tpu.telemetry import EventLog, Telemetry, read_events
from ddl25spring_tpu.telemetry import trace as trace_mod
from ddl25spring_tpu.telemetry.trace import Tracer
from ddl25spring_tpu.tokenizers import ByteTokenizer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                   ctx_size=64)
PAGED = PagedKVConfig(num_blocks=33, block_len=4, max_blocks_per_seq=8)
PREFIXES = ("serve.", "engine.", "train.")


def traced(log_dir, fn):
    """Run ``fn`` under the profiler as any harness would start it, and hand
    back its result with the program's host events: dicts of name, start
    and end in ns, and the event's integer statistics."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    events.append({
                        "name": e.name, "start": e.start_ns,
                        "end": e.start_ns + e.duration_ns,
                        "counters": {k: v for k, v in e.stats
                                     if isinstance(v, int)}})
    return out, sorted(events, key=lambda e: (e["start"], -e["end"]))


def parent_of(event, events):
    """The innermost other event that holds ``event`` in time."""
    holders = [p for p in events if p is not event
               and p["start"] <= event["start"] and event["end"] <= p["end"]]
    return min(holders, key=lambda p: p["end"] - p["start"], default=None)


# ------------------------------------------------------------------- serving

# table A of ISSUE 27: span -> (parent, counters)
SERVE_SPANS = {
    "serve.tick": (None, {"n", "queued", "in_flight", "blocks_in_use"}),
    "serve.admit": ("serve.tick", {"admitted"}),
    "engine.step": ("serve.tick", set()),
    "engine.prefill.stage": ("engine.step", set()),
    "engine.prefill.dispatch": ("engine.step",
                                {"slot", "seq", "off", "n_valid", "final",
                                 "attended_positions"}),
    "engine.prefill.fetch": ("engine.step", set()),
    "engine.decode.stage": ("engine.step", set()),
    "engine.decode.dispatch": ("engine.step",
                               {"dispatch", "active", "live_positions",
                                "gathered_positions"}),
    "engine.decode.fetch": ("engine.step", set()),
    "engine.decode.book": ("engine.step", {"emitted"}),
    "serve.emit": ("serve.tick", {"tokens", "retired"}),
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tiny Scheduler over Engine with ``events=None``, every tick under
    the profiler, with the benchmark's slot-state ledger beside it."""
    sys.path[:0] = [os.path.join(REPO, "benchmarks")]
    from serve_cell import TickLedger

    params = llama.init_llama(jax.random.key(0), TINY)
    engine = Engine(params, TINY, PAGED, 3, prefill_chunk=8)
    sched = Scheduler(engine)
    rng = np.random.default_rng(0)
    for i, (plen, new) in enumerate([(5, 4), (19, 6), (9, 1), (12, 5),
                                     (3, 7)]):
        sched.submit(Request(rid=f"r{i}", max_new=new, prompt=tuple(
            int(t) for t in rng.integers(1, 250, plen))), now=0.0)
    ledger = TickLedger(engine)

    def run():
        n = 0
        while sched.outstanding:
            emitted = sched.tick()
            ledger.after_tick(n, float(n), emitted, sched.records)
            n += 1
        return n

    ticks, events = traced(tmp_path_factory.mktemp("serve"), run)
    return {"ticks": ticks, "events": events, "ledger": ledger,
            "sched": sched, "engine": engine}


@pytest.mark.parametrize("name", sorted(SERVE_SPANS))
def test_serving_span_is_on_the_timeline_nested_with_its_counters(
        served, name):
    parent, counters = SERVE_SPANS[name]
    mine = [e for e in served["events"] if e["name"] == name]
    assert mine, f"no {name} in the trace"
    for e in mine:
        p = parent_of(e, served["events"])
        assert (p["name"] if p else None) == parent
        assert set(e["counters"]) == counters
    if name == "serve.tick":
        assert [e["counters"]["n"] for e in mine] == list(
            range(served["ticks"]))


def test_the_scheduler_made_no_tracer_and_the_spans_also_accumulate(served):
    sched, engine = served["sched"], served["engine"]
    assert sched.tracer is None and sched.events is None
    assert sched.spans.count("serve.tick") == served["ticks"]
    assert engine.spans.count("engine.decode.dispatch") == \
        engine.decode_dispatches
    assert engine.spans.total("engine.step") <= \
        sched.spans.total("serve.tick")


def test_dispatch_counters_give_what_the_slot_state_ledger_reckons(served):
    """Tokens processed, the context they attended to, and for each decode
    step its active slots and live positions: from the counters of
    ``engine.prefill.dispatch`` / ``engine.decode.dispatch`` alone, and
    from ``benchmarks/serve_cell.py::TickLedger``'s reading of slot state.

    They differ in one case, and there the counters are right: a request
    of ``max_new=1`` retires on the token of its final prefill chunk, its
    slot is gone when the ledger looks, and the ledger misses that chunk
    (r2: 9 prompt tokens in chunks of 8, the last 1 token at offset 8).
    The benchmark's traffic has no such request (outputs of 16 and up)."""
    events, ledger = served["events"], served["ledger"]
    ticks = [e for e in events if e["name"] == "serve.tick"]
    rows = []
    steps = []
    for tick in ticks:
        tokens = context = 0
        for e in events:
            if not (tick["start"] <= e["start"] and e["end"] <= tick["end"]):
                continue
            c = e["counters"]
            if e["name"] == "engine.prefill.dispatch":
                n, off = c["n_valid"], c["off"]
                tokens += n
                context += n * off + n * (n + 1) // 2
            elif e["name"] == "engine.decode.dispatch":
                tokens += c["active"]
                context += c["live_positions"]
                steps.append({"tick": tick["counters"]["n"],
                              "active": c["active"],
                              "live_positions": c["live_positions"]})
                # what the program reads as built: on the path this test
                # runs, the CPU's, every slot's whole table (where the
                # kernel is the attention, the decoding slots' live blocks:
                # tests/test_paged_attention.py)
                assert engine_mod.paged_attention_path(
                    1, TINY.num_heads, TINY.head_dim,
                    jnp.dtype(TINY.dtype))["impl"] == "xla"
                assert c["gathered_positions"] == 3 * PAGED.max_seq_len
        rows.append((tokens, context))
    reckoned = [(r["tokens_processed"], r["context_sum"])
                for r in ledger.rows]
    apart = {i: (a[0] - b[0], a[1] - b[1])
             for i, (a, b) in enumerate(zip(rows, reckoned)) if a != b}
    assert len(rows) == len(reckoned) and apart == {5: (1, 1 * 8 + 1)}
    # the chunk gathers its table whole on this path (where the kernel is
    # the attention, the live keys in key blocks: tests/test_chunk_attention.py)
    assert any(e["counters"] == {"slot": 2, "seq": 3, "off": 8, "n_valid": 1,
                                 "final": 1,
                                 "attended_positions": PAGED.max_seq_len}
               for e in events if e["name"] == "engine.prefill.dispatch")
    assert steps == [{k: s[k] for k in ("tick", "active", "live_positions")}
                     for s in ledger.decode_steps]
    assert [e["counters"]["dispatch"] for e in events
            if e["name"] == "engine.decode.dispatch"] == list(
                range(len(steps)))
    emitted = sum(e["counters"]["tokens"] for e in events
                  if e["name"] == "serve.emit")
    assert emitted == sum(len(r.tokens)
                          for r in served["sched"].records.values())


# ------------------------------------------------------------ named scopes

def loc_names(lowered) -> list:
    return re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))


def scope_parts(lowered) -> set:
    """Every part of every operation's name-stack path in the lowered text
    (a location without a ``/`` is a frame of the Python traceback)."""
    return {part for name in loc_names(lowered) if "/" in name
            for part in re.split(r"[/()]", name)}


def lower_programs(devices) -> dict:
    """The train step, ``decode_step``, ``prefill_chunk`` and
    ``verify_step``, each built anew and lowered at a tiny size."""
    params = llama.init_llama(jax.random.key(0), TINY)
    mesh = make_mesh({"data": 2}, devices=devices[:2])
    opt = optax.adam(1e-3)
    step = dp.make_grad_aggregation_step(
        lambda p, b: llama.forward_loss(p, b, TINY), opt, mesh,
        guard_nonfinite=True)
    state = dp.replicate(mesh, dp.init_state(params, opt))
    batch = dp.shard_batch(mesh, np.zeros((4, 16), np.int32))
    pool = init_pool(TINY, PAGED)
    fused = generate._fuse_blocks(params["blocks"])
    s = 2
    decode = make_decode_step(TINY, PAGED, s, None, None).lower(
        pool, params, fused, jnp.zeros((s, 8), jnp.int32),
        jnp.zeros(s, jnp.int32), jnp.zeros(s, jnp.int32),
        jnp.zeros((s, 2), jnp.uint32), jnp.zeros(s), jnp.zeros(s, bool))
    prefill = make_prefill_chunk(TINY, PAGED, 8, None, None).lower(
        pool, params, fused, jnp.zeros(8, jnp.int32),
        jnp.zeros(8, jnp.int32), jnp.int32(0), jnp.int32(8), jnp.int32(0),
        jnp.zeros(2, jnp.uint32), jnp.float32(0))
    k = 2
    verify = make_verify_step(TINY, PAGED, s, k, None, None).lower(
        pool, params, fused, jnp.zeros((s, 8), jnp.int32),
        jnp.zeros((s, k + 1), jnp.int32),
        jnp.zeros((s, k, TINY.vocab_size)), jnp.zeros(s, jnp.int32),
        jnp.ones(s, jnp.int32), jnp.zeros((s, 2), jnp.uint32), jnp.zeros(s),
        jnp.zeros(s, bool))
    return {"train": step.lower(state, batch), "decode": decode,
            "prefill": prefill, "verify": verify}


@pytest.fixture(scope="module")
def lowered(devices):
    return lower_programs(devices)


@pytest.fixture(scope="module")
def lowered_scopes(lowered):
    return {k: scope_parts(v) for k, v in lowered.items()}


SERVING_SCOPES = ["embed", "layers", "qkv", "paged.write", "paged.gather",
                  "paged.attend", "attn_out", "mlp", "head", "sample"]
TRAIN_SCOPES = ["embed", "attn", "mlp", "head_loss", "grad_sync",
                "optimizer", "guard"]
SERVING_PROGRAMS = {"decode": "decode_step", "prefill": "prefill_chunk",
                    "verify": "verify_step"}


@pytest.mark.parametrize("program,scope", [
    ("train", s) for s in TRAIN_SCOPES
] + [(p, s) for p in SERVING_PROGRAMS for s in SERVING_SCOPES
     # verify_step samples at every window position under no scope of its own
     if (p, s) != ("verify", "sample")])
def test_lowered_program_holds_the_scope(lowered_scopes, program, scope):
    assert scope in lowered_scopes[program]


def test_jax_writes_forward_and_backward_into_the_scope_path(lowered_scopes):
    """Forward, backward and recomputed forward are told apart by JAX's own
    markers, not by a scope of ours."""
    assert {"jvp", "transpose"} <= lowered_scopes["train"]


def tensor_types(shape) -> tuple:
    """The MLIR types of ``shape``, as it is and with a leading 1."""
    dims = "x".join(str(d) for d in shape)
    return (f"tensor<{dims}x", f"tensor<1x{dims}x")


@pytest.mark.parametrize("program", sorted(SERVING_PROGRAMS))
def test_the_stacked_pool_is_the_layer_scans_carry_and_is_never_sliced(
        lowered, program):
    """``_forward_paged`` carries the whole stacked pool through the
    ``layers`` scan and ``_block_paged`` scatters into it and gathers from
    it by (layer, block, offset): no operation takes one layer's pool out
    of the stacked pool or puts one back (a ``dynamic_slice`` /
    ``dynamic_update_slice`` pair on the pool is two copies of a layer's
    pool for every layer of every run)."""
    text = lowered[program].as_text()
    names = set(loc_names(lowered[program]))
    pool_shape = init_pool(TINY, PAGED)["k"].shape
    stacked = tensor_types(pool_shape)[0]
    # This reads JAX's printed MLIR by type. TINY and PAGED are chosen so
    # that no other tensor of these programs (the gathered cache
    # [S, Tmax, H, Dh] = [2, 32, 2, 8], the attention's operands, a
    # layer's weights) has the shape of a layer's pool
    # [num_blocks, block_len, H, Dh] = [33, 4, 2, 8]: change
    # them and a match below may be another tensor's. The positive match
    # on ``stacked`` keeps the negative ones from passing on a printing
    # that spells types another way.
    # the scan's loop carries K and V of the stacked pool, and the block,
    # called from its body, takes and returns them
    whiles = [ln for ln in text.splitlines() if "stablehlo.while" in ln
              and ln.split(") :")[-1].count(stacked) == 2]
    assert len(whiles) == 1
    body = f"jit({SERVING_PROGRAMS[program]})/layers/while/body/"
    assert body + "closed_call" in names
    # the block's operations keep the paths the trace's readers match
    # (``closed_call/paged.gather/gather`` in a device trace)
    assert {"paged.write/scatter", "paged.gather/gather"} <= names
    assert any(n.startswith("paged.attend/") for n in names)
    # no tensor anywhere is one layer's pool, with or without a leading 1
    for per_layer in tensor_types(pool_shape[1:]):
        assert per_layer not in text
    # and nothing but the two scatters makes a stacked pool (the scan's
    # own ``dynamic_slice``s take a layer's weights and its number)
    for ln in text.splitlines():
        if re.search(r"stablehlo\.(dynamic_slice|dynamic_update_slice|slice"
                     r"|concatenate|copy)\b", ln):
            assert stacked not in ln, ln


def test_compiled_decode_step_aliases_the_pool_arguments_to_its_results(
        lowered):
    """The donated pool comes back in the buffers it came in: with the
    pool as the loop's carry, argument, loop state and result can be one
    buffer (CPU compile; the chip's is read in PERF.md)."""
    head = lowered["decode"].compile().as_text().split("\n", 1)[0]
    alias = re.search(r"input_output_alias=\{(.*?\)) \}", head).group(1)
    # result 0 is argument 0 (pool K), result 1 is argument 1 (pool V)
    assert re.findall(r"\{(\d+)\}: \((\d+), \{\}", alias) == [
        ("0", "0"), ("1", "1")]


# ------------------------- section D: the set-up path is the parent's

OUR_SCOPES = set(SERVING_SCOPES) | set(TRAIN_SCOPES)


@pytest.fixture(scope="module")
def lowered_without_our_scopes(devices):
    """The three programs built and lowered with ``jax.named_scope`` doing
    nothing for every name of ours. A ``named_scope`` is an
    ``ExtendNameStackContextManager`` (also where it decorates a function,
    as in models/llama.py), so that is where it is switched off."""
    from jax._src import source_info_util as siu

    cls = siu.ExtendNameStackContextManager
    enter = cls.__enter__

    def enter_unless_ours(self):
        if self.name in OUR_SCOPES:
            self.prev = siu._source_info_context.context
            return None
        return enter(self)

    cls.__enter__ = enter_unless_ours
    try:
        return lower_programs(devices)
    finally:
        cls.__enter__ = enter


@pytest.mark.parametrize("program", ["train", *sorted(SERVING_PROGRAMS)])
def test_named_scopes_add_remove_and_reorder_no_operation(
        lowered, lowered_without_our_scopes, program):
    """The lowered text (locations left out) is the same with the scopes as
    they stand and with ``jax.named_scope`` patched to do nothing: a scope
    is a name in an operation's location and nothing else."""
    plain = lowered_without_our_scopes[program]
    assert not OUR_SCOPES & scope_parts(plain)      # the patch took
    assert OUR_SCOPES & scope_parts(lowered[program])
    assert lowered[program].as_text() == plain.as_text()


FRESH = """
import sys
import jax
NAMES = ("profile", "tensorboard", "tensorflow", "xprof")
def held():
    return {m for m in sys.modules if any(n in m for n in NAMES)}
with_jax = held()
import numpy as np
from ddl25spring_tpu.config import LlamaConfig, TrainConfig
from ddl25spring_tpu.telemetry import introspect
KEY = "jax_compilation_cache_include_metadata_in_key"
assert not getattr(jax.config, KEY)     # importing the package flips nothing
seen = []
call = introspect.CompileWatch.__call__
def watched(self, *a, **k):
    seen.append((self.name, len(self.compiles), getattr(jax.config, KEY)))
    return call(self, *a, **k)
introspect.CompileWatch.__call__ = watched
TINY = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                   ctx_size=64)
if sys.argv[1] == "engine":
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.serving.engine import Engine
    from ddl25spring_tpu.serving.kvcache import PagedKVConfig
    from ddl25spring_tpu.serving.scheduler import Request, Scheduler
    engine = Engine(llama.init_llama(jax.random.key(0), TINY), TINY,
                    PagedKVConfig(num_blocks=17, block_len=4,
                                  max_blocks_per_seq=8), 2, prefill_chunk=8)
    assert getattr(jax.config, KEY) and not seen
    sched = Scheduler(engine)
    sched.submit(Request(rid="r", max_new=3, prompt=(5, 6, 7)), now=0.0)
    while sched.outstanding:
        sched.tick()
    assert sched.spans.count("serve.tick") and engine.spans.count(
        "engine.decode.dispatch")
else:
    from ddl25spring_tpu.parallel import make_mesh
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train.llm import train_llm_dp
    train_llm_dp(model_cfg=TINY, train_cfg=TrainConfig(
        batch_size=2, seq_len=16, iters=2, lr=3e-3, data=1),
        mesh=make_mesh({"data": 1}), tokenizer=ByteTokenizer(), log_every=0)
first = [flag for _, compiled, flag in seen if compiled == 0]
assert first and all(first), seen
assert held() == with_jax, sorted(held() - with_jax)
print("ok", len(seen))
"""


@pytest.mark.parametrize("what", ["engine", "train"])
def test_fresh_process_imports_no_profiler_and_keys_the_cache_by_names(
        what):
    """In a fresh interpreter with no profiler started, building and
    running an ``Engine`` (a train step) imports no module of a profiler
    beyond what ``import jax`` brings, and the compile cache's key holds
    the operations' metadata before the first watched compile
    (telemetry/introspect.py ``CompileWatch``)."""
    proc = subprocess.run(
        [sys.executable, "-c", FRESH, what],
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "ok" in proc.stdout, proc.stderr[-3000:]


@pytest.mark.parametrize("dh_major", [False, True])
def test_flash_kernels_carry_stable_names(dh_major):
    from ddl25spring_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 16, 2, 8), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=8, block_k=8,
                               dh_major=dh_major, interpret=True).sum()

    fwd = str(jax.make_jaxpr(loss)(q, q, q))
    both = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q))
    assert "flash_attention_fwd" in fwd
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert name in both
        # what `flash_attn_roofline.train` matches, and may not be edited
        assert re.match("^flash_attention", name)


# ------------------------------------------------------- telemetry/trace.py

def test_trace_module_imports_and_makes_spans_without_jax(tmp_path):
    code = """
import json, sys
from ddl25spring_tpu.telemetry.events import EventLog
from ddl25spring_tpu.telemetry.trace import Spans, Tracer, annotate
log = EventLog(sys.argv[1], run_id="r")
tracer = Tracer(log, phases=Spans())
with tracer.span("outer", trace="t", annotation="x.outer",
                 counters={"n": 1}):
    with tracer.phases("inner", n=3) as a:
        a.set_metadata(m=4)
with annotate("bare", k=1):
    pass
log.close()
assert tracer.phases.count("inner") == tracer.phases.count("outer") == 1
assert "jax" not in sys.modules, "telemetry.trace pulled jax in"
print("ok")
"""
    proc = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "e.jsonl")],
        env={**os.environ, "PYTHONPATH": REPO}, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == 0 and "ok" in proc.stdout, proc.stderr[-2000:]
    spans = [e for e in read_events(str(tmp_path / "e.jsonl"), strict=True)
             if e["type"] == "span"]
    assert [e["name"] for e in spans] == ["outer"]


def test_with_no_trace_live_a_span_emits_the_event_it_always_did(tmp_path):
    """``annotation=`` and ``counters=`` are the profiler's: neither reaches
    the JSONL event, which is the one the span emitted before."""
    ticks = iter(range(100, 10_000, 7))
    events = []
    for kwargs in ({}, {"annotation": "train.data", "counters": {"it": 3}}):
        path = str(tmp_path / f"e{len(events)}.jsonl")
        log = EventLog(path, run_id="r")
        tracer = Tracer(log, clock_ns=lambda: next(ticks))
        tracer._id = 9          # tracers number themselves process-wide
        with tracer.span("stage", trace="train", chunk=2, **kwargs):
            pass
        log.close()
        (e,) = [e for e in read_events(path, strict=True)
                if e["type"] == "span"]
        events.append({k: v for k, v in e.items() if k not in ("t", "seq")})
    plain, annotated = events
    assert plain["name"] == "stage" and plain["chunk"] == 2
    assert plain["span_id"] == "s9.1" and plain["dur_ns"] == 7
    assert {**annotated, "start_ns": 0} == {**plain, "start_ns": 0}
    assert "it" not in annotated and "annotation" not in annotated


def test_the_gate_is_gone_and_device_trace_only_starts_and_stops(
        tmp_path, monkeypatch):
    assert not hasattr(trace_mod, "_DEVICE_TRACE_DEPTH")
    assert not hasattr(trace_mod, "_profiling")
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    with trace_mod.device_trace("somewhere"):
        calls.append(("inside",))
    assert calls == [("start", "somewhere"), ("inside",), ("stop",)]


def test_held_open_spans_stay_out_of_the_profilers_trace(tmp_path):
    """``Tracer.start`` spans (the serving request spans) overlap instead
    of nesting; only ``with`` spans reach the profiler."""
    def run():
        tracer = Tracer(None)
        a = tracer.start("serve.held_a")
        b = tracer.start("serve.held_b")
        a.end()
        with tracer.span("serve.scoped"):
            pass
        b.end()

    _, events = traced(tmp_path, run)
    assert [e["name"] for e in events] == ["serve.scoped"]


# ------------------------------------------------------------------ training

@pytest.mark.parametrize("k", [1, 2])
def test_run_loop_totals_and_train_annotations_cover_the_same_intervals(
        tmp_path, devices, k):
    """`_run_loop`'s `Spans` totals (keys `data`, `dispatch`, `sink`,
    `checkpoint`) and the `train.*` annotations of the same phases: as
    many of each, and the same seconds. An annotation holds its span and,
    after the span's clock has stopped, the append of the span's own event
    to the JSONL stream (`Tracer._finish`): a write that takes 0.1 ms as a
    rule and 1 to 20 ms when the workers of a whole tier-1 run share the
    disk (read 4.8 ms on a 3.7 ms `sink` span here). So the seconds of
    those appends are measured and taken out of the comparison, and what
    is left, the cost of entering and leaving the annotation (0.01 to 0.25
    ms a phase in 24 runs on the CPU), is held to 2 ms a span."""
    from ddl25spring_tpu.train.llm import train_llm_dp

    iters = 6
    stream_name = {"data": "stage", "dispatch": "compute", "sink": "sink",
                   "checkpoint": "checkpoint"}
    append_s = dict.fromkeys(stream_name.values(), 0.0)

    def run():
        with Telemetry(str(tmp_path / "run"), step_every=2) as tel:
            emit = tel.events.emit

            def timed_emit(type, **fields):
                t0 = time.perf_counter()
                try:
                    return emit(type, **fields)
                finally:
                    if type == "span" and fields["name"] in append_s:
                        append_s[fields["name"]] += time.perf_counter() - t0

            tel.events.emit = timed_emit
            train_llm_dp(
                model_cfg=TINY,
                train_cfg=TrainConfig(batch_size=2, seq_len=16, iters=iters,
                                      lr=3e-3, data=2, steps_per_dispatch=k),
                mesh=make_mesh({"data": 2}, devices=devices[:2]),
                tokenizer=ByteTokenizer(), log_every=0, telemetry=tel,
                checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=4)
            return read_events(tel.events_path, strict=True)

    stream, events = traced(tmp_path / "trace", run)
    snap = next(e for e in stream if e["type"] == "run_end")["metrics"]
    for phase in ("data", "dispatch", "sink", "checkpoint"):
        mine = [e for e in events if e["name"] == "train." + phase]
        assert len(mine) == snap["counters"][f"phase/{phase}_count"] > 0
        held = sum(e["end"] - e["start"] for e in mine) / 1e9
        total = snap["gauges"][f"phase/{phase}_s"]
        assert total <= held + 1e-4 * len(mine)
        assert (held - total - append_s[stream_name[phase]]
                < 2e-3 * len(mine)), (phase, held, total, append_s)
    its = [e["counters"]["it"] for e in events
           if e["name"] == "train.dispatch"]
    assert its == list(range(0, iters, k))
    # the event stream's own spans keep their names: stage, compute, ...
    names = {e["name"] for e in stream if e["type"] == "span"}
    assert {"dispatch", "stage", "compute", "sink"} <= names
    assert not any(n.startswith("train.") for n in names)
