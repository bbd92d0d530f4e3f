"""The gradient exchange on the profiler's timeline: `_run_loop` puts the
step's collectives on every `train.dispatch` as the counters
`sync_payload_bytes`, `sync_wire_bytes` and `collectives`, taken from the
`comm` records that the step's own first trace leaves (docs/COMPONENTS.md).

Each case runs the trainer on a `data` mesh of 1 and of 4 host devices at a
tiny size, under the profiler, and holds the counters to what
`comm.measure_comm` reckons for the same step, called here only."""

import glob
import math
import os
import re

import jax
import pytest

from ddl25spring_tpu.config import LlamaConfig, TrainConfig
from ddl25spring_tpu.parallel import make_mesh
from ddl25spring_tpu.telemetry import Telemetry, comm
from ddl25spring_tpu.tokenizers import ByteTokenizer
from ddl25spring_tpu.train.llm import train_llm_dp

TINY = LlamaConfig(vocab_size=259, dmodel=16, num_heads=2, n_layers=2,
                   ctx_size=64)
COUNTERS = ("sync_payload_bytes", "sync_wire_bytes", "collectives")
ITERS = 4


class Capture:
    """A `fault_plan=` that hands the step on unchanged and keeps it, with
    the shapes and shardings of its first call's arguments."""

    def __bool__(self):
        return True

    def wrap_step(self, step_fn, stats=None, *, start=0):
        self.step_fn, self.args = step_fn, None

        def call(state, batch):
            if self.args is None:
                self.args = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding),
                    (state, batch))
            return step_fn(state, batch)
        return call


def dispatch_counters(log_dir, fn):
    """Run `fn` under the profiler; the counters of its `train.dispatch`
    annotations, in order of `it`."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [dict(e.stats) for e in line.events
                          if e.name == "train.dispatch"]
    return sorted(spans, key=lambda c: c["it"])


def train(n, devices, tmp_path, **kw):
    capture = Capture()
    spd = kw.pop("steps_per_dispatch", 1)
    spans = dispatch_counters(tmp_path / "trace", lambda: train_llm_dp(
        model_cfg=TINY,
        train_cfg=TrainConfig(batch_size=2, seq_len=16, iters=ITERS,
                              lr=3e-3, data=n, steps_per_dispatch=spd),
        mesh=make_mesh({"data": n}, devices=devices[:n]),
        tokenizer=ByteTokenizer(), log_every=0, fault_plan=capture, **kw))
    return capture, spans


def expected(n, params_bytes, steps=1):
    """One `pmean` of the fp32 gradient tree and one of the loss a step,
    each over the ring: 2 (n - 1) / n of the payload on each device's wire."""
    payload = steps * (params_bytes + 4)
    return {"sync_payload_bytes": payload,
            "sync_wire_bytes": round(2 * (n - 1) / n * payload),
            "collectives": 2 * steps}


def params_bytes():
    from ddl25spring_tpu.models import llama
    shapes = jax.eval_shape(lambda: llama.init_llama(jax.random.key(0),
                                                     TINY))
    return sum(4 * math.prod(x.shape) for x in jax.tree.leaves(shapes))


@pytest.fixture(scope="module", params=[1, 4], ids=["data1", "data4"])
def run(request, devices, tmp_path_factory):
    """One run a mesh, counting the traces of the loss: its Python body runs
    only while the step is traced."""
    from ddl25spring_tpu.models import llama

    n, forward_loss, traces = request.param, llama.forward_loss, []

    def counted(*a, **k):
        traces.append(1)
        return forward_loss(*a, **k)

    llama.forward_loss = counted
    try:
        capture, spans = train(n, devices, tmp_path_factory.mktemp(f"d{n}"))
    finally:
        llama.forward_loss = forward_loss
    return {"n": n, "step": capture.step_fn, "args": capture.args,
            "spans": spans, "compiles": len(capture.step_fn.compiles),
            "traces": len(traces)}


def test_every_dispatch_carries_the_exchange_measure_comm_reckons(run):
    """The first dispatch too: it traced the step and added the counters at
    its end."""
    spans = run["spans"]
    assert [s["it"] for s in spans] == list(range(ITERS))
    want = comm.dispatch_counters(
        comm.measure_comm(run["step"], *run["args"]))
    assert want == expected(run["n"], params_bytes())
    for s in spans:
        assert {k: s[k] for k in COUNTERS} == want, s


def test_the_step_is_traced_and_compiled_once(run):
    assert run["traces"] == 1 and run["compiles"] == 1
    assert run["spans"][-1]["sync_wire_bytes"] == (
        0 if run["n"] == 1 else 3 * run["spans"][-1]["sync_payload_bytes"]
        // 2)


def test_the_lowered_text_is_the_same_with_and_without_the_collector(run):
    jax.clear_caches()
    with comm.collecting() as records:
        collected = run["step"].lower(*run["args"]).as_text()
    assert records, "the lowering did not trace the step"
    jax.clear_caches()
    assert run["step"].lower(*run["args"]).as_text() == collected


def test_every_all_reduce_of_the_step_stands_under_grad_sync(run):
    text = run["step"].lower(*run["args"]).compile().as_text()
    reduces = [line for line in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", line)]
    if run["n"] > 1:
        assert reduces
    for line in reduces:
        assert re.search(r'op_name="[^"]*/grad_sync/', line), line


@pytest.mark.parametrize("n", [1, 4])
def test_with_telemetry_the_counters_come_from_the_manifests_trace(
        devices, tmp_path, n):
    """The manifest's `measure_comm` traces the step before the loop, so
    the first call traces nothing: the loop takes that profile's records."""
    with Telemetry(str(tmp_path / "run"), step_every=2) as tel:
        _, spans = train(n, devices, tmp_path, telemetry=tel)
    want = expected(n, params_bytes())
    assert [{k: s[k] for k in COUNTERS} for s in spans] == [want] * ITERS


def test_a_chunked_dispatch_carries_the_exchange_of_its_steps(
        devices, tmp_path):
    capture, spans = train(4, devices, tmp_path, steps_per_dispatch=2)
    assert [s["it"] for s in spans] == [0, 2]
    want = comm.dispatch_counters(
        comm.measure_comm(capture.step_fn, *capture.args))
    assert want == expected(4, params_bytes(), steps=2)
    assert [{k: s[k] for k in COUNTERS} for s in spans] == [want] * 2
