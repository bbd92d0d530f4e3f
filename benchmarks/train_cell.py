"""A training cell: one call of the trainer's entry, timed from outside.

`train_llm_dp` builds the weights, the optimizer state and the compiled step
itself and hands nothing back but a report, so the benchmark reaches the one
step-with-state it builds through the three hooks the entry already has:

- `tokenizer=`: token ids over the whole vocabulary, from the seed;
- `fault_plan=`: an object whose `wrap_step(step_fn)` the trainer calls on
  the step it built. The benchmark's wrapper calls the step unchanged, puts a
  `bench.dispatch` annotation round it, and on the first steps reads, on the
  device, what `correct` compares;
- `loss_sink=`: called from `_run_loop` after a host sync on the losses. The
  benchmark's clock is stamped there. The window opens at the stamp after
  the warm-up steps and closes at the first stamp `--seconds` later; the
  run loop is then ended the way a scheduler ends it, by SIGTERM, which
  `_run_loop` turns into a clean return at the next step boundary.

So set-up and window drive one object through one call, by `_run_loop`'s own
data, shard, dispatch and sink.
"""

from __future__ import annotations

import os
import shutil
import signal
import statistics
from typing import List, Optional

import numpy as np

import harness
import reference as ref
from harness import Check

BIG_ITERS = 10 ** 6


class SeedTokenizer:
    """The trainer's tokenizer interface over a seeded stream of ids drawn
    uniformly from the whole vocabulary; the text it is given is ignored.
    No end-of-document id: rows are `seq_len` ids of the stream."""

    def __init__(self, vocab: int, seed: int, chunk: int, spans: dict):
        self.vocab_size = vocab
        self.eos_id = -1
        self._rng = np.random.default_rng([seed, 0x7261696E])
        self._chunk = chunk
        self._spans = spans

    def encode(self, text: str, *, add_bos: bool = False) -> list:
        t0 = harness.now()
        ids = self._rng.integers(0, self.vocab_size, self._chunk).tolist()
        self._spans["data"].append((t0, harness.now()))
        return ids


class StepProbe:
    """The `fault_plan=` object: wraps the trainer's own step. `fault` is
    for the tests and the control only (`state_unchanged`, `half_batch`)."""

    def __init__(self, seed: int, dims: ref.Dims, checked: int, spans: dict,
                 fault: Optional[str] = None):
        self.seed, self.dims, self.checked = seed, dims, checked
        self.spans, self.fault = spans, fault
        self.calls = 0
        self._between = None
        self.step_fn = None
        self.batches: List[np.ndarray] = []
        self.grad_norms = None
        self.param_change = None

    def __bool__(self) -> bool:
        return True

    def wrap_step(self, step_fn, stats=None, *, start: int = 0):
        self.step_fn = step_fn
        return self

    def __call__(self, state, batch):
        import jax

        i = self.calls
        self.calls += 1
        if self._between is not None:
            self._between.__exit__(None, None, None)
        if i < self.checked:
            self.batches.append(np.asarray(batch).reshape(
                -1, batch.shape[-1]))
        if self.fault == "half_batch":
            batch = batch[: batch.shape[0] // 2]
        kept = None
        if self.fault == "state_unchanged":
            kept = jax.tree.map(lambda x: x.copy(), state)
        t0 = harness.now()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            new_state, out = self.step_fn(state, batch)
        self.spans["dispatch"].append((t0, harness.now()))
        if kept is not None:
            new_state = kept
        if i == 0:
            mu = next(n.mu for n in jax.tree.leaves(
                new_state.opt_state, is_leaf=lambda n: hasattr(n, "mu"))
                if hasattr(n, "mu"))
            self.grad_norms = ref.leaf_norms(mu)
        if i == self.checked - 1:
            # The step has to be over before the seed's weights are made
            # again beside its state: set-up, so the wait costs no rate.
            jax.block_until_ready(out)
            start = ref.make_weights(self.seed, self.dims, "float32")
            self.param_change = ref.leaf_diff_norms(new_state.params, start)
            del start
        # what the host does until it next dispatches: sink, data, shard
        self._between = jax.profiler.TraceAnnotation("bench.between_steps")
        self._between.__enter__()
        return new_state, out


def compare_training(got: dict, want: dict, limits: dict) -> List[Check]:
    """The numbers of a training cell's `correct`. `got` and `want` each
    hold `losses`, `grad_norms` and `param_change` by leaf. Gaps are gaps of
    norms, by the worst leaf, against the reference's norm of that leaf or
    of the median leaf, whichever is larger. Leaves whose reference gradient
    is under a thousandth of the median leaf's are left out of the change:
    under Adam they move by round-off alone. The largest relative gap of the
    steps' losses is returned beside the checks, and is compared only where
    the cell's limits file gives it a limit: on the v5e it separates nothing
    (PERF.md, section 2)."""
    loss_gap = max(abs(g - w) / abs(w)
                   for g, w in zip(got["losses"], want["losses"]))
    g_ref = want["grad_norms"]
    g_med = statistics.median(g_ref.values())
    grad_gaps = {k: abs(got["grad_norms"][k] - g_ref[k]) / max(g_ref[k], g_med)
                 for k in g_ref}
    c_ref = want["param_change"]
    moved = [k for k in c_ref if g_ref[k] >= 1e-3 * g_med]
    c_med = statistics.median(c_ref[k] for k in moved)
    change_gaps = {k: abs(got["param_change"][k] - c_ref[k])
                   / max(c_ref[k], c_med) for k in moved}
    worst_g = max(grad_gaps, key=grad_gaps.get)
    worst_c = max(change_gaps, key=change_gaps.get)
    checks = [Check("grad_norm_gap", grad_gaps[worst_g],
                    limits["grad_norm_gap"], worst_g),
              Check("param_change_gap", change_gaps[worst_c],
                    limits["param_change_gap"], worst_c)]
    if "loss_gap" in limits:
        checks.insert(0, Check("loss_gap", loss_gap, limits["loss_gap"]))
    return checks, loss_gap


def model_config(cell: harness.Cell, dims: ref.Dims):
    from ddl25spring_tpu.config import LlamaConfig

    tr = cell.traffic
    return LlamaConfig(
        vocab_size=dims.vocab, dmodel=dims.d, num_heads=dims.heads,
        n_layers=dims.layers, ctx_size=tr["seq_len"], ffn_hidden=dims.ffn,
        norm_eps=dims.eps, rope_theta=dims.theta,
        dtype=cell.config["compute_dtype"],
        param_dtype=cell.config["weights_dtype"]["train"],
        attention_impl=tr["attention"], remat=bool(tr["remat"]))


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, *, fault: Optional[str] = None,
        reference_too: bool = True):
    import jax

    from ddl25spring_tpu.config import TrainConfig
    from ddl25spring_tpu.train.llm import train_llm_dp

    dev = harness.device_info(cell.chips)
    cache_dir = harness.enable_compile_cache()
    tr = cell.traffic
    dims = ref.Dims.from_config(cell.config)
    mcfg = model_config(cell, dims)
    # the same from the seed, whatever its size: jax keys take 32 bits
    seed32 = seed % (2 ** 32)
    tcfg = TrainConfig(batch_size=tr["batch_per_chip"], seq_len=tr["seq_len"],
                       lr=tr["lr"], iters=BIG_ITERS, seed=seed32,
                       data=cell.chips, optimizer=tr["optimizer"])
    spans = {"data": [], "dispatch": []}
    tok = SeedTokenizer(dims.vocab, seed, tr["seq_len"], spans)
    probe = StepProbe(seed32, dims, tr["checked_steps"], spans, fault)
    sink_every, warm = int(tr["sink_every"]), int(tr["warm_steps"])
    if warm % sink_every or warm < tr["checked_steps"]:
        raise harness.BenchError("warm_steps must be a multiple of "
                                 "sink_every and cover the checked steps")
    tokens_per_step = cell.chips * tr["batch_per_chip"] * tr["seq_len"]
    length = float(tr.get("trace_seconds", seconds)) if trace else seconds
    trace_dir = os.path.join(harness.ROOT, ".bench_out", cell.name, "trace")
    state = {"open": None, "closed": None, "compiles_at_open": 0}
    logs: List[str] = []

    def sink(it: int, loss: float) -> None:
        t = harness.now()
        if it < warm or state["closed"] is not None:
            return
        if state["open"] is None:
            state["compiles_at_open"] = len(probe.step_fn.compiles)
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                t = harness.now()
            state["open"] = (it, t)
            return
        if t - state["open"][1] >= length:
            if trace:
                jax.profiler.stop_trace()
            state["closed"] = (it, t)
            os.kill(os.getpid(), signal.SIGTERM)

    report = train_llm_dp(
        mcfg, tcfg, tokenizer=tok, aggregation=tr["aggregation"],
        log_every=0, log_fn=logs.append, warmup_steps_excluded=warm,
        loss_sink=sink, sink_every=sink_every, fault_plan=probe)
    if state["closed"] is None:
        raise harness.BenchError(f"the window never closed: {logs}")
    (it0, t0), (it1, t1) = state["open"], state["closed"]
    window_s = t1 - t0
    steps = it1 - it0
    peak = harness.memory_peak_bytes(dev["devices"])
    compiles_in_window = (len(probe.step_fn.compiles)
                          - state["compiles_at_open"])
    losses = report.losses
    notes = [
        f"note cell={cell.name} seed={seed} steps_in_window={steps} "
        f"window_s={window_s:.4f} setup_s={t0 - t_process:.3f} "
        f"compile_cache={cache_dir}",
        f"note report.tokens_per_sec={report.tokens_per_sec:.2f} "
        f"(the trainer's own, over its whole call; not the metric) "
        f"first_losses={[round(x, 5) for x in losses[:4]]} "
        f"last_loss={losses[-1]:.5f} preempted={report.preempted}",
        f"note memory_peak_bytes={peak} compiles_in_window="
        f"{compiles_in_window} compile_seconds="
        f"{[round(c.seconds, 2) for c in probe.step_fn.compiles]}",
    ]
    # ---- what the timed path produced on its first steps, then the
    # reference over the same rows, once the trainer's state is freed.
    got = {"losses": [float(x) for x in losses[: tr["checked_steps"]]],
           "grad_norms": {k: float(v) / (1.0 - ref.ADAM_B1)
                          for k, v in probe.grad_norms.items()},
           "param_change": {k: float(v)
                            for k, v in probe.param_change.items()}}
    rows = np.concatenate(probe.batches)
    duplicate_rows = len(rows) - len({r.tobytes() for r in rows})
    nonfinite = sum(1 for x in losses if not np.isfinite(x))
    checks = [Check("compiles_in_window", compiles_in_window, 0),
              Check("duplicate_rows", duplicate_rows, 0),
              Check("nonfinite_losses", nonfinite, 0)]
    if reference_too:
        t_ref = harness.now()
        want = ref.train_three(seed32, dims, probe.batches, tr["lr"])
        compared, loss_gap = compare_training(got, want, cell.limits)
        checks += compared
        notes.append(f"note reference_s={harness.now() - t_ref:.2f} "
                     f"reference_losses={want['losses']} loss_gap="
                     f"{loss_gap!r} (printed, not compared) worst_leaves="
                     f"{[c.where for c in compared]}")
    metrics = {}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": cell.chips, "memory_peak_bytes": peak}
    result = {"correct": all(c.ok for c in checks), "attempted": steps,
              "failed": nonfinite, "metrics": metrics, "device": device}
    if not trace:
        values = {"train_tokens_per_s": steps * tokens_per_step / window_s
                  / cell.chips, "setup_s": t0 - t_process}
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        import xplane

        tracefile = xplane.find_xplane(trace_dir)
        t = xplane.Trace(tracefile)
        ctx = harness.RunContext(
            cell=cell, dims=dims, peaks=dev["peaks"], chips=cell.chips,
            window=(t0, t1),
            counters={"tokens": steps * tokens_per_step, "steps": steps},
            spans=spans, records=[], steps=[], trace=t)
        metrics.update(harness.read_per_layer(ctx))
        # busy and window on the trace's own clock, first to last event
        device["busy_s"] = t.busy_s()
        device["window_s"] = t.span_s()
        result["breakdown"] = {"device_ops": t.top_ops(10),
                               "idle_gaps": t.idle_gaps(10)}
        notes.append(f"note trace={os.path.relpath(tracefile, harness.ROOT)} "
                     f"programs={t.program_names()}")
    return result, checks, notes
