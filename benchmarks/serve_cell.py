"""A serving cell: the benchmark's own open loop over `Scheduler.submit` and
`Scheduler.tick`, on a real clock, in one thread.

Set-up makes the weights on the device from the seed in the served type,
builds the `Engine` and warms its two programs with one request. The window
then offers the traffic file's requests when they are due, ticks while
anything is outstanding, and stamps every `(request, token)` pair `tick()`
returns. Offering stops at `--seconds`; what is due by then is drained, or,
where the traffic file says `"at_close": "stop"` (a backlog), the loop ends
at the close with the queue as it stands.

What the benchmark reads of the program besides `Scheduler.submit`, `.tick`,
`.outstanding`, `.records` (`RequestRecord.admit_t`, `.done_t`, `.tokens`,
`.queue_wait_s`, `.prompt_len`) and `.peak_in_flight`: `Engine.slots` (a
slot's `seq`, `prompt`, `prefill_off`, `phase`, `produced`), `Engine.pos`,
`Engine.decode_dispatches`, `Engine.watches()` and `Engine.step` (wrapped by
the planted fault of the tests). PERF.md lists them for the tracing issue.

`correct`: once the window has closed, `memory_peak_bytes` is read and the
engine is freed, a sample of the finished requests, drawn from the seed and
with the longest in it, goes through the plain reference once each (prompt
and served tokens, one full forward pass); the number compared is the widest
gap by which a served token's logit lies below the reference's best.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Dict, List, Optional

import numpy as np

import harness
import reference as ref
import traffic_gen
from harness import Check


def model_config(cell: harness.Cell, dims: ref.Dims):
    from ddl25spring_tpu.config import LlamaConfig

    return LlamaConfig(
        vocab_size=dims.vocab, dmodel=dims.d, num_heads=dims.heads,
        n_layers=dims.layers, ffn_hidden=dims.ffn, norm_eps=dims.eps,
        rope_theta=dims.theta,
        ctx_size=cell.traffic["block_len"] * cell.traffic["max_blocks_per_seq"],
        dtype=cell.config["compute_dtype"],
        param_dtype=cell.config["weights_dtype"]["serve"])


class TickLedger:
    """What each tick made the engine do, read from the engine's public slot
    state after the tick: tokens processed, the context they attended to,
    tokens sampled, and for a decode step its active slots and their live
    cache positions."""

    def __init__(self, engine):
        self.engine = engine
        self._off: Dict[int, int] = {}      # admission seq -> prefill_off
        self._dispatches = engine.decode_dispatches
        self.rows: List[dict] = []          # one a tick: t, work done
        self.decode_steps: List[dict] = []  # one a decode dispatch

    def after_tick(self, tick: int, t: float, emitted, records) -> None:
        eng = self.engine
        tokens = context = sampled = 0
        for slot in eng.slots:
            if slot is None:
                continue
            old = self._off.get(slot.seq, 0)
            n = slot.prefill_off - old
            if n > 0:
                tokens += n
                context += n * old + n * (n + 1) // 2
                self._off[slot.seq] = slot.prefill_off
                if slot.prefill_off >= len(slot.prompt):
                    sampled += 1
        if eng.decode_dispatches != self._dispatches:
            self._dispatches = eng.decode_dispatches
            live = [int(eng.pos[s]) for s, slot in enumerate(eng.slots)
                    if slot is not None and slot.phase == "decode"
                    and slot.produced > 1]
            # requests that retired on this step's token
            for rid in {rid for rid, _ in emitted}:
                rec = records[rid]
                if rec.done_t is not None and len(rec.tokens) > 1:
                    live.append(rec.prompt_len + len(rec.tokens) - 1)
            self.decode_steps.append({"tick": tick, "t": t, "active": len(live),
                                      "live_positions": sum(live)})
            tokens += len(live)
            context += sum(live)
            sampled += len(live)
        self.rows.append({"t": t, "tokens_processed": tokens,
                          "context_sum": context, "sampled": sampled})

    def totals(self, t0: float, t1: float) -> dict:
        rows = [r for r in self.rows if t0 <= r["t"] <= t1]
        return {k: sum(r[k] for r in rows)
                for k in ("tokens_processed", "context_sum", "sampled")}


def _alter_tokens(engine, every: int):
    """Fault for the tests: every `every`-th token the engine emits is
    altered where it is produced (the event), not where it is fed back."""
    plain_step = engine.step
    count = {"n": 0}

    def step():
        out = []
        for ev in plain_step():
            count["n"] += 1
            if count["n"] % every == 0:
                ev = ev._replace(token=(ev.token + 1) % engine.cfg.vocab_size)
            out.append(ev)
        return out

    engine.step = step


def served_gaps(seed32: int, dims: ref.Dims, samples: List[tuple],
                pad_to: int, control: bool = False) -> List[float]:
    """For each (prompt, served tokens): the widest gap, over the served
    positions, by which the chosen token's logit lies below the reference's
    best. `control=False`: the chosen tokens are the served ones.
    `control=True`: they are what the fp8 forward pass puts first at each of
    the same positions."""
    import jax.numpy as jnp

    weights = ref.make_weights(seed32, dims, "bfloat16")
    gap_fn = ref.make_gap_below_best(dims)
    first_fn = ref.make_first_choice(dims, ref.CONTROL) if control else None
    out = []
    for prompt, served in samples:
        toks = np.zeros(pad_to, np.int32)
        n = len(prompt) + len(served)
        toks[:len(prompt)] = prompt
        toks[len(prompt):n] = served
        toks_j = jnp.asarray(toks)
        chosen = first_fn(weights, toks_j) if control else toks_j[1:]
        gaps = np.asarray(gap_fn(weights, toks_j, chosen))
        out.append(float(gaps[len(prompt) - 1: n - 1].max()))
    return out


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, *, fault: Optional[str] = None,
        reference_too: bool = True, control_too: bool = False):
    import jax

    from ddl25spring_tpu.serving.engine import Engine
    from ddl25spring_tpu.serving.kvcache import PagedKVConfig
    from ddl25spring_tpu.serving.scheduler import Request, Scheduler

    dev = harness.device_info(cell.chips)
    cache_dir = harness.enable_compile_cache()
    tr = cell.traffic
    dims = ref.Dims.from_config(cell.config)
    mcfg = model_config(cell, dims)
    seed32 = seed % (2 ** 32)
    paged = PagedKVConfig(num_blocks=tr["num_blocks"],
                          block_len=tr["block_len"],
                          max_blocks_per_seq=tr["max_blocks_per_seq"],
                          kv_dtype=cell.config["cache_dtype"])
    # set-up by phase, for the notes: reaching the chip, the traffic, the
    # weights, the engine (its fused copies and the pool), the warm-up
    phases = [("device", harness.now())]
    requests = traffic_gen.offered(tr, seed, seconds, dims.vocab)
    phases.append(("traffic", harness.now()))
    params = jax.block_until_ready(
        ref.make_weights(seed32, dims, mcfg.param_dtype))
    phases.append(("weights", harness.now()))
    engine = Engine(params, mcfg, paged, tr["num_slots"],
                    prefill_chunk=tr["prefill_chunk"])
    # one device runs its programs in order: a trifle dispatched now is
    # ready when the engine's copies and its pool are
    (jax.numpy.zeros(()) + 1).block_until_ready()
    phases.append(("engine", harness.now()))
    del params
    if fault == "token_altered":
        _alter_tokens(engine, every=7)
    elif fault is not None:
        raise harness.BenchError(f"unknown fault {fault!r}")
    clock = time.perf_counter

    def as_request(o: traffic_gen.Offered) -> Request:
        return Request(rid=o.rid, prompt=o.prompt, max_new=o.max_new,
                       temperature=float(tr["temperature"]), seed=seed32)

    # ---- warm-up: one request of two prefill chunks and a few decode
    # steps compiles both programs and walks every host path once.
    warm = Scheduler(engine, clock=clock)
    chunk = tr["prefill_chunk"]
    warm_len = min(chunk + 1, paged.max_seq_len - 4)
    warm.submit(Request(rid="warm", prompt=tuple(range(1, warm_len + 1)),
                        max_new=4, temperature=float(tr["temperature"])))
    while warm.outstanding:
        warm.tick()
    phases.append(("warm", harness.now()))
    compiles_before = sum(len(w.compiles) for w in engine.watches())
    sched = Scheduler(engine, clock=clock)
    ledger = TickLedger(engine)
    spans = {"wait": [], "submit": [], "tick": []}
    stamps: Dict[str, List[float]] = {o.rid: [] for o in requests}
    late: Dict[str, float] = {}
    # A traced run is the same window with its last `trace_seconds` traced:
    # stopping the profiler stalls this thread for seconds, and at the close
    # that disturbs nothing the per-layer metrics read. The trace's metrics
    # are of the traced part, the per-request ones of the whole window.
    length = min(float(tr.get("trace_seconds", seconds)), seconds)
    trace_from = seconds - length
    stop_at_close = tr.get("at_close", "drain") == "stop"
    trace_dir = os.path.join(harness.ROOT, ".bench_out", cell.name, "trace")
    ann = jax.profiler.TraceAnnotation
    t0 = clock()
    i = 0
    tracing = "before" if trace else "off"
    part = [t0, t0 + seconds]          # the part the trace covers
    drain_until = t0 + seconds + float(tr["drain_limit_s"])
    n_tick = 0
    while True:
        t = clock() - t0
        if t >= seconds and (stop_at_close or tracing == "on"):
            if tracing == "on":
                part[1] = clock()
                jax.profiler.stop_trace()
                tracing = "done"
            if stop_at_close:
                break
        if stop_at_close and i == len(requests) and not sched.outstanding:
            break                   # the backlog ran dry: not a sound run
        if i < len(requests) and requests[i].due <= min(t, seconds):
            a = clock()
            with ann("bench.submit"):
                while i < len(requests) and requests[i].due <= min(
                        t, seconds):
                    o = requests[i]
                    sched.submit(as_request(o), now=clock())
                    late[o.rid] = (clock() - t0) - o.due
                    i += 1
            spans["submit"].append((a, clock()))
            continue
        if tracing == "before" and t >= trace_from:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            part[0], tracing = clock(), "on"
        if sched.outstanding:
            a = clock()
            # numbered, so that a reader pairs a traced run of a program
            # with the ledger's row of the tick that dispatched it
            with ann(f"bench.tick#{n_tick}"):
                emitted = sched.tick()
            b = clock()
            spans["tick"].append((a, b))
            for rid, _ in emitted:
                stamps[rid].append(b)
            ledger.after_tick(n_tick, b, emitted, sched.records)
            n_tick += 1
            if b > drain_until:
                break
            continue
        if i < len(requests) and requests[i].due <= seconds:
            a = clock()
            with ann("bench.wait"):
                time.sleep(max(0.0, min(requests[i].due - (a - t0), 0.05)))
            spans["wait"].append((a, clock()))
            continue
        break
    if tracing == "on":
        part[1] = clock()
        jax.profiler.stop_trace()
        tracing = "done"
    t_end = clock()
    t1 = t0 + seconds
    ran_dry = stop_at_close and t_end < t1
    if tracing == "before" and not ran_dry:
        raise harness.BenchError("the run ended before its traced part")
    p0, p1 = part
    peak = harness.memory_peak_bytes(dev["devices"])
    compiles_in_window = (sum(len(w.compiles) for w in engine.watches())
                          - compiles_before)
    due = requests[:i]
    recs = sched.records
    if stop_at_close:
        # a backlog is cut at the close: what is queued or in flight then
        # is neither attempted nor failed
        due = [o for o in due if recs[o.rid].done_t is not None]
    never = [o.rid for o in due if not stamps[o.rid]]
    unfinished = [o.rid for o in due if recs[o.rid].done_t is None]
    short = [o.rid for o in due if recs[o.rid].done_t is not None
             and len(recs[o.rid].tokens) != o.max_new]
    ttft = [stamps[o.rid][0] - (t0 + o.due) for o in due if stamps[o.rid]]
    gaps = [b - a for o in requests[:i]
            for a, b in zip(stamps[o.rid], stamps[o.rid][1:])]
    in_window = sum(1 for o in requests[:i] for s in stamps[o.rid] if s <= t1)
    records = [{"rid": o.rid, "late_s": late[o.rid],
                "ttft_s": (stamps[o.rid][0] - (t0 + o.due)
                           if stamps[o.rid] else None),
                "queue_wait_s": recs[o.rid].queue_wait_s,
                "prompt_len": len(o.prompt), "max_new": o.max_new}
               for o in requests[:i]
               if stamps[o.rid] and stamps[o.rid][0] <= max(p1, t1)]
    n_out = sum(len(stamps[o.rid]) for o in requests[:i])
    waits = [r["queue_wait_s"] for r in records
             if r["queue_wait_s"] is not None]
    notes = [
        f"note cell={cell.name} seed={seed} offered={len(due)} "
        f"of={len(requests)} finished={len(due) - len(unfinished)} "
        f"output_tokens={n_out} in_window={in_window} ticks="
        f"{len(ledger.rows)} decode_steps={len(ledger.decode_steps)} "
        f"window_s={seconds:.3f} traced_s={p1 - p0 if trace else 0:.3f} "
        f"drained_s={t_end - t1:.3f} "
        f"setup_s={t0 - t_process:.3f} compile_cache={cache_dir}",
        f"note ttft_ms p50={1e3 * (harness.quantile(ttft, .5) or 0):.1f} "
        f"p90={1e3 * (harness.quantile(ttft, .9) or 0):.1f} "
        f"max={1e3 * max(ttft, default=0):.1f} n={len(ttft)}; itl_ms p50="
        f"{1e3 * (harness.quantile(gaps, .5) or 0):.2f} p95="
        f"{1e3 * (harness.quantile(gaps, .95) or 0):.2f} n={len(gaps)}; "
        f"late_ms p95={1e3 * (harness.quantile(late.values(), .95) or 0):.2f}"
        f"; queue_wait_ms p50={1e3 * (harness.quantile(waits, .5) or 0):.2f}"
        f"; peak_in_flight={sched.peak_in_flight} "
        f"queued_at_close={sum(1 for o in due if (recs[o.rid].admit_t or 1e99) > t1)}",
        "note setup by phase: " + " ".join(
            f"{name}={t - prev:.2f}" for (name, t), prev in zip(
                phases, [t_process] + [t for _, t in phases])),
        f"note memory_peak_bytes={peak} compiles_in_window="
        f"{compiles_in_window} compile_seconds="
        f"{[round(c.seconds, 2) for w in engine.watches() for c in w.compiles]}",
    ]
    # ---- the sample for `correct`, then free the engine before the
    # reference takes the memory.
    finished = [o for o in due if recs[o.rid].done_t is not None
                and o.rid not in short]
    samples: List[tuple] = []
    if finished:
        rng = np.random.default_rng([seed, 0x636865636B])
        longest = max(finished, key=lambda o: len(o.prompt) + o.max_new)
        k = min(int(tr["checked_requests"]), len(finished))
        rest = [o for o in finished if o is not longest]
        picks = [longest] + [rest[j] for j in
                             rng.permutation(len(rest))[: k - 1]]
        samples = [(np.asarray(o.prompt, np.int32),
                    np.asarray(recs[o.rid].tokens, np.int32)) for o in picks]
    decode_steps = ledger.decode_steps
    counters = ledger.totals(p0, p1)
    del engine, sched, warm, ledger
    checks = [Check("compiles_in_window", compiles_in_window, 0),
              Check("requests_wrong_length", len(short), 0)]
    if stop_at_close:
        # a backlog that the engine empties before the close times less
        # work than the window: a later benchmark PR makes it longer
        checks.append(Check("queue_empty_before_close", int(ran_dry), 0))
    else:
        checks += [Check("requests_without_first_token", len(never), 0),
                   Check("requests_unfinished", len(unfinished), 0)]
    got = {}
    if reference_too and samples:
        t_ref = harness.now()
        worst = served_gaps(seed32, dims, samples, paged.max_seq_len)
        got["served"] = worst
        checks.append(Check("served_logit_gap", max(worst),
                            cell.limits["served_logit_gap"]))
        if control_too:
            low = served_gaps(seed32, dims, samples, paged.max_seq_len,
                              control=True)
            got["control"] = low
            got["control_check"] = Check("served_logit_gap", max(low),
                                         cell.limits["served_logit_gap"])
        notes.append(
            f"note reference_s={harness.now() - t_ref:.2f} checked_requests="
            f"{len(samples)} checked_tokens={sum(len(s) for _, s in samples)}"
            f" gaps={[round(g, 4) for g in worst]}")
    elif reference_too:
        checks.append(Check("served_logit_gap", float("nan"),
                            cell.limits["served_logit_gap"]))
    metrics = {}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": cell.chips, "memory_peak_bytes": peak}
    result = {"correct": all(c.ok for c in checks), "attempted": len(due),
              "failed": len(set(never) | set(unfinished) | set(short)),
              "metrics": metrics, "device": device}
    if not trace:
        values = {
            "serve_tokens_per_s": lambda: in_window / seconds,
            "itl_p95_ms": lambda: 1e3 * harness.quantile(gaps, 0.95),
            "setup_s": lambda: t0 - t_process}
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]](),
                                      "unit": m["unit"]}
    elif tracing == "done":
        import xplane

        tracefile = xplane.find_xplane(trace_dir)
        t = xplane.Trace(tracefile)
        ctx = harness.RunContext(
            cell=cell, dims=dims, peaks=dev["peaks"], chips=cell.chips,
            window=(p0, p1), counters=counters, spans=spans,
            records=records, steps=decode_steps, trace=t)
        metrics.update(harness.read_per_layer(ctx))
        # busy and window on the trace's own clock, first to last event
        device["busy_s"] = t.busy_s()
        device["window_s"] = t.span_s()
        result["breakdown"] = {"device_ops": t.top_ops(10),
                               "idle_gaps": t.idle_gaps(10)}
        notes.append(f"note trace={os.path.relpath(tracefile, harness.ROOT)} "
                     f"programs={t.program_names()}")
    result["_got"] = got
    return result, checks, notes
