"""Render a telemetry run report from a JSONL event stream.

The read side of the telemetry layer (ddl25spring_tpu/telemetry): given a
run directory (or an events.jsonl path directly), print a human report —
manifest, per-collective comm volume, step-time percentiles, phase
breakdown, fault counters, FL round summary, heartbeat status. Pure
stdlib + the telemetry read helpers; never imports jax, so it runs
instantly next to (or instead of) a live training process.

Example:
    python -m experiments.hw1b_llm --cpu --quick --telemetry-dir /tmp/obs
    python -m experiments.obs_report /tmp/obs/dp1
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

# Submodule imports keep this report jax-free (the package __init__ is
# also safe — its comm.py re-exports are lazy — but importing exactly what
# is used makes the no-jax contract explicit).
from ddl25spring_tpu.telemetry.events import iter_runs, read_events
from ddl25spring_tpu.telemetry.heartbeat import read_heartbeat
from ddl25spring_tpu.telemetry.introspect import attainment
from ddl25spring_tpu.telemetry.registry import percentile
from ddl25spring_tpu.telemetry.trace import trace_trees, tree_check


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:,.1f} {unit}" if unit != "B" else f"{n:,.0f} B"
        n /= 1024
    return f"{n:,.1f} GiB"


def _section(title: str) -> None:
    print(f"\n== {title} " + "=" * max(0, 60 - len(title)))


def _fmt_num(v) -> str:
    """Device-derived metrics (loss, accuracy) reach the stream as the
    strings "nan"/"inf" when non-finite (EventLog keeps the JSONL strict)
    — exactly the runs this report exists to diagnose, so print them
    instead of crashing on the float format spec."""
    return f"{v:.4f}" if isinstance(v, (int, float)) else str(v)


def _print_violation(e: dict) -> None:
    print(f"  {e.get('slo', '?'):20s} "
          f"{_fmt_num(e.get('value'))} vs threshold "
          f"{_fmt_num(e.get('threshold'))} "
          f"(window {_fmt_num(e.get('window_s'))}s)")


def report_run(events: list, heartbeat_path: str = None) -> None:
    """Print the report for ONE run_id's event list."""
    if events and all(e.get("type") == "slo_violation" for e in events):
        # A sidecar slo_monitor appends its violations under its OWN
        # run_id (iter_runs keeps writers apart); render them as the
        # monitor's verdict on the stream, not as a crashed run.
        _section(f"slo violations (monitor {events[0].get('run_id')})")
        for e in events:
            _print_violation(e)
        return
    by_type = {}
    for e in events:
        # .get: non-strict mode keeps parseable-but-typeless lines; the
        # tolerant reader buckets them under None rather than crashing.
        by_type.setdefault(e.get("type"), []).append(e)

    manifest = (by_type.get("manifest") or [None])[0]
    run_end = (by_type.get("run_end") or [None])[-1]
    steps = by_type.get("step", [])
    faults = by_type.get("fault", [])
    rounds = by_type.get("fl_round", [])
    cohorts = by_type.get("fl_cohort", [])
    tiers = by_type.get("fl_tier", [])
    remeshes = by_type.get("remesh", [])
    req_enq = by_type.get("request_enqueue", [])
    req_pre = by_type.get("request_prefill", [])
    req_tok = by_type.get("request_token", [])
    req_done = by_type.get("request_done", [])

    _section("run")
    print(f"run_id: {events[0].get('run_id')}   events: {len(events)}")
    if manifest:
        for k in ("trainer", "platform", "jax_version", "n_devices", "mesh",
                  "start_step"):
            if manifest.get(k) is not None:
                print(f"{k}: {manifest[k]}")
        # The activation-sync mode (TrainConfig.psa) changes what the
        # model-axis wire rows below MEAN — echo it whenever set so a
        # profile reader never compares a relaxed-sync run against a
        # full-sync one without noticing.
        psa = (manifest.get("train_cfg") or {}).get("psa")
        if psa:
            print(f"psa: {psa}")
        # Likewise the bucketed backward (ISSUE 19): the per-label rows
        # below split into per-bucket ring legs under comm_buckets > 1,
        # and a reader comparing dispatch counts across runs needs to
        # know the bucket count up front.
        cb = (manifest.get("train_cfg") or {}).get("comm_buckets")
        if isinstance(cb, int) and cb > 1:
            print(f"comm_buckets: {cb}")

    comm = (manifest or {}).get("comm")
    if comm:
        _section("comm volume (static, per step)")
        print(f"payload: {_fmt_bytes(comm['payload_bytes_per_step'])}   "
              f"wire/device: "
              f"{_fmt_bytes(comm['wire_bytes_per_device_per_step'])}")
        for label, agg in sorted(comm["collectives"].items(),
                                 key=lambda kv: -kv[1]["payload_bytes"]):
            print(f"  {label:28s} {agg['op']:12s} axis={agg['axis']}"
                  f"({agg['axis_size']})  x{agg['calls']:<5d} "
                  f"payload {_fmt_bytes(agg['payload_bytes']):>12s}  "
                  f"wire {_fmt_bytes(agg['wire_bytes_per_device']):>12s}")
        # Per-mesh-axis attribution (hierarchical collectives): the DCN
        # row IS the scarce-tier wire budget, and the MODEL row is the
        # PSA activation-sync budget (tp.psa_sync_wire_bytes) — so a
        # single-axis TP manifest still renders the table. Absent on
        # pre-PR-12 manifests — skip silently.
        # Per-bucket ring dispatch counts (ISSUE 19 bucketed backward):
        # fold the per-label ``*ring_grad_b<N>*`` legs into per-axis
        # bucket tallies so the wire-budget table shows how many times
        # each bucket's ring dispatched — the sub-1/n chunking's dispatch
        # overhead, next to the bytes it re-orders.
        bucket_calls = {}
        for label, agg in comm["collectives"].items():
            m = re.search(r"ring_grad_b(\d+)", str(label))
            if m:
                per_ax = bucket_calls.setdefault(agg.get("axis"), {})
                b = int(m.group(1))
                per_ax[b] = per_ax.get(b, 0) + agg.get("calls", 0)
        axes = comm.get("axes")
        if axes and (len(axes) > 1 or "model" in axes or bucket_calls):
            print("per-axis wire budget:")
            for ax, agg in sorted(axes.items(),
                                  key=lambda kv:
                                  -kv[1]["wire_bytes_per_device"]):
                per_ts = agg.get("wire_bytes_per_device_per_train_step")
                print(f"  axis {ax:6s}({agg['axis_size']})  x"
                      f"{agg['calls']:<5d} payload "
                      f"{_fmt_bytes(agg['payload_bytes']):>12s}  wire "
                      f"{_fmt_bytes(agg['wire_bytes_per_device']):>12s}"
                      + (f"  ({_fmt_bytes(per_ts)}/step)"
                         if per_ts is not None else ""))
                bk = bucket_calls.get(ax)
                if bk:
                    counts = sorted(set(bk.values()))
                    detail = (f"x{counts[0]} dispatches each"
                              if len(counts) == 1 else
                              "  ".join(f"b{b}:x{c}"
                                        for b, c in sorted(bk.items())))
                    print(f"    bucketed ring: {len(bk)} buckets  "
                          f"{detail}")

    if steps:
        _section("steps")
        # Per-step seconds from the event stream's (dt_s, steps) deltas —
        # events are emitted every step_every iterations, so dt_s/steps is
        # the mean over that window; the distribution is over windows.
        # Warmup-flagged windows (compile/replay in dt_s) are excluded.
        dts = [e["dt_s"] / e["steps"] for e in steps
               if e.get("steps") and not e.get("warmup")]
        losses = [e["loss"] for e in steps if e.get("loss") is not None]
        print(f"step events: {len(steps)}   "
              f"iters {steps[0]['it']}..{steps[-1]['it']}")
        if losses:
            print(f"loss: {_fmt_num(losses[0])} -> {_fmt_num(losses[-1])}")
        if dts:
            print("step time: " + "  ".join(
                f"p{q:g}={percentile(dts, q) * 1e3:.1f}ms"
                for q in (50, 95, 99)) + f"  n={len(dts)} windows")

    if req_enq or req_pre or req_done or req_tok:
        # Serving section (schema v2 request_* events, serving/scheduler.py;
        # schema v6 tags them per engine). Runs with no serving events skip
        # this silently — training and serving streams share one schema,
        # not one workload. Percentile tables group PER ENGINE: an
        # N-engine fleet's streams must not pool into one table (each
        # engine has its own pool, so "peak blocks in use" pooled across
        # engines would compare apples to a sum of oranges), with the
        # fleet-wide aggregate kept as the headline. Untagged (pre-v6 /
        # single-engine) events group under one unlabeled engine, which
        # renders exactly the old single-table output.
        _section("serving")
        print(f"requests: {len(req_enq)} enqueued   {len(req_pre)} admitted"
              f"   {len(req_done)} done   {len(req_tok)} token events")

        def _latency_lines(done_events, indent=""):
            waits = [e["queue_wait_s"] for e in done_events
                     if isinstance(e.get("queue_wait_s"), (int, float))]
            ttfts = [e["ttft_s"] for e in done_events
                     if isinstance(e.get("ttft_s"), (int, float))]
            for label, vals in (("queue wait", waits), ("ttft", ttfts)):
                if vals:
                    print(indent + f"{label}: " + "  ".join(
                        f"p{q:g}={percentile(vals, q) * 1e3:.1f}ms"
                        for q in (50, 95, 99)) + f"  n={len(vals)}")

        _latency_lines(req_done)
        total_tokens = sum(e["tokens"] for e in req_done
                           if isinstance(e.get("tokens"), int))
        if req_done and req_pre:
            # Busy-span throughput from the stream's own timestamps:
            # first admission -> last completion (fleet-wide).
            span = max(e["t"] for e in req_done) - min(e["t"] for e in req_pre)
            if span > 0:
                print(f"sustained: {total_tokens / span:,.1f} tok/s "
                      f"({total_tokens} tokens over {span:.2f}s busy span)")
        engines = sorted({e.get("engine") for e in req_pre + req_done
                          if e.get("engine") is not None})
        if engines:
            for eid in engines:
                mine = [e for e in req_done if e.get("engine") == eid]
                blocks = [e["blocks_in_use"] for e in req_pre + req_done
                          if e.get("engine") == eid
                          and isinstance(e.get("blocks_in_use"), int)]
                print(f"engine {eid}: {len(mine)} done"
                      + (f"   peak blocks in use {max(blocks)}"
                         if blocks else ""))
                _latency_lines(mine, indent="  ")
        else:
            blocks = [e["blocks_in_use"] for e in req_pre + req_done
                      if isinstance(e.get("blocks_in_use"), int)]
            if blocks:
                print(f"peak blocks in use: {max(blocks)}")
        tenants = sorted({e.get("tenant") for e in req_done
                          if isinstance(e.get("tenant"), str)})
        if len(tenants) > 1:
            for cls in tenants:
                mine = [e for e in req_done if e.get("tenant") == cls]
                print(f"class {cls}: {len(mine)} done")
                _latency_lines(mine, indent="  ")
        specs = by_type.get("speculate", [])
        if specs:
            # Speculative decoding (schema v7, serving/speculate.py): one
            # event per verify dispatch. Acceptance = accepted/proposed
            # draft tokens; tokens-per-dispatch = tokens the target's
            # verify dispatches landed (the dispatch-bound decode
            # headline) — a rate near 1/(k+1) of the emitted window means
            # the draft is degenerate (slo_monitor's acceptance floor).
            prop = sum(e.get("proposed", 0) for e in specs)
            acc = sum(e.get("accepted", 0) for e in specs)
            emitted = sum(e.get("emitted", 0) for e in specs
                          if isinstance(e.get("emitted"), int))
            ks = sorted({e.get("k") for e in specs
                         if isinstance(e.get("k"), int)})
            line = (f"speculate: {len(specs)} verify dispatches"
                    + (f"   k={'/'.join(map(str, ks))}" if ks else ""))
            if prop:
                line += f"   acceptance {acc}/{prop} = {acc / prop:.3f}"
            if emitted:
                line += f"   tokens/dispatch {emitted / len(specs):.2f}"
            print(line)

    routes = by_type.get("route", [])
    deploys = by_type.get("deploy", [])
    if routes or deploys:
        # Fleet section (schema v6, serving/fleet.py + serving/deploy.py):
        # router decisions and live weight rollouts.
        _section("serving fleet (routing / deploys)")
        if routes:
            per_engine = {}
            for e in routes:
                per_engine[e.get("engine")] = \
                    per_engine.get(e.get("engine"), 0) + 1
            policy = next((e.get("policy") for e in routes
                           if e.get("policy")), "?")
            print(f"routed: {len(routes)} requests under {policy}   "
                  + "  ".join(f"engine {k}: {v}"
                              for k, v in sorted(per_engine.items(),
                                                 key=lambda kv:
                                                 str(kv[0]))))
        for e in deploys:
            print(f"  deploy version {e.get('version')} -> "
                  f"engine {e.get('engine', '?')}  "
                  f"({e.get('in_flight', 0)} in flight, "
                  f"{e.get('queued', 0)} queued across the swap)")

    nums = by_type.get("numerics", [])
    if nums:
        # Numerics section (schema v5, telemetry/introspect.py): the
        # in-jit run-health samples. Pre-v5 streams simply have no
        # ``numerics`` events and skip this silently.
        _section("numerics (in-jit run health)")
        gnorms = [e["grad_norm"] for e in nums
                  if isinstance(e.get("grad_norm"), (int, float))]
        print(f"samples: {len(nums)}   iters "
              f"{nums[0].get('it')}..{nums[-1].get('it')}"
              + (f"   grad_norm {_fmt_num(gnorms[0])} -> "
                 f"{_fmt_num(gnorms[-1])}" if gnorms else ""))
        # Worst-drifting layer group: widest max/min spread of the
        # update/param ratio across the run's samples — the knob that
        # moves before a spike becomes a StepGuard skip.
        spread = {}
        for e in nums:
            for g, d in (e.get("groups") or {}).items():
                r = d.get("update_ratio")
                if isinstance(r, (int, float)) and r > 0:
                    lo, hi = spread.get(g, (r, r))
                    spread[g] = (min(lo, r), max(hi, r))
        drifts = sorted(((hi / lo, g, lo, hi)
                         for g, (lo, hi) in spread.items() if lo > 0),
                        reverse=True)
        for d, g, lo, hi in drifts[:3]:
            print(f"  {g:16s} update/param ratio {lo:.3g} .. {hi:.3g} "
                  f"(x{d:.2f} drift)")
        bad = [e for e in nums if e.get("nonfinite_grads")]
        for e in bad:
            print(f"  it {e.get('it', '?'):>6}: NON-FINITE grads in "
                  f"{e['nonfinite_grads']}   <-- BAD")

    compiles = by_type.get("compile", [])
    if compiles:
        # Compile/retrace section (schema v5, introspect.CompileWatch).
        _section("compile / retrace")
        by_name = {}
        for e in compiles:
            agg = by_name.setdefault(e.get("name", "?"),
                                     {"n": 0, "s": 0.0, "retraces": 0,
                                      "flops": None, "bytes": None})
            agg["n"] += 1
            if isinstance(e.get("seconds"), (int, float)):
                agg["s"] += e["seconds"]
            if e.get("retrace"):
                agg["retraces"] += 1
            if isinstance(e.get("flops"), (int, float)):
                agg["flops"] = e["flops"]
            if isinstance(e.get("bytes_accessed"), (int, float)):
                agg["bytes"] = e["bytes_accessed"]
        for name, agg in sorted(by_name.items()):
            line = (f"  {name:28s} compiles {agg['n']:<3d} "
                    f"{agg['s']:8.2f}s total")
            if agg["flops"]:
                line += f"  {agg['flops'] / 1e6:,.1f} MFLOP/dispatch"
            if agg["retraces"]:
                line += f"  RETRACES {agg['retraces']}   <-- BAD"
            print(line)

    peaks = (manifest or {}).get("peaks")
    if compiles and peaks:
        # Attainment section: what each dispatch ACHIEVED vs the roofline
        # peaks the manifest recorded (the chip's published peaks by
        # device_kind, the calibrated baseline on the CPU). Numerators: the compiled
        # program's HLO flops/bytes normalized PER STEP by the compile
        # event's own steps_per_dispatch (same rule as slo_monitor — a
        # ragged tail chunk's smaller program must not be costed as a
        # full-K one), then scaled by each dispatch's step count (the
        # parent ``dispatch`` span's ``steps``); denominator: the
        # ``compute`` span durations.
        prog = next((e for e in reversed(compiles)
                     if isinstance(e.get("flops"), (int, float))
                     and e["flops"] > 0), None)
        span_events = by_type.get("span", [])
        by_span_id = {e.get("span_id"): e for e in span_events}
        # ``compiled``-stamped spans (the trainer marks a dispatch whose
        # call compiled — warmup, tail-chunk shapes) are excluded: a
        # compile-dominated interval is not an attainment sample.
        computes = [e for e in span_events
                    if e.get("name") == "compute"
                    and not e.get("compiled")
                    and isinstance(e.get("dur_ns"), (int, float))
                    and e["dur_ns"] > 0]
        if prog is not None and computes:
            _section("attainment (vs roofline peaks)")
            spd = prog.get("steps_per_dispatch")
            spd = spd if isinstance(spd, int) and spd > 0 else 1
            flops_step = prog["flops"] / spd
            bytes_step = (prog["bytes_accessed"] / spd
                          if isinstance(prog.get("bytes_accessed"),
                                        (int, float)) else None)
            mfus, gbs = [], []
            for s in computes:
                parent = by_span_id.get(s.get("parent_span_id"), {})
                steps = parent.get("steps")
                steps = steps if isinstance(steps, int) and steps > 0 else 1
                att = attainment(flops_step * steps,
                                 (bytes_step * steps
                                  if bytes_step is not None else None),
                                 s["dur_ns"] / 1e9, peaks)
                if att["mfu"] is not None:
                    mfus.append(att["mfu"])
                if att["bytes_per_sec"] is not None:
                    gbs.append(att["bytes_per_sec"] / 1e9)
            print(f"program: {prog.get('name')}   "
                  f"{flops_step / 1e6:,.1f} MFLOP/step   "
                  f"peaks: {peaks.get('source', '?')}")
            if mfus:
                print("mfu: " + "  ".join(
                    f"p{q:g}={percentile(mfus, q):.4f}"
                    for q in (50, 99)) + f"  n={len(mfus)} dispatches")
            if gbs:
                print("memory: " + "  ".join(
                    f"p{q:g}={percentile(gbs, q):.2f} GB/s"
                    for q in (50, 99)))

    mems = by_type.get("memory", [])
    preflight = (manifest or {}).get("preflight")
    if mems or preflight:
        # Memory section (schema v9, telemetry/memory.py): the preflight
        # fit estimate, the measured compiled footprint it cross-checks
        # against (the latest compile event's memory_analysis bytes —
        # argument bytes ARE the resident state+window, the comparable
        # quantity), and the live meter's sampled peaks per source.
        _section("memory")
        if preflight:
            parts = "  ".join(
                f"{k.replace('_bytes', '')}={_fmt_bytes(preflight[k])}"
                for k in ("params_bytes", "opt_state_bytes",
                          "residual_bytes", "window_bytes",
                          "kv_pool_bytes")
                if isinstance(preflight.get(k), (int, float))
                and preflight[k] > 0)
            print(f"preflight (per device, world "
                  f"{preflight.get('n_data', '?')}): "
                  f"{_fmt_bytes(preflight.get('device_bytes', 0))}   "
                  + parts)
            # The preflight estimates the TRAINER's footprint, so prefer
            # a train/-namespaced compile for the cross-check; a stream
            # with only serving compiles falls back to the latest.
            accounted = [e for e in compiles
                         if isinstance(e.get("argument_bytes"),
                                       (int, float))]
            measured = next(
                (e for e in reversed(accounted)
                 if str(e.get("name", "")).startswith("train/")),
                accounted[-1] if accounted else None)
            if measured is not None and isinstance(
                    preflight.get("state_bytes"), (int, float)):
                predicted = (preflight["state_bytes"]
                             + preflight.get("window_bytes", 0))
                arg = measured["argument_bytes"]
                rel = (abs(arg - predicted) / predicted if predicted
                       else None)
                print(f"measured ({measured.get('name', '?')}): args "
                      f"{_fmt_bytes(arg)}  temp "
                      f"{_fmt_bytes(measured.get('temp_bytes', 0))}  "
                      f"peak {_fmt_bytes(measured.get('device_bytes', 0))}"
                      + (f"   vs preflight {rel:+.1%}"
                         if rel is not None else ""))
        if mems:
            by_source = {}
            for e in mems:
                by_source.setdefault(e.get("source", "?"), []).append(e)
            for source, evs in sorted(by_source.items()):
                peaks_ = {}
                for e in evs:
                    for k, v in e.items():
                        if (k.endswith("_bytes")
                                and isinstance(v, (int, float))):
                            peaks_[k] = max(peaks_.get(k, 0), v)
                last = evs[-1]
                line = f"  {source:8s} samples {len(evs):<5d}"
                for k in ("device_bytes", "rss_bytes", "pool_used_bytes",
                          "mirror_bytes"):
                    if k in peaks_:
                        line += (f"  peak {k.replace('_bytes', '')} "
                                 f"{_fmt_bytes(peaks_[k])}")
                if isinstance(last.get("holes"), int):
                    line += (f"  frag holes={last['holes']} "
                             f"largest_run={last.get('largest_run', '?')}")
                print(line)

    spans = by_type.get("span", [])
    if spans:
        # Traces section (schema v4 span events, telemetry/trace.py): the
        # causal structure behind the flat percentiles above. The
        # self-check line is the layer auditing itself — orphans (a span
        # naming a parent the stream never closed) and imbalance
        # (children outlasting their parent) are propagation bugs, and a
        # report that silently rendered them would hide exactly the class
        # of defect tracing exists to expose.
        _section("traces")
        trees = trace_trees(events)
        checks = {tid: tree_check(t) for tid, t in trees.items()}
        orphans = sum(c["orphans"] for c in checks.values())
        imbalanced = sum(c["imbalanced"] for c in checks.values())
        print(f"spans: {len(spans)}   traces: {len(trees)}   "
              f"self-check: {orphans} orphaned, {imbalanced} imbalanced"
              + ("" if not (orphans or imbalanced) else "   <-- BAD"))
        # Per-request breakdown over traces rooted in a single "request"
        # span (the serving trees; the train/fleet traces have per-
        # dispatch/per-round roots and are better read in Perfetto).
        reqs = {tid: t["roots"][0] for tid, t in trees.items()
                if len(t["roots"]) == 1
                and t["roots"][0].get("name") == "request"}
        if reqs:
            durs = sorted((r.get("dur_ns", 0), tid)
                          for tid, r in reqs.items())
            total_ms = [d / 1e6 for d, _ in durs]
            print(f"request spans: {len(reqs)}   total: " + "  ".join(
                f"p{q:g}={percentile(total_ms, q):.1f}ms"
                for q in (50, 95, 99)))
            # Critical path of the slowest p99 request: which child spans
            # its end-to-end time actually went to.
            p99 = percentile([d for d, _ in durs], 99)
            dur, tid = next((d, t) for d, t in durs if d >= p99)
            tree, root = trees[tid], reqs[tid]
            kids = tree["children"].get(root.get("span_id"), [])
            print(f"slowest p99 request: {tid}  "
                  f"{dur / 1e6:.1f}ms end-to-end")
            for k in kids:
                pct = 100 * k.get("dur_ns", 0) / max(dur, 1)
                n_sub = len(tree["children"].get(k.get("span_id"), []))
                print(f"  {k.get('name', '?'):14s} "
                      f"{k.get('dur_ns', 0) / 1e6:9.2f}ms  {pct:5.1f}%"
                      + (f"  ({n_sub} children)" if n_sub else ""))

    slo_events = by_type.get("slo_violation", [])
    if slo_events:
        _section("slo violations")
        for e in slo_events:
            _print_violation(e)

    if remeshes:
        _section("remesh (elastic recoveries)")
        for e in remeshes:
            lost = e.get("lost")
            # Multi-axis meshes (DP×PP) tag each remesh with the axis that
            # moved and the (D, S) factorization old -> new; a "stage"
            # axis means a layer re-partition (state re-sliced by
            # coordinate id), "data" a pure row-drop/grow reshard.
            old_s, new_s = e.get("old_shape"), e.get("new_shape")
            topo = ""
            if old_s and new_s:
                axis = e.get("axis", "data")
                kind = ("re-partition" if axis == "stage" else "reshard")
                topo = (f"  [{old_s[0]}x{old_s[1]} -> "
                        f"{new_s[0]}x{new_s[1]}, {axis} axis: {kind}]")
            print(f"  step {e.get('it', '?'):>6}: "
                  f"{e.get('old_world', '?')} -> {e.get('new_world', '?')} "
                  f"devices"
                  + (f" (lost {lost})" if lost else "")
                  + topo
                  + f"  via {e.get('path', '?')}"
                  + (f"  {e['seconds']:.3f}s lost"
                     if isinstance(e.get("seconds"), (int, float)) else "")
                  + (f"  {e['steps_replayed']} steps replayed"
                     if e.get("steps_replayed") is not None else ""))

    scales = by_type.get("scale", [])
    if scales:
        # Autoscaler section (schema v8 ``scale`` events,
        # resilience/autoscale.py): the control plane's decision stream.
        # Each event carries the POST-transition allocation; the re-mesh
        # that applied it is the ``remesh`` event whose detection step is
        # the decision's iteration, which is where the transition's cost
        # (seconds) lives.
        _section("scale (autoscaler)")
        by_detect = {e.get("detected_at"): e for e in remeshes}
        for e in scales:
            applied = by_detect.get(e.get("it"))
            print(f"  it {e.get('it', '?'):>6}: "
                  f"{e.get('direction', '?'):14s} -> "
                  f"train {e.get('train_world', '?')} / "
                  f"serve {e.get('serve_engines', '?')} engines  "
                  f"({e.get('signal', '?')} {_fmt_num(e.get('value'))})"
                  + (f"  applied in {applied['seconds']:.3f}s"
                     if applied and isinstance(applied.get("seconds"),
                                               (int, float)) else ""))
        allocs = [f"{e.get('train_world', '?')}t/"
                  f"{e.get('serve_engines', '?')}s" for e in scales]
        print(f"  allocation over time: ... -> " + " -> ".join(allocs))

    if rounds:
        _section("fl rounds")
        accs = [r["test_accuracy"] for r in rounds
                if r.get("test_accuracy") is not None]
        walls = [r["wall_s"] for r in rounds if r.get("wall_s") is not None]
        print(f"rounds: {len(rounds)}")
        if accs:
            print(f"test accuracy: {_fmt_num(accs[0])} -> "
                  f"{_fmt_num(accs[-1])}")
        if walls:
            print("round time: " + "  ".join(
                f"p{q:g}={percentile(walls, q):.3f}s" for q in (50, 95, 99)))

    if cohorts or tiers:
        # Fleet-scale FL section (schema v3 fl_cohort / fl_tier events,
        # fl/fleet.py): how the cohort-streaming rounds moved bytes
        # through the edge/server tiers. Runs without fleet events skip
        # this silently, same as the serving section.
        _section("fl fleet (cohort streaming)")
        if cohorts:
            clients = [e["clients"] for e in cohorts
                       if isinstance(e.get("clients"), int)]
            print(f"cohort dispatches: {len(cohorts)}"
                  + (f"   clients/cohort p50="
                     f"{percentile(clients, 50):.0f} "
                     f"max={max(clients)}" if clients else ""))
        by_tier = {}
        for e in tiers:
            agg = by_tier.setdefault(e.get("tier", "?"),
                                     {"rounds": 0, "bytes": 0, "inputs": 0})
            agg["rounds"] += 1
            if isinstance(e.get("payload_bytes"), (int, float)):
                agg["bytes"] += e["payload_bytes"]
            agg["inputs"] += (e.get("clients") or e.get("inputs") or 0)
        for tier, agg in by_tier.items():
            print(f"  tier {tier:8s} rounds {agg['rounds']:<4d} "
                  f"inputs {agg['inputs']:<8d} "
                  f"payload {_fmt_bytes(agg['bytes'])}")

    metrics = (run_end or {}).get("metrics") or {}
    phase = {k: v for k, v in metrics.get("gauges", {}).items()
             if k.startswith("phase/") and k.endswith("_s")}
    if phase:
        _section("phase breakdown")
        total = sum(phase.values())
        for k, v in sorted(phase.items(), key=lambda kv: -kv[1]):
            name = k[len("phase/"):-len("_s")]
            pct = 100 * v / total if total else 0
            print(f"  {name:12s} {v:10.3f}s  {pct:5.1f}%")

    counters = {k: v for k, v in metrics.get("counters", {}).items()
                if k.startswith("faults/") and v}
    if faults or counters:
        _section("faults")
        for e in faults:
            print(f"  it {e.get('it', e.get('round', '?')):>6}: "
                  f"{e['counters']}")
        if counters:
            print(f"  totals: "
                  f"{ {k[len('faults/'):]: int(v) for k, v in counters.items()} }")
    elif run_end:
        print("\nfaults: none recorded")

    hists = metrics.get("histograms", {})
    if hists:
        _section("metrics (run_end snapshot)")
        for name, h in sorted(hists.items()):
            print(f"  {name:16s} n={h['count']:<6d} mean={h['mean']:.4g}  "
                  f"p50={h['p50']:.4g}  p95={h['p95']:.4g}  "
                  f"p99={h['p99']:.4g}  max={h['max']:.4g}")

    if run_end:
        _section("run end")
        for k in ("steps", "preempted", "remeshes", "tokens_per_sec",
                  "post_remesh_tokens_per_sec", "wall_s",
                  "final_accuracy"):
            if run_end.get(k) is not None:
                print(f"{k}: {run_end[k]}")
    else:
        print("\nNO run_end event — the run is live, was killed, or "
              "crashed mid-stream.")

    if heartbeat_path:
        hb = read_heartbeat(heartbeat_path)
        _section("heartbeat")
        if hb is None:
            print("no readable heartbeat")
        else:
            age = time.time() - hb.get("time", 0)
            print(f"pid {hb.get('pid')}  step {hb.get('step')}  "
                  f"seq {hb.get('seq')}  phase {hb.get('phase', '-')}  "
                  f"age {age:.1f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path", help="telemetry run dir (containing "
                                 "events.jsonl) or an events.jsonl path")
    ap.add_argument("--strict", action="store_true",
                    help="fail on malformed/invalid events instead of "
                         "skipping them")
    a = ap.parse_args(argv)

    if os.path.isdir(a.path):
        events_path = os.path.join(a.path, "events.jsonl")
        heartbeat_path = os.path.join(a.path, "heartbeat.json")
        if not os.path.exists(heartbeat_path):
            heartbeat_path = None
    else:
        events_path = a.path
        heartbeat_path = None
    if not os.path.exists(events_path):
        print(f"no event stream at {events_path}", file=sys.stderr)
        return 2
    events = read_events(events_path, strict=a.strict)
    if not events:
        print(f"{events_path}: empty event stream", file=sys.stderr)
        return 2
    # The heartbeat file belongs to the LATEST writer — attaching it to
    # every run in a multi-run stream (relaunches share the dir) would
    # make dead runs look alive.
    runs = list(iter_runs(events))
    for i, run in enumerate(runs):
        report_run(run, heartbeat_path if i == len(runs) - 1 else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
