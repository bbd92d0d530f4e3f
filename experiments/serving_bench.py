"""Serving smoke + load bench: seeded Poisson traffic through the engine(s).

The end-to-end proof of the serving subsystem (ddl25spring_tpu/serving) on
the CPU mesh, CI-runnable (tier1.yml) — drives ~100 seeded Poisson
requests with mixed prompt/output lengths through the continuous-batching
scheduler and CHECKS the acceptance bars itself:

- correctness: every request retires with exactly ``max_new`` tokens, the
  telemetry stream carries each token exactly once (zero dropped, zero
  duplicated), and a sampled subset is verified BITWISE against
  ``generate()`` run alone on that request at the same seed;
- memory: the allocator never exceeds the pool, and the pool's device
  bytes are strictly below N separate ``max_len`` caches at the observed
  peak concurrency (the paged pool's reason to exist);
- liveness: the pool is sized BELOW peak naive demand (slots × per-request
  worst case), so admissions must queue under load — completing every
  request anyway is the no-deadlock evidence.

``--speculate K`` (single-engine mode) runs the workload TWICE — plain,
then speculating with a SAME-WEIGHTS draft (greedy acceptance is
deterministically 1, which turns the tokens-per-dispatch bar into an
exact arithmetic claim instead of a statistical one) — and self-checks
the ISSUE 13 bars: identical token streams (bitwise, both runs sampled
against ``generate()``), zero retraces on BOTH engines across the
speculate on/off × k grid with the documented compile sets (2 plain /
4 speculating), acceptance rate in [0, 1] (== 1 here), and
``tokens_per_dispatch`` ≥ 2× the plain engine's at k ≥ 3, recorded in
the JSON. ``--prefix-share`` arms CoW prefix sharing on the same runs
(streams must not move).

``--engines N`` (N > 1) generalizes the smoke to the SERVING FLEET
(serving/fleet.py): a two-class multi-tenant Poisson workload (priorities
+ per-class SLO targets) routed across N engines by the predicted-TTFT
router, with ``--hot-swap`` driving one MID-RUN live weight publication
through the full deploy path (params → publish-dir checkpoint →
digest-verified restore-at-saved-shapes → staggered per-engine
swap-at-token-boundary). Fleet-mode bars, on top of the single-engine
ones (bitwise parity holds at ANY engine count — routing is a latency
decision): every engine compiled exactly two programs with zero retraces
ACROSS the hot-swap, the deploy rolled out to every engine, a ``deploy``
span is present in the Perfetto export, and the per-class SLO verdict
(slo_monitor's per-class rolling windows) replays clean.

Outputs: a latency-percentile JSON (``--out``) and the request_* telemetry
JSONL (``--telemetry-dir``, rendered by ``obs_report``); exit 1 on any
failed check with the diagnostics in the JSON (tier1.yml uploads it either
way).

Example:
    python -m experiments.serving_bench --out serving-latency.json \
        --telemetry-dir /tmp/serving
    python -m experiments.serving_bench --engines 3 --hot-swap \
        --out fleet-serving.json --telemetry-dir /tmp/fleet
    python -m experiments.obs_report /tmp/serving
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _stream_no_drop_no_dup(stream, workload) -> bool:
    """The telemetry-path token contract, shared by both smokes: the
    JSONL stream must carry every (request, index) exactly once."""
    seen = {}
    for e in stream:
        if e.get("type") == "request_token":
            seen.setdefault(e["req"], []).append(e["i"])
    return all(sorted(seen.get(r.rid, [])) == list(range(r.max_new))
               for r in workload)


def _bitwise_sample(workload, recs, params, cfg, paged, *, seed, verify):
    """Sampled bitwise parity vs generate() alone (each distinct request
    shape costs one generate() compile), shared by both smokes. Returns
    (sample_size, mismatched_rids)."""
    import numpy as np

    from ddl25spring_tpu.serving import reference_stream

    rng = np.random.default_rng(seed + 1)
    sample = (list(workload) if verify >= len(workload) else
              [workload[i] for i in rng.choice(len(workload), verify,
                                               replace=False)])
    mismatches = [r.rid for r in sample
                  if reference_stream(params, cfg, paged, r)
                  != recs[r.rid].tokens]
    return len(sample), mismatches


def _build(seed: int):
    import jax

    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama

    # Reduced config, the serving analogue of bench._reduced_dp_setup: the
    # checks are structural (parity, occupancy, liveness), so model scale
    # only costs wall time.
    cfg = LlamaConfig(vocab_size=512, dmodel=64, num_heads=2, n_layers=2,
                      ctx_size=64, attention_impl="xla")
    params = llama.init_llama(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def run(a) -> dict:
    import jax

    from ddl25spring_tpu.serving import (PagedKVConfig, SpecConfig,
                                         blocks_for, naive_cache_bytes,
                                         pool_bytes, run_serving,
                                         synthetic_workload)
    from ddl25spring_tpu.telemetry import Telemetry
    from ddl25spring_tpu.telemetry.events import read_events

    cfg, params = _build(a.seed)
    paged = PagedKVConfig(num_blocks=a.blocks, block_len=a.block_len,
                          max_blocks_per_seq=a.max_blocks_per_seq)
    prompt_lens, max_news = (4, 12, 24), (4, 8, 16)
    workload = synthetic_workload(
        seed=a.seed, n_requests=a.requests, rate_rps=a.rate,
        vocab_size=cfg.vocab_size, prompt_lens=prompt_lens,
        max_news=max_news, temperatures=(0.0, 0.8))

    # The liveness premise: per-request worst case × slots exceeds the
    # pool, so the run MUST queue admissions — completing anyway is the
    # no-deadlock evidence the acceptance bar asks for.
    worst = blocks_for(max(prompt_lens) + max(max_news) - 1, a.block_len)
    naive_peak_blocks = a.slots * worst
    checks = {}
    checks["pool_below_naive_demand"] = (paged.num_blocks - 1
                                         < naive_peak_blocks)

    tel = Telemetry(a.telemetry_dir) if a.telemetry_dir else None
    events = tel.events if tel else None
    if events:
        events.manifest(jax_version=jax.__version__,
                        platform=jax.default_backend(), trainer="serving",
                        slots=a.slots, blocks=a.blocks,
                        block_len=a.block_len, requests=a.requests)
    t0 = time.perf_counter()
    report = run_serving(params, cfg, paged, workload, num_slots=a.slots,
                         prefill_chunk=a.prefill_chunk, events=events,
                         prefix_share=a.prefix_share)
    wall = time.perf_counter() - t0

    spec_block = None
    if a.speculate:
        # Speculative pass with a SAME-WEIGHTS draft: greedy acceptance
        # is deterministically 1 (identical logits ⇒ the argmax chain
        # always matches), so the bars are exact arithmetic, not
        # statistical claims. The tokens-per-dispatch comparison runs
        # the workload through ONE slot (arrivals at t=0, sequential):
        # batch 1 is the dispatch-bound regime the decode roofline names
        # (each token streams every weight byte), where the plain engine
        # is exactly 1 token/dispatch and speculation multiplies it by
        # the accepted window. At higher concurrency the plain engine
        # earns batching credit while speculation drains slots faster
        # than prefill refills them, so the mixed-concurrency ratio
        # conflates scheduling with the per-dispatch win — the loaded
        # figures are still reported (the Poisson run above), the BAR is
        # judged where it is well-defined.
        import dataclasses as _dc
        import os
        saturated = [_dc.replace(r, arrival=0.0) for r in workload]
        plain_sat = run_serving(
            params, cfg, paged, saturated, num_slots=1,
            prefill_chunk=a.prefill_chunk,
            prefix_share=a.prefix_share)
        # Its own telemetry stream (telemetry-dir/spec): sharing the
        # plain run's would double every (request, index) token event
        # and fail the exactly-once contract.
        spec_tel = (Telemetry(os.path.join(a.telemetry_dir, "spec"))
                    if a.telemetry_dir else None)
        spec_report = run_serving(
            params, cfg, paged, saturated, num_slots=1,
            prefill_chunk=a.prefill_chunk,
            events=spec_tel.events if spec_tel else None,
            prefix_share=a.prefix_share,
            speculate=SpecConfig(k=a.speculate, draft_params=params))
        if spec_tel:
            spec_tel.close()
            spec_stream = read_events(spec_tel.events_path)
            spec_events = [e for e in spec_stream
                           if e.get("type") == "speculate"]
            checks["spec_events_per_dispatch"] = (
                len(spec_events) == spec_report.decode_dispatches)
            checks["spec_stream_no_drop_no_dup"] = _stream_no_drop_no_dup(
                spec_stream, workload)
        # GREEDY streams are bitwise invariant across plain/speculative
        # and any admission timing (all equal generate()'s — the plain
        # run's are sampled against it below). Sampled requests are
        # distribution-correct under rejection sampling, not
        # path-identical, so they are excluded here by design.
        checks["spec_greedy_streams_identical"] = all(
            spec_report.records[r.rid].tokens == report.records[r.rid].tokens
            for r in workload if r.temperature == 0.0)
        checks["spec_zero_retraces_on_off_grid"] = (
            report.retraces == 0 and plain_sat.retraces == 0
            and spec_report.retraces == 0)
        # Documented compile sets: 2 plain, 4 speculating (prefill +
        # verify + draft's two; decode_step idles).
        checks["spec_compile_contract"] = (report.compiles == 2
                                           and spec_report.compiles == 4)
        checks["spec_acceptance_sane"] = (
            spec_report.acceptance_rate is not None
            and 0.0 <= spec_report.acceptance_rate <= 1.0)
        checks["spec_acceptance_is_one_for_same_weights"] = (
            spec_report.acceptance_rate == 1.0)
        if a.speculate >= 3:
            checks["spec_tokens_per_dispatch_2x"] = (
                spec_report.tokens_per_dispatch
                >= 2 * plain_sat.tokens_per_dispatch)
        spec_block = {
            "k": a.speculate,
            "tokens_per_dispatch": spec_report.tokens_per_dispatch,
            "tokens_per_dispatch_plain": plain_sat.tokens_per_dispatch,
            "acceptance_rate": spec_report.acceptance_rate,
            "decode_dispatches": spec_report.decode_dispatches,
            "decode_dispatches_plain": plain_sat.decode_dispatches,
            "draft_dispatches": spec_report.draft_dispatches,
            "sustained_tokens_per_sec":
                spec_report.aggregates.get("sustained_tokens_per_sec"),
        }

    recs = report.records
    checks["all_completed"] = (
        report.aggregates.get("completed") == a.requests)
    checks["token_counts_exact"] = all(
        len(recs[r.rid].tokens) == r.max_new for r in workload)

    # Zero dropped / duplicated through the TELEMETRY path too: the JSONL
    # stream must carry every (request, index) exactly once.
    if events:
        events.run_end(steps=report.aggregates.get("completed", 0),
                       wall_s=wall, **{
                           k: report.aggregates.get(k) for k in
                           ("total_tokens", "sustained_tokens_per_sec")})
        tel.close()
        stream = read_events(tel.events_path)
        checks["stream_no_drop_no_dup"] = _stream_no_drop_no_dup(stream,
                                                                 workload)

        # Span-tree completeness (ISSUE 8 acceptance bar): every request
        # reconstructs into ONE rooted tree with zero orphaned spans —
        # the scheduler's queue→prefill(+chunks)→decode→retire lifecycle
        # propagated every context correctly. And the Chrome-trace export
        # of the same stream must round-trip as valid JSON with one
        # complete ("X") event per span.
        from ddl25spring_tpu.telemetry.trace import trace_trees, tree_check
        from experiments.trace_export import chrome_trace
        trees = trace_trees(stream)
        req_trees = [trees.get(r.rid) for r in workload]
        tree_problems = []
        for r, t in zip(workload, req_trees):
            c = tree_check(t) if t is not None else None
            if c is None or c["roots"] != 1 or c["orphans"] != 0:
                tree_problems.append(r.rid)
        checks["span_trees_complete"] = not tree_problems
        n_spans = sum(1 for e in stream if e.get("type") == "span")
        exported = json.loads(json.dumps(chrome_trace(stream)))
        checks["trace_export_valid"] = (
            isinstance(exported.get("traceEvents"), list)
            and sum(1 for ev in exported["traceEvents"]
                    if ev.get("ph") == "X") == n_spans > 0)

    n_verified, mismatches = _bitwise_sample(workload, recs, params, cfg,
                                             paged, seed=a.seed,
                                             verify=a.verify)
    checks["bitwise_parity_vs_generate"] = not mismatches

    checks["pool_never_exceeded"] = (report.peak_blocks_in_use
                                     <= report.pool_blocks)
    # Retrace detector (ISSUE 9): the engine compiles exactly its two
    # programs and NEVER retraces — admission/retirement/raggedness are
    # data. A retrace here means a shape leaked into a compiled step.
    checks["zero_retraces"] = report.retraces == 0
    checks["two_compiled_programs"] = report.compiles == 2
    # Memory bar, two forms: the CONFIG-level inequality (pool < the slots
    # × max_len caches generate() would allocate for the same concurrency
    # ceiling) holds at any load; the observed-peak form only demonstrates
    # anything when the workload actually overlapped enough streams, so it
    # is asserted only when the run saturated its slots — a sparse --rate
    # must not turn "workload too light to show the win" into a failure.
    checks["kv_bytes_below_naive"] = (
        report.pool_bytes < naive_cache_bytes(cfg, a.slots,
                                              paged.max_seq_len))
    if report.peak_concurrency >= a.slots:
        checks["kv_bytes_below_naive_at_observed_peak"] = (
            report.pool_bytes < report.naive_bytes_at_peak)

    out = {
        "metric": "serving_smoke",
        "requests": a.requests,
        "slots": a.slots,
        "pool_blocks": report.pool_blocks,
        "peak_blocks_in_use": report.peak_blocks_in_use,
        "peak_concurrency": report.peak_concurrency,
        "pool_bytes": report.pool_bytes,
        "naive_bytes_at_peak": report.naive_bytes_at_peak,
        "naive_peak_blocks": naive_peak_blocks,
        "wall_s": round(wall, 3),
        "compiles": report.compiles,
        "retraces": report.retraces,
        "verified_bitwise": n_verified,
        "parity_mismatches": mismatches,
        "span_tree_problems": (tree_problems if events else None),
        "aggregates": report.aggregates,
        "tokens_per_dispatch": report.tokens_per_dispatch,
        "speculate": spec_block,
        "prefix_share": bool(a.prefix_share),
        "checks": checks,
        "ok": all(checks.values()),
    }
    if spec_block is not None:
        # Trajectory rows for bench_compare (its ``rows`` shape):
        # tokens-per-dispatch is a THROUGHPUT-like metric — higher is
        # better, bench_compare's default direction (pinned in
        # tests/test_speculate.py) — so a draft regression that halves
        # the window gates exactly like a tok/s drop would.
        out["spec_tokens_per_dispatch"] = spec_block["tokens_per_dispatch"]
        out["rows"] = [{
            "metric": "tokens_per_dispatch",
            "value": spec_block["tokens_per_dispatch"],
            "unit": "tokens/target-dispatch",
            "platform": jax.default_backend(),
            "variant": f"spec-k{a.speculate}",
        }]
    return out


def run_fleet(a) -> dict:
    """The N-engine fleet smoke (module docstring): multi-tenant traffic,
    SLO-aware routing, one mid-run hot-swap through the deploy path."""
    import os

    import jax

    from ddl25spring_tpu.serving import (CheckpointPublisher, TrafficClass,
                                         WeightPublisher, blocks_for,
                                         class_slos, multi_tenant_workload,
                                         run_serving_fleet)
    from ddl25spring_tpu.telemetry import Telemetry
    from ddl25spring_tpu.telemetry.events import read_events
    from experiments.slo_monitor import SLOConfig, replay_monitor

    cfg, params = _build(a.seed)
    from ddl25spring_tpu.serving import PagedKVConfig
    paged = PagedKVConfig(num_blocks=a.blocks, block_len=a.block_len,
                          max_blocks_per_seq=a.max_blocks_per_seq)
    # Two tenant classes: latency-sensitive chat (higher priority, tight
    # shapes) and throughput batch (longer outputs). SLO ceilings are
    # deliberately generous — the verdict proves the per-class plumbing,
    # not the latency of a noisy CI host paying XLA compiles.
    classes = (
        TrafficClass("chat", rate_rps=a.rate * 2 / 3, prompt_lens=(4, 12),
                     max_news=(4, 8), temperatures=(0.0, 0.8), priority=1,
                     ttft_p99_s=120.0, queue_p99_s=120.0),
        TrafficClass("batch", rate_rps=a.rate / 3, prompt_lens=(12, 24),
                     max_news=(8, 16), temperatures=(0.0,), priority=0,
                     ttft_p99_s=240.0, queue_p99_s=240.0),
    )
    n_chat = (a.requests * 2) // 3
    workload = multi_tenant_workload(
        seed=a.seed, classes=classes,
        n_per_class={"chat": n_chat, "batch": a.requests - n_chat},
        vocab_size=cfg.vocab_size)

    checks = {}
    worst = blocks_for(24 + 16 - 1, a.block_len)
    checks["pool_below_naive_demand"] = (paged.num_blocks - 1
                                         < a.slots * worst)

    tel = Telemetry(a.telemetry_dir) if a.telemetry_dir else None
    events = tel.events if tel else None
    if events:
        events.manifest(jax_version=jax.__version__,
                        platform=jax.default_backend(),
                        trainer="serving-fleet", engines=a.engines,
                        slots=a.slots, blocks=a.blocks,
                        block_len=a.block_len, requests=len(workload),
                        policy=a.policy, admission=a.admission)

    # The mid-run publication, through the REAL deploy path: same weights
    # (so the bitwise bar must hold across the swap), but routed via the
    # publish-dir checkpoint, its SHA-256 digest manifest, and the
    # restore-at-saved-shapes read — not an in-process pointer pass.
    publish_after = publish_params = publish_version = None
    if a.hot_swap:
        import tempfile
        pub_dir = os.path.join(a.telemetry_dir or tempfile.mkdtemp(),
                               "publish")
        pub = CheckpointPublisher(pub_dir)
        pub(1200, params)               # "the trainer's step 1200"
        pub.close()
        got = WeightPublisher(pub_dir, params).poll()
        checks["publish_roundtrip"] = got is not None
        if got is not None:
            publish_version, publish_params = got
            publish_after = max(1, a.requests // 3)

    from ddl25spring_tpu.serving import SpecConfig
    spec = (SpecConfig(k=a.speculate, draft_params=params)
            if a.speculate else None)
    t0 = time.perf_counter()
    report = run_serving_fleet(
        params, cfg, paged, workload, num_engines=a.engines,
        num_slots=a.slots, prefill_chunk=a.prefill_chunk, events=events,
        policy=a.policy, admission=a.admission, speculate=spec,
        prefix_share=a.prefix_share,
        publish_after=publish_after, publish_params=publish_params,
        publish_version=publish_version)
    wall = time.perf_counter() - t0

    recs = report.records
    checks["all_completed"] = (report.aggregates.get("completed")
                               == len(workload))
    checks["token_counts_exact"] = all(
        len(recs[r.rid].tokens) == r.max_new for r in workload)
    checks["engines_all_used"] = all(
        agg["completed"] > 0 for agg in report.per_engine.values())
    # Each engine: exactly its documented program set (2 plain; 4 with
    # speculation — prefill + verify + the draft's two, decode idling),
    # zero retraces — ACROSS the hot-swap (an equal-shape swap is data,
    # never a shape; a target swap leaves the draft untouched).
    want_programs = 4 if a.speculate else 2
    checks["documented_programs_per_engine"] = all(
        c == want_programs for c in report.compiles)
    checks["zero_retraces_per_engine"] = all(r == 0 for r in report.retraces)
    if a.hot_swap:
        checks["deploy_rolled_out_all_engines"] = (
            sorted(d["engine"] for d in report.deploys)
            == list(range(a.engines)))

    slo = {}
    if events:
        events.run_end(steps=report.aggregates.get("completed", 0),
                       wall_s=wall, **{
                           k: report.aggregates.get(k) for k in
                           ("total_tokens", "sustained_tokens_per_sec")})
        tel.close()
        stream = read_events(tel.events_path)
        checks["stream_no_drop_no_dup"] = _stream_no_drop_no_dup(stream,
                                                                 workload)
        # Aggregate per-class SLO verdict: slo_monitor's per-class rolling
        # windows replayed over this stream (the same tool tier1.yml runs
        # as a CLI gate over the uploaded telemetry).
        monitor = replay_monitor(
            stream, SLOConfig(window_s=30.0, per_class=class_slos(classes)))
        slo = {"violations": monitor.violations,
               "breakdown": monitor.breakdown()}
        checks["per_class_slo_ok"] = not monitor.violations
        if a.hot_swap:
            # The deploy must be VISIBLE evidence: one deploy event per
            # engine in the stream, and a ``deploy`` span in the Perfetto
            # export (the acceptance bar names the export specifically).
            from experiments.trace_export import chrome_trace
            deploy_events = [e for e in stream if e.get("type") == "deploy"]
            checks["deploy_events_per_engine"] = (
                sorted(e.get("engine") for e in deploy_events)
                == list(range(a.engines)))
            exported = json.loads(json.dumps(chrome_trace(stream)))
            checks["deploy_span_in_perfetto_export"] = any(
                ev.get("ph") == "X" and ev.get("name") == "deploy"
                for ev in exported.get("traceEvents", []))

    # Bitwise parity vs generate() alone — regardless of engine count,
    # routing, priorities, or the mid-run same-weights hot-swap. With
    # speculation armed the bar applies to GREEDY streams (sampled ones
    # are distribution-correct under rejection sampling, not
    # path-identical — the documented stochastic contract).
    pool = ([r for r in workload if r.temperature == 0.0]
            if a.speculate else workload)
    n_verified, mismatches = _bitwise_sample(pool, recs, params, cfg,
                                             paged, seed=a.seed,
                                             verify=a.verify)
    checks["bitwise_parity_vs_generate"] = not mismatches

    checks["pool_never_exceeded"] = all(
        p <= report.pool_blocks for p in report.peak_blocks_per_engine)

    out = {
        "metric": "fleet_serving_smoke",
        "engines": a.engines,
        "policy": a.policy,
        "admission": a.admission,
        "requests": len(workload),
        "hot_swap": bool(a.hot_swap),
        "deploys": report.deploys,
        "pool_blocks": report.pool_blocks,
        "peak_blocks_per_engine": report.peak_blocks_per_engine,
        "compiles": report.compiles,
        "retraces": report.retraces,
        "wall_s": round(wall, 3),
        "verified_bitwise": n_verified,
        "parity_mismatches": mismatches,
        "aggregates": report.aggregates,
        "per_class": report.per_class,
        "per_engine": {str(k): v for k, v in report.per_engine.items()},
        "slo": slo,
        "checks": checks,
        "ok": all(checks.values()),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate (requests/sec)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--blocks", type=int, default=33,
                    help="pool blocks incl. the reserved trash block")
    ap.add_argument("--block-len", type=int, default=8)
    ap.add_argument("--max-blocks-per-seq", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--verify", type=int, default=12,
                    help="requests to verify bitwise against generate()")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="single-engine mode: second pass speculating "
                         "with a same-weights draft proposing K tokens "
                         "per round; self-checks identical streams, the "
                         "compile contract, acceptance == 1 and "
                         "tokens-per-dispatch >= 2x plain (K >= 3)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="arm CoW prefix sharing (streams must not move)")
    ap.add_argument("--quick", action="store_true",
                    help="reduced request count (CI variance smoke)")
    ap.add_argument("--engines", type=int, default=1,
                    help="serving engines; > 1 runs the FLEET smoke "
                         "(multi-tenant traffic, SLO-aware router)")
    ap.add_argument("--policy", default="predicted_ttft",
                    choices=("least_loaded", "predicted_ttft"),
                    help="fleet router dispatch policy")
    ap.add_argument("--admission", default="fcfs", choices=("fcfs", "sjf"),
                    help="scheduler admission policy (fleet mode)")
    ap.add_argument("--hot-swap", action="store_true",
                    help="fleet mode: one mid-run live weight publication "
                         "through the deploy path (same weights — the "
                         "bitwise bar must hold across it)")
    ap.add_argument("--out", default=None, help="result JSON path")
    ap.add_argument("--telemetry-dir", default=None)
    a = ap.parse_args(argv)
    if a.quick:
        a.requests = min(a.requests, 30)
        a.verify = min(a.verify, 6)

    out = run_fleet(a) if a.engines > 1 else run(a)
    line = json.dumps(out)
    if a.out:
        with open(a.out, "w") as f:
            f.write(line + "\n")
    print(line)
    if not out["ok"]:
        failed = [k for k, v in out["checks"].items() if not v]
        print(f"serving smoke FAILED checks: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
