"""Front end: synthetic heavy-traffic workloads + the serving run driver.

The load half of the serving subsystem: a seeded Poisson arrival process
over mixed prompt/output length distributions (`synthetic_workload` — the
"millions of users" stand-in the north star asks to be measured against),
its multi-tenant generalization (`TrafficClass`/`multi_tenant_workload`:
one Poisson stream per class with its own rates, admission priority and
per-class SLO targets, merged arrival-ordered — the fleet's traffic,
serving/fleet.py), and `run_serving`, the driver that replays a workload
through the continuous-batching scheduler in (fast-forwarded) real time
and aggregates per-request latency into the serving headline: sustained
tok/s + p50/p95/p99 queue wait and TTFT at N concurrent streams.

Determinism contract: the workload is fully determined by its seed (one
`np.random.default_rng` drives arrivals, lengths, temperatures, prompt
tokens and per-request sampling seeds), and request CONTENT determines
request TOKENS (scheduler.py's admission-order invariant) — so latency
numbers are load-dependent but every token stream is reproducible and
checkable against `generate()` one request at a time
(experiments/serving_bench.py does exactly that).

The clock is wall time with idle fast-forward: while requests are in
flight the engine does real work and latencies are honest measurements;
when the engine and queue are BOTH empty, the clock jumps to the next
arrival instead of sleeping, so a light workload doesn't stretch CI
wall time. Fast-forward never runs while anything is queued or in flight,
so it cannot shrink a queue wait or a TTFT.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import LlamaConfig
from ..telemetry.events import EventLog
from ..telemetry.registry import percentile
from .engine import Engine
from .kvcache import PagedKVConfig, naive_cache_bytes, pool_bytes
from .scheduler import Request, RequestRecord, Scheduler


def synthetic_workload(*, seed: int, n_requests: int, rate_rps: float,
                       vocab_size: int,
                       prompt_lens: Sequence[int] = (8, 16, 48),
                       prompt_weights: Optional[Sequence[float]] = None,
                       max_news: Sequence[int] = (8, 16, 32),
                       max_new_weights: Optional[Sequence[float]] = None,
                       temperatures: Sequence[float] = (0.0, 0.8),
                       temperature_weights: Optional[Sequence[float]] = None,
                       tenant: str = "default", priority: int = 0,
                       rid_prefix: str = "req",
                       ) -> List[Request]:
    """Seeded Poisson arrivals (exponential inter-arrival at ``rate_rps``)
    over mixed prompt/output length and temperature mixtures.

    Lengths draw from small DISCRETE sets rather than continuous
    distributions on purpose: the paged engine is shape-oblivious, but the
    per-request `generate()` parity reference compiles once per distinct
    (prompt_len, max_new, temperature) combination — a discrete mixture
    keeps the verification sweep to a handful of compiles while still
    exercising raggedness. Widen the sets (or pass weights) to skew the
    mix; the engine itself never recompiles."""
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs: List[Request] = []
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / rate_rps))
        tp = int(rng.choice(np.asarray(prompt_lens), p=prompt_weights))
        mx = int(rng.choice(np.asarray(max_news), p=max_new_weights))
        temp = float(rng.choice(np.asarray(temperatures, np.float64),
                                p=temperature_weights))
        prompt = tuple(int(x) for x in rng.integers(0, vocab_size, tp))
        reqs.append(Request(rid=f"{rid_prefix}-{i:04d}", prompt=prompt,
                            max_new=mx, temperature=temp,
                            seed=int(rng.integers(0, 2 ** 31 - 1)),
                            arrival=t, tenant=tenant, priority=priority))
    return reqs


@dataclass(frozen=True)
class TrafficClass:
    """One tenant class of a multi-tenant workload: its own Poisson rate,
    length/temperature mixture, admission ``priority`` (higher admits
    first at a contended boundary — scheduler.py), and optional per-class
    SLO targets (consumed by ``experiments/slo_monitor.py``'s per-class
    verdicts and the fleet smoke). A class is a traffic SHAPE: counts
    belong to the ``multi_tenant_workload`` call."""
    name: str
    rate_rps: float
    prompt_lens: Sequence[int] = (8, 16, 48)
    max_news: Sequence[int] = (8, 16, 32)
    temperatures: Sequence[float] = (0.0, 0.8)
    priority: int = 0
    ttft_p99_s: Optional[float] = None
    queue_p99_s: Optional[float] = None


def multi_tenant_workload(*, seed: int, classes: Sequence[TrafficClass],
                          n_per_class, vocab_size: int) -> List[Request]:
    """Merge one seeded Poisson stream per traffic class into a single
    arrival-ordered workload. Each class draws from its own child seed
    (derived from ``seed`` and the class position), so adding a class
    never perturbs another's stream; request ids are ``<class>-<i>`` and
    every request carries its class name as ``tenant`` plus the class
    ``priority``. ``n_per_class`` is an int (same count for every class)
    or a ``{name: count}`` mapping."""
    reqs: List[Request] = []
    for idx, cls in enumerate(classes):
        n = (n_per_class[cls.name] if isinstance(n_per_class, dict)
             else int(n_per_class))
        reqs.extend(synthetic_workload(
            seed=seed + 7919 * (idx + 1), n_requests=n,
            rate_rps=cls.rate_rps, vocab_size=vocab_size,
            prompt_lens=cls.prompt_lens, max_news=cls.max_news,
            temperatures=cls.temperatures, tenant=cls.name,
            priority=cls.priority, rid_prefix=cls.name))
    return sorted(reqs, key=lambda r: (r.arrival, r.rid))


def class_slos(classes: Sequence[TrafficClass]) -> Dict[str, Dict[str, float]]:
    """The per-class SLO table in ``experiments/slo_monitor.py``'s
    ``SLOConfig.per_class`` shape: {class: {objective: threshold}},
    classes with no targets omitted."""
    out: Dict[str, Dict[str, float]] = {}
    for cls in classes:
        limits = {}
        if cls.ttft_p99_s is not None:
            limits["ttft_p99_s"] = cls.ttft_p99_s
        if cls.queue_p99_s is not None:
            limits["queue_p99_s"] = cls.queue_p99_s
        if limits:
            out[cls.name] = limits
    return out


def reference_stream(params: dict, cfg: LlamaConfig, paged: PagedKVConfig,
                     req: Request, *, top_k: Optional[int] = None,
                     top_p: Optional[float] = None) -> List[int]:
    """The bitwise-parity reference: ``generate()`` run ALONE on one
    request. One implementation for every consumer of the parity bar
    (tests + serving_bench), because the construction rules are load-
    bearing and easy to get silently wrong: ``max_len`` must pin to
    ``paged.max_seq_len`` (so both sides reduce over identically-shaped
    score rows), ``kv_dtype`` must match the pool's storage dtype, and
    key/temperature are passed only for sampling requests (greedy
    ``generate`` forbids a key-less temperature, and its greedy path
    ignores the key exactly like the engine's where-select)."""
    import jax
    import jax.numpy as jnp

    from ..models import generate

    kw = dict(max_len=paged.max_seq_len, kv_dtype=paged.kv_dtype,
              top_k=top_k, top_p=top_p)
    if req.temperature > 0:
        kw.update(key=jax.random.PRNGKey(req.seed),
                  temperature=req.temperature)
    toks = generate.generate(params, jnp.asarray(req.prompt)[None], cfg,
                             req.max_new, **kw)[0].tolist()
    if req.eos_id is not None and req.eos_id in toks:
        # generate() has no early stop (one compiled scan to the max_new
        # horizon); a request with an EOS id is served its stream
        # truncated at the first EOS INCLUSIVE — the scheduler retires the
        # slot at that boundary, so nothing after it was ever emitted.
        toks = toks[:toks.index(req.eos_id) + 1]
    return toks


class _Clock:
    """Monotonic seconds since start, with idle fast-forward (module
    docstring): `now` advances with wall time; `fast_forward` adds the gap
    to the next arrival without sleeping through it."""

    def __init__(self):
        self._t0 = time.monotonic()
        self._skew = 0.0

    def now(self) -> float:
        return time.monotonic() - self._t0 + self._skew

    def fast_forward(self, to: float) -> None:
        self._skew += max(0.0, to - self.now())


@dataclass
class ServingReport:
    """One serving run's outcome: per-request records + the aggregate row."""
    records: Dict[str, RequestRecord]
    aggregates: dict
    wall_s: float
    peak_blocks_in_use: int
    pool_blocks: int
    pool_bytes: int = 0
    naive_bytes_at_peak: int = 0
    peak_concurrency: int = 0
    requests: List[Request] = field(default_factory=list)
    # Compile/retrace accounting (telemetry/introspect.py CompileWatch on
    # the engine's program set): the contract is compiles == the
    # documented set (2 plain; 4 with speculation — prefill + verify +
    # the draft's two, decode_step idling) and retraces == 0 for ANY
    # workload — raggedness is data, not shapes.
    compiles: int = 0
    retraces: int = 0
    # Speculative decoding accounting (serving/speculate.py): target
    # decode dispatches (verify dispatches when speculating), tokens they
    # emitted, and the draft's (cheap) dispatch count. tokens_per_dispatch
    # = decode_tokens / decode_dispatches, a count: ≈1×avg-batch
    # without speculation, ×(accepted+1) with it.
    decode_dispatches: int = 0
    decode_tokens: int = 0
    draft_dispatches: int = 0
    tokens_per_dispatch: Optional[float] = None
    spec_proposed: int = 0
    spec_accepted: int = 0
    acceptance_rate: Optional[float] = None


def aggregate_latency(records: Dict[str, RequestRecord],
                      busy_span_s: Optional[float] = None) -> dict:
    """p50/p95/p99 queue wait + TTFT, per-request tok/s, and the sustained
    throughput — the serving row's numbers, shared by serving_bench,
    chip_smoke.py and the tests so no consumer re-derives them
    differently. ``busy_span_s`` (run_serving supplies it) is the
    engine's accumulated working time; without it the fallback span is
    first admission → last completion, which is only honest when the
    clock contains no fast-forwarded idle gaps (record timestamps come
    from the skewed clock, so under sparse load the fallback would count
    jumped idle time as serving time and deflate the figure).

    Always returns the FULL record shape: an empty (or all-in-flight)
    window yields ``completed: 0`` with ``None`` percentiles and rates,
    and a single-request window yields its degenerate percentiles —
    never a key-missing dict callers must special-case. The fleet's
    per-class/per-engine slices make empty windows a legitimate steady
    state (a quiet tenant, an engine mid-rollout), so the shape contract
    is pinned (tests/test_fleet_serving.py)."""
    pct = lambda vals: {f"p{q:g}": (percentile(vals, q) if vals else None)
                        for q in (50, 95, 99)}
    done = [r for r in records.values() if r.done_t is not None]
    if not done:
        return {"completed": 0, "total_tokens": 0,
                "sustained_tokens_per_sec": None,
                "busy_span_s": busy_span_s,
                "queue_wait_s": pct([]), "ttft_s": pct([]),
                "request_tokens_per_sec": pct([])}
    waits = [r.queue_wait_s for r in done if r.queue_wait_s is not None]
    ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
    rates = [r.tokens_per_sec for r in done if r.tokens_per_sec is not None]
    total_tokens = sum(len(r.tokens) for r in done)
    span = busy_span_s if busy_span_s is not None else (
        max(r.done_t for r in done)
        - min(r.admit_t for r in done if r.admit_t is not None))
    return {
        "completed": len(done),
        "total_tokens": total_tokens,
        "sustained_tokens_per_sec": (total_tokens / span if span > 0
                                     else None),
        "busy_span_s": span,
        "queue_wait_s": pct(waits),
        "ttft_s": pct(ttfts),
        "request_tokens_per_sec": pct(rates),
    }


def run_serving(params: dict, cfg: LlamaConfig, paged: PagedKVConfig,
                workload: Sequence[Request], *, num_slots: int,
                prefill_chunk: int = 16, top_k: Optional[int] = None,
                top_p: Optional[float] = None,
                events: Optional[EventLog] = None,
                token_events: bool = True,
                speculate=None,
                prefix_share: bool = False) -> ServingReport:
    """Replay ``workload`` (arrival offsets in seconds) through a fresh
    engine + scheduler; returns per-request records and the aggregate row.
    Every request is guaranteed retired on return — reservation-based
    admission cannot deadlock (scheduler.py), so the loop's only exit is
    completion. ``speculate`` (a ``SpecConfig``) turns on draft-propose /
    one-dispatch-verify decoding; ``prefix_share`` maps identical
    full-block prompt prefixes copy-on-write."""
    engine = Engine(params, cfg, paged, num_slots,
                    prefill_chunk=prefill_chunk, top_k=top_k, top_p=top_p,
                    speculate=speculate, prefix_share=prefix_share)
    clock = _Clock()
    sched = Scheduler(engine, events=events, token_events=token_events,
                      clock=clock.now)
    pending = sorted(workload, key=lambda r: r.arrival)
    busy_s = 0.0       # real working time, fast-forwarded idle excluded —
    i = 0              # the denominator of sustained tok/s
    while i < len(pending) or sched.outstanding:
        now = clock.now()
        while i < len(pending) and pending[i].arrival <= now:
            sched.submit(pending[i], now=now)
            i += 1
        if sched.outstanding == 0:
            clock.fast_forward(pending[i].arrival)   # idle: jump, don't sleep
            continue
        sched.tick()
        busy_s += clock.now() - now
    peak_conc = sched.peak_in_flight   # recorded at admission (scheduler.py)
    spec_prop = sum(e.get("proposed", 0) for e in sched.spec_rounds)
    spec_acc = sum(e.get("accepted", 0) for e in sched.spec_rounds)
    report = ServingReport(
        records=sched.records,
        aggregates=aggregate_latency(sched.records, busy_span_s=busy_s),
        wall_s=clock.now(),
        peak_blocks_in_use=engine.allocator.peak_in_use,
        pool_blocks=engine.allocator.capacity,
        compiles=sum(len(w.compiles) for w in engine.watches()),
        retraces=sum(w.retraces for w in engine.watches()),
        pool_bytes=pool_bytes(cfg, paged),
        naive_bytes_at_peak=naive_cache_bytes(
            cfg, max(1, peak_conc), paged.max_seq_len, paged.kv_dtype),
        peak_concurrency=peak_conc,
        requests=list(workload),
        decode_dispatches=engine.decode_dispatches,
        decode_tokens=engine.decode_tokens,
        draft_dispatches=engine.draft_dispatches,
        tokens_per_dispatch=(engine.decode_tokens / engine.decode_dispatches
                             if engine.decode_dispatches else None),
        spec_proposed=spec_prop,
        spec_accepted=spec_acc,
        acceptance_rate=(spec_acc / spec_prop if spec_prop else None))
    return report
