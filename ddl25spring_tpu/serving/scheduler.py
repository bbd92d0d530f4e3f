"""Continuous-batching scheduler: admit/retire at token boundaries.

Orca-style iteration-level scheduling over the slot engine (engine.py):
instead of freezing a batch for a whole generation (`generate()`'s scan),
the scheduler revisits the batch at EVERY token boundary — admitting queued
requests into freed slots, advancing one prefill chunk, decoding one token
for everyone in flight, and retiring finished sequences (their blocks
return to the pool immediately).

Admission policy — reservation-based, FCFS by default (the documented
seam, now a config knob):
- ``admit`` reserves a request's worst-case block count up front
  (``Engine.required_blocks``), all-or-nothing. An admitted request can
  therefore ALWAYS run to completion: pool exhaustion can only delay
  admissions, never strand in-flight work, so there is no deadlock and no
  need for mid-flight preemption — the liveness bar the serving smoke
  pins (`experiments/serving_bench.py` completes every request with the
  pool sized below peak naive demand). The cost is utilization: blocks a
  short-stopping request never writes sit reserved until retirement.
  vLLM's alternative — allocate lazily per block, preempt-and-recompute a
  victim on exhaustion — buys that utilization back at the price of
  recompute; swap `_admit` (and add victim selection) to explore it.
- ``admission="fcfs"`` (default): strict arrival order — the queue head
  blocks the line even when a smaller request behind it would fit.
  Keeping arrival order makes queue-wait percentiles meaningful under
  the Poisson load harness. This mode is byte-for-byte the pre-knob
  behavior (pinned in tests/test_fleet_serving.py).
- ``admission="sjf"``: size-aware — when the pool is tight (the head's
  reservation doesn't fit but a slot is free), admit the SHORTEST
  reservation among the same-priority queued requests that does fit,
  ties broken by arrival. Strictly more admissions per boundary under
  mixed lengths, at the price of possible head-of-line latency for the
  large request (its turn still comes: the pool drains toward its
  reservation, and ``submit`` already rejected anything that could
  never fit).
- Priorities (``Request.priority``, higher first): admission considers
  the highest-priority queued class first, FCFS (or SJF) within it.
  With every priority equal (the default 0) both modes reduce to their
  single-class behavior, so single-tenant streams are untouched.

Admission order is a LATENCY decision only: per-slot state (position, RNG
key, temperature) is carried per sequence and every engine op is
row-independent, so WHICH slot a request lands in — or who shares a step
with it — never changes its tokens (the bitwise bar in
tests/test_serving.py::test_admission_order_does_not_change_tokens).

Telemetry: every lifecycle edge emits a ``request_*`` event (schema v2,
telemetry/events.py) through the shared JSONL stream — queue wait, TTFT,
per-token progress, blocks held — rendered as p50/p95/p99 by
`experiments/obs_report.py`.

Tracing (schema v4, telemetry/trace.py): each request is ONE trace
(trace_id = the request id) with a ``request`` root span and
``queue`` → ``prefill`` (with per-tick ``prefill_chunk`` children) →
``decode`` → ``retire`` child spans, all on the scheduler's clock — so
queue-wait/TTFT percentiles and the span timeline agree by construction.
Contexts are held host-side per request and passed explicitly; nothing
crosses into the compiled engine programs, so the engine's two-programs
contract and the zero-in-jit-overhead invariant are untouched. A
``prefill_chunk`` span covers the whole engine step that advanced the
chunk (one compiled call serves every slot — the per-slot share is not
observable from the host), flagged with the chunk index; reassemble with
``telemetry.trace.trace_trees`` or export via
``experiments/trace_export.py``.

Host time by cause (``Scheduler.spans``, a ``telemetry.trace.Spans``, with
or without ``events=``): every ``tick()`` is a ``serve.tick`` span (counters
``n`` the tick's index, ``queued``, ``in_flight``, ``blocks_in_use`` as it
began, and ``state_bytes_in_use`` where the model carries state) round ``serve.admit`` (``admitted``), the engine's own
``engine.step`` and ``serve.emit`` (the loop over the engine's events to
the return: ``tokens``, ``retired``). They are on real time, never in the
event stream, and under a live profiler they stand on its timeline with
their counters (telemetry/trace.py), which is where a reader attributes
the chip's idle gaps inside a tick.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..telemetry.events import EventLog
from ..telemetry.trace import Span, Spans, Tracer
from .engine import Engine


@dataclass(frozen=True)
class Request:
    """One generation request. ``seed`` feeds ``jax.random.PRNGKey`` when
    ``temperature > 0`` (equal seed ⇒ the stream ``generate()`` would emit
    alone). ``arrival`` is an offset in seconds from workload start — the
    load harness's Poisson schedule, ignored by direct submitters.
    ``eos_id``: emitting this token retires the request at that token
    boundary, returning ALL its worst-case-reserved blocks immediately
    (the stream up to and including the EOS is still bitwise
    ``generate()``'s, which has no early stop — see ``Scheduler.tick``).
    ``tenant`` names the traffic class (frontend.TrafficClass) for
    per-class SLO accounting; ``priority`` orders admission (higher
    first) — both are latency knobs only, never token knobs."""
    rid: str
    prompt: Tuple[int, ...]
    max_new: int
    temperature: float = 0.0
    seed: int = 0
    arrival: float = 0.0
    eos_id: Optional[int] = None
    tenant: str = "default"
    priority: int = 0


@dataclass
class RequestRecord:
    """Per-request lifecycle + emitted tokens (the scheduler's ground truth
    for the zero-dropped/zero-duplicated assertion)."""
    rid: str
    prompt_len: int
    max_new: int
    blocks: int = 0
    tenant: str = "default"
    engine: Optional[int] = None   # fleet: which engine served it
    enqueue_t: Optional[float] = None
    admit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None
    tokens: List[int] = field(default_factory=list)

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.admit_t is None or self.enqueue_t is None:
            return None
        return self.admit_t - self.enqueue_t

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None or self.enqueue_t is None:
            return None
        return self.first_token_t - self.enqueue_t

    @property
    def tokens_per_sec(self) -> Optional[float]:
        if self.done_t is None or self.admit_t is None:
            return None
        dt = self.done_t - self.admit_t
        return len(self.tokens) / dt if dt > 0 else None


class Scheduler:
    """FCFS continuous batching over one Engine.

    >>> sched = Scheduler(engine, events=telemetry.events)
    >>> sched.submit(req, now=0.0)
    >>> while sched.outstanding:
    ...     sched.tick()
    >>> sched.records[req.rid].tokens
    """

    def __init__(self, engine: Engine, *, events: Optional[EventLog] = None,
                 token_events: bool = True,
                 clock: Callable[[], float] = time.monotonic,
                 engine_id: Optional[int] = None,
                 admission: str = "fcfs",
                 memory_every: int = 0):
        if admission not in ("fcfs", "sjf"):
            raise ValueError(f"admission must be 'fcfs' or 'sjf' "
                             f"(got {admission!r})")
        self.engine = engine
        self.events = events
        self.token_events = token_events
        self.clock = clock
        # Admission-policy seam (module docstring): "fcfs" is byte-for-byte
        # the historical behavior; "sjf" is size-aware within a priority.
        self.policy = admission
        # Fleet seam: tag every request_* event (and span) with the engine
        # this scheduler fronts, so an N-engine stream's percentiles can
        # be grouped per engine (obs_report) instead of pooled.
        self.engine_id = (engine_id if engine_id is not None
                          else getattr(engine, "engine_id", None))
        self._tag = ({"engine": self.engine_id}
                     if self.engine_id is not None else {})
        # Completions since the router last harvested (serving/fleet.py's
        # predicted-TTFT window feed): (done_t, ttft_s) appended at
        # retirement, drained by Router.harvest — bounded by whoever
        # consumes it, same O(requests) order as ``records`` without one.
        self.recent_done: List[Tuple[float, Optional[float]]] = []
        # Per-verify-dispatch speculation accounting (engine.last_spec
        # snapshots) — the host-side twin of the schema-v7 ``speculate``
        # events, kept even with no event stream so ServingReport can
        # compute acceptance/tokens-per-dispatch either way.
        self.spec_rounds: List[dict] = []
        if events is not None:
            # Late-bind the stream to the engine's compile watches: the
            # engine is built before any telemetry exists, but its
            # compilations (two programs plain, five with speculation —
            # and any retrace, a budget violation) should land in THIS
            # scheduler's event stream.
            from ..telemetry.introspect import bind_events
            for w in engine.watches():
                bind_events(w, events)
        # Per-request trace trees ride the scheduler's OWN clock (the load
        # harness fast-forwards it through idle gaps), so span timestamps
        # and the queue_wait_s/ttft_s latency fields share one timebase.
        self.tracer = (Tracer(events,
                              clock_ns=lambda: int(self.clock() * 1e9))
                       if events is not None else None)
        # Host time of a tick by cause (module docstring): always made,
        # never in the event stream, on the profiler's timeline when one
        # is live.
        self.spans = Spans()
        self._tick_n = 0
        self._spans: Dict[str, Dict[str, Span]] = {}   # rid -> open spans
        self._chunks: Dict[str, int] = {}              # rid -> chunks done
        # Live memory census (telemetry/memory.py, schema v9): every
        # ``memory_every``-th busy tick emits one ``memory`` event with
        # the pool occupancy + fragmentation census and this engine's
        # static params bytes. Default OFF (0): the serving hot loop pays
        # nothing — not even the counter compare — unless a harness arms
        # it; with it armed the census is host-list arithmetic only, so
        # served streams stay bitwise identical (the smoke pins this).
        self.memory_every = int(memory_every)
        self.memory_meter = None
        self._bytes_per_block = None
        self._ticks = 0
        if self.memory_every > 0:
            from ..telemetry.memory import MemoryMeter, tree_state_bytes
            self.memory_meter = MemoryMeter(events, source="serve")
            self.memory_meter.note(
                params_bytes=tree_state_bytes(engine.weights))
            try:
                from .kvcache import kv_bytes_per_token
                self._bytes_per_block = (
                    engine.paged.block_len
                    * kv_bytes_per_token(engine.cfg,
                                         engine.paged.kv_dtype))
            except Exception:
                self._bytes_per_block = None
        self.queue: List[Request] = []
        self.records: Dict[str, RequestRecord] = {}
        self._by_slot: Dict[int, Request] = {}
        self.completed = 0
        # High-water mark of in-flight requests, recorded AT admission —
        # the instant concurrency peaks. An end-of-tick sample would
        # undercount whenever a fully-loaded step also retires someone.
        self.peak_in_flight = 0

    # -------------------------------------------------------------- lifecycle
    def submit(self, req: Request, now: Optional[float] = None) -> None:
        """Enqueue; raises for a request NO pool state could ever serve
        (so the queue can never hold an unadmittable head — the liveness
        precondition)."""
        need = self.engine.required_blocks(len(req.prompt), req.max_new)
        positions = len(req.prompt) + req.max_new - 1
        if (need > self.engine.allocator.capacity
                or positions > self.engine.paged.max_seq_len):
            raise ValueError(
                f"{req.rid}: needs {positions} cache positions / {need} "
                f"blocks but the engine serves at most "
                f"{self.engine.paged.max_seq_len} positions / "
                f"{self.engine.allocator.capacity} blocks — oversized for "
                "this engine at any load")
        now = self.clock() if now is None else now
        self.queue.append(req)
        self.records[req.rid] = RequestRecord(
            rid=req.rid, prompt_len=len(req.prompt), max_new=req.max_new,
            blocks=need, tenant=req.tenant, engine=self.engine_id,
            enqueue_t=now)
        if self.events:
            self.events.request_enqueue(
                req=req.rid, prompt_len=len(req.prompt), max_new=req.max_new,
                temperature=req.temperature, queued=len(self.queue),
                tenant=req.tenant, priority=req.priority, **self._tag)
        if self.tracer:
            root = self.tracer.start("request", trace=req.rid,
                                     prompt_len=len(req.prompt),
                                     max_new=req.max_new, **self._tag)
            self._spans[req.rid] = {
                "root": root,
                "queue": self.tracer.start("queue", parent=root.ctx)}

    @property
    def outstanding(self) -> int:
        """Requests not yet retired (queued + in flight)."""
        return len(self.queue) + len(self._by_slot)

    def tick(self) -> List[Tuple[str, int]]:
        """One token boundary: admit, advance the engine, retire. Returns
        the (rid, token) pairs emitted this boundary."""
        # a model that carries state (a state-space mixer, short
        # convolutions): what its slots' state holds
        state = ({"state_bytes_in_use": self.engine.state_bytes_in_use()}
                 if self.engine.state_bytes_per_slot else {})
        with self.spans("serve.tick", n=self._tick_n, queued=len(self.queue),
                        in_flight=len(self._by_slot),
                        blocks_in_use=self.engine.blocks_in_use(), **state):
            self._tick_n += 1
            with self.spans("serve.admit") as admit:
                admit.set_metadata(admitted=self._admit())
            if not self.engine.busy:
                return []
            chunk_spans: List[Tuple[str, Span]] = []
            if self.tracer:
                # Slots without a first token advance exactly one prefill
                # chunk in this step (engine contract); open their chunk
                # spans BEFORE the step so the span covers the compiled
                # call.
                for slot, req in self._by_slot.items():
                    if self.records[req.rid].first_token_t is None:
                        i = self._chunks.get(req.rid, 0)
                        self._chunks[req.rid] = i + 1
                        chunk_spans.append((req.rid, self.tracer.start(
                            "prefill_chunk",
                            parent=self._spans[req.rid]["prefill"].ctx,
                            chunk=i)))
            events = self.engine.step()
            now = self.clock()  # post-step: token timestamps include the step
            for _, s in chunk_spans:
                s.end()
            with self.spans("serve.emit") as emit:
                retired = self.completed
                emitted = self._emit(events, now)
                emit.set_metadata(tokens=len(emitted),
                                  retired=self.completed - retired)
            return emitted

    def _emit(self, events, now: float) -> List[Tuple[str, int]]:
        """``tick()``'s second half: the engine's events into the records
        and the event stream, retirements, the speculation and memory
        accounting. Returns the (rid, token) pairs delivered."""
        emitted: List[Tuple[str, int]] = []
        eos_retired: set = set()
        eos_dropped = 0
        for ev in events:
            if ev.slot in eos_retired:
                # The slot EOS-retired earlier THIS tick (engine.step can
                # emit a final prefill token and a same-boundary decode
                # token for one slot): anything after the EOS is post-end
                # and never existed semantically — drop it. Scoped to
                # this tick's EOS retirements only, so an event for a
                # slot the scheduler genuinely doesn't own still raises
                # (a dropped-token bug must stay loud).
                eos_dropped += 1
                continue
            req = self._by_slot[ev.slot]
            rec = self.records[req.rid]
            rec.tokens.append(ev.token)
            if ev.first:
                rec.first_token_t = now
                if self.tracer:
                    spans = self._spans[req.rid]
                    spans["prefill"].end(
                        chunks=self._chunks.get(req.rid, 0))
                    spans["decode"] = self.tracer.start(
                        "decode", parent=spans["root"].ctx, slot=ev.slot)
            if self.events and self.token_events:
                self.events.request_token(req=req.rid,
                                          i=len(rec.tokens) - 1,
                                          tok=ev.token, slot=ev.slot,
                                          **self._tag)
            done = ev.done
            early_eos = False
            if not done and req.eos_id is not None and ev.token == req.eos_id:
                # EOS early retirement: the request is semantically
                # finished at THIS token boundary, so its blocks — the
                # whole worst-case reservation, including the tail it will
                # now never write — go back to the pool immediately
                # instead of idling until the max_new horizon. Purely a
                # capacity decision: the emitted stream is generate()'s
                # stream truncated at the first EOS (the engine never fed
                # the EOS back, so nothing downstream of it ever existed).
                # Under speculation one verify window can BOTH emit the
                # EOS mid-window and reach max_new at its last row — the
                # engine then already self-retired the slot while
                # emitting the tail this loop is about to drop, so the
                # explicit retire is conditional on the slot still being
                # live (blocks are back in the pool either way).
                if self.engine.slots[ev.slot] is not None:
                    self.engine.retire(ev.slot)
                eos_retired.add(ev.slot)
                done = early_eos = True
            if done:
                rec.done_t = now
                del self._by_slot[ev.slot]
                self.completed += 1
                self.recent_done.append((now, rec.ttft_s))
                if self.tracer:
                    spans = self._spans.pop(req.rid)
                    self._chunks.pop(req.rid, None)
                    # Always opened at the first token (a one-token request
                    # gets a zero-duration decode: first == done in one
                    # engine event).
                    spans["decode"].end(tokens=len(rec.tokens))
                    # The retire point: blocks (the whole worst-case
                    # reservation) return to the pool here — an instant on
                    # the timeline rather than an interval, since the free
                    # is a host list append.
                    self.tracer.start("retire", parent=spans["root"].ctx,
                                      blocks_freed=rec.blocks).end()
                    spans["root"].end(tokens=len(rec.tokens),
                                      **({"eos": True} if early_eos else {}))
                if self.events:
                    self.events.request_done(
                        req=req.rid, tokens=len(rec.tokens),
                        queue_wait_s=rec.queue_wait_s, ttft_s=rec.ttft_s,
                        tokens_per_sec=rec.tokens_per_sec,
                        blocks_freed=rec.blocks,
                        blocks_in_use=self.engine.blocks_in_use(),
                        tenant=req.tenant, **self._tag,
                        **({"eos": True} if early_eos else {}))
            emitted.append((req.rid, ev.token))
        if self.engine.last_spec is not None:
            # One ``speculate`` event per verify dispatch (schema v7):
            # the round's proposed/accepted/rejected counts — the
            # acceptance-rate and tokens-per-dispatch feed for obs_report
            # and slo_monitor's acceptance floor. Emitted AFTER the event
            # loop so ``emitted`` counts tokens actually DELIVERED: a
            # mid-window EOS drops the window tail above, and those
            # tokens must not inflate tokens-per-dispatch (the CI 2× bar
            # measures delivered throughput). proposed/accepted/rejected
            # stay verify-outcome accounting — EOS truncation is not a
            # draft failure, so the acceptance floor never sees it.
            spec = self.engine.last_spec
            if eos_dropped:
                spec = {**spec, "emitted": spec["emitted"] - eos_dropped}
            self.spec_rounds.append(spec)
            if self.events:
                self.events.speculate(**spec, **self._tag)
        if eos_dropped:
            # Keep the report's token count (ServingReport.decode_tokens
            # → tokens_per_dispatch) on the same delivered basis.
            self.engine.decode_tokens -= eos_dropped
        if self.memory_meter is not None:
            self._ticks += 1
            if self._ticks % self.memory_every == 0:
                from ..telemetry.memory import allocator_census
                self.memory_meter.sample(
                    tick=self._ticks, in_flight=len(self._by_slot),
                    queued=len(self.queue),
                    **allocator_census(
                        self.engine.allocator,
                        bytes_per_block=self._bytes_per_block),
                    **self._tag)
        return emitted

    # ---------------------------------------------------------- weight swap
    def swap_weights(self, params, version, *, fused=None) -> None:
        """Hot-swap the engine's weights at the CURRENT token boundary
        (between ``tick()`` calls — the only place this scheduler ever
        is, host-driven), without touching queued or in-flight requests:
        their next tokens sample under the new weights, nothing emitted
        changes, nothing recompiles (``Engine.swap_params`` enforces the
        equal-tree contract). Emits a ``deploy`` event + span (schema
        v6) carrying the publication ``version`` and how many streams
        crossed the swap live.

        With speculation on, a tick is one whole draft-propose + verify
        round, so a swap between ticks necessarily lands at a VERIFY
        boundary: a round's proposals and its verification always run
        under one generation of target weights — draft and target never
        mix generations mid-window. (The draft keeps its own weights; a
        stale draft can only lower acceptance, never correctness.)"""
        span = (self.tracer.start("deploy", trace=f"deploy-{version}",
                                  version=version,
                                  in_flight=len(self._by_slot),
                                  queued=len(self.queue), **self._tag)
                if self.tracer else None)
        self.engine.swap_params(params, fused=fused)
        if span is not None:
            span.end()
        if self.events:
            self.events.deploy(version=version,
                               in_flight=len(self._by_slot),
                               queued=len(self.queue), **self._tag)

    # -------------------------------------------------------------- admission
    def _pick_admittable(self) -> Optional[int]:
        """Queue index of the next request to admit under the policy seam
        (module docstring), or None when nothing admits this boundary.
        Highest priority class first; within it, FCFS — or, under "sjf"
        when the class head's reservation doesn't fit, the shortest
        fitting reservation (ties by arrival)."""
        if self.engine.free_slot() is None:
            # nothing admits without a slot: a full engine does not walk a
            # backlog of a thousand requests every tick to learn that
            return None
        top = max(r.priority for r in self.queue)
        first = next(i for i, r in enumerate(self.queue) if r.priority == top)
        head = self.queue[first]
        if self.engine.can_admit(len(head.prompt), head.max_new,
                                 prompt=head.prompt):
            return first
        if self.policy == "sjf":
            fitting = [i for i, r in enumerate(self.queue)
                       if r.priority == top
                       and self.engine.can_admit(len(self.queue[i].prompt),
                                                self.queue[i].max_new,
                                                prompt=self.queue[i].prompt)]
            if fitting:
                return min(fitting,
                           key=lambda i: (self.records[self.queue[i].rid]
                                          .blocks, i))
        return None

    def _admit(self) -> int:
        """Admit while the policy yields a fitting request; stop when the
        (priority-ordered) head blocks the line — under "fcfs" that is
        strict arrival order, byte-for-byte the historical behavior.
        Returns how many were admitted."""
        admitted = 0
        while self.queue:
            pick = self._pick_admittable()
            if pick is None:
                break
            admitted += 1
            head = self.queue.pop(pick)
            key = (jax.random.PRNGKey(head.seed)
                   if head.temperature > 0 else None)
            slot = self.engine.admit(np.asarray(head.prompt, np.int32),
                                     head.max_new,
                                     temperature=head.temperature, key=key)
            self._by_slot[slot] = head
            self.peak_in_flight = max(self.peak_in_flight,
                                      len(self._by_slot))
            rec = self.records[head.rid]
            rec.admit_t = self.clock()
            if self.tracer:
                spans = self._spans[head.rid]
                spans["queue"].end()
                spans["prefill"] = self.tracer.start(
                    "prefill", parent=spans["root"].ctx, slot=slot,
                    blocks=rec.blocks)
            if self.events:
                self.events.request_prefill(
                    req=head.rid, slot=slot, blocks=rec.blocks,
                    queue_wait_s=rec.queue_wait_s,
                    blocks_in_use=self.engine.blocks_in_use(),
                    **self._tag)
        return admitted
