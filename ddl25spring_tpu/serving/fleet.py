"""Serving fleet: SLO-aware multi-engine router + live weight hot-swap.

The scale-out half of the serving subsystem (ROADMAP item 1): PR 6's slot
engine serves one mesh; a production front end is MANY engines behind a
router. This module replicates the engine N ways — each replica keeps the
single-engine contract intact (two compiled programs, zero retraces,
streams bitwise ``generate()``'s) — and fronts them with:

- ``Router`` — per-request dispatch under a policy seam:
  * ``least_loaded``: fewest outstanding requests (queued + in flight),
    ties to the lowest engine id — deterministic given identical state.
  * ``predicted_ttft``: the same rolling-window shape
    ``experiments/slo_monitor.py`` evaluates SLOs over, fed per engine
    from completed-request TTFTs (``Scheduler.recent_done``): predicted
    TTFT on engine e = median TTFT over e's window × (1 + outstanding_e /
    num_slots) — a queue-depth-scaled service-time estimate. Engines with
    an empty window fall back to the fleet-wide window, then to
    least-loaded ordering, so cold starts still spread.
  Routing is a LATENCY decision only: per-slot state and row-independent
  engine math mean WHICH engine (like which slot) a request lands on can
  never change its tokens — the bitwise bar holds at any engine count
  (tests/test_fleet_serving.py pins N ∈ {1, 3} against ``generate()``).

- **Live weight hot-swap** — ``publish()`` hands the fleet a new
  (equal-shape) weight tree and rolls it out ONE ENGINE PER TICK: each
  engine swaps at its own token boundary (``Scheduler.swap_weights`` →
  ``Engine.swap_params``) without dropping queued or in-flight streams,
  and because the rollout staggers, the fleet is never globally paused —
  at most one engine is swapping at any boundary while the rest serve.
  The "drain" of the elastic discipline (resilience/elastic.py) is the
  token boundary itself: the host drives every compiled call, so between
  ticks an engine has nothing in flight by construction. Publication
  provenance (watching the trainer's checkpoint stream) lives in
  serving/deploy.py; this module only applies an already-loaded tree.

- **Active capacity** — ``set_active(k)`` restricts NEW routes to engines
  ``[0, k)`` while deactivated engines drain their outstanding work to
  completion. This is the serving half of the elasticity control plane:
  resilience/autoscale.py moves capacity between the training mesh and
  this fleet by pairing ``set_active`` with the trainer's elastic
  ``resize`` at a chunk edge (experiments/autoscale_smoke.py).

Telemetry (schema v6): one ``route`` event per dispatch decision, one
``deploy`` event + span per engine swap, and every ``request_*`` event
tagged with its ``engine`` — ``experiments/obs_report.py`` groups the
serving section per engine, ``experiments/slo_monitor.py`` issues
per-class/per-engine verdicts, and the ``deploy`` spans land on the
Perfetto timeline via ``experiments/trace_export.py``.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

from ..config import LlamaConfig
from ..telemetry.events import EventLog
from ..telemetry.registry import percentile
from .engine import Engine
from .frontend import _Clock, aggregate_latency
from .kvcache import PagedKVConfig, pool_bytes
from .scheduler import Request, RequestRecord, Scheduler

POLICIES = ("least_loaded", "predicted_ttft")


class Router:
    """SLO-aware dispatch over a set of schedulers (module docstring).

    Holds one rolling TTFT window per engine — the slo_monitor window
    shape: a deque of (t, value) pruned to ``window_s`` behind the
    scheduler clock — fed by ``harvest()`` from each scheduler's
    ``recent_done``. ``pick`` never mutates engine state; the decision
    inputs it used land in the ``route`` event for the stream to audit.
    """

    def __init__(self, scheds: Sequence[Scheduler], *,
                 policy: str = "least_loaded", window_s: float = 30.0,
                 events: Optional[EventLog] = None):
        if policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES} "
                             f"(got {policy!r})")
        self.scheds = list(scheds)
        self.policy = policy
        self.window_s = window_s
        self.events = events
        self._ttft: List[deque] = [deque() for _ in self.scheds]

    def harvest(self, now: float) -> None:
        """Pull new completions into the per-engine windows; prune."""
        horizon = now - self.window_s
        for dq, sched in zip(self._ttft, self.scheds):
            for t, ttft in sched.recent_done:
                if ttft is not None:
                    dq.append((t, ttft))
            sched.recent_done.clear()
            while dq and dq[0][0] < horizon:
                dq.popleft()

    def predicted_ttft(self, eid: int) -> Optional[float]:
        """Queue-depth-scaled TTFT estimate for a request dispatched to
        ``eid`` now; None while no window (anywhere) has a sample."""
        vals = [v for _, v in self._ttft[eid]]
        if not vals:       # cold engine: borrow the fleet-wide window
            vals = [v for dq in self._ttft for _, v in dq]
        if not vals:
            return None
        sched = self.scheds[eid]
        return percentile(vals, 50) * (
            1.0 + sched.outstanding / max(1, sched.engine.num_slots))

    def pick(self, req: Request, now: float,
             eligible: Optional[Sequence[int]] = None) -> int:
        """Choose the engine for ``req`` and emit the ``route`` event.
        ``eligible`` restricts the choice (the fleet's active-capacity
        seam: a drained-but-not-yet-reactivated engine must not receive
        new work); default is every engine."""
        self.harvest(now)
        ids = (list(eligible) if eligible is not None
               else list(range(len(self.scheds))))
        if not ids:
            raise ValueError("Router.pick: no eligible engines")
        loads = [s.outstanding for s in self.scheds]
        if self.policy == "least_loaded":
            eid = min(ids, key=lambda i: (loads[i], i))
            predicted = None
        else:
            predictions = {i: self.predicted_ttft(i) for i in ids}
            # No samples yet anywhere → identical (None) predictions:
            # the load/id tie-break below IS least-loaded, so a cold
            # fleet still spreads deterministically.
            eid = min(ids,
                      key=lambda i: (predictions[i]
                                     if predictions[i] is not None else 0.0,
                                     loads[i], i))
            predicted = predictions[eid]
        if self.events is not None:
            self.events.route(req=req.rid, engine=eid, policy=self.policy,
                              tenant=req.tenant, outstanding=loads,
                              predicted_ttft_s=predicted)
        return eid


class ServingFleet:
    """N slot engines behind one router, with staggered weight hot-swap.

    >>> fleet = ServingFleet(params, cfg, paged, num_engines=3,
    ...                      num_slots=8, events=telemetry.events)
    >>> fleet.submit(req)                       # router picks the engine
    >>> while fleet.outstanding:
    ...     fleet.tick()
    >>> fleet.publish(new_params, version=1200)  # rolls out over N ticks

    Every engine is a full PR 6 engine (own pool, own two compiled
    programs); the fleet adds routing, the publish rollout, and merged
    accounting. ``admission`` passes through to every scheduler
    (scheduler.py's policy seam)."""

    def __init__(self, params: dict, cfg: LlamaConfig, paged: PagedKVConfig,
                 *, num_engines: int, num_slots: int,
                 prefill_chunk: int = 16, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 events: Optional[EventLog] = None,
                 token_events: bool = True,
                 policy: str = "least_loaded", window_s: float = 30.0,
                 admission: str = "fcfs", speculate=None,
                 prefix_share: bool = False,
                 memory_every: int = 0,
                 clock: Callable[[], float] = time.monotonic):
        if num_engines < 1:
            raise ValueError(f"num_engines={num_engines}")
        if not isinstance(cfg, LlamaConfig):
            raise NotImplementedError(
                "the fleet routes over engines of LlamaConfig models only: "
                "its publish path fuses and swaps that tree "
                f"({type(cfg).__name__} is served by one Engine; ROADMAP.md)")
        self.cfg = cfg
        self.paged = paged
        self.clock = clock
        # ``speculate`` (serving/speculate.py SpecConfig) arms EVERY
        # engine with the draft + verify programs — per-engine draft
        # pools, like per-engine block pools. ``prefix_share`` likewise
        # (prefix caches are per engine: blocks are physical pool
        # indices, so sharing cannot cross engines — the routing seam
        # ROADMAP 1b's prefix-affinity policy will exploit).
        self.engines = [Engine(params, cfg, paged, num_slots,
                               prefill_chunk=prefill_chunk, top_k=top_k,
                               top_p=top_p, engine_id=i,
                               speculate=speculate,
                               prefix_share=prefix_share)
                        for i in range(num_engines)]
        # ``memory_every`` arms each scheduler's per-engine memory meter
        # (scheduler.py; schema v9) — every census event carries its
        # ``engine`` tag, so the fleet's N pools stay distinguishable.
        self.scheds = [Scheduler(eng, events=events,
                                 token_events=token_events, clock=clock,
                                 engine_id=i, admission=admission,
                                 memory_every=memory_every)
                       for i, eng in enumerate(self.engines)]
        self.router = Router(self.scheds, policy=policy, window_s=window_s,
                             events=events)
        self.engine_of: Dict[str, int] = {}     # rid -> routed engine
        self._swap = None       # pending publish: rolls out one engine/tick
        self._active = num_engines  # engines [0, _active) accept new work
        self.deploys: List[dict] = []

    # ------------------------------------------------------------- capacity
    @property
    def active_engines(self) -> int:
        """How many engines currently accept NEW requests."""
        return self._active

    def set_active(self, k: int) -> None:
        """Serve new requests on engines ``[0, k)`` only — the autoscaler's
        capacity seam (resilience/autoscale.py). Shrinking DRAINS rather
        than drops: a deactivated engine stops receiving routes immediately
        but ``tick()`` keeps advancing any engine with outstanding work, so
        its queued and in-flight streams finish on the engine they started
        on (per-slot state cannot migrate) — same chunk-edge discipline as
        the trainer's elastic drain. Growing is instant: a reactivated
        engine holds no state a request could miss (weights roll out to
        every engine regardless of active status, see ``publish``)."""
        k = int(k)
        if not 1 <= k <= len(self.engines):
            raise ValueError(f"set_active({k}): fleet has "
                             f"{len(self.engines)} engines; need 1 <= k <= "
                             f"{len(self.engines)}")
        self._active = k

    # ------------------------------------------------------------- dispatch
    def submit(self, req: Request, now: Optional[float] = None) -> int:
        now = self.clock() if now is None else now
        eid = self.router.pick(req, now, eligible=range(self._active))
        self.scheds[eid].submit(req, now=now)
        self.engine_of[req.rid] = eid
        return eid

    @property
    def outstanding(self) -> int:
        return sum(s.outstanding for s in self.scheds)

    @property
    def swap_pending(self) -> bool:
        return self._swap is not None

    def tick(self) -> List[tuple]:
        """One fleet boundary: advance the publish rollout by AT MOST one
        engine (the stagger that keeps the fleet serving through a
        deploy), then tick every engine with work. Returns the merged
        (rid, token) pairs."""
        if self._swap is not None:
            # Peek-then-pop: the engine leaves the rollout only AFTER its
            # swap succeeded, so an unexpected per-engine failure neither
            # drops the engine from the rollout nor wedges the fleet with
            # a half-applied publish (publish() already validated the
            # tree, so the expected failure mode here is none).
            eid = self._swap["remaining"][0]
            self.scheds[eid].swap_weights(self._swap["params"],
                                          self._swap["version"],
                                          fused=self._swap["fused"])
            self._swap["remaining"].popleft()
            self.deploys.append({"version": self._swap["version"],
                                 "engine": eid, "t": self.clock()})
            if not self._swap["remaining"]:
                self._swap = None
        emitted: List[tuple] = []
        for sched in self.scheds:
            if sched.outstanding:
                emitted.extend(sched.tick())
        return emitted

    # -------------------------------------------------------------- publish
    def publish(self, params: dict, *, version) -> None:
        """Queue a fleet-wide weight swap: engine i swaps at the i-th
        subsequent ``tick()``'s boundary. Validates the equal-tree
        contract HERE, against the current weights, so a bad publish
        fails atomically with the fleet untouched and fully serviceable
        (every engine holds the same tree, so one verdict is every
        engine's); fuses the block stack ONCE for all engines."""
        if self._swap is not None:
            raise RuntimeError(
                f"publish({version!r}): previous publish "
                f"{self._swap['version']!r} is still rolling out "
                f"({len(self._swap['remaining'])} engines to go)")
        from ..models import generate
        from .engine import _match_placement, check_swappable
        check_swappable(self.engines[0].boot, params)
        # Normalize placement ONCE against the fleet's boot params (every
        # engine was built from the same tree, so one reference serves
        # all): each engine's swap then re-validates but never re-copies,
        # and the fused view is computed from the already-normalized tree.
        params = _match_placement(params, self.engines[0].boot)
        self._swap = {"version": version, "params": params,
                      "fused": generate._fuse_blocks(params["blocks"]),
                      "remaining": deque(range(len(self.engines)))}

    # ----------------------------------------------------------- accounting
    @property
    def records(self) -> Dict[str, RequestRecord]:
        merged: Dict[str, RequestRecord] = {}
        for sched in self.scheds:
            merged.update(sched.records)
        return merged

    @property
    def completed(self) -> int:
        return sum(s.completed for s in self.scheds)

    def pool_headroom(self, k: Optional[int] = None) -> float:
        """Min free-block fraction across the first ``k`` engines (default:
        the currently active set) — the autoscaler's guard-rail feed
        (resilience/autoscale.py ``min_headroom_frac``): scaling serving
        UP is only safe if the pools it lands on have room. Host list
        arithmetic only; pass a prospective ``k`` to ask "would k active
        engines have headroom?" before committing the scale."""
        k = self._active if k is None else max(1, min(int(k),
                                                      len(self.engines)))
        return min(e.allocator.free_blocks / max(1, e.allocator.capacity)
                   for e in self.engines[:k])

    def compiles(self) -> List[int]:
        return [sum(len(w.compiles) for w in e.watches())
                for e in self.engines]

    def retraces(self) -> List[int]:
        return [sum(w.retraces for w in e.watches())
                for e in self.engines]


@dataclass
class FleetReport:
    """One fleet run's outcome: merged records, fleet-wide + per-class +
    per-engine aggregates, per-engine compile/retrace budgets (each engine
    promises exactly two programs, zero retraces — across any number of
    hot-swaps), and the deploy rollout log."""
    records: Dict[str, RequestRecord]
    aggregates: dict
    per_class: Dict[str, dict]
    per_engine: Dict[int, dict]
    engine_of: Dict[str, int]
    wall_s: float
    num_engines: int
    pool_blocks: int
    pool_bytes_per_engine: int
    peak_blocks_per_engine: List[int] = field(default_factory=list)
    compiles: List[int] = field(default_factory=list)
    retraces: List[int] = field(default_factory=list)
    deploys: List[dict] = field(default_factory=list)
    requests: List[Request] = field(default_factory=list)


def run_serving_fleet(params: dict, cfg: LlamaConfig, paged: PagedKVConfig,
                      workload: Sequence[Request], *, num_engines: int,
                      num_slots: int, prefill_chunk: int = 16,
                      top_k: Optional[int] = None,
                      top_p: Optional[float] = None,
                      events: Optional[EventLog] = None,
                      token_events: bool = True,
                      policy: str = "least_loaded", window_s: float = 30.0,
                      admission: str = "fcfs", speculate=None,
                      prefix_share: bool = False,
                      memory_every: int = 0,
                      publish_after: Optional[int] = None,
                      publish_params: Optional[dict] = None,
                      publish_version=None) -> FleetReport:
    """``frontend.run_serving`` generalized to N engines: replay the
    workload through a fresh fleet in (fast-forwarded) real time. With
    ``publish_after`` set, one live publish of ``publish_params`` fires
    at the first boundary where that many requests have completed —
    the mid-run hot-swap the fleet smoke drives (same-weights there, so
    the bitwise bar holds across it). The loop's only exits are
    completion + a drained rollout: reservation-based admission cannot
    deadlock, and a pending swap applies within ``num_engines`` ticks."""
    clock = _Clock()
    fleet = ServingFleet(params, cfg, paged, num_engines=num_engines,
                         num_slots=num_slots, prefill_chunk=prefill_chunk,
                         top_k=top_k, top_p=top_p, events=events,
                         token_events=token_events, policy=policy,
                         window_s=window_s, admission=admission,
                         speculate=speculate, prefix_share=prefix_share,
                         memory_every=memory_every, clock=clock.now)
    pending = sorted(workload, key=lambda r: (r.arrival, r.rid))
    published = publish_after is None
    busy_s = 0.0
    i = 0
    while i < len(pending) or fleet.outstanding or fleet.swap_pending:
        now = clock.now()
        while i < len(pending) and pending[i].arrival <= now:
            fleet.submit(pending[i], now=now)
            i += 1
        if not published and fleet.completed >= publish_after:
            fleet.publish(publish_params, version=publish_version)
            published = True
        if (fleet.outstanding == 0 and not fleet.swap_pending
                and i < len(pending)):
            clock.fast_forward(pending[i].arrival)   # idle: jump, not sleep
            continue
        fleet.tick()
        busy_s += clock.now() - now
    records = fleet.records
    classes = sorted({r.tenant for r in records.values()})
    per_class = {c: aggregate_latency({k: r for k, r in records.items()
                                       if r.tenant == c})
                 for c in classes}
    per_engine = {}
    for eid in range(num_engines):
        agg = aggregate_latency({k: r for k, r in records.items()
                                 if r.engine == eid})
        agg["peak_blocks_in_use"] = fleet.engines[eid].allocator.peak_in_use
        per_engine[eid] = agg
    return FleetReport(
        records=records,
        aggregates=aggregate_latency(records, busy_span_s=busy_s),
        per_class=per_class, per_engine=per_engine,
        engine_of=dict(fleet.engine_of), wall_s=clock.now(),
        num_engines=num_engines,
        pool_blocks=fleet.engines[0].allocator.capacity,
        pool_bytes_per_engine=pool_bytes(cfg, paged),
        peak_blocks_per_engine=[e.allocator.peak_in_use
                                for e in fleet.engines],
        compiles=fleet.compiles(), retraces=fleet.retraces(),
        deploys=list(fleet.deploys), requests=list(workload))
