"""Live SLO monitor: tail a telemetry stream, flag rolling-window breaches.

The watch-it-while-it-runs half of the observability layer (ISSUE 8): the
event stream and obs_report explain a run after the fact; this tool reads
the SAME stream while the run is alive and raises ``slo_violation`` events
the moment a rolling-window objective breaks. Pure stdlib + the telemetry
read helpers — never imports jax — so it runs as a sidecar (or inside the
watchdog, which embeds ``SLOMonitor`` as a health signal next to the
heartbeat).

Objectives (each enabled by passing its threshold):
- ``--ttft-p99``   p99 time-to-first-token (s) over the window
  (``request_done.ttft_s``);
- ``--queue-p99``  p99 queue wait (s) over the window;
- ``--min-tps``    sustained tokens/sec floor — violated only while work
  is OUTSTANDING (enqueued > done), so an idle server is not "stalled";
- ``--max-skip-rate``  StepGuard skips per training step over the window
  (``fault`` counter deltas / ``step`` event step counts);
- ``--heartbeat-stale``  seconds since the heartbeat moved (live mode
  reads heartbeat.json next to the stream; check mode compares the last
  beat to the last event);
- ``--slo-mfu``    MFU floor over the window — achieved FLOP/s from the
  ``compile`` events' HLO flops (normalized per step by each event's own
  ``steps_per_dispatch``, so ragged tail-chunk programs don't skew the
  window) × the window's step count ÷ the window's step time, against
  the manifest's recorded roofline peaks (the chip's published peaks
  by device_kind, the calibrated baseline on the CPU; schema v5).
  Caveat: ``cost_analysis`` counts
  a ``lax.scan`` body once (still so under jax 0.9.0), so a fused
  K-step program's flops read as ONE step's and chunked-mode MFU is
  biased low by ~K — set the floor from the same stream's observed
  steady-state values, not from first principles;
- ``--slo-gradnorm``  grad-norm spike-rate ceiling: the fraction of the
  window's ``numerics`` samples whose global grad norm exceeds
  ``--gradnorm-factor`` × the window median (the drift signal that
  precedes a StepGuard skip);
- ``--slo-headroom``  OOM-headroom floor (schema v9 ``memory`` events):
  the free fraction of the ``--device-bytes`` budget left by the
  window's PEAK sampled ``device_bytes`` (params + optimizer moments +
  residuals + window + KV pool — telemetry/memory.py's census). Peak,
  not latest: a pool that spikes into the red between samples of calm
  is the OOM precursor this objective exists to catch. Requires
  ``--device-bytes`` (the per-device budget to judge against — an HBM
  size on chip, an explicit budget in CI);
- ``--class-slo NAME:ttft_p99=S[,queue_p99=S]`` (repeatable) — PER-CLASS
  objectives over the multi-tenant fleet's ``request_done`` events
  (schema v6 ``tenant`` tags, serving/frontend.py TrafficClass):
  each class gets its own rolling p99 windows, and a breach is reported
  as ``<class>:ttft_p99_s`` so one tenant's misses never hide in a
  fleet-wide percentile. The summary additionally carries a
  ``breakdown`` of run-total per-class AND per-engine latency aggregates
  (the ``engine`` tags the fleet scheduler stamps), so an N-engine
  stream yields per-engine verdicts next to the aggregate one.

Two modes:
- **live** (default): follow the growing file (incremental reads, torn
  final line buffered until its newline arrives — the tailer never
  misparses a mid-write line), evaluate every ``--poll``, print and (with
  ``--emit``, default ON live) append ``slo_violation`` events to the
  stream — O_APPEND keeps the writer's lines and ours from interleaving,
  and ``iter_runs`` keeps the runs apart. Stops at ``--duration``, or at
  the stream's ``run_end`` once nothing is outstanding.
- **--check**: replay a COMPLETE stream in event time (no wall clock),
  evaluating once per quarter-window; nonzero exit when any objective was
  breached — the CI mode tier1.yml runs over the serving smoke's stream.

Example (the serving smoke's stream):
    python -m experiments.slo_monitor serving-telemetry --check \\
        --ttft-p99 5.0 --min-tps 10
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from ddl25spring_tpu.telemetry.events import EventLog, read_events
from ddl25spring_tpu.telemetry.heartbeat import read_heartbeat
from ddl25spring_tpu.telemetry.introspect import FlightRecorder
from ddl25spring_tpu.telemetry.registry import percentile


class StreamTailer:
    """Incremental JSONL reader for a growing file.

    Keeps a byte offset and buffers a torn final line until its newline
    arrives — a mid-``write()`` line is never misparsed, the same
    tolerance as ``read_events`` but without re-reading the file each
    poll. ``from_end=True`` starts at the CURRENT end of file: the
    watchdog monitors only what happens after it attaches, so a dead
    run's leftovers (its never-completed request_enqueue events) cannot
    poison a fresh monitor's outstanding-work counters. A file that
    SHRANK is handled per mode: the default resets to 0 and re-reads (a
    recycled dir — duplicate events are harmless to a rolling window,
    silence about a new run is not), while ``from_end`` re-attaches at
    the new end — the common shrink there is a relaunched writer's
    EventLog healing a torn fragment by a few bytes, and a reset to 0
    would replay the whole dead-run history ``from_end`` exists to
    skip."""

    def __init__(self, path: str, *, from_end: bool = False):
        self.path = path
        self._from_end = from_end
        self._offset = 0
        if from_end:
            # Attach after the last NEWLINE, not at raw EOF: if the file
            # currently ends in a dead writer's torn fragment, a
            # relaunching EventLog will heal it by truncating to exactly
            # that newline — an attach at raw EOF would then sit past the
            # truncation point and (after the file regrows) read from the
            # middle of a new line, losing its first event.
            try:
                with open(path, "rb") as f:
                    f.seek(0, os.SEEK_END)
                    size = f.tell()
                    back = min(size, 1 << 16)
                    f.seek(size - back)
                    nl = f.read(back).rfind(b"\n")
                    self._offset = size - back + nl + 1 if nl != -1 else 0
            except OSError:
                pass                      # no file yet: start at 0
        self._buf = b""

    def poll(self) -> List[Dict[str, Any]]:
        try:
            size = os.stat(self.path).st_size
            if size < self._offset:
                self._offset = size if self._from_end else 0
                self._buf = b""
            with open(self.path, "rb") as f:
                f.seek(self._offset)
                data = f.read()
        except OSError:
            return []
        if not data:
            return []
        self._offset += len(data)
        lines = (self._buf + data).split(b"\n")
        self._buf = lines.pop()        # b"" when data ended in a newline
        events = []
        for line in lines:
            if not line.strip():
                continue
            try:
                e = json.loads(line)
            except ValueError:
                continue               # sealed fragment / corruption: skip
            if isinstance(e, dict):
                events.append(e)
        return events


@dataclass
class SLOConfig:
    """Thresholds; ``None`` disables an objective."""
    window_s: float = 30.0
    ttft_p99_s: Optional[float] = None
    queue_p99_s: Optional[float] = None
    min_tokens_per_sec: Optional[float] = None
    max_skip_rate: Optional[float] = None
    heartbeat_stale_s: Optional[float] = None
    # Run-health objectives (schema v5 numerics/compile events).
    min_mfu: Optional[float] = None
    max_gradnorm_spike_rate: Optional[float] = None
    gradnorm_spike_factor: float = 10.0
    # Speculative-decoding acceptance floor (schema v7 ``speculate``
    # events): accepted/proposed draft tokens over the window. A
    # degenerate draft decays acceptance toward 0 (at the tokens-per-
    # dispatch level, toward 1/(k+1) of the window) — a THROUGHPUT
    # regression the tok/s floor may not catch on a lightly-loaded
    # fleet, so it is its own objective, not a silent slowdown.
    min_acceptance_rate: Optional[float] = None
    # OOM-headroom floor (schema v9 ``memory`` events): minimum free
    # fraction of ``device_budget_bytes`` left by the window's peak
    # sampled ``device_bytes``. Both must be set for the objective to
    # arm — a floor without a budget has nothing to judge against.
    min_headroom_frac: Optional[float] = None
    device_budget_bytes: Optional[float] = None
    # Per-traffic-class objectives (schema v6 ``tenant`` tags):
    # {class: {"ttft_p99_s": s, "queue_p99_s": s}} — the
    # serving.frontend.class_slos shape. Violations are keyed
    # "<class>:<objective>".
    per_class: Optional[Dict[str, Dict[str, float]]] = None


class SLOMonitor:
    """Rolling-window SLO state machine: ``feed`` events (any order of
    types; timestamps from their ``t`` field), then ``evaluate(now)``.

    A violation is reported on the ok→breached TRANSITION per objective
    (and again if it re-breaches after recovering), not on every poll —
    a sustained breach is one incident, not one event per second. The
    currently-breached set is ``active``; every incident ever seen is in
    ``violations``."""

    def __init__(self, cfg: SLOConfig, emit: Optional[EventLog] = None):
        self.cfg = cfg
        self.emit = emit
        self._ttft: deque = deque()     # (t, seconds)
        self._wait: deque = deque()     # (t, seconds)
        self._tokens: deque = deque()   # (t, count)
        self._token_events = False      # stream has per-token granularity
        self.first_token_t: Optional[float] = None
        self._skips: deque = deque()    # (t, count)
        self._steps: deque = deque()    # (t, count)
        # Run-health state (schema v5): dispatch timing from non-warmup
        # step events, program flops from compile events, peaks from the
        # manifest, grad norms from numerics samples. Flops are held
        # PER STEP — each compile event's flops divided by the step count
        # that event itself carries — so a tail-chunk program (smaller
        # flops AND smaller window) normalizes the same as the full-K one
        # and last-compile-wins cannot skew the floor.
        self._dts: deque = deque()      # (t, steps, dt_s)
        self._gradnorms: deque = deque()  # (t, grad_norm)
        self._spec: deque = deque()     # (t, proposed, accepted)
        self._mem: deque = deque()      # (t, device_bytes) — schema v9
        self._flops_per_step: Optional[float] = None
        self._peak_flops: Optional[float] = None
        # Per-class rolling windows (one ttft + one wait deque per class
        # with a configured SLO) and run-total per-class / per-engine
        # accumulators for the summary breakdown — totals, not windows:
        # the breakdown is a run verdict, the windows are the live alarm.
        self._cls_ttft: Dict[str, deque] = {}
        self._cls_wait: Dict[str, deque] = {}
        self._by_class: Dict[str, dict] = {}
        self._by_engine: Dict[Any, dict] = {}
        self.enqueued = 0
        self.done = 0
        self.run_ended = False
        self.first_event_t: Optional[float] = None
        self.last_event_t: Optional[float] = None
        self.active: Dict[str, dict] = {}
        self.violations: List[dict] = []

    def feed(self, events: List[Dict[str, Any]]) -> None:
        for e in events:
            t = e.get("t")
            if not isinstance(t, (int, float)):
                continue
            self.first_event_t = (t if self.first_event_t is None
                                  else min(self.first_event_t, t))
            self.last_event_t = (t if self.last_event_t is None
                                 else max(self.last_event_t, t))
            etype = e.get("type")
            if etype == "request_enqueue":
                self.enqueued += 1
            elif etype == "request_token":
                if not self._token_events:
                    # First per-token event: from here tokens are counted
                    # at token granularity, never ALSO at done granularity
                    # (a request's tokens always precede its done, so no
                    # done was ever counted before this flips).
                    self._token_events = True
                    self._tokens.clear()
                self._tokens.append((t, 1))
                if self.first_token_t is None or t < self.first_token_t:
                    self.first_token_t = t
            elif etype == "request_done":
                self.done += 1
                if not self._token_events and isinstance(e.get("tokens"),
                                                         int):
                    # Streams recorded with Scheduler(token_events=False)
                    # still carry throughput at completion granularity —
                    # without this, the tok/s floor would read a healthy
                    # quiet-stream server as permanently stalled.
                    self._tokens.append((t, e["tokens"]))
                    if self.first_token_t is None or t < self.first_token_t:
                        self.first_token_t = t
                if isinstance(e.get("ttft_s"), (int, float)):
                    self._ttft.append((t, e["ttft_s"]))
                if isinstance(e.get("queue_wait_s"), (int, float)):
                    self._wait.append((t, e["queue_wait_s"]))
                self._feed_done_tags(t, e)
            elif etype == "fault":
                counters = e.get("counters") or {}
                skips = counters.get("skipped_steps", 0)
                if isinstance(skips, int) and skips > 0:
                    self._skips.append((t, skips))
            elif etype == "step":
                steps = e.get("steps")
                if isinstance(steps, int) and steps > 0:
                    self._steps.append((t, steps))
                    if (not e.get("warmup")
                            and isinstance(e.get("dt_s"), (int, float))
                            and e["dt_s"] > 0):
                        self._dts.append((t, steps, e["dt_s"]))
            elif etype == "manifest":
                peaks = e.get("peaks") or {}
                if isinstance(peaks.get("flops_per_sec"), (int, float)):
                    self._peak_flops = peaks["flops_per_sec"]
            elif etype == "compile":
                if isinstance(e.get("flops"), (int, float)) and e["flops"] > 0:
                    spd = e.get("steps_per_dispatch")
                    spd = spd if isinstance(spd, int) and spd > 0 else 1
                    self._flops_per_step = e["flops"] / spd
            elif etype == "numerics":
                if isinstance(e.get("grad_norm"), (int, float)):
                    self._gradnorms.append((t, e["grad_norm"]))
            elif etype == "speculate":
                if (isinstance(e.get("proposed"), int)
                        and isinstance(e.get("accepted"), int)
                        and e["proposed"] > 0):
                    self._spec.append((t, e["proposed"], e["accepted"]))
            elif etype == "memory":
                if isinstance(e.get("device_bytes"), (int, float)) \
                        and e["device_bytes"] >= 0:
                    self._mem.append((t, e["device_bytes"]))
            elif etype == "run_end":
                self.run_ended = True

    # Per-(class/engine) breakdown samples kept per group: ``done`` counts
    # stay exact, but the latency lists are bounded — the live monitor is
    # a days-long sidecar, and unbounded per-request accumulation is
    # exactly the leak this tool exists to catch in others. At the cap
    # the percentiles become most-recent-window figures (still exact for
    # CI-scale --check replays, which stay far below it).
    BREAKDOWN_CAP = 10_000

    def _feed_done_tags(self, t: float, e: Dict[str, Any]) -> None:
        """Per-class windows (only classes with a configured SLO) and
        run-total class/engine breakdown accumulators, from one
        ``request_done``'s ``tenant``/``engine`` tags (schema v6)."""
        ttft = e.get("ttft_s")
        wait = e.get("queue_wait_s")
        cls = e.get("tenant")
        if isinstance(cls, str) and self.cfg.per_class \
                and cls in self.cfg.per_class:
            if isinstance(ttft, (int, float)):
                self._cls_ttft.setdefault(cls, deque()).append((t, ttft))
            if isinstance(wait, (int, float)):
                self._cls_wait.setdefault(cls, deque()).append((t, wait))
        for key, agg in ((cls, self._by_class),
                         (e.get("engine"), self._by_engine)):
            if key is None:
                continue
            rec = agg.setdefault(
                key, {"done": 0, "ttft": deque(maxlen=self.BREAKDOWN_CAP),
                      "wait": deque(maxlen=self.BREAKDOWN_CAP)})
            rec["done"] += 1
            if isinstance(ttft, (int, float)):
                rec["ttft"].append(ttft)
            if isinstance(wait, (int, float)):
                rec["wait"].append(wait)

    def breakdown(self) -> Dict[str, Any]:
        """Run-total per-class and per-engine latency aggregates — the
        summary's group-by view of the same stream the rolling windows
        alarm on (keys stringified for JSON)."""
        def agg(groups):
            return {str(k): {
                "done": rec["done"],
                "ttft_p99_s": (percentile(rec["ttft"], 99)
                               if rec["ttft"] else None),
                "queue_p99_s": (percentile(rec["wait"], 99)
                                if rec["wait"] else None),
            } for k, rec in sorted(groups.items(), key=lambda kv:
                                   str(kv[0]))}
        return {"per_class": agg(self._by_class),
                "per_engine": agg(self._by_engine)}

    def _prune(self, now: float) -> None:
        horizon = now - self.cfg.window_s
        for dq in (self._ttft, self._wait, self._tokens, self._skips,
                   self._steps, self._dts, self._gradnorms, self._spec,
                   self._mem,
                   *self._cls_ttft.values(), *self._cls_wait.values()):
            while dq and dq[0][0] < horizon:
                dq.popleft()

    def evaluate(self, now: float,
                 heartbeat: Optional[dict] = None) -> List[dict]:
        """Measure every enabled objective over [now - window, now];
        returns the NEW violations (transitions into breach)."""
        self._prune(now)
        cfg = self.cfg
        measured: Dict[str, tuple] = {}   # slo -> (value, threshold)
        if cfg.ttft_p99_s is not None and self._ttft:
            v = percentile([x for _, x in self._ttft], 99)
            if v > cfg.ttft_p99_s:
                measured["ttft_p99_s"] = (v, cfg.ttft_p99_s)
        if cfg.queue_p99_s is not None and self._wait:
            v = percentile([x for _, x in self._wait], 99)
            if v > cfg.queue_p99_s:
                measured["queue_p99_s"] = (v, cfg.queue_p99_s)
        for cls, limits in (cfg.per_class or {}).items():
            # Per-class windows: a quiet class has an empty window and no
            # verdict (idle ≠ breached — same posture as the global
            # objectives), a busy one is judged against ITS thresholds.
            for slo, dq in (("ttft_p99_s", self._cls_ttft.get(cls)),
                            ("queue_p99_s", self._cls_wait.get(cls))):
                limit = limits.get(slo)
                if limit is None or not dq:
                    continue
                v = percentile([x for _, x in dq], 99)
                if v > limit:
                    measured[f"{cls}:{slo}"] = (v, limit)
        if (cfg.min_tokens_per_sec is not None
                and self.enqueued > self.done):
            # Outstanding work is what makes a low rate a STALL rather
            # than an idle lull. Two regimes:
            # - no token has EVER arrived: that is startup (XLA compile),
            #   not a throughput deficit — grant one full window from the
            #   stream's birth before calling it a stall (a compile
            #   longer than the window is indistinguishable from one);
            # - tokens have flowed: judge the floor over the OBSERVED
            #   span since the first token, capped at the window — a
            #   partial window must not deflate a healthy rate, and the
            #   pre-first-token compile gap must not count against it.
            if self.first_token_t is None:
                if (self.first_event_t is not None
                        and now - self.first_event_t > cfg.window_s):
                    measured["tokens_per_sec"] = (0.0,
                                                  cfg.min_tokens_per_sec)
            else:
                span = min(cfg.window_s,
                           max(now - self.first_token_t, 1e-9))
                v = sum(n for _, n in self._tokens) / span
                if v < cfg.min_tokens_per_sec:
                    measured["tokens_per_sec"] = (v, cfg.min_tokens_per_sec)
        if (cfg.min_mfu is not None and self._dts
                and self._flops_per_step and self._peak_flops):
            # Achieved FLOP/s over the window's step events: per-step
            # program flops × steps ÷ step seconds (per-step, so chunked
            # runs with ragged tail programs normalize correctly).
            steps = sum(s for _, s, _ in self._dts)
            secs = sum(d for _, _, d in self._dts)
            if secs > 0 and steps > 0:
                v = (self._flops_per_step * steps / secs
                     / self._peak_flops)
                if v < cfg.min_mfu:
                    measured["mfu"] = (v, cfg.min_mfu)
        if (cfg.max_gradnorm_spike_rate is not None
                and len(self._gradnorms) >= 4):
            # Spike = a sample above factor × the window MEDIAN (robust
            # to the spikes themselves); at least 4 samples so a lone
            # sample can never be its own baseline.
            norms = sorted(x for _, x in self._gradnorms)
            median = norms[len(norms) // 2]
            if median > 0:
                spikes = sum(x > cfg.gradnorm_spike_factor * median
                             for _, x in self._gradnorms)
                v = spikes / len(self._gradnorms)
                if v > cfg.max_gradnorm_spike_rate:
                    measured["gradnorm_spike_rate"] = (
                        v, cfg.max_gradnorm_spike_rate)
        if cfg.min_acceptance_rate is not None and self._spec:
            # Windowed acceptance over verify dispatches. Idle (no
            # speculate events in the window) is not a breach — same
            # posture as the latency objectives; a DEGENERATE draft keeps
            # proposing and failing, which is exactly what lands here.
            prop = sum(p for _, p, _ in self._spec)
            acc = sum(a for _, _, a in self._spec)
            if prop > 0:
                v = acc / prop
                if v < cfg.min_acceptance_rate:
                    measured["spec_acceptance_rate"] = (
                        v, cfg.min_acceptance_rate)
        if (cfg.min_headroom_frac is not None and cfg.device_budget_bytes
                and self._mem):
            # Headroom = free fraction of the budget at the window's PEAK
            # sample (an idle window is no verdict, same as the latency
            # objectives). Can go negative: a census already over budget
            # reads as negative headroom, unambiguously breached.
            peak = max(b for _, b in self._mem)
            v = 1.0 - peak / cfg.device_budget_bytes
            if v < cfg.min_headroom_frac:
                measured["headroom_frac"] = (v, cfg.min_headroom_frac)
        if cfg.max_skip_rate is not None and self._skips:
            steps = sum(n for _, n in self._steps)
            skips = sum(n for _, n in self._skips)
            v = skips / max(steps, skips)    # skipped steps consumed data
            if v > cfg.max_skip_rate:
                measured["guard_skip_rate"] = (v, cfg.max_skip_rate)
        if cfg.heartbeat_stale_s is not None and heartbeat is not None \
                and isinstance(heartbeat.get("time"), (int, float)):
            v = now - heartbeat["time"]
            if v > cfg.heartbeat_stale_s:
                measured["heartbeat_stale_s"] = (v, cfg.heartbeat_stale_s)

        fresh = []
        for slo, (value, threshold) in measured.items():
            record = {"slo": slo, "value": value, "threshold": threshold,
                      "window_s": cfg.window_s, "t_eval": now}
            if slo not in self.active:
                fresh.append(record)
                self.violations.append(record)
                if self.emit is not None:
                    self.emit.slo_violation(**record)
            self.active[slo] = record
        for slo in list(self.active):
            if slo not in measured:
                del self.active[slo]     # recovered; a re-breach re-fires
        return fresh


def check_stream(events: List[Dict[str, Any]], cfg: SLOConfig,
                 heartbeat: Optional[dict] = None,
                 emit: Optional[EventLog] = None) -> List[dict]:
    """Offline replay for ``--check``; returns the violation list (see
    ``replay_monitor`` for the full monitor, breakdown included)."""
    return replay_monitor(events, cfg, heartbeat=heartbeat,
                          emit=emit).violations


def replay_monitor(events: List[Dict[str, Any]], cfg: SLOConfig,
                   heartbeat: Optional[dict] = None,
                   emit: Optional[EventLog] = None) -> SLOMonitor:
    """Offline replay: walk the stream in event time, evaluating every
    quarter-window and once at the end — a stream that goes SILENT
    mid-run (the stall case) is caught at that final evaluation, whose
    ``now`` is the heartbeat's last beat when that is newer than the
    last event (a dead writer's stream ends, its staleness does not).
    Returns the monitor itself: ``violations`` for the verdict,
    ``breakdown()`` for the per-class/per-engine group-by (the fleet
    smoke consumes both)."""
    monitor = SLOMonitor(cfg, emit=emit)
    events = sorted(events, key=lambda e: e.get("t", 0.0))
    last_eval = None
    for e in events:
        monitor.feed([e])
        t = e.get("t")
        if not isinstance(t, (int, float)):
            continue
        if last_eval is None:
            last_eval = t
        elif t - last_eval >= cfg.window_s / 4:
            monitor.evaluate(t, heartbeat)
            last_eval = t
    if monitor.last_event_t is not None:
        end = monitor.last_event_t
        if heartbeat is not None and isinstance(heartbeat.get("time"),
                                                (int, float)):
            end = max(end, heartbeat["time"])
        monitor.evaluate(end, heartbeat)
    return monitor


def parse_class_slo(specs) -> Optional[Dict[str, Dict[str, float]]]:
    """``--class-slo`` values ("NAME:ttft_p99=S[,queue_p99=S]") into the
    ``SLOConfig.per_class`` table."""
    names = {"ttft_p99": "ttft_p99_s", "queue_p99": "queue_p99_s"}
    per: Dict[str, Dict[str, float]] = {}
    for spec in specs or []:
        name, _, rest = spec.partition(":")
        if not name or not rest:
            raise ValueError(f"--class-slo {spec!r}: expected "
                             "NAME:ttft_p99=S[,queue_p99=S]")
        limits = {}
        for part in rest.split(","):
            k, _, v = part.partition("=")
            key = names.get(k.strip())
            if key is None or not v:
                raise ValueError(f"--class-slo {spec!r}: unknown objective "
                                 f"{k.strip()!r} (known: "
                                 f"{', '.join(names)})")
            limits[key] = float(v)
        per[name] = limits
    return per or None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path", help="telemetry run dir (containing "
                                 "events.jsonl) or an events.jsonl path")
    ap.add_argument("--check", action="store_true",
                    help="replay the complete stream in event time; exit "
                         "1 if any objective was breached")
    ap.add_argument("--window", type=float, default=30.0,
                    help="rolling window seconds")
    ap.add_argument("--ttft-p99", type=float, default=None,
                    help="p99 TTFT ceiling (s)")
    ap.add_argument("--queue-p99", type=float, default=None,
                    help="p99 queue wait ceiling (s)")
    ap.add_argument("--min-tps", type=float, default=None,
                    help="sustained tokens/sec floor while work is "
                         "outstanding")
    ap.add_argument("--max-skip-rate", type=float, default=None,
                    help="StepGuard skipped-steps / steps ceiling")
    ap.add_argument("--heartbeat-stale", type=float, default=None,
                    help="heartbeat age ceiling (s)")
    ap.add_argument("--slo-mfu", type=float, default=None,
                    help="MFU floor over the window (achieved FLOP/s from "
                         "compile-event flops + step timing, vs the "
                         "manifest's roofline peaks)")
    ap.add_argument("--slo-acceptance", type=float, default=None,
                    help="speculative-decoding acceptance-rate floor over "
                         "the window (accepted/proposed draft tokens from "
                         "schema-v7 speculate events; a degenerate draft "
                         "is an SLO breach, not a silent slowdown)")
    ap.add_argument("--slo-headroom", type=float, default=None,
                    help="OOM-headroom floor: minimum free fraction of "
                         "--device-bytes left by the window's peak "
                         "memory-event device_bytes (schema v9)")
    ap.add_argument("--device-bytes", type=float, default=None,
                    help="per-device byte budget --slo-headroom judges "
                         "against (HBM size on chip; an explicit budget "
                         "in CI)")
    ap.add_argument("--slo-gradnorm", type=float, default=None,
                    help="grad-norm spike-rate ceiling (fraction of the "
                         "window's numerics samples above "
                         "--gradnorm-factor x the window median)")
    ap.add_argument("--gradnorm-factor", type=float, default=10.0,
                    help="spike threshold multiple of the window-median "
                         "grad norm")
    ap.add_argument("--class-slo", action="append", default=None,
                    metavar="NAME:ttft_p99=S[,queue_p99=S]",
                    help="per-traffic-class objectives (repeatable) over "
                         "the fleet's tenant-tagged request_done events; "
                         "violations key as '<class>:<objective>'")
    ap.add_argument("--poll", type=float, default=2.0,
                    help="live mode: seconds between evaluations")
    ap.add_argument("--duration", type=float, default=None,
                    help="live mode: stop after this many seconds")
    ap.add_argument("--emit", dest="emit", action="store_true",
                    default=None,
                    help="append slo_violation events to the stream "
                         "(default: on live, off under --check)")
    ap.add_argument("--no-emit", dest="emit", action="store_false")
    ap.add_argument("--out", default=None,
                    help="write the violation list as JSON here")
    a = ap.parse_args(argv)

    if os.path.isdir(a.path):
        events_path = os.path.join(a.path, "events.jsonl")
        heartbeat_path = os.path.join(a.path, "heartbeat.json")
    else:
        events_path = a.path
        heartbeat_path = os.path.join(os.path.dirname(a.path) or ".",
                                      "heartbeat.json")
    try:
        per_class = parse_class_slo(a.class_slo)
    except ValueError as e:
        ap.error(str(e))
    cfg = SLOConfig(window_s=a.window, ttft_p99_s=a.ttft_p99,
                    queue_p99_s=a.queue_p99,
                    min_tokens_per_sec=a.min_tps,
                    max_skip_rate=a.max_skip_rate,
                    heartbeat_stale_s=a.heartbeat_stale,
                    min_mfu=a.slo_mfu,
                    max_gradnorm_spike_rate=a.slo_gradnorm,
                    gradnorm_spike_factor=a.gradnorm_factor,
                    min_acceptance_rate=a.slo_acceptance,
                    min_headroom_frac=a.slo_headroom,
                    device_budget_bytes=a.device_bytes,
                    per_class=per_class)
    if a.slo_headroom is not None and not a.device_bytes:
        ap.error("--slo-headroom requires --device-bytes (the budget the "
                 "free fraction is measured against)")
    emit_default = not a.check
    emit = a.emit if a.emit is not None else emit_default
    # heal=False: we are a SIDECAR on a possibly-LIVE stream — append
    # only, never truncate what might be another writer's in-flight line.
    log = (EventLog(events_path, run_id=f"slo-{os.getpid()}", heal=False)
           if emit else None)
    if log is not None:
        # Arm a flight recorder in THIS process (the run's own recorder
        # only sees events its process emits — a sidecar's violation
        # never crosses that tap): every tailed event feeds the ring, and
        # the violation we emit dumps a postmortem bundle next to the
        # run's own (triggers narrowed to slo_violation so a fault the
        # trainer already bundled is not bundled twice).
        recorder = FlightRecorder(
            os.path.join(os.path.dirname(events_path) or ".",
                         "postmortem"),
            triggers=("slo_violation",))
        log.observers.append(recorder.observe)
    else:
        recorder = None

    def _hb():
        return (read_heartbeat(heartbeat_path)
                if os.path.exists(heartbeat_path) else None)

    if a.check:
        if not os.path.exists(events_path):
            print(f"no event stream at {events_path}", file=sys.stderr)
            return 2
        events = read_events(events_path)
        if recorder is not None:
            for e in events:          # bundle context; never re-triggers
                recorder.ingest(e)
        monitor = replay_monitor(events, cfg, heartbeat=_hb(), emit=log)
        violations = monitor.violations
    else:
        tailer = StreamTailer(events_path)
        monitor = SLOMonitor(cfg, emit=log)
        t0 = time.time()
        while True:
            fresh = tailer.poll()
            if recorder is not None:
                for e in fresh:       # bundle context; never re-triggers
                    recorder.ingest(e)
            monitor.feed(fresh)
            for v in monitor.evaluate(time.time(), _hb()):
                print(f"[slo] VIOLATION {v['slo']}: {v['value']:.4g} vs "
                      f"threshold {v['threshold']:.4g} "
                      f"(window {v['window_s']:.0f}s)", flush=True)
            if a.duration is not None and time.time() - t0 >= a.duration:
                break
            if monitor.run_ended and monitor.enqueued <= monitor.done:
                break
            time.sleep(a.poll)
        violations = monitor.violations
    if log is not None:
        log.close()

    summary = {"events_path": events_path, "window_s": cfg.window_s,
               "violations": violations, "ok": not violations,
               # Per-class/per-engine group-by of the same stream —
               # run totals, so an N-engine multi-tenant run reads as N+K
               # verdicts instead of one pooled percentile table.
               "breakdown": monitor.breakdown()}
    if a.out:
        with open(a.out, "w") as f:
            json.dump(summary, f)
            f.write("\n")
    print(json.dumps(summary))
    return 1 if (a.check and violations) else 0


if __name__ == "__main__":
    sys.exit(main())
