"""Unified telemetry layer (ISSUE 2 tentpole).

One observability subsystem the whole stack reports through:

- ``events``: schema-versioned, append-only JSONL event stream (run
  manifest, per-step records, fault events, FL round summaries).
- ``registry``: MetricsRegistry — counters/gauges/histograms with
  p50/p95/p99, absorbing Spans, StepTimer and ResilienceStats as adapters.
- ``comm``: trace-time communication-volume accounting around the
  collectives in parallel/{dp,tp,sp,ep,pp,compress}.py — bytes per
  psum/all-gather per step, computed statically, zero in-jit overhead.
- ``costs``: compiled-HLO cost analysis via lower().compile()
  .cost_analysis(), recorded by the compile watches.
- ``memory``: unified memory observability (schema v9) — guarded
  ``memory_analysis()`` program footprints, the jax-free ``MemoryMeter``
  live sampler (host RSS, state/mirror bytes, KV pool occupancy +
  fragmentation), and the ``preflight`` per-device fit estimator the
  headroom SLO and autoscaler guard rail read.
- ``heartbeat``: atomic liveness file consumed by experiments/watchdog.py
  as a first-class stall signal.
- ``trace``: span contexts (trace/span/parent ids, explicit propagation)
  over the event stream — per-request/per-round causal timelines,
  exported to Perfetto by experiments/trace_export.py and watched live by
  experiments/slo_monitor.py — and the program's host spans on the
  profiler's clock: every ``with`` span (``Tracer.span``, ``Spans``) is a
  ``jax.profiler.TraceAnnotation`` with its counters whenever jax is
  imported, whoever started the profiler.

``Telemetry`` bundles the per-run pieces (event log + heartbeat +
registry) behind one handle the trainers/servers accept.
Render a recorded run with ``python -m experiments.obs_report <dir>``.
"""

from __future__ import annotations

import os
from typing import Optional

from .costs import hlo_cost
from .events import (EventLog, SCHEMA_VERSION, default_run_id, read_events,
                     validate_event)
from .heartbeat import Heartbeat, read_heartbeat
from .introspect import (CompileWatch, FlightRecorder, NumericsSummary,
                         bind_events, device_peaks, make_summarizer,
                         watch)
from .memory import (MemoryMeter, allocator_census, compiled_memory,
                     host_rss_bytes, preflight, program_memory)
from .registry import MetricsRegistry
from .trace import (Span, SpanContext, Spans, Tracer, device_trace,
                    trace_trees, tree_check)

# comm.py imports jax at module level; everything else here is stdlib-only.
# Lazy re-export (PEP 562) keeps jax OUT of processes that only read
# telemetry — the watchdog's LivenessMonitor and experiments/obs_report
# import telemetry submodules and must stay featherweight/jax-free.
_LAZY_COMM = ("CommProfile", "measure_comm")


def __getattr__(name: str):
    if name in _LAZY_COMM:
        from . import comm
        return getattr(comm, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "CommProfile", "CompileWatch", "EventLog", "FlightRecorder",
    "Heartbeat", "MemoryMeter", "MetricsRegistry", "NumericsSummary",
    "SCHEMA_VERSION",
    "Span", "SpanContext", "Spans", "Telemetry", "Tracer",
    "allocator_census", "bind_events", "compiled_memory",
    "default_run_id", "device_peaks", "device_trace",
    "hlo_cost", "host_rss_bytes", "make_summarizer", "measure_comm",
    "preflight", "program_memory", "read_events",
    "read_heartbeat", "trace_trees", "tree_check", "validate_event", "watch",
]

EVENTS_NAME = "events.jsonl"
HEARTBEAT_NAME = "heartbeat.json"


class Telemetry:
    """Per-run telemetry bundle: event log + heartbeat + metrics registry.

    >>> tel = Telemetry("/tmp/run")          # events.jsonl, heartbeat.json
    >>> train_llm_dp(..., telemetry=tel)
    >>> # python -m experiments.obs_report /tmp/run

    ``step_every`` is the per-step event cadence — each step event forces a
    host sync of the loss (same cost model as the trainers' ``loss_sink``),
    so the default matches the trainers' ``sink_every``. The heartbeat is
    sync-free and beats every iteration regardless.

    ``flight=True`` (default) arms the anomaly flight recorder
    (introspect.FlightRecorder): a bounded ring over this run's events,
    dumped as a self-contained postmortem bundle under
    ``<out_dir>/postmortem/`` the moment a ``fault``/``remesh``/
    ``slo_violation`` event crosses the stream. Zero cost until a trigger
    fires; render bundles with ``python -m experiments.postmortem``.
    """

    def __init__(self, out_dir: str, *, run_id: Optional[str] = None,
                 step_every: int = 10, flight: bool = True):
        os.makedirs(out_dir, exist_ok=True)
        self.out_dir = out_dir
        self.run_id = run_id or default_run_id()
        # Floor at 1: the trainers take `it % step_every`, and a 0 from a
        # "disable step events" misread would ZeroDivisionError-sink the
        # run — the one failure mode this layer promises never to cause.
        self.step_every = max(1, int(step_every))
        self.events = EventLog(os.path.join(out_dir, EVENTS_NAME),
                               run_id=self.run_id)
        self.heartbeat = Heartbeat(os.path.join(out_dir, HEARTBEAT_NAME))
        self.registry = MetricsRegistry()
        self.flight = None
        if flight:
            self.flight = FlightRecorder(os.path.join(out_dir, "postmortem"))
            self.events.observers.append(self.flight.observe)
        # No default Tracer here: every emitter needs its own (the
        # serving scheduler binds its fast-forwarded clock, the trainers
        # their phase accumulator), and an unused one would burn a slot
        # in the process-wide tracer-id sequence, making span ids depend
        # on how many Telemetry bundles were ever constructed. Build one
        # with ``Tracer(telemetry.events)``.

    @property
    def events_path(self) -> str:
        return self.events.path

    @property
    def heartbeat_path(self) -> str:
        return self.heartbeat.path

    def close(self) -> None:
        self.events.close()

    def __enter__(self) -> "Telemetry":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
