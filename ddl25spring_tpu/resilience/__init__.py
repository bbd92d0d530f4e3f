"""Resilience layer: deterministic fault injection + self-healing loops.

Two halves that test each other (the design mirrors fl/attacks.py vs
fl/defenses.py, but for *benign* infrastructure faults instead of Byzantine
clients):

- ``faults``     — seedable ``FaultPlan``: NaN/Inf/spike gradients at chosen
                   steps, FL client drop/straggle per round, on-disk
                   checkpoint corruption, simulated SIGTERM preemption.
- ``guard``      — ``StepGuard``: all-finite + EMA-anomaly checked steps
                   with skip-and-count and rollback-to-last-good-checkpoint.
- ``retry``      — exponential backoff with seeded jitter, applied to
                   checkpoint IO and native tokenstream loading.
- ``preemption`` — SIGTERM → force-save-resumable-checkpoint → clean exit.
- ``elastic``    — ``ElasticController``: replica loss (``device_loss``
                   faults → ``ReplicaLossError``) → drain at the chunk
                   edge, re-mesh onto the survivors, reshard params +
                   ZeRO-1 optimizer state (and int8-ring EF residuals)
                   N→M, re-split the stream, resume — from a host-RAM
                   mirror (fast) or the checkpoint (slow). Bidirectional:
                   returned capacity (``device_return`` faults →
                   ``ReplicaReturnSignal``, or an autoscaler decision)
                   grows M→N through the same machinery.
- ``autoscale``  — ``Autoscaler``: SLO-driven policy loop moving replicas
                   between the training mesh and the serving fleet
                   (sustained TTFT pressure → shrink training, hand the
                   chips to serving; traffic ebb → reverse), emitting
                   schema-v8 ``scale`` events.

Counters land in ``metrics.ResilienceStats``; knobs in
``config.ResilienceConfig``. Wire-ins: train/llm.py (guarded loops),
fl/servers.py (survivor re-weighting), parallel/dp.py (in-step finiteness
guard), checkpoint.py (corrupt-step fallback, atomic best-weights),
experiments/watchdog.py (crash-loop-aware relaunch backoff).
"""

from .autoscale import (Autoscaler, AutoscalePolicy,  # noqa: F401
                        ScaleDecision)
from .elastic import (ElasticController, RemeshRecord,  # noqa: F401
                      Resume)
from .faults import (FaultEvent, FaultPlan, ReplicaLossError,  # noqa: F401
                     ReplicaReturnSignal, corrupt_latest_checkpoint,
                     parse_spec)
from .preemption import PreemptionHandler  # noqa: F401
from .retry import backoff_schedule, retry_call, with_retry  # noqa: F401

# guard imports jax at module scope; everything above is numpy/stdlib-only
# (elastic defers its parallel/ imports into recover()).
# Load it lazily (PEP 562) so jax-free supervisors — experiments/watchdog.py
# pulling in backoff_schedule — don't pay jax's import time and memory.
_GUARD_EXPORTS = ("StepGuard",)
__all__ = ["Autoscaler", "AutoscalePolicy", "ElasticController",
           "FaultEvent", "FaultPlan", "RemeshRecord", "ReplicaLossError",
           "ReplicaReturnSignal", "Resume", "ScaleDecision",
           "corrupt_latest_checkpoint", "parse_spec", "PreemptionHandler",
           "backoff_schedule", "retry_call", "with_retry",
           *_GUARD_EXPORTS]


def __getattr__(name):
    if name in _GUARD_EXPORTS:
        from . import guard
        return getattr(guard, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
