"""Production serving layer: continuous batching over a paged KV cache.

Six layers (ISSUE 6 + ISSUE 11 / ROADMAP item 1), bottom-up:

- kvcache   — fixed-size device block pool + host free-list allocator;
              sequences of different lengths share one pool through
              per-slot block tables instead of each owning a ``max_len``
              cache (vLLM-style paging, static-shape/one-compile).
- engine    — ``prefill_chunk`` / ``decode_step`` (+ ``verify_step``
              when speculating) compiled ONCE over a fixed slot axis;
              chunked prefill interleaves with in-flight decode;
              token-boundary weight hot-swap seam (``swap_params``);
              CoW prefix sharing (``prefix_share``); bitwise-parity
              with ``models.generate`` pinned in tests.
- speculate — draft-propose / one-dispatch-verify speculative decoding
              (``SpecConfig``, ``DraftEngine``, ``make_verify_step``):
              greedy streams bitwise ``generate()``'s, stochastic via
              rejection sampling; schema-v7 ``speculate`` events.
- scheduler — Orca-style iteration-level (continuous) batching:
              reservation-based admission (never deadlocks) behind a
              policy seam (FCFS default; size-aware "sjf"; priorities),
              retirement frees blocks at the next token boundary;
              ``request_*`` telemetry events, per-engine tagged.
- frontend  — seeded Poisson load generator, now multi-tenant
              (``TrafficClass`` / ``multi_tenant_workload``: per-class
              rates, SLO targets, admission priorities) + ``run_serving``
              driver (the tests' and ``chip_smoke.py``'s) and the
              latency aggregation ``experiments/obs_report.py`` renders.
- fleet     — N engines behind an SLO-aware ``Router`` (least-loaded /
              predicted-TTFT over slo_monitor-shaped rolling windows)
              with live weight hot-swap rolled out one engine per token
              boundary; ``run_serving_fleet`` driver.
- deploy    — the train→deploy conveyor: ``CheckpointPublisher`` (the
              trainer's ``on_checkpoint`` hook, params-only checkpoint
              stream) and ``WeightPublisher`` (digest-verified,
              restore-at-saved-shapes watcher feeding the fleet).
"""

from .deploy import CheckpointPublisher, WeightPublisher  # noqa: F401
from .engine import Engine, TokenEvent  # noqa: F401
from .fleet import (FleetReport, Router, ServingFleet,  # noqa: F401
                    run_serving_fleet)
from .frontend import (ServingReport, TrafficClass,  # noqa: F401
                       aggregate_latency, class_slos, multi_tenant_workload,
                       reference_stream, run_serving, synthetic_workload)
from .kvcache import (TRASH_BLOCK, BlockAllocator,  # noqa: F401
                      PagedKVConfig, blocks_for, init_pool, init_state,
                      kv_bytes_per_token, naive_cache_bytes, pool_bytes,
                      state_bytes_per_slot)
from .scheduler import Request, RequestRecord, Scheduler  # noqa: F401
from .speculate import DraftEngine, SpecConfig  # noqa: F401
