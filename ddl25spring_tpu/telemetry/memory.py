"""Unified memory observability: device/host byte accounting (schema v9).

The stack observes time (spans), wire bytes (comm profiles), numerics and
compiles — this module closes the last unobserved axis, memory, with four
pieces sharing one schema-v9 ``memory`` event shape:

- **Static program footprint** — ``program_memory`` /
  ``compiled_memory`` pull ``compiled.memory_analysis()`` (argument /
  output / temp / generated-code bytes) through ONE reader: a backend
  that can't account a field reports it negative and the field is
  dropped; one that accounts nothing gives None.
  ``introspect.CompileWatch`` stamps these onto every ``compile``
  event; ``experiments/pp_schedules.py`` reads its programs through here
  too.
- **Live accounting** — ``MemoryMeter``, a jax-free sampler emitting one
  ``memory`` event per cadence point (trainer chunk edges, scheduler
  ticks): host RSS (``host_rss_bytes``), training-state / elastic-mirror
  bytes (``tree_state_bytes`` — shape × dtype arithmetic on host-visible
  metadata, NEVER a device sync), and KV pool occupancy + fragmentation
  (``allocator_census`` over ``BlockAllocator``'s free list). The meter
  is pure host bookkeeping: losses and served streams are bitwise
  identical with it on or off, and it adds zero dispatches/retraces
  (pinned in tests/test_memory.py and the CI memory smoke).
- **Preflight fit estimation** — ``preflight`` predicts the per-device
  byte budget (params + optimizer moments + EF residuals + batch window
  + KV pool) from configs alone, BEFORE any compile, via
  ``jax.eval_shape`` — cross-checked against the measured
  ``memory_analysis`` footprint (tests pin agreement within 10%, and
  the ZeRO-1 moments at ~1/n of replicated).
- **Headroom SLO feed** — every sample carries ``device_bytes`` (the sum
  of its device-resident components) so ``experiments/slo_monitor.py``'s
  ``--slo-headroom`` can judge free fraction against a ``--device-bytes``
  budget, and ``resilience/autoscale.py`` can refuse to scale serving
  into a pool that cannot fit it.

Import contract: jax-free at module scope (same as introspect's readers
and slo_monitor) — jax/comm/model imports happen lazily inside the
functions that need them, so the stdlib-only consumers (obs_report,
postmortem, slo_monitor, fleet_smoke's host sampler) can import this
module without dragging in a backend.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, Optional

# CompiledMemoryStats attribute names. A field a backend does not account
# is negative and is dropped on its own. ``alias`` counts donated input
# buffers that XLA reuses for outputs — subtracted from the peak total
# below so a donated-state trainer is not double-billed for its state.
_STAT_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
)

# The components of one ``memory`` event that live in DEVICE memory —
# summed into ``device_bytes`` (the headroom SLO's numerator) when the
# sampler didn't provide a total itself.
_DEVICE_COMPONENTS = ("params_bytes", "opt_state_bytes", "residual_bytes",
                      "window_bytes", "pool_used_bytes")


def compiled_memory(compiled) -> Optional[dict]:
    """Static footprint of an ALREADY-compiled program, or None when the
    backend can't account it. Shared by the repo's two
    ``memory_analysis()`` call sites (CompileWatch, pp_schedules)."""
    return _normalize_stats(compiled.memory_analysis())


def program_memory(jitted_fn, *args, **kwargs) -> Optional[dict]:
    """Static footprint of the compiled program for ``jitted_fn(*args)``.

    Mirrors ``costs.hlo_cost``: arguments may be real pytrees or
    ``jax.ShapeDtypeStruct``s; compiles the program if it isn't already —
    call where a compile is acceptable (CompileWatch only calls it on a
    dispatch that ALREADY paid a compile), not on a hot path. None when
    any link of lower→compile→memory_analysis is unavailable."""
    lower = getattr(jitted_fn, "lower", None)
    if lower is None:
        return None                       # not a jitted callable
    try:
        compiled = lower(*args, **kwargs).compile()
    except Exception:
        return None
    return compiled_memory(compiled)


def _normalize_stats(stats: Any) -> Optional[dict]:
    """``CompiledMemoryStats`` → one flat dict of floats; None when the
    backend reported nothing usable (None, or every field negative)."""
    out: Dict[str, Any] = {}
    for name, attr in _STAT_FIELDS:
        v = getattr(stats, attr, None)
        if v is not None and v >= 0:
            out[name] = float(v)
    if not any(k in out for k, _ in _STAT_FIELDS[:3]):
        return None                       # no byte accounting at all
    # Peak device residency of one dispatch: inputs + outputs + transients
    # + program code, minus the donated buffers counted on both sides.
    out["device_bytes"] = max(0.0, sum(
        out.get(k, 0.0) for k in ("argument_bytes", "output_bytes",
                                  "temp_bytes", "generated_code_bytes"))
        - out.get("alias_bytes", 0.0))
    return out


def host_rss_bytes() -> Optional[int]:
    """Peak resident-set size of this process in bytes (``ru_maxrss`` —
    KiB on Linux, bytes on macOS), or None where rusage is unavailable.
    The shared host sampler fleet_smoke's RSS-bound check and the
    MemoryMeter's ``rss_bytes`` field both read."""
    try:
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    except Exception:
        return None
    return int(ru) * (1 if sys.platform == "darwin" else 1024)


def tree_state_bytes(tree: Any) -> Optional[int]:
    """Exact logical bytes of a pytree's leaves (comm.tree_bytes — shape ×
    dtype itemsize, host-side metadata only, never a device sync), or
    None when jax is unavailable. For numpy-only trees (the elastic
    mirror's host snapshots) ``np_tree_bytes`` stays jax-free."""
    try:
        from .comm import tree_bytes
        return int(tree_bytes(tree))
    except Exception:
        return None


def np_tree_bytes(tree: Any) -> int:
    """Bytes of a HOST (numpy) pytree without importing jax: walks nested
    dict/list/tuple/NamedTuple containers summing leaf ``nbytes``. The
    elastic mirror census uses this so resilience stays jax-free."""
    if tree is None:
        return 0
    nbytes = getattr(tree, "nbytes", None)
    if nbytes is not None and not isinstance(tree, (dict, list, tuple)):
        try:
            return int(nbytes)
        except (TypeError, ValueError):
            return 0
    if isinstance(tree, dict):
        return sum(np_tree_bytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(np_tree_bytes(v) for v in tree)
    return 0


def allocator_census(allocator, *, bytes_per_block: Optional[int] = None,
                     ) -> Dict[str, Any]:
    """One ``BlockAllocator``'s occupancy + fragmentation snapshot:
    ``blocks_in_use``/``free_blocks``/``peak_blocks_in_use`` plus the
    free-list ``holes``/``largest_run`` census. With ``bytes_per_block``
    (``pool_bytes / num_blocks``) occupancy also lands in bytes — the
    ``pool_used_bytes`` the headroom SLO sums into ``device_bytes``."""
    out: Dict[str, Any] = {
        "blocks_in_use": int(allocator.in_use),
        "free_blocks": int(allocator.free_blocks),
        "blocks_capacity": int(allocator.capacity),
        "peak_blocks_in_use": int(allocator.peak_in_use),
    }
    out.update(allocator.fragmentation())
    if bytes_per_block:
        out["pool_used_bytes"] = out["blocks_in_use"] * int(bytes_per_block)
        out["pool_capacity_bytes"] = (out["blocks_capacity"]
                                      * int(bytes_per_block))
        out["peak_pool_used_bytes"] = (out["peak_blocks_in_use"]
                                       * int(bytes_per_block))
    return out


class MemoryMeter:
    """Jax-free live memory sampler: one schema-v9 ``memory`` event per
    ``sample()`` call, merging static per-run figures (``note``-d once —
    e.g. the preflight's params/moments bytes) with the cadence point's
    live fields (mirror bytes, pool census, stream position).

    Zero-overhead contract: every field is host-side bookkeeping (RSS
    from rusage, byte figures from shape metadata, pool stats from the
    host allocator) — no device syncs, no extra dispatches, so losses
    and served streams are bitwise identical with the meter on or off.
    Emission is guarded like every telemetry writer: a broken event log
    loses the sample, never the run. ``events=None`` keeps the meter as
    a pure accumulator (``peaks`` still track) — fleet_smoke uses that
    to keep its RSS-bound check independent of telemetry being on.
    """

    def __init__(self, events=None, *, source: str = "host",
                 static: Optional[Dict[str, Any]] = None):
        self.events = events
        self.source = source
        self.static: Dict[str, Any] = dict(static or {})
        self.samples = 0
        # Running maxima of every numeric byte/occupancy field seen — the
        # ``peak_*_bytes`` bench rows and the postmortem census read these.
        self.peaks: Dict[str, float] = {}

    def note(self, **fields: Any) -> None:
        """Merge static per-run figures into every subsequent sample."""
        self.static.update({k: v for k, v in fields.items()
                            if v is not None})

    def sample(self, source: Optional[str] = None,
               **fields: Any) -> Dict[str, Any]:
        """One cadence point: returns the merged record and (when an
        event log is bound) emits it as a ``memory`` event."""
        rec = dict(self.static)
        rec.update({k: v for k, v in fields.items() if v is not None})
        rss = host_rss_bytes()
        if rss is not None:
            rec.setdefault("rss_bytes", rss)
        if "device_bytes" not in rec:
            parts = [rec[k] for k in _DEVICE_COMPONENTS
                     if isinstance(rec.get(k), (int, float))]
            if parts:
                rec["device_bytes"] = float(sum(parts))
        self.samples += 1
        for k, v in rec.items():
            if (isinstance(v, (int, float)) and not isinstance(v, bool)
                    and (k.endswith("_bytes") or k in ("blocks_in_use",
                                                       "holes"))):
                prev = self.peaks.get(k)
                self.peaks[k] = float(v) if prev is None else max(prev,
                                                                  float(v))
        if self.events is not None:
            try:
                self.events.memory(source=source or self.source, **rec)
            except Exception:
                pass               # a meter must never sink its host
        return rec


def preflight(model_cfg, train_cfg=None, *, mesh=None, n_data=None,
              aggregation: str = "gradient", optimizer=None,
              paged=None, serve_cfg=None) -> Optional[dict]:
    """Per-device byte budget BEFORE any compile: what the training state
    (params + optimizer moments + EF residuals), the batch window and the
    serving KV pool will occupy on one device, from configs alone via
    ``jax.eval_shape`` (abstract — no arrays materialize, nothing
    compiles). None when jax/the model can't be imported.

    The figures this pins (cross-checked against the measured
    ``memory_analysis`` footprint in tests/test_memory.py):

    - ``params_bytes`` — replicated per device in every DP aggregation;
    - ``opt_state_bytes`` — per device. ``aggregation="zero1"`` shards
      the moments: each device holds ``optimizer.init`` of its padded
      1/n flat slice (dp._zero1_setup's geometry), so this lands at
      ~1/n of ``opt_state_replicated_bytes`` — the ZeRO-1 memory-parity
      claim (arXiv 2004.13336) as a number instead of prose;
    - ``residual_bytes`` — the int8-ring EF residual trees
      (compress.OverlapEFState) when ``wire`` carries error feedback:
      one padded flat vector for the ring slice plus a 1/n gather slice;
    - ``window_bytes`` — the ``[K, B, T]`` int32 dispatch window's
      per-device shard (K = steps_per_dispatch, B = per-replica batch);
    - ``kv_pool_bytes`` — the paged serving pool (kvcache.pool_bytes)
      when ``paged`` is given (``serve_cfg`` defaults to ``model_cfg``);
      sized from the model's description, so a latent-attention model's
      is one row a position a layer (``config.ModelDescription``).

    ``device_bytes`` totals the components — the number to hold against
    an accelerator's HBM (or slo_monitor's ``--device-bytes`` budget)
    before committing to a compile.
    """
    try:
        import math as _math

        import jax
        import jax.numpy as jnp

        from ..models import llama
        from .comm import tree_bytes
    except Exception:
        return None
    try:
        from ..config import describe
        desc = describe(model_cfg)
        if desc.plain:
            abstract = jax.eval_shape(
                lambda: llama.init_llama(jax.random.key(0), model_cfg))
        else:
            # a described model (latent attention, expert layers): its own
            # tree, and through ``pool_bytes`` below its own pool
            from ..models import latent
            abstract = jax.eval_shape(
                lambda: latent.init_params(jax.random.key(0), desc))
        params_bytes = int(tree_bytes(abstract))
        count = sum(int(_math.prod(leaf.shape))
                    for leaf in jax.tree.leaves(abstract))
    except Exception:
        return None
    if n_data is None:
        if mesh is not None:
            n_data = (mesh.shape.get("data", 1)
                      * mesh.shape.get("dcn", 1))
        elif train_cfg is not None:
            n_data = train_cfg.data * max(1, train_cfg.dcn)
        else:
            n_data = 1
    n = max(1, int(n_data))
    if optimizer is None:
        try:
            import optax
            lr = train_cfg.lr if train_cfg is not None else 1e-3
            name = getattr(train_cfg, "optimizer", "adam")
            if name == "adam":
                optimizer = optax.adam(lr)
            else:
                from ..ops.adam import make_optimizer
                optimizer = make_optimizer(name, lr)
        except Exception:
            return None
    padded = -(-count // n) * n            # dp._zero1_setup's flat pad
    local = padded // n
    try:
        opt_replicated = int(tree_bytes(jax.eval_shape(optimizer.init,
                                                       abstract)))
        if aggregation == "zero1":
            opt_local = int(tree_bytes(jax.eval_shape(
                optimizer.init,
                jax.ShapeDtypeStruct((local,), jnp.float32))))
        else:
            opt_local = opt_replicated
    except Exception:
        return None
    residual_bytes = 0
    wire = getattr(train_cfg, "wire", "fp32") if train_cfg else "fp32"
    ovl = getattr(train_cfg, "overlap_microbatches", 0) if train_cfg else 0
    if ovl >= 1 and "ef" in str(wire):
        # OverlapEFState per device: ring_residual slice [1, Ppad] fp32 +
        # gather_residual's 1/n shard [Ppad/n] fp32.
        residual_bytes = 4 * (padded + local)
    window_bytes = 0
    if train_cfg is not None:
        K = max(1, getattr(train_cfg, "steps_per_dispatch", 1))
        window_bytes = (K * train_cfg.batch_size * train_cfg.seq_len
                        * 4)               # int32 tokens, per-device shard
    kv_pool_bytes = 0
    if paged is not None:
        try:
            from ..serving.kvcache import pool_bytes
            kv_pool_bytes = int(pool_bytes(serve_cfg or model_cfg, paged))
        except Exception:
            kv_pool_bytes = 0
    state_bytes = params_bytes + opt_local + residual_bytes
    return {
        "n_data": n,
        "param_count": int(count),
        "params_bytes": params_bytes,
        "opt_state_bytes": opt_local,
        "opt_state_replicated_bytes": opt_replicated,
        "residual_bytes": residual_bytes,
        "window_bytes": window_bytes,
        "kv_pool_bytes": kv_pool_bytes,
        "state_bytes": state_bytes,
        "device_bytes": state_bytes + window_bytes + kv_pool_bytes,
    }
