"""Trace-time communication-volume accounting for the parallel layer.

Evaluating the comm-efficiency directions in PAPERS.md — compressed
allreduce (DynamiQ, arxiv 2602.08923) and quantized allreduce in XLA
(EQuARX, arxiv 2506.17615) — needs per-collective byte counts that the
stack previously never produced. This module provides them with ZERO
in-jit overhead: the ``pmean``/``psum``/... wrappers below delegate
straight to ``jax.lax`` (the compiled HLO is bit-identical to calling lax
directly), but while JAX is *tracing* the step they record each
collective's operand payload into the active collector. Tracing happens
once per compilation, in Python, so the accounting is static — measured at
trace time, free at run time.

Usage: ``parallel/{dp,tp,sp,ep,pp,compress}.py`` call these wrappers
instead of raw lax collectives, and

    profile = measure_comm(step_fn, state, batch)   # or ShapeDtypeStructs

abstractly traces the step (``jax.eval_shape`` — no compile, no execute)
with a collector installed. The resulting ``CommProfile`` reports payload
bytes and estimated per-device wire bytes per step, per collective label.

Accounting semantics (what the numbers MEAN):
- ``payload_bytes`` is the local operand size in its wire dtype — the
  quantity the compression levers act on (bf16 halves it, int8 quarters
  it vs fp32).
- ``wire_bytes_per_device`` applies the standard ring-algorithm factors to
  the payload: allreduce (psum/pmean/pmax) ``2·(n−1)/n``, all_gather
  ``(n−1)`` × the local shard sent, psum_scatter ``(n−1)/n``, ppermute
  ``1`` (one neighbor send). n = the mesh axis size; n = 1 makes every
  reduce's wire cost 0, as it should.
- ``scale`` multiplies a record for collectives inside ``lax.scan`` bodies,
  which trace once but execute many times — the call site passes the trip
  count (e.g. the SP ring passes its hop count, PP its tick count).

Known under-count, by design: collectives SYNTHESIZED by autodiff
transposition (e.g. the backward hops of a differentiated in-forward
ppermute, or psum transposes in TP/PP forward bodies) never appear in user
code, so trace-time accounting cannot see them. The post-AD data-parallel
collectives — the gradient allreduce family that the compressed-wire work
targets — are exact. Call sites that KNOW their op is differentiated pass
``scale=2`` (forward + cotangent) where that correction applies.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

import jax
import numpy as np
from jax import lax

_collector: contextvars.ContextVar[Optional[list]] = \
    contextvars.ContextVar("ddl25_comm_collector", default=None)


@dataclass(frozen=True)
class CommRecord:
    """One collective call site, as seen at trace time."""
    op: str                  # pmean | psum | pmax | all_gather | ...
    label: str               # call-site semantic name ("grad_allreduce", ...)
    axis: str                # mesh axis name
    axis_size: int           # participants on the axis, static at trace time
    payload_bytes: int       # local operand bytes in the wire dtype
    scale: int               # executions per step (scan trip count, ...)

    @property
    def wire_bytes_per_device(self) -> float:
        """Ring-algorithm per-device wire estimate for ONE execution."""
        n = self.axis_size
        if self.op in ("pmean", "psum", "pmax"):
            factor = 2.0 * (n - 1) / n
        elif self.op == "all_gather":
            factor = float(n - 1)
        elif self.op == "psum_scatter":
            factor = (n - 1) / n
        elif self.op == "ppermute":
            factor = 1.0 if n > 1 else 0.0
        else:
            factor = 1.0
        return factor * self.payload_bytes

    def as_dict(self) -> dict:
        return {"op": self.op, "label": self.label, "axis": self.axis,
                "axis_size": self.axis_size,
                "payload_bytes": int(self.payload_bytes),
                "scale": int(self.scale),
                "wire_bytes_per_device": self.wire_bytes_per_device}


@dataclass
class CommProfile:
    """All collectives of one traced step, with per-step aggregates."""
    records: List[CommRecord] = field(default_factory=list)

    @property
    def payload_bytes_per_step(self) -> int:
        return sum(r.payload_bytes * r.scale for r in self.records)

    @property
    def wire_bytes_per_device_per_step(self) -> float:
        return sum(r.wire_bytes_per_device * r.scale for r in self.records)

    def by_label(self) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for r in self.records:
            agg = out.setdefault(r.label, {
                "op": r.op, "axis": r.axis, "axis_size": r.axis_size,
                "calls": 0, "payload_bytes": 0,
                "wire_bytes_per_device": 0.0})
            agg["calls"] += r.scale
            agg["payload_bytes"] += r.payload_bytes * r.scale
            agg["wire_bytes_per_device"] += r.wire_bytes_per_device * r.scale
        return out

    def by_axis(self) -> Dict[str, dict]:
        """Per-MESH-AXIS aggregates — the hierarchical-collective budget
        view (parallel/compress.py two-level drivers): every record
        carries the axis its collective crossed, so DCN-axis bytes (the
        scarce tier of a ``hier_data_mesh``) aggregate separately from
        ICI-axis bytes. The CI wire gate (experiments/comm_wire_smoke.py)
        reads the ``dcn`` entry; the flat ring's single ``data`` axis
        aggregates exactly as the per-step totals do."""
        out: Dict[str, dict] = {}
        for r in self.records:
            agg = out.setdefault(r.axis, {
                "axis_size": r.axis_size, "calls": 0, "payload_bytes": 0,
                "wire_bytes_per_device": 0.0})
            agg["calls"] += r.scale
            agg["payload_bytes"] += r.payload_bytes * r.scale
            agg["wire_bytes_per_device"] += r.wire_bytes_per_device * r.scale
        return out

    def as_dict(self, *, steps_per_dispatch: int = 1,
                overlap_microbatches: int = 1) -> dict:
        """JSON-able shape for the run manifest / bench telemetry block.

        The profile's aggregates cover one traced CALL. For a fused
        multi-step driver (parallel/dp.py ``make_multi_step``,
        parallel/pp.py ``make_pipeline_multi_step`` and the DP×PP overlap
        drivers — every PP collective records at ``scale=K`` through the
        bodies' ``comm_scale``) one call is one dispatch of K steps —
        pass ``steps_per_dispatch=K`` and the dict carries the
        per-TRAIN-STEP normalization alongside the per-dispatch totals,
        so "wire bytes per step" stays comparable across K (the
        no-regression check the zero1/scan work is held to).

        Normalization rule (pinned in tests/test_telemetry.py so future
        drivers can't double-count): the per-train-step figures divide the
        per-dispatch totals by ``steps_per_dispatch`` ONLY. The overlap
        driver's M microbatch rings (parallel/compress.py) are all part of
        ONE step's traffic — its unrolled ring hops each record their own
        ppermute at ``scale=K``, so dividing by K already yields the exact
        per-step bytes, and dividing by M as well would under-count a
        step's wire M×. ``overlap_microbatches`` = M > 1 instead ADDS the
        per-microbatch-ring view (per-train-step ÷ M) alongside, for
        readers sizing one ring trip.
        """
        d = {
            "payload_bytes_per_step": self.payload_bytes_per_step,
            "wire_bytes_per_device_per_step":
                self.wire_bytes_per_device_per_step,
            "collectives": self.by_label(),
            # Per-axis attribution (``by_axis``): on a hierarchical mesh
            # the ``dcn`` entry IS the scarce-tier budget; per-train-step
            # normalization follows the same ÷K-only rule as the totals.
            "axes": {
                ax: {**agg, **({"wire_bytes_per_device_per_train_step":
                                agg["wire_bytes_per_device"]
                                / steps_per_dispatch}
                               if steps_per_dispatch > 1 else {})}
                for ax, agg in self.by_axis().items()
            },
        }
        if steps_per_dispatch > 1:
            d["steps_per_dispatch"] = int(steps_per_dispatch)
            d["payload_bytes_per_train_step"] = \
                self.payload_bytes_per_step / steps_per_dispatch
            d["wire_bytes_per_device_per_train_step"] = \
                self.wire_bytes_per_device_per_step / steps_per_dispatch
        if overlap_microbatches > 1:
            d["overlap_microbatches"] = int(overlap_microbatches)
            per_step = (self.wire_bytes_per_device_per_step
                        / steps_per_dispatch)
            d["wire_bytes_per_device_per_microbatch"] = \
                per_step / overlap_microbatches
        return d


def tree_bytes(tree: Any) -> int:
    """Exact byte count of a pytree's leaves (shape × dtype itemsize) —
    the unit of every payload figure in this module. Public because the
    FL fleet engine (fl/fleet.py) accounts its tier-crossing uploads with
    the same rule the collective wrappers use, so 'payload bytes' means
    one thing across the whole telemetry stream."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", None)
        itemsize = np.dtype(dtype).itemsize if dtype is not None else 4
        total += int(math.prod(shape)) * itemsize
    return total


_tree_bytes = tree_bytes          # internal alias (pre-v3 call sites)


def _record(op: str, label: Optional[str], axis_name: str, operand: Any,
            scale: int) -> None:
    col = _collector.get()
    if col is None:
        return
    col.append(CommRecord(op=op, label=label or op, axis=axis_name,
                          axis_size=int(lax.axis_size(axis_name)),
                          payload_bytes=_tree_bytes(operand),
                          scale=int(scale)))


# ------------------------------------------------------------- the wrappers
# Same signatures as jax.lax (plus label/scale); compiled output identical.

def pmean(x, axis_name: str, *, label: Optional[str] = None,
          scale: int = 1):
    _record("pmean", label, axis_name, x, scale)
    return lax.pmean(x, axis_name)


def psum(x, axis_name: str, *, label: Optional[str] = None, scale: int = 1):
    _record("psum", label, axis_name, x, scale)
    return lax.psum(x, axis_name)


def pmax(x, axis_name: str, *, label: Optional[str] = None, scale: int = 1):
    _record("pmax", label, axis_name, x, scale)
    return lax.pmax(x, axis_name)


def all_gather(x, axis_name: str, *, tiled: bool = False,
               label: Optional[str] = None, scale: int = 1):
    _record("all_gather", label, axis_name, x, scale)
    return lax.all_gather(x, axis_name, tiled=tiled)


def psum_scatter(x, axis_name: str, *, scatter_dimension: int = 0,
                 tiled: bool = False, label: Optional[str] = None,
                 scale: int = 1):
    _record("psum_scatter", label, axis_name, x, scale)
    return lax.psum_scatter(x, axis_name,
                            scatter_dimension=scatter_dimension, tiled=tiled)


def ppermute(x, axis_name: str, perm, *, label: Optional[str] = None,
             scale: int = 1):
    _record("ppermute", label, axis_name, x, scale)
    return lax.ppermute(x, axis_name, perm)


# ------------------------------------------------------------- measurement

@contextlib.contextmanager
def collecting() -> Iterator[List[CommRecord]]:
    """Install a fresh collector for the duration of the block; any tracing
    that happens inside lands its collective records in the yielded list."""
    records: List[CommRecord] = []
    token = _collector.set(records)
    try:
        yield records
    finally:
        _collector.reset(token)


def measure_comm(fn, *args, **kwargs) -> Optional[CommProfile]:
    """Static comm profile of one call of ``fn(*args)``.

    Abstractly traces ``fn`` via ``jax.eval_shape`` — no compile, no
    execution, and the trace lands in the jit cache, so measuring a
    freshly built step BEFORE its first real call costs nothing extra.
    Arguments may be real pytrees or ``jax.ShapeDtypeStruct``s.

    A function whose trace is already cached re-uses it without running the
    Python body, which would silently record nothing — in that case the
    one retry after ``jax.clear_caches()`` forces a fresh trace (and evicts
    warm compilations: prefer measuring before first execution). Returns
    None when tracing itself fails.
    """
    for attempt in (0, 1):
        with collecting() as records:
            try:
                jax.eval_shape(fn, *args, **kwargs)
            except Exception:
                return None
        if records:
            return CommProfile(records)
        if attempt == 0:
            jax.clear_caches()
    return CommProfile([])       # traced fresh; genuinely no collectives
