"""Run-health introspection: in-jit numerics, compile/retrace accounting,
roofline attainment, and the anomaly flight recorder (ISSUE 9 tentpole).

PRs 3 and 8 say *that* a run is slow or sick (span timelines, SLO
breaches, StepGuard skips); this module says *why*:

- **In-jit numerics summaries** (``make_summarizer``): per-layer-group
  grad norm, param norm and update/param ratio computed INSIDE the
  existing compiled step — the summary rides the loss output of the same
  dispatch, so instrumentation adds zero extra dispatches and (because
  extra outputs never perturb XLA's computation of the existing ones)
  losses and params are bitwise identical with summaries on vs off
  (pinned in tests/test_introspect.py at K∈{1,4}). A per-leaf finite
  mask rides along, so a non-finite gradient is attributed to a NAMED
  tree path, not "somewhere".
- **Compile/retrace observability** (``CompileWatch``): a transparent
  wrapper over any jitted entry point that notices ``_cache_size()``
  growth, times the compiling call, costs the program via
  ``costs.hlo_cost`` and emits a ``compile`` event (schema v5) — with a
  retrace detector for factories whose documented invariant is ONE
  compiled program (serving's two engine steps, fleet's cohort steps).
- **Attainment accounting** (``device_peaks``): the roofline
  denominators — the chip's published peaks by ``device_kind``, or a
  calibrated baseline for the CPU the tests run on — land in the run
  manifest so obs_report /
  slo_monitor can turn (compile event flops, span/step durations) into
  achieved FLOP/s, HBM GB/s and MFU without jax.
- **Anomaly flight recorder** (``FlightRecorder``): a bounded ring of
  recent events plus the pinned manifest / last numerics / compile
  records, dumped as a self-contained postmortem JSON bundle the moment
  a ``fault``, ``remesh`` or ``slo_violation`` event crosses the stream.
  Render with ``python -m experiments.postmortem <telemetry-dir>``.

Import contract: module import is jax-free (the read-side tools —
obs_report, postmortem, slo_monitor — import helpers from here); jax is
imported lazily inside the functions that build in-jit code.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

# --------------------------------------------------------------- tree paths

def path_str(path) -> str:
    """jax key path -> "blocks/attn/wq"-style string (stable, readable)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def leaf_paths(tree) -> List[str]:
    """Path strings of every leaf, in ``tree_flatten_with_path`` order —
    the SAME order ``make_summarizer``'s finite mask and
    ``FaultPlan``'s targeted ``nan_grad`` use, so an index in one names
    the same leaf in the others."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [path_str(p) for p, _ in flat]


def nonfinite_leaves(tree, *, limit: int = 8) -> List[str]:
    """Host-side attribution: paths of leaves carrying any NaN/Inf
    (syncs each leaf — fault-path only). At most ``limit`` paths are
    returned, with a ``"... +N more"`` tail when truncated."""
    import jax
    import numpy as np

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    bad = []
    for p, leaf in flat:
        try:
            arr = np.asarray(leaf)
        except Exception:
            continue
        if arr.dtype.kind == "f" and not np.isfinite(arr).all():
            bad.append(path_str(p))
    if len(bad) > limit:
        bad = bad[:limit] + [f"... +{len(bad) - limit} more"]
    return bad


# ------------------------------------------------------- in-jit numerics

class NumericsSummary(NamedTuple):
    """The in-jit half of a numerics sample: per-GROUP sums of squares
    (sqrt happens at emission — host side) and the per-LEAF gradient
    finite mask. All leaves are tiny ([G]/[L] fp32/bool) so the summary
    rides the step's outputs for free."""
    grad_sq: Any      # [G] f32 — per-group Σ grad²
    param_sq: Any     # [G] f32 — per-group Σ new_param²
    update_sq: Any    # [G] f32 — per-group Σ (new_param − old_param)²
    grad_finite: Any  # [L] bool — per-leaf all-finite(grad)


class NumericsHandle:
    """One model's numerics instrumentation: the static leaf→group
    geometry plus ``summarize`` (call INSIDE the compiled step) and
    ``event_fields`` (host-side rendering into a ``numerics`` event).

    Groups: every top-level key of the params tree is a group, except
    ``layered_keys`` entries (default: ``"blocks"``, llama's stacked
    [L, ...] transformer stack), which expand to one group per leading
    index — per-layer-group norms from stacked leaves without unstacking
    anything.
    """

    def __init__(self, groups: List[str], paths: List[str],
                 summarize: Callable):
        self.groups = groups          # [G] group names
        self.paths = paths            # [L] leaf paths (flatten order)
        self.summarize = summarize    # (params, grads, new_params) -> NumericsSummary

    def event_fields(self, summary, *, index: Optional[int] = None,
                     top: int = 4) -> Dict[str, Any]:
        """Host-side: sync the (tiny) summary arrays and shape the
        ``numerics`` event payload. ``index`` slices a stacked [K, ...]
        summary from a fused multi-step dispatch (use -1 for the chunk's
        last step)."""
        import numpy as np

        def host(x):
            a = np.asarray(x)
            return a[index] if index is not None else a

        grad = np.sqrt(host(summary.grad_sq))
        param = np.sqrt(host(summary.param_sq))
        upd = np.sqrt(host(summary.update_sq))
        finite = host(summary.grad_finite)
        with np.errstate(invalid="ignore", divide="ignore"):
            ratio = np.where(param > 0, upd / param, 0.0)
        # NaN ratios (non-finite params) sort to the top via nan_to_max.
        ratio_rank = np.where(np.isfinite(ratio), ratio, np.inf)
        worst = int(np.argmax(ratio_rank))
        order = np.argsort(-ratio_rank)[:max(1, top)]
        fields: Dict[str, Any] = {
            "grad_norm": float(np.sqrt(np.sum(grad ** 2))),
            "worst_group": self.groups[worst],
            "worst_update_ratio": float(ratio[worst]),
            "groups": {
                self.groups[i]: {
                    "grad_norm": float(grad[i]),
                    "param_norm": float(param[i]),
                    "update_ratio": float(ratio[i]),
                } for i in order
            },
        }
        if not bool(finite.all()):
            bad = [self.paths[i] for i in np.flatnonzero(~finite)]
            if len(bad) > 8:
                bad = bad[:8] + [f"... +{len(bad) - 8} more"]
            fields["nonfinite_grads"] = bad
        return fields


def make_summarizer(params_template, *,
                    layered_keys: Tuple[str, ...] = ("blocks",),
                    psum_axis=None) -> NumericsHandle:
    """Build the in-jit numerics summarizer for one params tree.

    ``summarize(params, grads, new_params)`` must be called inside the
    step's jit: it computes per-group sums of squares over grads /
    new-params / (new − old) and the per-leaf gradient finite mask, all
    with ops on values the step already holds — no extra dispatch, no
    effect on the existing outputs (bitwise; tests pin it).

    ``psum_axis``: ZeRO-1's local gradients differ per shard, so grad
    stats (and the finite mask) are psum-agreed over the named axis —
    one tiny extra collective ([G]+[L] scalars) INSIDE the same
    dispatch; the replicated-gradient path passes None and pays nothing.
    Accepts a tuple of axis names too — the overlap/ring drivers agree
    over every data axis of a hierarchical (dcn × data) mesh.
    The psum'd grad norm is then the RMS-style Σ-over-shards of local
    grads (a drift/NaN signal, not bitwise the pmean'd gradient's norm —
    documented, since only zero1 takes this branch).
    """
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(params_template)
    paths = [path_str(p) for p, _ in flat]

    # Static leaf -> group geometry. A layered leaf ("blocks/...") maps
    # to L groups via its leading axis; others to their top-level key.
    groups: List[str] = []
    group_idx: Dict[str, int] = {}

    def gid(name: str) -> int:
        if name not in group_idx:
            group_idx[name] = len(groups)
            groups.append(name)
        return group_idx[name]

    layered: List[Optional[int]] = []   # first group id of the leaf's layers
    plain: List[Optional[int]] = []     # group id for non-layered leaves
    for p, leaf in flat:
        top = path_str(p[:1])
        shape = getattr(leaf, "shape", ())
        if top in layered_keys and len(shape) >= 1 and shape[0] >= 1:
            base = gid(f"{top}/0")
            for i in range(1, shape[0]):
                gid(f"{top}/{i}")
            layered.append(base)
            plain.append(None)
        else:
            layered.append(None)
            plain.append(gid(top))
    n_groups = len(groups)

    def _group_sq(tree):
        leaves = jax.tree.leaves(tree)
        acc = jnp.zeros((n_groups,), jnp.float32)
        for leaf, lay, pl in zip(leaves, layered, plain):
            x = leaf.astype(jnp.float32)
            if lay is not None:
                per_layer = jnp.sum(
                    x.reshape(x.shape[0], -1) ** 2, axis=1)
                acc = acc.at[lay:lay + x.shape[0]].add(per_layer)
            else:
                acc = acc.at[pl].add(jnp.sum(x ** 2))
        return acc

    def summarize(params, grads, new_params) -> NumericsSummary:
        grad_sq = _group_sq(grads)
        finite = jnp.stack([jnp.all(jnp.isfinite(g))
                            for g in jax.tree.leaves(grads)])
        if psum_axis is not None:
            # Raw lax collectives on purpose: the comm wrappers' static
            # wire profile is pinned by tests at instrumentation-off
            # parity, and these few hundred bytes are observability tax,
            # not payload — accounted here, in this comment, not there.
            grad_sq = jax.lax.psum(grad_sq, psum_axis)
            finite = jax.lax.psum(jnp.logical_not(finite)
                                  .astype(jnp.int32), psum_axis) == 0
        upd = jax.tree.map(lambda n, o: n.astype(jnp.float32)
                           - o.astype(jnp.float32), new_params, params)
        return NumericsSummary(grad_sq=grad_sq,
                               param_sq=_group_sq(new_params),
                               update_sq=_group_sq(upd),
                               grad_finite=finite)

    return NumericsHandle(groups, paths, summarize)


def split_step_output(out):
    """(loss, numerics-or-None) from a step's second output — the shape
    contract instrumented steps share with plain ones: a bare loss array,
    or ``(loss, NumericsSummary)`` when instrumentation is on."""
    if isinstance(out, tuple) and len(out) == 2 \
            and isinstance(out[1], NumericsSummary):
        return out[0], out[1]
    return out, None


# ------------------------------------------------ compile/retrace watching

class CompileRecord(NamedTuple):
    name: str
    seconds: float        # wall time of the compiling call (trace+compile
    #                       +run — the user-visible stall)
    cache_size: int       # entries after this call
    retrace: bool         # broke the factory's max_caches invariant
    flops: Optional[float]
    bytes_accessed: Optional[float]
    memory: Optional[dict] = None   # static device footprint (schema v9:
    #                       memory.compiled_memory — argument/output/temp/
    #                       generated-code bytes), None when unaccountable


class CompileWatch:
    """Transparent wrapper over a jitted callable that turns compilations
    into ``compile`` events.

    Detection is ``_cache_size()`` growth across a call (eval_shape /
    ``lower().compile()`` do not grow it), so the
    steady-state overhead is one int comparison per dispatch. On growth:
    the call's wall time is recorded, the program is costed via
    ``costs.compiled_cost`` AND byte-accounted via
    ``memory.compiled_memory`` (ONE extra compile shared by both, paid
    only on an event that already paid one, and only when someone is
    listening), and a ``compile`` event is emitted to ``self.events``
    when bound — carrying flops/bytes_accessed for attainment plus the
    schema-v9 static footprint (argument/output/temp/generated-code
    bytes), so every watched program's device byte budget is in the
    stream.

    ``max_caches``: the factory's documented compile budget — serving's
    engine steps and fleet's cohort steps promise ONE program; any growth
    past the budget is flagged ``retrace=True`` and counted in
    ``self.retraces`` (the invariant the cohort-padding / data-not-shape
    designs exist to protect). ``None`` disables the invariant (chunked
    training legitimately compiles a tail-chunk shape).

    Attribute access delegates to the wrapped callable, so
    ``_cache_size()`` / ``lower`` / ``eval_shape`` users see the original
    jit object.

    A watched program is identified by its names too. Its
    ``jax.named_scope``s and kernel names are what a device trace's
    readers match, and JAX's persistent compilation cache leaves an
    operation's metadata out of its key by default: an executable built
    by a commit with other names would be handed back with those names
    in every profile (seen on the chip, PERF.md section 6, PR 26). So the
    first watch of a process puts the metadata into the key
    (``jax_compilation_cache_include_metadata_in_key``): here, and
    nowhere else in the repo. The metadata holds source lines, so an edit
    that moves lines of a watched program compiles it again; what the
    flag costs a warm set-up is in PERF.md section 6, PR 27.
    """

    def __init__(self, fn: Callable, *, name: str,
                 max_caches: Optional[int] = 1, cost: bool = True,
                 events=None, meta: Optional[Dict[str, Any]] = None,
                 meta_fn: Optional[Callable] = None):
        import jax
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        self._fn = fn
        self.name = name
        self.max_caches = max_caches
        self._cost = cost
        self.events = events          # late-bindable EventLog
        self.meta = dict(meta or {})
        # Per-CALL meta derived from the compiling call's arguments
        # (guarded; merged over ``meta``) — how the chunked trainer stamps
        # each compile event with the ACTUAL window size, so a tail
        # chunk's smaller program is not mistaken for a full-K one by
        # per-step normalizers (slo_monitor's MFU floor).
        self.meta_fn = meta_fn
        self.compiles: List[CompileRecord] = []
        self.retraces = 0

    def _size(self) -> Optional[int]:
        size = getattr(self._fn, "_cache_size", None)
        if size is None:
            return None
        try:
            return int(size())
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        before = self._size()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        after = self._size()
        if before is not None and after is not None and after > before:
            seconds = time.perf_counter() - t0
            retrace = (self.max_caches is not None
                       and after > self.max_caches)
            flops = bytes_accessed = None
            mem = None
            if self._cost and self.events is not None:
                # One guarded lower→compile serves BOTH accountants —
                # the flop/byte cost model (costs.compiled_cost) and the
                # static memory footprint (memory.compiled_memory) — so
                # observing memory costs no compile beyond what costing
                # already paid.
                from .costs import compiled_cost
                from .memory import compiled_memory
                lower = getattr(self._fn, "lower", None)
                compiled = None
                if lower is not None:
                    try:
                        compiled = lower(*args, **kwargs).compile()
                    except Exception:
                        compiled = None
                if compiled is not None:
                    hlo = compiled_cost(compiled)
                    if hlo is not None:
                        flops = hlo["flops"]
                        bytes_accessed = hlo["bytes_accessed"]
                    mem = compiled_memory(compiled)
            rec = CompileRecord(self.name, seconds, after, retrace,
                                flops, bytes_accessed, mem)
            self.compiles.append(rec)
            if retrace:
                self.retraces += 1
            if self.events is not None:
                meta = dict(self.meta)
                if self.meta_fn is not None:
                    try:
                        meta.update(self.meta_fn(*args, **kwargs))
                    except Exception:
                        pass
                self.events.compile(
                    name=self.name, seconds=seconds, cache_size=after,
                    retrace=retrace, flops=flops,
                    bytes_accessed=bytes_accessed,
                    **(mem or {}), **meta)
        return out

    def __getattr__(self, attr):
        return getattr(self._fn, attr)


def watch(fn: Callable, *, name: str, max_caches: Optional[int] = 1,
          cost: bool = True, events=None,
          meta: Optional[Dict[str, Any]] = None,
          meta_fn: Optional[Callable] = None) -> CompileWatch:
    """Wrap ``fn`` in a ``CompileWatch`` (idempotent: re-watching a watch
    re-binds its name/budget instead of stacking wrappers)."""
    if isinstance(fn, CompileWatch):
        fn.name = name
        fn.max_caches = max_caches
        if events is not None:
            fn.events = events
        if meta:
            fn.meta.update(meta)
        if meta_fn is not None:
            fn.meta_fn = meta_fn
        return fn
    return CompileWatch(fn, name=name, max_caches=max_caches, cost=cost,
                        events=events, meta=meta, meta_fn=meta_fn)


def bind_events(fn, events) -> None:
    """Late-bind an EventLog to a ``CompileWatch`` (no-op for anything
    else) — how the serving scheduler attaches its stream to the
    engine's already-built watches."""
    if isinstance(fn, CompileWatch):
        fn.events = events


# ------------------------------------------------------ roofline peaks

# Published per-chip peaks, keyed by ``jax.Device.device_kind`` — the
# denominators every attainment number in this repo is quoted against.
# A device that is not in the table is an error, not a default.
DEVICE_PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {"flops_per_sec": 197e12, "hbm_bytes_per_sec": 819e9,
                    "source": 'Google Cloud documentation, "TPU v5e" '
                              "(bf16 peak / HBM bandwidth)"},
}

_cpu_peak_cache: Dict[str, Any] = {}


def calibrate_cpu_peak(*, n: int = 384, repeats: int = 3) -> Dict[str, Any]:
    """Measured-not-guessed CPU roofline: time a small f32 matmul chain
    and report achieved FLOP/s — the calibrated baseline a CPU test run's
    attainment is quoted against (an absolute-peak claim for an
    oversubscribed CI host would be fiction; a measured one is a fair
    yardstick). Cached per process; ~10 ms."""
    if _cpu_peak_cache:
        return dict(_cpu_peak_cache)
    import numpy as np

    a = np.random.default_rng(0).standard_normal((n, n)).astype(np.float32)
    b = a.copy()
    a @ b                                    # warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        a @ b
        best = min(best, time.perf_counter() - t0)
    flops = 2.0 * n ** 3 / max(best, 1e-9)
    _cpu_peak_cache.update({
        "flops_per_sec": flops,
        # Effective memory bandwidth proxy: the same matmul's operand +
        # output traffic — a loose floor, flagged as calibrated.
        "hbm_bytes_per_sec": 3.0 * 4 * n * n / max(best, 1e-9),
        "source": f"calibrated ({n}^3 f32 matmul on this host)",
    })
    return dict(_cpu_peak_cache)


def device_peaks(device) -> Dict[str, Any]:
    """Roofline denominators for ``device`` (a ``jax.Device``): the
    published peaks of its ``device_kind`` from ``DEVICE_PEAKS``. The CPU
    the tests run on gets the calibrated baseline, labelled as such in its
    ``source``; no accelerator ever does — an accelerator kind that is not
    in the table raises. Lands in the run manifest so jax-free readers
    (obs_report, slo_monitor) never re-derive it."""
    if device.platform == "cpu":
        return calibrate_cpu_peak()
    peaks = DEVICE_PEAKS.get(device.device_kind)
    if peaks is None:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}): add it to "
            "telemetry/introspect.py DEVICE_PEAKS with its source before "
            "quoting a utilisation on it")
    return dict(peaks)


def attainment(flops: Optional[float], bytes_accessed: Optional[float],
               seconds: float, peaks: Dict[str, Any]) -> Dict[str, Any]:
    """One dispatch's achieved rates vs the peaks: ``{"flops_per_sec",
    "mfu", "bytes_per_sec", "hbm_frac"}`` (fields None when the matching
    numerator/denominator is missing). Pure arithmetic — shared by
    obs_report and slo_monitor, jax-free."""
    out: Dict[str, Any] = {"flops_per_sec": None, "mfu": None,
                           "bytes_per_sec": None, "hbm_frac": None}
    if seconds <= 0:
        return out
    if isinstance(flops, (int, float)) and flops > 0:
        out["flops_per_sec"] = flops / seconds
        peak = peaks.get("flops_per_sec")
        if isinstance(peak, (int, float)) and peak > 0:
            out["mfu"] = out["flops_per_sec"] / peak
    if isinstance(bytes_accessed, (int, float)) and bytes_accessed > 0:
        out["bytes_per_sec"] = bytes_accessed / seconds
        peak = peaks.get("hbm_bytes_per_sec")
        if isinstance(peak, (int, float)) and peak > 0:
            out["hbm_frac"] = out["bytes_per_sec"] / peak
    return out


# ------------------------------------------------------ flight recorder

# Event types whose arrival dumps a bundle: a StepGuard/fault-injection
# trip, an elastic re-mesh, a live SLO breach.
TRIGGER_TYPES = ("fault", "remesh", "slo_violation")

BUNDLE_KIND = "ddl25_postmortem"


class FlightRecorder:
    """Bounded ring over the live event stream + pinned context, dumped
    as a self-contained postmortem bundle when an anomaly event crosses.

    Attach as an ``EventLog`` observer (``Telemetry`` does this by
    default); every emitted event enters the ring, and the manifest /
    latest ``numerics`` / latest ``memory`` (the memory census) /
    ``compile`` events are additionally PINNED so
    they survive ring eviction — a bundle must carry its own context, not
    a pointer into a stream that may be unreadable where the bundle is
    read.

    Bounds: the ring holds ``capacity`` events; a dump serializes at most
    ``max_bytes`` (oldest ring events dropped first, count recorded in
    the bundle); at most ``max_bundles`` bundles are written per recorder
    (a crash-looping run must not fill the disk with identical
    postmortems — the cap and the drop count are themselves diagnostics).
    """

    def __init__(self, out_dir: str, *, capacity: int = 256,
                 max_bytes: int = 256 * 1024, max_bundles: int = 16,
                 triggers: Tuple[str, ...] = TRIGGER_TYPES):
        self.out_dir = out_dir
        self.capacity = max(1, int(capacity))
        self.max_bytes = max(4096, int(max_bytes))
        self.max_bundles = max(1, int(max_bundles))
        # Which event types dump. The trainer's recorder uses the full
        # set; the slo_monitor sidecar narrows to ("slo_violation",) so a
        # fault the TRAINER'S recorder already bundled is not bundled
        # twice from the tailed stream.
        self.triggers = tuple(triggers)
        self.ring: List[Dict[str, Any]] = []
        self.manifest: Optional[Dict[str, Any]] = None
        self.last_numerics: Optional[Dict[str, Any]] = None
        self.last_memory: Optional[Dict[str, Any]] = None
        self.compiles: List[Dict[str, Any]] = []
        self.bundles: List[str] = []
        self.suppressed = 0          # triggers past max_bundles
        self.write_errors = 0

    def observe(self, event: Dict[str, Any]) -> None:
        """EventLog observer: ring + pin + trigger. Never raises (same
        contract as ``EventLog.emit`` — observability must not sink the
        observed)."""
        try:
            self.ingest(event)
            if event.get("type") in self.triggers:
                self.dump(reason=event.get("type"), trigger=event)
        except Exception:
            self.write_errors += 1

    def ingest(self, event: Dict[str, Any]) -> None:
        """Ring + pin WITHOUT triggering — how a sidecar (slo_monitor)
        feeds the events it merely TAILED for bundle context, so a
        violation already in the stream cannot re-dump on replay."""
        etype = event.get("type")
        self.ring.append(event)
        if len(self.ring) > self.capacity:
            del self.ring[:len(self.ring) - self.capacity]
        if etype == "manifest":
            self.manifest = event
        elif etype == "numerics":
            self.last_numerics = event
        elif etype == "memory":
            # The memory census (schema v9): the last MemoryMeter sample
            # before the trip — RSS, state/mirror bytes, pool occupancy
            # and fragmentation — pinned so every postmortem can say what
            # memory looked like when things went wrong.
            self.last_memory = event
        elif etype == "compile":
            self.compiles.append(event)
            if len(self.compiles) > 32:
                del self.compiles[:len(self.compiles) - 32]

    def dump(self, *, reason: str,
             trigger: Optional[Dict[str, Any]] = None) -> Optional[str]:
        """Write one bundle; returns its path (None when capped/failed)."""
        if len(self.bundles) >= self.max_bundles:
            self.suppressed += 1
            return None
        bundle = {
            "bundle": BUNDLE_KIND,
            "schema": _schema_version(),
            "reason": reason,
            "t": time.time(),
            "run_id": (trigger or self.manifest or {}).get("run_id"),
            "trigger": trigger,
            "attribution": (trigger or {}).get("attribution"),
            "manifest": self.manifest,
            "last_numerics": self.last_numerics,
            "memory": self.last_memory,
            "compiles": self.compiles,
            "recent_events": list(self.ring),
            "dropped_events": 0,
        }
        try:
            data = _fit_bundle(bundle, self.max_bytes)
            os.makedirs(self.out_dir, exist_ok=True)
            # First free index at/after this recorder's count: a relaunch
            # reusing the telemetry dir (or a sidecar recorder sharing it)
            # must not overwrite a dead run's postmortem — the bundle that
            # explains the death is the one worth keeping.
            n = len(self.bundles)
            while True:
                path = os.path.join(self.out_dir,
                                    f"postmortem-{n:03d}-{reason}.json")
                if not os.path.exists(path):
                    break
                n += 1
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(data)
            os.replace(tmp, path)
            self.bundles.append(path)
            return path
        except Exception:
            self.write_errors += 1
            return None


def _schema_version() -> int:
    from .events import SCHEMA_VERSION
    return SCHEMA_VERSION


def _fit_bundle(bundle: Dict[str, Any], max_bytes: int) -> str:
    """Serialize under the byte cap: evict oldest ring events (recording
    how many) until it fits; as a last resort drop the ring entirely —
    the pinned context alone is still a useful postmortem."""
    data = json.dumps(bundle, default=str)
    while len(data.encode()) > max_bytes and bundle["recent_events"]:
        drop = max(1, len(bundle["recent_events"]) // 4)
        del bundle["recent_events"][:drop]
        bundle["dropped_events"] += drop
        data = json.dumps(bundle, default=str)
    return data


def load_bundle(path: str) -> Dict[str, Any]:
    """Read one postmortem bundle back (jax-free; raises on a file that
    is not a bundle — the renderer's input validation)."""
    with open(path) as f:
        bundle = json.load(f)
    if not isinstance(bundle, dict) or bundle.get("bundle") != BUNDLE_KIND:
        raise ValueError(f"{path}: not a {BUNDLE_KIND} bundle")
    return bundle


def find_bundles(root: str) -> List[str]:
    """Bundle paths under ``root`` (a telemetry dir or its ``postmortem/``
    subdir), sorted."""
    hits: List[str] = []
    for base, _, files in os.walk(root):
        for f in files:
            if f.startswith("postmortem-") and f.endswith(".json"):
                hits.append(os.path.join(base, f))
    return sorted(hits)
