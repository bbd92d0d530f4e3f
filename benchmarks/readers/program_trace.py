"""What the program itself wrote into a profiler trace: its host spans with
their counters, and the scope path of every device operation.

`xplane.py` keeps the benchmark's own annotations (`bench.*`) and an
operation's name and shape. This reads the same `.xplane.pb` once more, once a
run, for what the program put there (ddl25spring_tpu/telemetry/trace.py,
docs/COMPONENTS.md):

- host spans: events of a `/host:` plane named `serve.*`, `engine.*` or
  `train.*`, each a `jax.profiler.TraceAnnotation` of the program. Their
  counters are the event's statistics (the annotation's keyword arguments and
  what `set_metadata` added). Nesting is by time on one thread.
- scope paths: an `XLA Ops` event's metadata carries a statistic `tf_op`, the
  HLO instruction's `op_name`: `jit(local_step)/transpose(jvp(attn))/...`,
  `jit(decode_step)/while/body/paged.gather/gather`. `jax.named_scope` names
  and JAX's own markers (`jvp(...)`, `transpose(...)`, `checkpoint`,
  `rematted_computation`) are parts of that path. `jax.profiler.ProfileData`
  does not hand out an event's metadata, so the file is read here as plain
  protobuf wire format (the few fields of `XSpace` that are needed).

Times are nanoseconds on the profiler's clock, `line.timestamp_ns +
event.offset_ps / 1000`, the same numbers `ProfileData` and so `xplane.Trace`
give, so intervals of the two can be mixed.

Everything returns nothing (`None`, an empty list) where the program wrote no
such span or path, as it does at a commit before the spans existed.
"""

from __future__ import annotations

import bisect
import os
import re
import statistics
import struct
from typing import Dict, List, Optional, Tuple

import model_cost
from xplane import CONTAINERS, short_name

PROGRAM_SPANS = ("serve.", "engine.", "train.")
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}

# What the chip waits for when it is idle inside a tick, by the innermost
# program span over the instant.
IDLE_CAUSES = {
    "stage": re.compile(r"^engine\.(prefill|decode)\.(stage|dispatch)$"),
    "sync": re.compile(r"^engine\.(prefill|decode)\.fetch$"),
    "book": re.compile(r"^(engine\.decode\.book|serve\.emit|serve\.admit)$"),
}

# The phases of a training step, by the operation's scope path. The first
# that matches wins: the head and the optimizer by our scopes, then what JAX
# writes itself: under `transpose(jvp(...))`, the backward of a block under
# `jax.checkpoint` reads `.../checkpoint/<op>` and its recomputed forward
# `.../checkpoint/rematted_computation/<op>` (jax 0.9.0).
TRAIN_PHASES = (
    ("optimizer", re.compile(r"(^|[/(])optimizer([/)]|$)")),
    ("head_loss", re.compile(r"(^|[/(])head_loss([/)]|$)")),
    ("remat", re.compile(r"rematted_computation")),
    ("backward", re.compile(r"transpose\(")),
)

# The parts of a decode step, by the operation's scope path; the first that
# matches wins. `paged` is the attention over the block pool as the program
# wrote it; `dense` the rest of the model. What matches neither is
# `unscoped`: it runs inside the step under none of the program's scopes of
# work (`layers` only names the scan): the scan's slicing of each layer's
# pool out of the stacked pool and writing it back, whole-pool copies, and
# whatever the compiler hoisted out of the scope it was written in.
DECODE_PARTS = (
    ("paged", re.compile(r"(^|[/(])paged\.(write|gather|attend)([/)]|$)")),
    ("dense", re.compile(
        r"(^|[/(])(embed|qkv|attn_out|mlp|head|sample)([/)]|$)")),
)


# ------------------------------------------------------------- wire format

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview-free slice of `buf`."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wire == 1:
            v = buf[i:i + 8]
            i += 8
        elif wire == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, wire, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes) -> Tuple[int, object]:
    """XStat: (metadata id, value). A `ref_value` comes back as ("ref", id)."""
    mid, val = 0, None
    for f, wire, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif f == 6:
            val = bytes(v)
        elif f == 7:
            val = ("ref", v)
    return mid, val


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for f, _, v in _fields(buf):
        if f == 1:
            key = _signed(v)
        elif f == 2:
            val = v
    return key, val


class _Plane:
    """One XPlane: name, lines [(name, timestamp_ns, [event bytes])], and
    the metadata tables by id."""

    def __init__(self, buf: bytes):
        self.name = ""
        self.lines: List[Tuple[str, int, List[bytes]]] = []
        self.event_names: Dict[int, str] = {}
        self.event_stats: Dict[int, List[bytes]] = {}
        self.stat_names: Dict[int, str] = {}
        for f, _, v in _fields(buf):
            if f == 2:
                self.name = bytes(v).decode()
            elif f == 3:
                self.lines.append(self._line(v))
            elif f == 4:
                mid, md = _map_entry(v)
                stats = []
                for g, _, w in _fields(md):
                    if g == 2:
                        self.event_names[mid] = bytes(w).decode(
                            "utf-8", "replace")
                    elif g == 5:
                        stats.append(w)
                self.event_stats[mid] = stats
            elif f == 5:
                mid, md = _map_entry(v)
                for g, _, w in _fields(md):
                    if g == 2:
                        self.stat_names[mid] = bytes(w).decode()

    @staticmethod
    def _line(buf: bytes) -> Tuple[str, int, List[bytes]]:
        name, ts, events = "", 0, []
        for f, _, v in _fields(buf):
            if f == 2:
                name = bytes(v).decode()
            elif f == 3:
                ts = _signed(v)
            elif f == 4:
                events.append(v)
        return name, ts, events

    def stats(self, raw: List[bytes]) -> Dict[str, object]:
        out = {}
        for s in raw:
            mid, val = _stat(s)
            if isinstance(val, tuple):
                val = self.stat_names.get(val[1], "")
            out[self.stat_names.get(mid, str(mid))] = val
        return out


def _event(buf: bytes) -> Tuple[int, int, int, List[bytes]]:
    """XEvent: (metadata id, offset_ps, duration_ps, raw stats)."""
    mid = off = dur = 0
    stats = []
    for f, _, v in _fields(buf):
        if f == 1:
            mid = _signed(v)
        elif f == 2:
            off = _signed(v)
        elif f == 3:
            dur = _signed(v)
        elif f == 4:
            stats.append(v)
    return mid, off, dur, stats


# ------------------------------------------------------------------ the trace

class Span:
    """One host span of the program."""
    __slots__ = ("name", "start", "end", "counters", "parent", "depth")

    def __init__(self, name, start, end, counters):
        self.name, self.start, self.end = name, start, end
        self.counters = counters
        self.parent: Optional["Span"] = None
        self.depth = 0

    def __repr__(self):
        return f"Span({self.name!r}, {self.start}, {self.end}, {self.counters})"


def nest(spans: List[Span]) -> List[Span]:
    """Sort by start (the longer first where two start together) and give
    each span its parent and depth: the innermost span that contains it."""
    spans = sorted(spans, key=lambda s: (s.start, -s.end))
    stack: List[Span] = []
    for s in spans:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        # a span that only overlaps (another thread's) is no parent
        while stack and stack[-1].end < s.end:
            stack.pop()
        s.parent = stack[-1] if stack else None
        s.depth = len(stack)
        stack.append(s)
    return spans


def innermost_segments(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """Disjoint (start, end, name) in time order: over each instant that any
    span covers, the name of the innermost one."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Span] = []
    t = 0

    def emit(a: int, b: int, name: str) -> None:
        if b > a:
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))

    def advance(to: int) -> None:
        nonlocal t
        while stack and stack[-1].end <= to:
            top = stack.pop()
            emit(t, top.end, top.name)
            t = max(t, top.end)
        if stack:
            emit(t, to, stack[-1].name)
        t = max(t, to)

    ordered = nest(list(spans))
    for s in ordered:
        advance(s.start)
        while stack and stack[-1].end < s.end:   # overlaps, does not hold it
            stack.pop()
        stack.append(s)
    if ordered:
        advance(max(s.end for s in ordered))
    return out


def split_by_segments(gaps: List[Tuple[int, int]],
                      segments: List[Tuple[int, int, str]]
                      ) -> Dict[str, int]:
    """Each gap's nanoseconds given, instant by instant, to the segment that
    covers them; what no segment covers goes to `""`."""
    acc: Dict[str, int] = {}
    starts = [s for s, _, _ in segments]
    for g0, g1 in gaps:
        covered = 0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(segments) and segments[i][0] < g1:
            s, e, name = segments[i]
            cut = min(e, g1) - max(s, g0)
            if cut > 0:
                acc[name] = acc.get(name, 0) + cut
                covered += cut
            i += 1
        if g1 - g0 > covered:
            acc[""] = acc.get("", 0) + (g1 - g0) - covered
    return acc


class ProgramTrace:
    """The program's spans and the scope paths of chip 0's operations."""

    def __init__(self, path: str):
        self.path = path
        self.size = os.path.getsize(path)
        self.spans: List[Span] = []
        # (short name, start, end, scope path) of chip 0's XLA Ops
        self.ops: List[Tuple[str, int, int, str]] = []
        with open(path, "rb") as f:
            space = f.read()
        for fnum, _, buf in _fields(space):
            if fnum != 1:
                continue
            head = bytes(buf[:256])
            if b"/device:TPU:0" not in head and b"/host:" not in head:
                continue                    # metadata and other chips
            plane = _Plane(buf)
            if plane.name == "/device:TPU:0":
                self._read_ops(plane)
            elif plane.name.startswith("/host:"):
                self._read_spans(plane)
        self.spans = nest(self.spans)
        # leaf operations (a container's time is its children's) by their
        # middle: a run's last operation can end a nanosecond past the run
        self._leaves = sorted((op for op in self.ops
                               if not CONTAINERS.match(op[0])),
                              key=lambda op: op[1] + op[2])
        self._middles = [(op[1] + op[2]) // 2 for op in self._leaves]

    def _read_ops(self, plane: _Plane) -> None:
        paths: Dict[int, Tuple[str, str]] = {}
        for name, ts, events in plane.lines:
            if name != "XLA Ops":
                continue
            for raw in events:
                mid, off, dur, _ = _event(raw)
                if mid not in paths:
                    md = plane.stats(plane.event_stats.get(mid, []))
                    paths[mid] = (short_name(plane.event_names.get(mid, "")),
                                  str(md.get("tf_op", "") or ""))
                start = ts + off // 1000        # as ProfileData reckons
                self.ops.append((paths[mid][0], start, start + dur // 1000,
                                 paths[mid][1]))

    def _read_spans(self, plane: _Plane) -> None:
        mine = {mid for mid, name in plane.event_names.items()
                if name.startswith(PROGRAM_SPANS)}
        if not mine:
            return
        for _, ts, events in plane.lines:
            for raw in events:
                mid, off, dur, stats = _event(raw)
                if mid not in mine:
                    continue
                counters = plane.stats(plane.event_stats.get(mid, []) + stats)
                start = ts + off // 1000
                self.spans.append(Span(
                    plane.event_names[mid], start, start + dur // 1000,
                    {k: int(v) for k, v in counters.items()
                     if isinstance(v, int)
                     or (isinstance(v, str) and v.lstrip("-").isdigit())}))

    # -------------------------------------------------------------- queries
    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def has_paths(self) -> bool:
        return any(p for _, _, _, p in self.ops)

    def leaf_ops_inside(self, r0: int, r1: int
                        ) -> List[Tuple[str, int, int, str]]:
        """Leaf operations whose middle lies in [r0, r1)."""
        return self._leaves[bisect.bisect_left(self._middles, r0):
                            bisect.bisect_left(self._middles, r1)]

    def top_ops(self, n: int = 10) -> List[Tuple[str, float, str]]:
        acc: Dict[Tuple[str, str], int] = {}
        for name, s, e, p in self._leaves:
            acc[(name, p)] = acc.get((name, p), 0) + e - s
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [(name, ns / 1e9, p) for (name, p), ns in top]


_CACHE: Dict[str, ProgramTrace] = {}
_NOTED: set = set()


def note_once(key, line: str) -> None:
    """Print a run's note once, however many metrics ask for its number."""
    if key not in _NOTED:
        _NOTED.add(key)
        print(line, flush=True)


def of(ctx) -> Optional[ProgramTrace]:
    """The run's program trace, read once; None for an untraced run."""
    if ctx.trace is None:
        return None
    path = ctx.trace.path
    if path not in _CACHE:
        _CACHE.clear()
        t = _CACHE[path] = ProgramTrace(path)
        names = sorted({s.name for s in t.spans})
        note_once((path, "trace"), (
            f"note program trace: xplane_bytes={t.size} spans={len(t.spans)} "
            f"names={names} ops={len(t.ops)} with_scope_path="
            f"{sum(1 for o in t.ops if o[3])}"))
        if t.has_paths():
            note_once((path, "top"), "note top ops by scope: " + "; ".join(
                f"{name} {sec:.4f}s [{p}]" for name, sec, p in t.top_ops(12)))
    return _CACHE[path]


# ------------------------------------------------------- what the readers share

def phase_of(path: str) -> str:
    for phase, rx in TRAIN_PHASES:
        if rx.search(path):
            return phase
    return "forward"


def whole_runs(ctx, pt: ProgramTrace, program: str):
    """(start, end, leaf ops) of the runs of `program` that the trace holds
    whole: those with as many operations as the fullest run, to a fiftieth
    (one cut by the trace's edge holds fewer). Beside them, how many runs
    hold an operation at all."""
    runs = [(r0, r1, pt.leaf_ops_inside(r0, r1))
            for r0, r1 in ctx.trace.program_intervals(program)]
    runs = [r for r in runs if r[2]]
    whole = max((len(r[2]) for r in runs), default=0)
    return [r for r in runs if len(r[2]) >= 0.98 * whole], len(runs)


def step_phases(ctx, program: str = "") -> Optional[Dict[str, float]]:
    """Device milliseconds a training step by phase, over the whole runs of
    the step program, each standing for the steps the host counted over the
    program's runs (as `xplane_kernel_roofline` counts them). `forward` is
    the rest of the runs' time, so the phases sum to the step."""
    pt = of(ctx)
    if pt is None or not pt.has_paths():
        return None
    runs, n_runs = whole_runs(ctx, pt, program)
    if not runs:
        return None
    steps_a_run = max(1, round(ctx.counters.get("steps", 0) / n_runs))
    steps = steps_a_run * len(runs)
    ns = {"optimizer": 0, "head_loss": 0, "remat": 0, "backward": 0,
          "forward_ops": 0}
    for _, _, ops in runs:
        for _, s, e, path in ops:
            phase = phase_of(path)
            ns["forward_ops" if phase == "forward" else phase] += e - s
    run_ns = sum(r1 - r0 for r0, r1, _ in runs)
    out = {k: v / 1e6 / steps for k, v in ns.items()}
    out["step"] = run_ns / 1e6 / steps
    out["forward"] = out["step"] - sum(
        out[k] for k in ("optimizer", "head_loss", "remat", "backward"))
    note_once((pt.path, "phases"), (
        "note train step by phase, device ms a step over "
        f"{len(runs)} whole runs of {steps_a_run} step(s): " + " ".join(
            f"{k}={out[k]:.3f}" for k in (
                "forward", "backward", "remat", "head_loss", "optimizer",
                "step")) + f" (forward is the rest of the run; its own "
        f"operations take {out['forward_ops']:.3f}, between operations "
        f"{out['forward'] - out['forward_ops']:.3f})"))
    return out


def idle_split(ctx) -> Optional[Dict[str, float]]:
    """Chip 0's idle share of the trace's span, in %, by cause: each idle
    instant goes to the innermost program span over it (`IDLE_CAUSES`), the
    rest to `other`. None where the program left no span."""
    pt = of(ctx)
    if pt is None or not pt.spans or not ctx.trace.ops:
        return None
    t0, t1 = ctx.trace.span()
    if t1 <= t0:
        return None
    busy = ctx.trace.busy_intervals(0)
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    by_name = split_by_segments(gaps, innermost_segments(pt.spans))
    total = sum(by_name.values())
    out = {cause: 0 for cause in IDLE_CAUSES}
    out["other"] = 0
    for name, ns in by_name.items():
        cause = next((c for c, rx in IDLE_CAUSES.items() if rx.match(name)),
                     "other")
        out[cause] += ns
    out = {k: 100.0 * v / (t1 - t0) for k, v in out.items()}
    out["total"] = 100.0 * total / (t1 - t0)
    note_once((pt.path, "idle"), (
        "note idle by cause, % of the trace's span: " + " ".join(
            f"idle_{k}={out[k]:.4f}" for k in (
                "stage", "sync", "book", "other", "total"))
        + "; by innermost span, ms: " + " ".join(
            f"{name or 'none'}={ns / 1e6:.2f}" for name, ns in sorted(
                by_name.items(), key=lambda kv: -kv[1]))))
    return out


def decode_part_of(path: str) -> str:
    for part, rx in DECODE_PARTS:
        if rx.search(path):
            return part
    return "unscoped"


def cache_ns(ops) -> Tuple[int, int]:
    """(paged, unscoped) nanoseconds of one decode run's leaf operations:
    together, all the step spends on the cache."""
    ns = {"paged": 0, "dense": 0, "unscoped": 0}
    for _, s, e, path in ops:
        ns[decode_part_of(path)] += e - s
    return ns["paged"], ns["unscoped"]


def decode_parts(ctx, program: str) -> Optional[Dict[str, float]]:
    """Device milliseconds of one decode step in its three parts, each the
    median over the whole runs of the decode program: `paged`, `unscoped`
    (`DECODE_PARTS`) and `dense`, the rest of the run, so that run by run
    the three are the run. None where no operation of the program carries
    one of the program's scopes (a commit before the scopes, or an
    executable that the compile cache handed back with another commit's
    names)."""
    pt = of(ctx)
    if pt is None or not pt.has_paths():
        return None
    runs = whole_runs(ctx, pt, program)[0]
    rows = [(r1 - r0,) + cache_ns(ops) for r0, r1, ops in runs]
    if not rows or max(p for _, p, _ in rows) <= 0:
        note_once((pt.path, "decode"), (
            f"note decode step in parts: no operation of {program} carries "
            f"a paged.* scope in {len(rows)} whole runs: the program has no "
            f"such scopes, or the executable came from a compile cache "
            f"filled by a commit with other names"))
        return None
    out = {"run": statistics.median(r for r, _, _ in rows) / 1e6,
           "paged": statistics.median(p for _, p, _ in rows) / 1e6,
           "unscoped": statistics.median(u for _, _, u in rows) / 1e6,
           "dense": statistics.median(r - p - u for r, p, u in rows) / 1e6}
    weights_once = 1e3 * model_cost.decode_step_cost(
        ctx.dims, 0, 0, ITEMSIZE[ctx.cell.config["weights_dtype"]["serve"]]
    )["bytes"] / ctx.peaks["hbm_bytes_per_s"]
    acc: Dict[Tuple[str, str], int] = {}
    for _, _, ops in runs:
        for name, s, e, path in ops:
            if decode_part_of(path) == "unscoped":
                acc[(name, path)] = acc.get((name, path), 0) + e - s
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:5]
    note_once((pt.path, "decode"), (
        f"note decode step in parts, device ms, medians over {len(rows)} "
        f"whole runs: dense={out['dense']:.3f} paged={out['paged']:.3f} "
        f"unscoped={out['unscoped']:.3f} run={out['run']:.3f} (dense is the "
        f"rest of each run; weights once over the peak bytes/s is "
        f"{weights_once:.3f}); largest unscoped, ms a run: " + "; ".join(
            f"{name} {ns / 1e6 / len(rows):.3f} [{path or 'no path'}]"
            for (name, path), ns in top)))
    return out


def paired_decode_runs(ctx, pt: ProgramTrace, program: str):
    """The whole runs of the decode program, each with the
    `engine.decode.dispatch` span that dispatched it: the one whose
    `engine.step` holds the middle of the run (the step ends after the host
    has fetched the run's tokens; the middle, because host and device clocks
    agree to a fraction of a millisecond only, and a run can seem to start
    before its dispatch). Returns (pairs, unpaired) with pairs = [(span,
    start, end, leaf ops)]; a run whose step began before the trace did, or
    was open when it stopped, pairs with none."""
    steps = sorted((s.parent.start, s.parent.end, s)
                   for s in pt.named("engine.decode.dispatch")
                   if s.parent is not None and s.parent.name == "engine.step")
    starts = [a for a, _, _ in steps]
    pairs, unpaired = [], 0
    for r0, r1, ops in whole_runs(ctx, pt, program)[0]:
        mid = (r0 + r1) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i < 0 or steps[i][1] <= mid:
            unpaired += 1
            continue
        pairs.append((steps[i][2], r0, r1, ops))
    return pairs, unpaired
