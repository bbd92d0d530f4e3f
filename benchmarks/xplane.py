"""Reduction of one `.xplane.pb` profiler trace to what the readers need.

The JAX profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`.
`jax.profiler.ProfileData` reads it: planes, their lines, and events with a
start and a duration in nanoseconds. On a TPU each chip is a plane
`/device:TPU:<n>` whose line `XLA Ops` holds one event an executed HLO
operation, named by its HLO line and kept here as `<name> <shape>` (a
Pallas kernel is one such event, `flash_attention.44 bf16[64,128,4096]`; a
scan is a `while.<n>` that contains its body's events), and whose line
`XLA Modules` holds one event a run of a compiled program, named
`jit_<function>(<id>)`.
Host threads are lines of the plane `/host:CPU`; a
`jax.profiler.TraceAnnotation` is an event there under its own name. The
benchmark's own start with `bench.`; one that is numbered (`bench.tick#17`)
is grouped under the part before the `#`.

Everything is computed over the events as recorded, on the trace's own
clock, in seconds:

- `busy_s`: the union of the `XLA Ops` intervals of a chip, averaged over
  the chips; `span_s`: from the first to the last recorded event, device
  operation or `bench.` annotation. Both are of one clock, so the idle
  share is `1 - busy_s / span_s` and cannot be negative.
- `program_runs(name)`: durations of the `XLA Modules` events whose name
  contains `name`; `program_intervals(name)` their starts and ends.
- `ops_inside(pattern, runs)`: for each of `runs`, summed seconds and the
  count of the matching `XLA Ops` events that lie inside it.
- `mark_at(prefix, t)`: the `bench.` annotation that covers instant `t`.
- `op_seconds(pattern)`: summed durations of the `XLA Ops` events whose
  name matches the regular expression.
- `top_ops(n)`: the operations that took most time, containers left out.
- `idle_gaps(n)`: the idle intervals of chip 0, each given to the host
  annotation named `bench.*` that overlaps most of it, summed by name.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Operations that only contain others: their time is their children's.
CONTAINERS = re.compile(r"^(while|conditional|call)([.:_\d ]|$)")
HOST_MARK = "bench."


def short_name(name: str) -> str:
    """An `XLA Ops` event is named by its whole HLO line, `%fusion.297 =
    bf16[4,4096,11008]{...} fusion(...), kind=kOutput, ...`. Keep the
    operation's own name and the shape it produces: `fusion.297
    bf16[4,4096,11008]`."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:96]
    shape = re.match(r"\(?[a-z0-9]+\[[0-9,]*\]", rest)
    return (head.lstrip("%") + (" " + shape.group(0).lstrip("(")
                                if shape else ""))[:96]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


class Trace:
    """One trace, reduced once. Times in nanoseconds inside, seconds out."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.path = path
        self.ops: Dict[int, List[Tuple[str, int, int]]] = {}
        self.modules: Dict[int, List[Tuple[str, int, int]]] = {}
        self.host: List[Tuple[str, int, int]] = []
        self.plane_names: List[str] = []
        for plane in data.planes:
            self.plane_names.append(plane.name)
            m = DEVICE_PLANE.match(plane.name)
            if m:
                chip = int(m.group(1))
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        ops = line.name == OPS_LINE
                        events = [(short_name(e.name) if ops else e.name,
                                   int(e.start_ns),
                                   int(e.start_ns + e.duration_ns))
                                  for e in line.events]
                        (self.ops if ops else self.modules)[chip] = events
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(HOST_MARK):
                            self.host.append((e.name, int(e.start_ns),
                                              int(e.start_ns + e.duration_ns)))

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def busy_intervals(self, chip: int) -> List[Tuple[int, int]]:
        return _union([(s, e) for _, s, e in self.ops.get(chip, [])])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        per = [sum(e - s for s, e in self.busy_intervals(c))
               for c in self.chips]
        return sum(per) / len(per) / 1e9

    def span(self) -> Tuple[int, int]:
        """First start and last end of everything recorded (ns)."""
        events = [ev for per in self.ops.values() for ev in per] + self.host
        if not events:
            return (0, 0)
        return (min(s for _, s, _ in events), max(e for _, _, e in events))

    def span_s(self) -> float:
        s, e = self.span()
        return (e - s) / 1e9

    def program_intervals(self, name: str = "", chip: int = 0
                          ) -> List[Tuple[int, int]]:
        return [(s, e) for n, s, e in self.modules.get(chip, []) if name in n]

    def program_runs(self, name: str, chip: int = 0) -> List[float]:
        return [(e - s) / 1e9 for s, e in self.program_intervals(name, chip)]

    def ops_inside(self, pattern: str, runs: List[Tuple[int, int]],
                   chip: int = 0) -> List[Tuple[float, int]]:
        """(summed seconds, number of events) of the matching ops inside
        each of `runs`."""
        rx = re.compile(pattern)
        hits = sorted((s, e) for n, s, e in self.ops.get(chip, [])
                      if rx.search(n))
        out = []
        for r0, r1 in runs:
            mine = [e - s for s, e in hits if s >= r0 and e <= r1]
            out.append((sum(mine) / 1e9, len(mine)))
        return out

    def mark_at(self, prefix: str, t: int):
        """Name of the host annotation starting with `prefix` that covers
        instant `t` (ns), or None."""
        for name, s, e in self.host:
            if s <= t < e and name.startswith(prefix):
                return name
        return None

    def program_names(self, chip: int = 0) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for n, s, e in self.modules.get(chip, []):
            out[re.sub(r"\(\d+\)$", "", n)] += (e - s) / 1e9
        return dict(out)

    def op_seconds(self, pattern: str, chip: int = 0) -> Tuple[float, int]:
        """(summed seconds, number of events) of the ops matching."""
        rx = re.compile(pattern)
        hits = [(e - s) for n, s, e in self.ops.get(chip, []) if rx.search(n)]
        return sum(hits) / 1e9, len(hits)

    def top_ops(self, n: int = 10, chip: int = 0) -> List[list]:
        acc: Dict[str, float] = defaultdict(float)
        for name, s, e in self.ops.get(chip, []):
            if not CONTAINERS.match(name):
                acc[name] += (e - s) / 1e9
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10, chip: int = 0) -> List[list]:
        """Idle seconds of a chip between its first and last operation, by
        the host annotation that covers most of each gap (`unmarked` where
        none does)."""
        busy = self.busy_intervals(chip)
        acc: Dict[str, float] = defaultdict(float)
        host = sorted(self.host, key=lambda h: h[1])
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            if s1 <= e0:
                continue
            best, best_cover = "unmarked", 0
            for name, hs, he in host:
                if hs >= s1:
                    break
                cover = min(he, s1) - max(hs, e0)
                # the innermost annotation wins a tie in cover
                if cover > 0 and cover >= best_cover:
                    best, best_cover = name.partition("#")[0], cover
            acc[best] += (s1 - e0) / 1e9
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def summary(self) -> dict:
        """What one looks at by hand before trusting the reduction."""
        return {"planes": self.plane_names, "chips": self.chips,
                "n_ops": {c: len(v) for c, v in self.ops.items()},
                "programs": self.program_names(),
                "host_marks": sorted({n.partition("#")[0]
                                      for n, _, _ in self.host}),
                "busy_s": self.busy_s(), "span_s": self.span_s(),
                "top_ops": self.top_ops(25)}
