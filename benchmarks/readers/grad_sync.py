"""The gradient exchange of a data-parallel training step: the operations of
the step program under the scope `grad_sync` (parallel/dp.py), on every chip,
and the counters the trainer puts on `train.dispatch` (`sync_payload_bytes`,
`sync_wire_bytes`, `collectives`; docs/COMPONENTS.md). `what`:

- `exposed_share`: over the whole runs of the step program on chip 0, the
  time in which the exchange is in flight and no operation outside
  `grad_sync` runs, over the runs' summed duration, in %. The exchange is in
  flight during any `grad_sync` operation's own interval, and for an
  asynchronous pair (`<op>-start` ... `<op>-done`) from the start of its
  start to the end of its done. The note gives the same share on every chip,
  the in-flight and the hidden milliseconds a step.
- `gbps`: the `sync_wire_bytes` of the `train.dispatch` spans paired with a
  whole run on chip 0, over those runs' in-flight time, in GB/s (1e9 bytes
  a second): what each chip put on the wire while its exchange was in
  flight. The k-th span by `it` pairs with the (k + o)-th whole run of
  chip 0, o the least offset at which no run starts before the span that
  dispatched it (to a millisecond, the host's and the device's clocks'
  agreement); a run that the host dispatched before the trace began pairs
  with no span. (A run cut by the trace's edge is never whole, and runs
  inside the trace are.)

Both return None where no operation of the trace carries `grad_sync`, and
`gbps` where no `train.dispatch` span carries `sync_wire_bytes` (a commit
before the counters).

`program_trace` reads chip 0 alone; this reads each `/device:TPU:<n>`
plane's `XLA Ops` with its operations' whole HLO lines, which name the
`-start` that a `-done` waits for.
"""
import re

import program_trace
from xplane import CONTAINERS, DEVICE_PLANE, OPS_LINE, _union, short_name

GRAD_SYNC = re.compile(r"(^|[/(])grad_sync([/)]|$)")
ASYNC = re.compile(r"^%?([\w.\-]+?)-(start|done)(\.\d+)?\s")
DONE_OF = re.compile(r"-done\([^%]*%([\w.\-]+)")
SLACK_NS = 1_000_000
ICI_NOTE = ("published ICI 1,600 Gbit/s = 200 GB/s a chip (Google Cloud "
            "documentation, TPU v5e)")

_CACHE = {}


def chip_ops(path):
    """{chip: [(hlo line, start ns, end ns, scope path)]} of every device
    plane's `XLA Ops`, in time order."""
    if path in _CACHE:
        return _CACHE[path]
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for fnum, _, buf in program_trace._fields(space):
        if fnum != 1 or b"/device:TPU:" not in bytes(buf[:256]):
            continue
        plane = program_trace._Plane(buf)
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        meta, ops = {}, []
        for name, ts, events in plane.lines:
            if name != OPS_LINE:
                continue
            for raw in events:
                mid, off, dur, _ = program_trace._event(raw)
                if mid not in meta:
                    md = plane.stats(plane.event_stats.get(mid, []))
                    meta[mid] = (plane.event_names.get(mid, ""),
                                 str(md.get("tf_op", "") or ""))
                start = ts + off // 1000
                ops.append((meta[mid][0], start, start + dur // 1000,
                            meta[mid][1]))
        out[int(m.group(1))] = sorted(ops, key=lambda op: op[1])
    _CACHE.clear()
    _CACHE[path] = out
    return out


def in_flight(ops):
    """(intervals, members) of one run's exchange: the intervals in which it
    is in flight, each `grad_sync` operation's own and each asynchronous
    pair's from its start's start to its done's end, and the indices of the
    operations that belong to it. A done names its start in its HLO line
    and belongs to a `grad_sync` start's pair whatever its own path (the
    compiler may leave a done without one, as it does `copy-done`); a
    `grad_sync` done that names none takes the starts of its kind in
    order."""
    starts, waiting, out, members = {}, {}, [], set()
    for i, (line, s, e, path) in enumerate(ops):
        head = line.partition(" = ")[0].strip().lstrip("%")
        m = ASYNC.match(head + " ")
        named = DONE_OF.search(line) if m and m.group(2) == "done" else None
        if not GRAD_SYNC.search(path) and not (
                named and named.group(1) in starts):
            continue
        members.add(i)
        if m and m.group(2) == "start":
            starts[head] = s
            waiting.setdefault(m.group(1), []).append(head)
            continue
        if m:
            queue = waiting.get(m.group(1), [])
            mine = named.group(1) if named and named.group(1) in starts \
                else (queue[0] if queue else None)
            if mine is not None:
                if mine in queue:
                    queue.remove(mine)
                out.append((starts.pop(mine), e))
                continue
        out.append((s, e))
    out += [(s, s) for s in starts.values()]    # a start never waited for
    return _union(out), members


def _minus(intervals, cover):
    """Nanoseconds of `intervals` (disjoint, sorted) outside `cover`."""
    total, j = 0, 0
    for s, e in intervals:
        t = s
        while j < len(cover) and cover[j][1] <= t:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            total += max(0, cover[k][0] - t)
            t = max(t, cover[k][1])
            k += 1
        total += max(0, e - t)
    return total


def run_exchange(ops):
    """(in-flight ns, exposed ns) of one run's operations."""
    flight, members = in_flight(ops)
    compute = _union([(s, e) for i, (line, s, e, _) in enumerate(ops)
                      if i not in members
                      and not CONTAINERS.match(short_name(line))])
    return sum(e - s for s, e in flight), _minus(flight, compute)


def whole_runs(ctx, ops, chip, program=""):
    """(start, end, operations) of the runs of `program` on `chip` that the
    trace holds whole (as many operations as the fullest, to a fiftieth),
    and all runs that hold an operation, in time order."""
    runs = []
    for r0, r1 in sorted(ctx.trace.program_intervals(program, chip)):
        mine = [op for op in ops if r0 <= (op[1] + op[2]) // 2 < r1]
        if mine:
            runs.append((r0, r1, mine))
    most = max((len(r[2]) for r in runs), default=0)
    return [r for r in runs if len(r[2]) >= 0.98 * most], runs


def pair_spans(spans, runs):
    """[(span, run)] by order from the least offset at which no run starts
    before its span (to `SLACK_NS`)."""
    spans = sorted(spans, key=lambda s: s.counters.get("it", 0))
    for o in range(len(runs) + 1):
        pairs = list(zip(spans, runs[o:]))
        if all(r[0] >= s.start - SLACK_NS for s, r in pairs):
            return pairs
    return []


def _exposed_share(ctx, per_chip):
    shares = {c: 100.0 * sum(x for _, x in parts)
              / sum(r1 - r0 for r0, r1, _ in whole)
              for c, (whole, parts, _) in per_chip.items()}
    whole, parts, runs = per_chip[0]
    steps = max(1, round(ctx.counters.get("steps", 0) / len(runs))) \
        * len(whole)
    flight = sum(f for f, _ in parts) / 1e6 / steps
    exposed = sum(x for _, x in parts) / 1e6 / steps
    kinds = {}
    for line, _, _, path in whole[0][2]:
        if GRAD_SYNC.search(path):
            kind = re.sub(r"\.\d+$", "", short_name(line).split(" ")[0])
            kinds[kind] = kinds.get(kind, 0) + 1
    program_trace.note_once((ctx.trace.path, "grad_sync"), (
        f"note gradient exchange over {len(whole)} whole runs on chip 0: "
        "exposed share by chip " + " ".join(
            f"{c}={v:.4f}%" for c, v in sorted(shares.items()))
        + f"; in flight {flight:.3f} ms a step, exposed {exposed:.3f}, "
        f"hidden under compute {flight - exposed:.3f}; grad_sync "
        f"operations of a run by kind: {kinds}"))
    return shares[0]


def _gbps(ctx, per_chip):
    pt = program_trace.of(ctx)
    spans = [s for s in pt.named("train.dispatch")
             if "sync_wire_bytes" in s.counters]
    if not spans:
        return None
    whole, parts, runs = per_chip[0]
    flight = {r[0]: f for r, (f, _) in zip(whole, parts)}
    pairs = pair_spans(spans, whole)
    flight_ns = sum(flight[r[0]] for _, r in pairs)
    if flight_ns <= 0:
        return None
    wire = sum(s.counters["sync_wire_bytes"] for s, _ in pairs)
    last = pairs[-1][0].counters
    program_trace.note_once((ctx.trace.path, "grad_sync_bytes"), (
        f"note gradient exchange by the dispatch counters: {len(pairs)} "
        f"spans paired with whole runs ({len(runs)} runs on chip 0); a "
        f"step sync_payload_bytes={last.get('sync_payload_bytes')} "
        f"sync_wire_bytes={last['sync_wire_bytes']} "
        f"collectives={last.get('collectives')}; {ICI_NOTE}"))
    return wire / (flight_ns / 1e9) / 1e9


def read(ctx, what, program=""):
    if ctx.trace is None:
        return None
    per_chip = {}
    for chip, ops in sorted(chip_ops(ctx.trace.path).items()):
        if not any(GRAD_SYNC.search(op[3]) for op in ops):
            continue
        whole, runs = whole_runs(ctx, ops, chip, program)
        if whole:
            per_chip[chip] = (whole, [run_exchange(r[2]) for r in whole],
                              runs)
    if 0 not in per_chip:
        return None
    if what == "exposed_share":
        return _exposed_share(ctx, per_chip)
    if what == "gbps":
        return _gbps(ctx, per_chip)
    raise ValueError(what)
