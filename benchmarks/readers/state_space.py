"""The per-layer metrics of a cell that serves a decoder of state-space
mixers beside grouped-query attention, from the program's own scopes and
counters (docs/COMPONENTS.md) and `state_space_cost`. `what`:

- `step_mfu`: required operations of the traced part (tokens and contexts
  from the benchmark's ledger, the recurrence by the program's `state_slots`
  on the decode dispatch spans and `n_valid` on the prefill dispatch spans
  that carry `state_slots`) over window x chips x peak, in %.
- `decode_roofline`: over the decode runs that pair with their
  `engine.decode.dispatch` span, the least time for the step's work (weights
  once, the state of `state_slots` slots read and written, the
  `live_positions` of the paged pool) over the runs' device time, in %.
- `ssm_ms`: median over the whole decode runs of the device time under
  `ssm.proj|conv|scan|norm|out` plus the time under none of the program's
  scopes of work (what the compiler hoists out of a scope, and the layer
  scan's handling of the state store, count against the mixer, so that it is
  not flattered by them), in ms.
- `ssm_roofline`: the least time for the mixers of the paired decode runs
  (`mixer_step_cost` of the span's `state_slots`) over that same device time,
  in %.
- `ssm_scan_roofline`: the same for the runs of `prefill_chunk` that pair
  with their `engine.prefill.dispatch` span: `mixer_chunk_cost` of the span's
  `n_valid` and `scan_chunks` over the time under `ssm.*` and under no
  scope, in %.

Everything returns None where the program wrote no such scope or counter, as
the commit before them does.
"""
import bisect
import re
import statistics

import program_trace
import state_space_cost as cost

PARTS = (
    ("ssm", re.compile(r"(^|[/(])ssm\.(proj|conv|scan|norm|out)([/)]|$)")),
    ("paged", re.compile(r"(^|[/(])paged\.(write|gather|attend)([/)]|$)")),
    ("dense", re.compile(
        r"(^|[/(])(embed|qkv|attn_out|mlp|head|sample)([/)]|$)")),
)
NAMES = ("ssm", "paged", "dense", "unscoped")


def part_of(path: str) -> str:
    for part, rx in PARTS:
        if rx.search(path):
            return part
    return "unscoped"


def parts_ns(ops) -> dict:
    ns = dict.fromkeys(NAMES, 0)
    for _, s, e, path in ops:
        ns[part_of(path)] += e - s
    return ns


def paired_runs(ctx, pt, program: str, span_name: str):
    """`program_trace.paired_decode_runs` for any of the engine's programs:
    the whole runs of `program`, each with the span `span_name` whose
    `engine.step` holds the middle of the run."""
    steps = sorted((s.parent.start, s.parent.end, s)
                   for s in pt.named(span_name)
                   if s.parent is not None and s.parent.name == "engine.step")
    starts = [a for a, _, _ in steps]
    pairs = []
    for r0, r1, ops in program_trace.whole_runs(ctx, pt, program)[0]:
        mid = (r0 + r1) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and steps[i][1] > mid:
            pairs.append((steps[i][2], r0, r1, ops))
    return pairs


def _itemsizes(ctx):
    cfg = ctx.cell.config
    return (program_trace.ITEMSIZE[cfg["cache_dtype"]],
            program_trace.ITEMSIZE[cfg.get("state_dtype", "float32")])


def _step_mfu(ctx, pt):
    scan = [s.counters["state_slots"] if s.name == "engine.decode.dispatch"
            else s.counters["n_valid"] for s in pt.spans
            if s.name in ("engine.decode.dispatch", "engine.prefill.dispatch")
            and "state_slots" in s.counters]
    if not scan or not ctx.counters.get("tokens_processed") \
            or ctx.window_s <= 0:
        return None
    flops = cost.serve_flops(ctx.dims, ctx.counters["tokens_processed"],
                             ctx.counters["context_sum"],
                             ctx.counters["sampled"], sum(scan))
    return 100.0 * flops / ctx.window_s / (ctx.chips
                                           * ctx.peaks["flops_per_s"])


def read(ctx, what, program="decode_step"):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    if what == "step_mfu":
        return _step_mfu(ctx, pt)
    if not pt.has_paths():
        return None
    if what == "ssm_ms":
        runs = program_trace.whole_runs(ctx, pt, program)[0]
        rows = [(r1 - r0, parts_ns(ops)) for r0, r1, ops in runs]
        if not rows or max(ns["ssm"] for _, ns in rows) <= 0:
            return None                 # a program without those scopes
        med = {k: statistics.median(ns[k] for _, ns in rows) / 1e6
               for k in NAMES}
        program_trace.note_once((pt.path, "state_space", program), (
            f"note {program} by scope, device ms, medians over "
            f"{len(rows)} whole runs: " + " ".join(
                f"{k}={v:.3f}" for k, v in med.items())
            + f" run={statistics.median(r for r, _ in rows) / 1e6:.3f}"))
        return med["ssm"] + med["unscoped"]
    itemsize, state_itemsize = _itemsizes(ctx)
    peak_f, peak_b = ctx.peaks["flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    span_name = ("engine.prefill.dispatch" if what == "ssm_scan_roofline"
                 else "engine.decode.dispatch")
    least = took = 0.0
    for span, r0, r1, ops in paired_runs(ctx, pt, program, span_name):
        c = span.counters
        if "state_slots" not in c:
            continue
        ns = parts_ns(ops)
        if what == "decode_roofline":
            w = cost.decode_step_cost(ctx.dims, c["live_positions"],
                                      c["active"], c["state_slots"],
                                      itemsize, state_itemsize)
            took += (r1 - r0) / 1e9
        elif what == "ssm_roofline":
            w = cost.mixer_step_cost(ctx.dims, c["state_slots"], itemsize,
                                     state_itemsize)
            took += (ns["ssm"] + ns["unscoped"]) / 1e9
        elif what == "ssm_scan_roofline":
            w = cost.mixer_chunk_cost(ctx.dims, c["n_valid"],
                                      c["scan_chunks"], itemsize,
                                      state_itemsize)
            took += (ns["ssm"] + ns["unscoped"]) / 1e9
        else:
            raise ValueError(what)
        least += max(w["flops"] / peak_f, w["bytes"] / peak_b)
    if took <= 0:
        return None
    return 100.0 * least / took
