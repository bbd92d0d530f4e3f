"""The per-layer metrics of a cell that serves a decoder of short
convolutions and grouped-query attention, a mixer a layer, with routed
experts, from the program's own scopes and counters (docs/COMPONENTS.md) and
`conv_experts_cost`. `what`:

- `step_mfu`: required operations of the traced part (tokens and contexts
  from the benchmark's ledger, the experts by the program's `pairs_held` and
  `chunk_pairs_held` on the spans of that part, none where the layers kept
  have no experts) over window x chips x peak, in %.
- `decode_roofline`: over the decode runs that pair with their
  `engine.decode.dispatch` span, the least time for the step's work
  (weights outside the experts once, the experts hit, the live positions of
  the pool, the tails of `state_slots` slots read and written) over the
  runs' device time, in %.
- `moe_roofline`: the least time for the expert layers of the paired runs
  (`experts_cost` of the step's `active`, `pairs_held`, `experts_hit`) over
  the device time under `moe.*` and under none of the program's scopes (the
  grouped product's custom calls carry no scope path), in %.
- `paged_roofline`: the least time for the attention over the pool of the
  paired runs (`paged_cost` of the span's `live_positions`: the key/value
  heads of the attention layers alone) over the time under `paged.*`, in %.
- `conv_ms`: median over the whole decode runs of the device time under
  `conv.proj|taps|out`, in ms; its note gives the step by scope.
- `moe_ms`: the same of the time under `moe.*` and under none of the
  program's scopes together (the grouped product's custom calls carry none,
  as `moe_roofline` counts them), in ms.

Everything returns None where the program wrote no such scope or counter, as
the commit before them does.
"""
import re
import statistics

import conv_experts_cost as cost
import program_trace

PARTS = (
    ("conv", re.compile(r"(^|[/(])conv\.(proj|taps|out)([/)]|$)")),
    ("paged", re.compile(r"(^|[/(])paged\.(write|gather|attend)([/)]|$)")),
    ("moe", re.compile(r"(^|[/(])moe\.(router|grouped|shared|combine)([/)]|$)")),
    ("dense", re.compile(
        r"(^|[/(])(embed|qkv|attn_out|mlp|head|sample)([/)]|$)")),
)
NAMES = ("conv", "paged", "moe", "dense", "unscoped")


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


def _book_of(pt, dispatch):
    """The `engine.decode.book` span of the step that `dispatch` is of."""
    for s in pt.named("engine.decode.book"):
        if s.parent is dispatch.parent:
            return s
    return None


def _step_mfu(ctx, pt):
    # the program's own counter of the slots whose tails a call carries is
    # how this program is told from one before it
    if not any("state_slots" in s.counters for s in pt.spans) \
            or not ctx.counters.get("tokens_processed") or ctx.window_s <= 0:
        return None
    held = [s.counters.get(k, 0) for s in pt.spans
            if s.name in ("engine.decode.book", "engine.prefill.fetch")
            for k in ("pairs_held", "chunk_pairs_held")]
    flops = cost.serve_flops(ctx.dims, ctx.counters["tokens_processed"],
                             ctx.counters["context_sum"],
                             ctx.counters["sampled"], sum(held))
    return 100.0 * flops / ctx.window_s / (ctx.chips
                                           * ctx.peaks["flops_per_s"])


def _scope_ms(ctx, pt, program, parts):
    """Median over the whole runs of `program` of the device time under
    `parts`, in ms."""
    runs = program_trace.whole_runs(ctx, pt, program)[0]
    rows = [(r1 - r0, parts_ns(ops)) for r0, r1, ops in runs]
    if not rows or max(ns["conv"] for _, ns in rows) <= 0:
        return None                     # a program without those scopes
    med = {k: statistics.median(ns[k] for _, ns in rows) / 1e6
           for k in NAMES}
    program_trace.note_once((pt.path, "conv_experts", program), (
        f"note {program} by scope, device ms, medians over {len(rows)} "
        "whole runs: " + " ".join(f"{k}={v:.3f}" for k, v in med.items())
        + f" run={statistics.median(r for r, _ in rows) / 1e6:.3f}"))
    return statistics.median(sum(ns[k] for k in parts)
                             for _, ns in rows) / 1e6


def read(ctx, what, program="decode_step"):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    if what == "step_mfu":
        return _step_mfu(ctx, pt)
    if not pt.has_paths():
        return None
    if what == "conv_ms":
        return _scope_ms(ctx, pt, program, ("conv",))
    if what == "moe_ms":
        return _scope_ms(ctx, pt, program, ("moe", "unscoped"))
    itemsize = program_trace.ITEMSIZE[ctx.cell.config["cache_dtype"]]
    peak_f, peak_b = ctx.peaks["flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    least = took = 0.0
    for span, r0, r1, ops in program_trace.paired_decode_runs(
            ctx, pt, program)[0]:
        c = span.counters
        book = _book_of(pt, span)
        if book is None or "pairs_held" not in book.counters \
                or "state_slots" not in c:
            continue
        held, hit = book.counters["pairs_held"], book.counters["experts_hit"]
        ns = parts_ns(ops)
        if what == "decode_roofline":
            w = cost.decode_step_cost(ctx.dims, c["live_positions"],
                                      c["active"], held, hit,
                                      c["state_slots"], itemsize)
            took += (r1 - r0) / 1e9
        elif what == "moe_roofline":
            w = cost.experts_cost(ctx.dims, c["active"], held, hit, itemsize)
            took += (ns["moe"] + ns["unscoped"]) / 1e9
        elif what == "paged_roofline":
            w = cost.paged_cost(ctx.dims, c["live_positions"], itemsize)
            took += ns["paged"] / 1e9
        else:
            raise ValueError(what)
        least += max(w["flops"] / peak_f, w["bytes"] / peak_b)
    if took <= 0:
        return None
    return 100.0 * least / took
