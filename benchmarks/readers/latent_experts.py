"""The per-layer metrics of a cell that serves a latent-attention decoder
with routed experts, from the program's own scopes and counters
(docs/COMPONENTS.md) and `latent_experts_cost`. `what`:

- `step_mfu`: required operations of the traced part (tokens and contexts
  from the benchmark's ledger, the routed experts by the program's
  `pairs_held` and `chunk_pairs_held` on the spans of that part) over
  window x chips x peak, in %.
- `decode_roofline`: over the decode runs that pair with their
  `engine.decode.dispatch` span, the least time for the step's work
  (weights outside the experts once, the experts hit, the live latent rows)
  over the runs' device time, in %.
- `latent_ms`, `moe_ms`: median over the whole decode runs of the device
  time under `latent.write|gather|attend`, or under `moe.*`, plus the time
  under none of the program's scopes of work (what the compiler hoists out
  of a scope counts against both, so neither is flattered by it), in ms.
- `latent_roofline`, `moe_roofline`: the least time for that part of the
  paired runs (`latent_attention_cost` of the span's `live_positions`;
  `expert_layers_cost` of the step's `active`, `pairs_held`, `experts_hit`)
  over the same device time, in %.

Everything returns None where the program wrote no such scope or counter,
as the commit before them does.
"""
import re
import statistics

import latent_experts_cost as cost
import program_trace

PARTS = (
    ("latent", re.compile(r"(^|[/(])latent\.(write|gather|attend)([/)]|$)")),
    ("moe", re.compile(r"(^|[/(])moe\.(router|grouped|shared|combine)([/)]|$)")),
    ("dense", re.compile(
        r"(^|[/(])(embed|q_proj|kv_proj|attn_out|mlp|head|sample)([/)]|$)")),
)


def part_of(path: str) -> str:
    for part, rx in PARTS:
        if rx.search(path):
            return part
    return "unscoped"


def parts_ns(ops) -> dict:
    ns = {"latent": 0, "moe": 0, "dense": 0, "unscoped": 0}
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
    held = [s.counters.get(k) for s in pt.spans
            if s.name in ("engine.decode.book", "engine.prefill.fetch")
            for k in ("pairs_held", "chunk_pairs_held")]
    held = [h for h in held if h is not None]
    if not held or not ctx.counters.get("tokens_processed") \
            or ctx.window_s <= 0:
        return None
    flops = cost.serve_flops(ctx.dims, ctx.counters["tokens_processed"],
                             ctx.counters["context_sum"],
                             ctx.counters["sampled"], sum(held))
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
    if what in ("latent_ms", "moe_ms"):
        runs = program_trace.whole_runs(ctx, pt, program)[0]
        rows = [(r1 - r0, parts_ns(ops)) for r0, r1, ops in runs]
        part = what[:-3]
        if not rows or max(ns[part] for _, ns in rows) <= 0:
            return None                 # a program without those scopes
        med = {k: statistics.median(ns[k] for _, ns in rows) / 1e6
               for k in ("latent", "moe", "dense", "unscoped")}
        program_trace.note_once((pt.path, "latent_experts"), (
            f"note decode step by scope, device ms, medians over "
            f"{len(rows)} whole runs: " + " ".join(
                f"{k}={v:.3f}" for k, v in med.items())
            + f" run={statistics.median(r for r, _ in rows) / 1e6:.3f}"))
        return med[part] + med["unscoped"]
    itemsize = program_trace.ITEMSIZE[ctx.cell.config["cache_dtype"]]
    peak_f, peak_b = ctx.peaks["flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    least = took = 0.0
    for span, r0, r1, ops in program_trace.paired_decode_runs(
            ctx, pt, program)[0]:
        book = _book_of(pt, span)
        if book is None or "pairs_held" not in book.counters:
            continue
        live, active = span.counters["live_positions"], span.counters["active"]
        held, hit = book.counters["pairs_held"], book.counters["experts_hit"]
        ns = parts_ns(ops)
        if what == "decode_roofline":
            c = cost.decode_step_cost(ctx.dims, live, active, held, hit,
                                      itemsize)
            took += (r1 - r0) / 1e9
        elif what == "latent_roofline":
            c = cost.latent_attention_cost(ctx.dims, live, itemsize)
            took += (ns["latent"] + ns["unscoped"]) / 1e9
        elif what == "moe_roofline":
            c = cost.expert_layers_cost(ctx.dims, active, held, hit, itemsize)
            took += (ns["moe"] + ns["unscoped"]) / 1e9
        else:
            raise ValueError(what)
        least += max(c["flops"] / peak_f, c["bytes"] / peak_b)
    if took <= 0:
        return None
    return 100.0 * least / took
