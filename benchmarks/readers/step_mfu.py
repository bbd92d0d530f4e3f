"""The whole step's share of the chips' peak: required model operations of
the window (no recomputation, causal attention) over window x chips x peak."""
import model_cost


def read(ctx, kind):
    if ctx.window_s <= 0:
        return None
    if kind == "train":
        tokens = ctx.counters.get("tokens")
        if not tokens:
            return None
        flops = tokens * model_cost.train_flops_per_token(
            ctx.dims, ctx.cell.traffic["seq_len"])
    elif kind == "serve":
        if not ctx.counters.get("tokens_processed"):
            return None
        flops = model_cost.serve_flops(
            ctx.dims, ctx.counters["tokens_processed"],
            ctx.counters["context_sum"], ctx.counters["sampled"])
    else:
        raise ValueError(kind)
    return 100.0 * flops / ctx.window_s / (
        ctx.chips * ctx.peaks["flops_per_s"])
