"""Model FLOPs of the rounds traced over the traced window at the chip's
bf16 peak, in percent: the whole round's share of the peak."""


def read(ctx):
    s = ctx.shape
    if not ctx.rounds or ctx.trace["window_s"] <= 0:
        return None
    flops = ctx.cell.model.model_flops(ctx.cell.sizes, s["tokens"], s["seq"])
    return 100.0 * flops * ctx.rounds / (ctx.trace["window_s"] * ctx.peaks["flops_per_s"])
