"""Whole-step share of the chip's peak: the model operations the window's
tokens require (prompts at their real length with last-position logits,
and every decode step; recomputation not counted) over the traced window
times the bf16 peak."""
from lib import measure


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    flops = measure.model_flops(ctx.arch, ctx.sizes, ctx.window)
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops"])
