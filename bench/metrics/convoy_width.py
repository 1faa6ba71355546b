"""Decode steps per fused decode dispatch over the window, all stages
(StageExecutor.stats decode_steps / decode_batches)."""
from lib import measure


def read(ctx):
    batches = measure.counter_delta(ctx.window, "decode_batches")
    if batches == 0:
        return None
    return measure.counter_delta(ctx.window, "decode_steps") / batches
