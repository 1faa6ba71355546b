"""Device milliseconds of the stage decode programs, convoys and single
steps of every stage, per decoded token over the traced window (each
session step passes every stage once). A wider convoy reads lower here."""
from lib import measure


def read(ctx):
    v = measure.labelled(ctx, "decode")
    tokens = measure.counter_delta(ctx.window, "decode_steps") / ctx.stages
    if v is None or tokens <= 0:
        return None
    # the named calls' time, scaled to the dispatches the counters saw
    return 1e3 * v["s"] * v["counted"] / v["calls"] / tokens
