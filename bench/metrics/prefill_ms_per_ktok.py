"""Device milliseconds of the stage prefill programs, every stage, per
thousand real prompt tokens prefilled in the traced window."""
from lib import measure


def read(ctx):
    v = measure.labelled(ctx, "prefill")
    tokens = sum(measure.prompts_prefilled(ctx.window))
    if v is None or not tokens:
        return None
    return 1e3 * v["s"] * v["counted"] / v["calls"] / (tokens / 1e3)
