"""90th percentile of time to first token over every request that fell due
in the window, timed from its due time (lib.measure.ttfts)."""
import math

from lib import measure


def read(ctx):
    p = measure.percentile(measure.ttfts(ctx.window), 90)
    # a failed request is infinitely late; report the longest measurable
    return 1e3 * (p if math.isfinite(p) else ctx.window.seconds)
