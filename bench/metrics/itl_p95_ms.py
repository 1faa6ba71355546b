"""95th percentile over every gap between consecutive output tokens of every
request, for gaps that ended inside the window."""
from lib import measure


def read(ctx):
    gaps = measure.itls(ctx.window)
    return 1e3 * measure.percentile(gaps, 95) if gaps else None
