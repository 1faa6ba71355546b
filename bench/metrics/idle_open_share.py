"""Share of the traced window in which the device is idle while at least
one request is open (a ``mw.client.session`` span): idle time the host
holds the chip back with work waiting. ``device_idle_share`` less this is
the idle time of the arrival process, which no host change removes. Read
from the trace's ``idle_by_span`` (``lib.hostpath``)."""
from lib import hostpath


def read(ctx):
    split = ctx.trace and ctx.trace.get("idle_by_span")
    if not split:
        return None
    idle = sum(s for name, s in split if name != hostpath.NO_REQUEST)
    return 100.0 * idle / ctx.trace["window_s"]
