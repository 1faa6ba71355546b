"""Mean milliseconds a decode envelope waits at a replica before its
convoy is submitted: from its arrival in the inbox, through the queue and
the gather of convoy mates, over the window, every replica of every stage
(the replicas' ``decode_wait_s_sum`` over ``decode_steps``)."""
from lib import hostpath


def read(ctx):
    wait = hostpath.replica_delta(ctx.window, "decode_wait_s_sum")
    steps = hostpath.replica_delta(ctx.window, "decode_steps")
    if wait is None or not steps:
        return None
    return 1e3 * wait / steps
