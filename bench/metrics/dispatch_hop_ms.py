"""Mean milliseconds a decode dispatch spends outside the executor call
itself: from the replica's submit of the call to a worker thread to its
coroutine's resume, less the call's own time on that thread (the thread
hop and the wait for the interpreter lock), over the window, every replica
(``dispatch_s_sum`` less ``exec_s_sum``, over ``decode_batches``)."""
from lib import hostpath


def read(ctx):
    dispatch = hostpath.replica_delta(ctx.window, "dispatch_s_sum")
    call = hostpath.replica_delta(ctx.window, "exec_s_sum")
    n = hostpath.replica_delta(ctx.window, "decode_batches")
    if dispatch is None or call is None or not n:
        return None
    return 1e3 * (dispatch - call) / n
