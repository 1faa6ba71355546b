"""Mean milliseconds the client takes from a response in hand to its token
appended, per token delivered in the window (``PipelineServer.client_stats``
``token_host_s_sum``). The logits' copy to the host waits for the last
stage's program to finish, as dispatch is asynchronous, so this holds that
wait as well as the copy, the margin and the argmax; a traced run's
``mw.client.token`` spans split the two against the device's operations."""
from lib import hostpath, measure


def read(ctx):
    host = hostpath.client_delta(ctx.window, "token_host_s_sum")
    tokens = measure.tokens_in_window(ctx.window)
    if host is None or not tokens:
        return None
    return 1e3 * host / tokens
