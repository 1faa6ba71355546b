"""Pending receive and send polls that found nothing, over every
communicator of the cluster (each replica's and the client's), per output
token delivered in the window: what the transport's busy-wait costs the
shared event loop a token."""
from lib import hostpath, measure


def read(ctx):
    replicas = hostpath.replica_delta(ctx.window, "polls_empty")
    client = hostpath.client_delta(ctx.window, "polls_empty")
    tokens = measure.tokens_in_window(ctx.window)
    if replicas is None or client is None or not tokens:
        return None
    return (replicas + client) / tokens
