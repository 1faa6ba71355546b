"""Output tokens delivered inside the window over the window's length."""
from lib import measure


def read(ctx):
    w = ctx.window
    return measure.tokens_in_window(w) / w.seconds
