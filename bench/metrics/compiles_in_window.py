"""Programs JAX compiled or loaded from its cache while the window ran."""


def read(ctx):
    return len(ctx.window.compiles)
