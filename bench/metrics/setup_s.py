"""Seconds from the process's start to the window's opening: building the
weights and the pipeline, compiling or loading every program, warming."""


def read(ctx):
    return ctx.setup_s
