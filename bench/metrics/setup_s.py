"""Seconds from process start to the window's start: generation,
compilation or reading it back, autotuning and warm-up."""


def read(ctx):
    return ctx.setup_s
