"""Share (%) of the traced window in which the device ran no operation."""


def read(ctx):
    return ctx.trace.idle_share() if ctx.trace is not None else None
