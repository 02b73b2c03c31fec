"""Detect requests completed and committed inside the window, over the
window."""

from harness.window import count_rate


def read(ctx):
    return count_rate(ctx.all_requests, ctx.t0, ctx.seconds)
