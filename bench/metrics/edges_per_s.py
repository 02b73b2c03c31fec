"""Directed COO entries times the detections completed in the window, over
the time from the window's start to the last of them."""

from harness.window import work_rate


def read(ctx):
    return work_rate(ctx.all_requests, ctx.t0, ctx.seconds, ctx.work_each)
