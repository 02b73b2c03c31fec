"""95th percentile, in ms, of the latency of every request completed in the
window, timed from when it was due."""

from harness.window import latency_percentile


def read(ctx):
    return latency_percentile(ctx.all_requests, ctx.t0, ctx.seconds, 95.0)
