"""Median queue-wait span, in ms: from admission to batch composition."""

import statistics


def read(ctx):
    waits = [t1 - t0 for r in ctx.requests
             for name, t0, t1 in r.info.get("spans", ())
             if name == "queue-wait"]
    return 1e3 * statistics.median(waits) if waits else None
