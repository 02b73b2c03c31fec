"""Milliseconds of engine time per served graph: the engine-dispatch and
device-sync spans of every distinct batch, over the graphs served."""

ENGINE = ("engine-dispatch", "device-sync")


def read(ctx):
    batches = {}
    for r in ctx.requests:
        for name, t0, t1 in r.info.get("spans", ()):
            if name in ENGINE:
                batches[(name, t0, t1)] = t1 - t0
    if not batches or not ctx.requests:
        return None
    return 1e3 * sum(batches.values()) / len(ctx.requests)
