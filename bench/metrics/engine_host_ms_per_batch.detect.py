"""Milliseconds of host work per engine dispatch: the stack span (filler,
stacking, reshape and transfer, inside engine-dispatch) and the unpack span
(per-result conversion and the engine's counters, after device-sync) of
each distinct batch, averaged over the batches.  A program that records
neither span gives no reading."""

HOST = ("stack", "unpack")


def read(ctx):
    spans = {(name, t0, t1) for r in ctx.requests
             for name, t0, t1 in r.info.get("spans", ()) if name in HOST}
    batches = sum(1 for name, _, _ in spans if name == "stack")
    if not batches:
        return None
    return 1e3 * sum(t1 - t0 for _, t0, t1 in spans) / batches
