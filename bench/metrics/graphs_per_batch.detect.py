"""Served detect requests over the distinct engine dispatches that
served them."""


def read(ctx):
    batches = {(t0, t1) for r in ctx.requests
               for name, t0, t1 in r.info.get("spans", ())
               if name == "engine-dispatch"}
    return len(ctx.requests) / len(batches) if batches else None
