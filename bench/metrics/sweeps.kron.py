"""Local-move sweeps of one detection (``Detection.stats['li_total']``),
averaged over the detections of the window."""


def read(ctx):
    s = [r.info["sweeps"] for r in ctx.requests if "sweeps" in r.info]
    return sum(s) / len(s) if s else None
