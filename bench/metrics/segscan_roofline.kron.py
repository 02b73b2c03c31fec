"""Share (%) of the segmented-scan kernel's roofline: the least time the
chip could take to move the kernel's operands and results (and to fold
each element once), over the device time of the kernel's events."""

import sys

from harness.trace import hlo_text, result_elements, roofline

KERNEL = r"^segscan_blocked"


def _flops(ev):
    # one combine per element of the result
    return float(result_elements(hlo_text(ev)))


def read(ctx):
    if ctx.trace is None:
        return None
    share, bound = roofline(ctx.trace.matching(KERNEL), ctx.peaks, _flops)
    if share is not None:
        print(f"segscan_roofline.kron: {bound}-bound", file=sys.stderr)
    return share
