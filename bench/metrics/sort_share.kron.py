"""Share (%) of the device's busy time spent in sort operations (the
two-key sort of every local-move half-sweep and the renumbering sorts)."""

SORT = r"(?i)sort"  # instruction names: sort.N, sort fusions


def read(ctx):
    t = ctx.trace
    if t is None or t.busy_s <= 0 or not t.matching(SORT):
        return None
    return 100.0 * t.op_seconds(SORT) / len(t.ops) / t.busy_s
