"""Communities of the answer whose members do not form one connected
component of the subgraph induced by the community's own edges; the
configurations state 0 (the exact limit)."""
from harness.checks import disconnected


def read(rec):
    if rec.labels is None:
        return None
    n, lo, hi, _ = rec.graph
    return disconnected(n, lo, hi, rec.labels)
