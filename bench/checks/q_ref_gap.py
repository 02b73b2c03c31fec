"""The gap, either way, between the modularity the program reported and
that of the plain reference Louvain on the same graph,
``|Q_ref - Q_reported|``."""


def read(rec):
    if rec.q_ref is None:
        return None
    return abs(rec.q_ref - rec.q_reported)
