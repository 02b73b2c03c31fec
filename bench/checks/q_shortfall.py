"""The shortfall, in modularity points, of a served partition's modularity
below that of the plain reference Louvain on the same graph,
``Q_ref - Q``."""


def read(rec):
    if rec.q_ref is None:
        return None
    return rec.q_ref - rec.q
