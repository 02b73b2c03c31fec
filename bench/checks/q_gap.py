"""The gap between the modularity the program reported for a partition and
that partition's modularity recomputed on the host in float64."""


def read(rec):
    return abs(rec.q_reported - rec.q)
