"""The Girvan-Newman benchmark graph: ``groups`` groups of ``group``
vertices, each vertex expecting ``degree`` neighbours, ``z_out`` of them
outside its own group (Girvan and Newman, PNAS 2002).

A pool of ``count`` graphs cycles through the configuration's ``z_out``
values, so it holds each of them equally often; the edges come from the
configuration's fixed ``graph_seed`` and the run's seed only orders the
pool.  Every seed serves the same graphs, and the same work, in another
order.
"""
from traffic.generators import canonical, planted_edges, rng_for


def make(spec: dict, seed: int, count: int = 1):
    group, n = spec["group"], spec["group"] * spec["groups"]
    fixed = spec["graph_seed"]
    out = []
    for i in range(count):
        z_out = spec["z_out"][i % len(spec["z_out"])]
        n, u, v = planted_edges(n, group, spec["degree"] - z_out, z_out,
                                rng_for(fixed, i))
        out.append((n, *canonical(n, u, v)))
    return [out[j] for j in rng_for(seed, 0).permutation(count)]
