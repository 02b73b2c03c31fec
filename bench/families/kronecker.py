"""The Graph500 Kronecker generator (initiator A, B, C, D = 1-A-B-C,
``2**scale`` vertices, ``edge_factor * 2**scale`` edges), with the
specification's random relabelling of vertices so that the hubs do not sit
at ids 0, 1, 2, ...

The edges and ``count`` relabellings of them come from the configuration's
fixed ``graph_seed``; the run's seed only orders them.  Louvain's work
depends on the vertex order, so every seed gets the same set of graphs, and
the same work, in another order.  Self-loops are dropped; duplicate edges
merge (weights summed) when the graph is canonicalised.
"""
from traffic.generators import canonical, kronecker_edges, relabel, rng_for


def make(spec: dict, seed: int, count: int = 1):
    a, b, c = spec["initiator"][:3]
    fixed = spec["graph_seed"]
    n, u, v = kronecker_edges(spec["scale"], spec["edge_factor"],
                              a, b, c, rng_for(fixed, 0))
    out = [(n, *canonical(n, *relabel(n, u, v, rng_for(fixed, 1 + i))))
           for i in range(count)]
    return [out[j] for j in rng_for(seed, 0).permutation(count)]
