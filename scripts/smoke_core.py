"""Quick manual smoke of the core pipeline (not a test)."""
import numpy as np
import jax.numpy as jnp

from repro.graph import sbm_graph, bridge_graph, ring_of_cliques
from repro.core import (
    LouvainConfig, louvain, modularity,
    disconnected_communities, split_labels,
)

def report(name, g, cfg):
    C, stats = louvain(g, cfg)
    q = modularity(g.src, g.dst, g.w, C)
    det = disconnected_communities(g.src, g.dst, g.w, C, g.n_nodes)
    print(
        f"{name:22s} split={cfg.split:7s} Q={float(q):+.4f} "
        f"passes={int(stats['passes'])} comms={int(stats['n_communities'])} "
        f"disc={int(det['n_disconnected'])}/{int(det['n_communities'])}"
    )
    return C, q, det

if __name__ == "__main__":
    g, labels = sbm_graph(n_nodes=200, n_blocks=5, p_in=0.4, p_out=0.01, seed=0)
    gb, bridge = bridge_graph()
    gr = ring_of_cliques(8, 6)

    for name, gg in [("sbm", g), ("bridge", gb), ("ring", gr)]:
        for split in ["none", "sp-pj", "sp-lp", "sl-pj"]:
            report(name, gg, LouvainConfig(split=split))

    # networkx cross-check on sbm
    import networkx as nx
    nxg = g.to_networkx()
    C, stats = louvain(g, LouvainConfig())
    part = {}
    Cn = np.asarray(C)[: int(g.n_nodes)]
    for v, c in enumerate(Cn):
        part.setdefault(int(c), set()).add(v)
    q_nx = nx.algorithms.community.modularity(nxg, list(part.values()))
    print("networkx modularity of our partition:", q_nx)
    comms_nx = nx.algorithms.community.louvain_communities(nxg, seed=0)
    print("networkx louvain Q:", nx.algorithms.community.modularity(nxg, comms_nx))
    # connectivity of every community
    bad = [c for c, vs in part.items() if not nx.is_connected(nxg.subgraph(vs))]
    print("disconnected (nx check):", bad)
