"""GSP-Louvain core: correctness vs networkx oracles + paper-claim assertions."""
import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    LouvainConfig, louvain, modularity,
    disconnected_communities, split_labels, aggregate,
)
from repro.core import _segments as seg
from repro.core.local_move import local_move
from repro.graph import (
    from_undirected, sbm_graph, rmat_graph, grid_graph, ring_of_cliques,
)


def _partition_sets(C, n):
    groups = {}
    for v, c in enumerate(np.asarray(C)[:n]):
        groups.setdefault(int(c), set()).add(v)
    return groups


def _random_graph(n, m, seed, ensure_connected=False):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, m)
    v = rng.integers(0, n, m)
    if ensure_connected:
        u = np.concatenate([u, np.arange(n - 1)])
        v = np.concatenate([v, np.arange(1, n)])
    keep = u != v
    return from_undirected(n, u[keep], v[keep])


# ---------------------------------------------------------------------------
# modularity + detector oracles
# ---------------------------------------------------------------------------

@given(st.integers(8, 40), st.integers(10, 80), st.integers(0, 10))
@settings(max_examples=10, deadline=None)
def test_modularity_matches_networkx(n, m, seed):
    g = _random_graph(n, m, seed, ensure_connected=True)
    C, _ = louvain(g, LouvainConfig())
    q_ours = float(modularity(g.src, g.dst, g.w, C))
    nxg = g.to_networkx()
    parts = [s for s in _partition_sets(C, int(g.n_nodes)).values()]
    q_nx = nx.algorithms.community.modularity(nxg, parts, weight="weight")
    assert q_ours == pytest.approx(q_nx, abs=1e-4)


@given(st.integers(10, 40), st.integers(10, 60), st.integers(0, 10))
@settings(max_examples=10, deadline=None)
def test_detector_matches_networkx(n, m, seed):
    g = _random_graph(n, m, seed)
    rng = np.random.default_rng(seed)
    # random community assignment -> some communities disconnected
    C = jnp.asarray(
        np.concatenate([rng.integers(0, 4, n), [g.n_cap]]).astype(np.int32))
    det = disconnected_communities(g.src, g.dst, g.w, C, g.n_nodes)
    nxg = g.to_networkx()
    expected = 0
    for c, verts in _partition_sets(C, n).items():
        sub = nxg.subgraph(verts)
        # vertices with no edges at all count as their own components
        n_comp = nx.number_connected_components(sub) if len(sub) else 0
        n_comp += len(verts) - sub.number_of_nodes()
        if n_comp > 1:
            expected += 1
    assert int(det["n_disconnected"]) == expected


# ---------------------------------------------------------------------------
# the paper's central claims
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def web_like():
    return rmat_graph(scale=11, edge_factor=8, seed=3)


def test_default_louvain_leaves_disconnected():
    """Paper §3.4: plain parallel Louvain produces internally-disconnected
    communities on power-law graphs (GVE-Louvain: ~3.9% on average).  The
    effect is statistical — assert it over a small seed family."""
    total = 0
    for seed in [1, 2, 3]:
        g = rmat_graph(scale=11, edge_factor=8, seed=seed)
        C, _ = louvain(g, LouvainConfig(split="none"))
        det = disconnected_communities(g.src, g.dst, g.w, C, g.n_nodes)
        total += int(det["n_disconnected"])
    assert total > 0


@pytest.mark.parametrize("split", ["sp-pj", "sp-lp", "sp-lpp",
                                   "sl-pj", "sl-lp", "sl-lpp"])
def test_split_modes_zero_disconnected(web_like, split):
    """Paper Fig. 3(c)/4(d): every SP/SL mode returns 0 disconnected."""
    g = web_like
    C, _ = louvain(g, LouvainConfig(split=split))
    det = disconnected_communities(g.src, g.dst, g.w, C, g.n_nodes)
    assert int(det["n_disconnected"]) == 0
    # every community is connected per networkx too
    nxg = g.to_networkx()
    for c, verts in _partition_sets(C, int(g.n_nodes)).items():
        sub = nxg.subgraph(verts)
        if sub.number_of_nodes() == len(verts) and len(verts) > 1:
            assert nx.is_connected(sub), f"community {c} disconnected"


def test_sp_quality_close_to_default(web_like):
    """Paper Fig. 3(b): SP modularity stays close to the default approach."""
    g = web_like
    q = {}
    for split in ["none", "sp-pj"]:
        C, _ = louvain(g, LouvainConfig(split=split))
        q[split] = float(modularity(g.src, g.dst, g.w, C))
    assert q["sp-pj"] >= q["none"] - 0.02


def test_quality_vs_networkx_louvain(web_like):
    g = web_like
    C, _ = louvain(g, LouvainConfig(split="sp-pj"))
    q = float(modularity(g.src, g.dst, g.w, C))
    nxg = g.to_networkx()
    comms = nx.algorithms.community.louvain_communities(nxg, seed=0)
    q_nx = nx.algorithms.community.modularity(nxg, comms)
    assert q >= 0.8 * q_nx  # parallel vs sequential gap stays bounded


def test_ring_of_cliques_exact():
    g = ring_of_cliques(8, 6)
    C, stats = louvain(g, LouvainConfig())
    assert int(stats["n_communities"]) == 8
    groups = _partition_sets(C, int(g.n_nodes))
    sizes = sorted(len(v) for v in groups.values())
    assert sizes == [6] * 8


# ---------------------------------------------------------------------------
# phase-level invariants
# ---------------------------------------------------------------------------

def test_local_move_monotone():
    g = grid_graph(16, 16)
    nv = g.nv
    K = jax.ops.segment_sum(g.w, g.src, num_segments=nv)
    C0 = jnp.arange(nv, dtype=jnp.int32)
    q0 = float(modularity(g.src, g.dst, g.w, C0))
    C, _, _ = local_move(g.src, g.dst, g.w, C0, K, K,
                         g.total_weight_2m(), tau=1e-3)
    q1 = float(modularity(g.src, g.dst, g.w, C))
    assert q1 >= q0 - 1e-6


def test_aggregate_preserves_2m():
    g = sbm_graph(80, 4, seed=3)[0]
    C, _ = louvain(g, LouvainConfig(max_passes=1))
    ns, nd, nw = aggregate(g.src, g.dst, g.w, C)
    assert float(jnp.sum(nw)) == pytest.approx(float(g.total_weight_2m()))
    # aggregated modularity of identity partition == original partition Q
    nv = g.nv
    ident = jnp.arange(nv, dtype=jnp.int32)
    q_super = float(modularity(ns, nd, nw, ident))
    q_orig = float(modularity(g.src, g.dst, g.w, C))
    assert q_super == pytest.approx(q_orig, abs=1e-5)


def test_renumber_dense():
    # labels are vertex ids of valid vertices, hence always < nv - 1 (ghost)
    labels = jnp.asarray(np.array([7, 7, 3, 9, 3, 10], np.int32))
    nv = 12
    valid = jnp.asarray([True] * 6 + [False] * 6)
    dense, n = seg.renumber(jnp.pad(labels, (0, 6)), valid, nv)
    assert int(n) == 4
    d = np.asarray(dense)[:6]
    assert set(d) == {0, 1, 2, 3}
    # same label -> same dense id
    assert d[0] == d[1] and d[2] == d[4]


def test_sync_ablations_run():
    g = sbm_graph(60, 3, seed=6)[0]
    for sync in ["handshake", "parity", "all"]:
        C, _ = louvain(g, LouvainConfig(sync=sync, max_passes=3))
        assert np.asarray(C).shape[0] == g.nv
