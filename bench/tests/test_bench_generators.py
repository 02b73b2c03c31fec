"""The benchmark's own graph generators."""
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness.manifest import load_module  # noqa: E402
from traffic.generators import (  # noqa: E402
    canonical, kronecker_edges, relabel, rng_for,
)


def make_graphs(spec, seed, count):
    return load_module(BENCH, "families", spec["family"]).make(
        spec, seed, count)


def _degrees(n, u, v):
    return np.bincount(u, minlength=n) + np.bincount(v, minlength=n)


def test_kronecker_relabelling_keeps_degrees_and_moves_the_hub():
    n, u0, v0 = kronecker_edges(10, 16, 0.57, 0.19, 0.19,
                                rng_for(2**31 + 11, 0))
    u1, v1 = relabel(n, u0, v0, rng_for(5, 0))
    d0, d1 = _degrees(n, u0, v0), _degrees(n, u1, v1)
    assert np.array_equal(np.sort(d0), np.sort(d1))
    assert int(np.argmax(d0)) == 0          # before relabelling: id 0
    assert int(np.argmax(d1)) != 0
    assert d1[0] < d0[0]


SPEC = {"family": "kronecker", "scale": 8, "edge_factor": 16,
        "initiator": [0.57, 0.19, 0.19, 0.05], "graph_seed": 500}


def _key(g):
    return g[1].tobytes() + g[2].tobytes()


def test_same_seed_same_graphs_any_large_seed():
    a, b = make_graphs(SPEC, 2**33 + 5, 4), make_graphs(SPEC, 2**33 + 5, 4)
    assert [_key(g) for g in a] == [_key(g) for g in b]


def test_every_seed_gets_the_same_relabellings_in_another_order():
    orders = [[_key(g) for g in make_graphs(SPEC, s, 4)]
              for s in range(2**33, 2**33 + 6)]
    assert all(sorted(o) == sorted(orders[0]) for o in orders)
    assert len(set(map(tuple, orders))) > 1
    assert len(set(orders[0])) == 4         # four distinct relabellings


def test_canonical_merges_duplicates_and_orders_pairs():
    lo, hi, w = canonical(4, [2, 0, 1, 2], [0, 2, 3, 1])
    assert lo.tolist() == [0, 1, 1] and hi.tolist() == [2, 2, 3]
    assert w.tolist() == [2.0, 1.0, 1.0]


def test_planted_pool_sizes_are_fixed_across_seeds_and_fit_the_ladder():
    from repro.service.buckets import DEFAULT_BUCKETS, choose_bucket

    cfg = json.loads(
        (BENCH / "configs" / "girvan-newman-128.json").read_text())
    pools = [make_graphs(cfg, seed, 64) for seed in (1, 2**32 + 3)]
    # every seed serves the same graphs, in another order
    assert sorted(map(_key, pools[0])) == sorted(map(_key, pools[1]))
    assert list(map(_key, pools[0])) != list(map(_key, pools[1]))
    for n, lo, hi, w in pools[0]:
        assert n == 128
        choose_bucket(n, 2 * lo.size, DEFAULT_BUCKETS)
    # each z_out value equally often: inter-group edges per vertex near it
    inter = sorted(2 * int(((lo // 32) != (hi // 32)).sum()) / n
                   for n, lo, hi, _ in pools[0])
    assert 0.5 < inter[0] < 1.5 and 7.0 < inter[-1] < 9.0
