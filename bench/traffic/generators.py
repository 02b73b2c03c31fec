"""Shared pieces of the benchmark's graph families (``bench/families/``).

Everything here is host-side numpy and imports nothing of the system under
test: the harness turns the undirected edge lists into the program's
``Graph`` itself, and the checks and the reference read the same lists.
A family file (``families/<family>.py``) builds its graphs from these.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a stream index."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, rng: np.random.Generator):
    """Graph500 Kronecker edge list before relabelling: ``(n, u, v)`` with
    self-loops dropped."""
    n = 1 << scale
    m = n * edge_factor
    u = np.zeros(m, dtype=np.int64)
    v = np.zeros(m, dtype=np.int64)
    ab, abc = a + b, a + b + c
    for _ in range(scale):
        r = rng.random(m)
        right = r >= ab  # quadrant c or d -> u bit set
        r2 = rng.random(m)
        # within the top half: b quadrant -> v bit; bottom half: d quadrant
        v_bit = np.where(right, r >= abc, r2 >= a / ab)
        u = (u << 1) | right.astype(np.int64)
        v = (v << 1) | v_bit.astype(np.int64)
    keep = u != v
    return n, u[keep], v[keep]


def relabel(n: int, u, v, rng: np.random.Generator):
    """The Graph500 vertex relabelling: a random permutation of the ids."""
    perm = rng.permutation(n)
    return perm[u], perm[v]


def planted_edges(n: int, group: int, z_in: float, z_out: float,
                  rng: np.random.Generator):
    """One planted-partition graph: ``(n, u, v)``, vertex ``i`` in group
    ``i // group``; each vertex expects ``z_in`` neighbours in its own
    group and ``z_out`` outside it."""
    iu, ju = np.triu_indices(n, k=1)
    same = (iu // group) == (ju // group)
    p_in = min(1.0, z_in / (group - 1))
    p_out = min(1.0, z_out / (n - group)) if n > group else 0.0
    keep = rng.random(iu.shape[0]) < np.where(same, p_in, p_out)
    return n, iu[keep], ju[keep]


def canonical(n: int, u, v):
    """Undirected simple weighted graph: ``(lo, hi, w)`` with ``lo < hi``,
    each pair once, ``w`` the multiplicity (float64)."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    key, count = np.unique(lo * n + hi, return_counts=True)
    return key // n, key % n, count.astype(np.float64)
