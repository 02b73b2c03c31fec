"""Host-side checks of a partition, independent of the system under test.

Every function reads the benchmark's own canonical undirected edge list
``(n, lo, hi, w)`` (``bench/traffic/generators.py``), never the program's
graph container.  Modularity follows the paper's convention: both
directions of every edge count, ``Q = sum_c [in_c / 2m - (tot_c / 2m)^2]``.

The numbers a run compares (names as printed in the result's ``checks``):

* ``disconnected`` -- communities whose members do not form one connected
  component of the subgraph induced by the community's own edges; the
  configuration's guarantee is 0 (the exact limit).
* ``q_gap`` -- the widest gap between the modularity the program reported
  for a partition and that partition's modularity recomputed here in
  float64.
* ``q_shortfall`` -- the widest shortfall, in modularity points, of a
  served partition's modularity below that of the plain reference Louvain
  on the same graph, ``Q_ref - Q``.
* ``q_ref_gap`` -- the widest gap, either way, between the modularity the
  program reported and that of the plain reference Louvain on the same
  graph, ``|Q_ref - Q_reported|``.

Every number is read in every run; a configuration's ``checks`` names the
ones it compares, each with its limit.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

CHECK_NAMES = ("disconnected", "q_gap", "q_shortfall", "q_ref_gap")


def disconnected(n: int, lo, hi, labels) -> int:
    """Communities of ``labels`` (length >= n) split into several pieces."""
    lab = np.asarray(labels)[:n].astype(np.int64)
    keep = lab[lo] == lab[hi]
    adj = coo_matrix((np.ones(int(keep.sum()), np.int8),
                      (lo[keep], hi[keep])), shape=(n, n))
    _, comp = connected_components(adj, directed=False)
    pairs = np.unique(lab * n + comp)
    _, parts = np.unique(pairs // n, return_counts=True)
    return int((parts > 1).sum())


def modularity(n: int, lo, hi, w, labels, dtype=np.float64) -> float:
    """Modularity of ``labels`` with every array and sum held in ``dtype``."""
    lab = np.asarray(labels)[:n].astype(np.int64)
    w = np.asarray(w).astype(dtype)
    two_m = np.add.reduce(w, dtype=dtype) * dtype(2)
    inside = np.add.reduce(w[lab[lo] == lab[hi]], dtype=dtype) * dtype(2)
    k = np.zeros(n, dtype)
    np.add.at(k, lo, w)
    np.add.at(k, hi, w)
    tot = np.zeros(int(lab.max()) + 1 if n else 1, dtype)
    np.add.at(tot, lab, k)
    frac = tot / two_m
    return float(inside / two_m - np.add.reduce(frac * frac, dtype=dtype))


class CheckTally:
    """The worst reading of each number over every checked answer; the
    numbers of ``limits`` are the ones compared."""

    def __init__(self, limits: dict):
        unknown = set(limits) - set(CHECK_NAMES)
        if unknown or not limits:
            raise KeyError(f"checks {sorted(unknown)}; known: {CHECK_NAMES}")
        self.limits = {k: float(v) for k, v in limits.items()}
        self.worst = {k: None for k in CHECK_NAMES}
        self.n_checked = 0
        self.n_reference = 0

    def _note(self, name: str, value: float) -> None:
        cur = self.worst[name]
        if cur is None or value > cur or np.isnan(value):
            self.worst[name] = float(value)

    def answer(self, graph, labels, q_reported: float) -> float:
        """Check one answer on ``graph = (n, lo, hi, w)``; returns its
        float64 modularity."""
        n, lo, hi, w = graph
        self._note("disconnected", disconnected(n, lo, hi, labels))
        q = modularity(n, lo, hi, w, labels)
        self._note("q_gap", abs(float(q_reported) - q))
        self.n_checked += 1
        return q

    def against_reference(self, q: float, q_reported: float,
                          q_ref: float) -> None:
        """One answer (modularity ``q`` recomputed, ``q_reported`` by the
        program) against the reference's ``q_ref`` on the same graph."""
        self._note("q_shortfall", q_ref - q)
        self._note("q_ref_gap", abs(q_ref - float(q_reported)))
        self.n_reference += 1

    def correct(self) -> bool:
        """Every compared number read and within its limit (NaN fails)."""
        return all(self.worst[k] is not None and self.worst[k] <= lim
                   for k, lim in self.limits.items())

    def as_dict(self) -> dict:
        return {k: {"value": self.worst[k], "limit": lim}
                for k, lim in self.limits.items()}

    def readings(self) -> dict:
        """Every number read, compared or not (for the control's runs)."""
        return dict(self.worst)

    def lines(self) -> list:
        return [f"check {k} {self.worst[k]!r} limit {lim!r}"
                for k, lim in self.limits.items()]
