"""Host-side checks of an answer, independent of the system under test.

Every check reads the benchmark's own canonical undirected edge list
``(n, lo, hi, w)`` (``bench/traffic/generators.py``), never the program's
graph container.  Modularity follows the paper's convention: both
directions of every edge count, ``Q = sum_c [in_c / 2m - (tot_c / 2m)^2]``.

Each number a run reads is one file, ``checks/<name>.py``, whose
``read(rec)`` takes one answer's :class:`Answer` and returns its reading,
or ``None`` where the record lacks what it reads.  Every check file is read
on every answer; a configuration's ``checks`` names the ones it compares,
each with its limit, and they are printed under those names in the
result's ``checks``.  So a check is added as one file.
"""
from __future__ import annotations

import dataclasses
import pathlib

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

from harness import manifest


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


@dataclasses.dataclass(frozen=True)
class Answer:
    """One answer as a check reads it."""

    graph: tuple | None     # the bench's canonical (n, lo, hi, w)
    labels: object          # the answer's labels (None: not at hand)
    q_reported: float       # the modularity the program reported
    q: float                # the labels' modularity, float64, on the host
    q_ref: float | None = None   # the reference's, where compared with it
    extra: dict = dataclasses.field(default_factory=dict)  # the driver's


class CheckTally:
    """The worst reading of each check file over every answer; the
    numbers of ``limits`` are the ones compared."""

    def __init__(self, limits: dict,
                 bench_dir: pathlib.Path = manifest.BENCH_DIR):
        names = manifest.names(bench_dir, "checks")
        unknown = set(limits) - set(names)
        if unknown or not limits:
            raise KeyError(f"checks {sorted(unknown)}; check files in "
                           f"{bench_dir / 'checks'}: {names}")
        self.limits = {k: float(v) for k, v in limits.items()}
        self.readers = {k: manifest.load_module(bench_dir, "checks", k).read
                        for k in names}
        self.worst = {k: None for k in names}
        self.n_checked = 0
        self.n_reference = 0

    def _note(self, name: str, value: float) -> None:
        cur = self.worst[name]
        if cur is None or value > cur or np.isnan(value):
            self.worst[name] = float(value)

    def read(self, rec: Answer) -> None:
        """Every check's reading of one answer."""
        for name, read in self.readers.items():
            value = read(rec)
            if value is not None:
                self._note(name, value)
        if rec.q_ref is not None:
            self.n_reference += 1

    def answer(self, graph, labels, q_reported: float, **extra) -> float:
        """Check one answer on ``graph = (n, lo, hi, w)``, with the
        driver's ``extra`` fields; returns its float64 modularity."""
        n, lo, hi, w = graph
        q = modularity(n, lo, hi, w, labels)
        self.read(Answer(graph, labels, float(q_reported), q, extra=extra))
        self.n_checked += 1
        return q

    def against_reference(self, q: float, q_reported: float,
                          q_ref: float) -> None:
        """One answer already read by :meth:`answer` (modularity ``q``
        recomputed, ``q_reported`` by the program) against the reference's
        ``q_ref`` on the same graph: its record holds no graph or labels,
        and a check that reads neither reads the same value again."""
        self.read(Answer(None, None, float(q_reported), q, q_ref))

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
