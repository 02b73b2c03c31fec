"""Plain reference: sequential Louvain with a split pass (GSP semantics).

A straightforward implementation of what the system under test computes,
written from the papers and importing nothing of the program:

* local moving (Blondel et al. 2008): visit vertices in id order, move each
  to the neighbouring community of largest modularity gain, sweep until a
  sweep gains less than ``tolerance`` or ``max_iters`` sweeps ran;
* split pass (the paper's GSP-Louvain): every community is split into the
  connected components of the subgraph its own edges induce, so no
  community is internally disconnected;
* aggregation: communities become vertices, and passes repeat until a pass
  moves nothing or ``max_passes`` ran.

``dtype`` holds every weight, degree, total and gain (the control computes
in ``bfloat16``, the nearest precision below the float32 the configuration
states).  Sequential Louvain does not reproduce the program's parallel
partition; the checks compare modularity, not labels.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components


def _csr(n, src, dst, w, dtype):
    order = np.lexsort((dst, src))
    src, dst, w = src[order], dst[order], w[order]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, dst, w.astype(dtype)


def _local_move(n, indptr, nbr, wt, k, two_m, dtype, *, max_iters,
                tolerance):
    comm = np.arange(n, dtype=np.int64)
    tot = k.copy()
    acc = np.zeros(n, dtype)
    zero = dtype(0)
    moved_any = False
    for _ in range(max_iters):
        gained = 0.0
        for i in range(n):
            s, e = indptr[i], indptr[i + 1]
            if s == e:
                continue
            nb = nbr[s:e]
            ww = wt[s:e]
            off = nb != i
            cn = comm[nb[off]]
            if cn.size == 0:
                continue
            a = comm[i]
            ki = k[i]
            tot[a] = tot[a] - ki
            np.add.at(acc, cn, ww[off])
            cand = np.unique(cn)
            gain = acc[cand] - ki * tot[cand] / two_m
            g_stay = acc[a] - ki * tot[a] / two_m
            best = int(np.argmax(gain))
            acc[cand] = zero
            b = int(cand[best])
            if b != a and gain[best] > g_stay:
                gained += float(gain[best] - g_stay)
                comm[i] = b
                moved_any = True
            tot[comm[i]] = tot[comm[i]] + ki
        if gained * 2.0 / float(two_m) < tolerance:
            break
    return comm, moved_any


def _split(n, src, dst, comm):
    """Connected components of each community's own subgraph."""
    keep = comm[src] == comm[dst]
    adj = coo_matrix((np.ones(int(keep.sum()), np.int8),
                      (src[keep], dst[keep])), shape=(n, n))
    _, comp = connected_components(adj, directed=False)
    return comp.astype(np.int64)


def louvain(n: int, lo, hi, w, *, dtype=np.float64, max_passes: int = 10,
            max_iters: int = 20, tolerance: float = 1e-6):
    """Labels ``int64[n]`` of the reference partition of the undirected
    graph ``(lo, hi, w)`` (``lo < hi``, each pair once)."""
    src = np.concatenate([lo, hi]).astype(np.int64)
    dst = np.concatenate([hi, lo]).astype(np.int64)
    wts = np.concatenate([w, w]).astype(np.float64)
    top = np.arange(n, dtype=np.int64)
    n_cur = n
    if not len(lo):
        return top
    for _ in range(max_passes):
        indptr, nbr, wt = _csr(n_cur, src, dst, wts, dtype)
        k = np.zeros(n_cur, dtype)
        np.add.at(k, np.repeat(np.arange(n_cur), np.diff(indptr)), wt)
        two_m = np.add.reduce(wt, dtype=dtype)
        comm, moved = _local_move(n_cur, indptr, nbr, wt, k, two_m, dtype,
                                  max_iters=max_iters, tolerance=tolerance)
        comp = _split(n_cur, src, dst, comm)
        _, dense = np.unique(comp, return_inverse=True)
        top = dense[top]
        n_next = int(dense.max()) + 1
        if not moved or n_next == n_cur:
            break
        # aggregate: one weighted edge per community pair, self-loops kept
        key = dense[src] * n_next + dense[dst]
        key, inv = np.unique(key, return_inverse=True)
        wts = np.bincount(inv, weights=wts)
        src, dst = key // n_next, key % n_next
        n_cur = n_next
    return top
