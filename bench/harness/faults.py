"""Faults planted under the timed path, and the control put in the
program's place: each must turn a run's ``correct`` false.

Used by ``bench/controls.py`` (readings on the chip at a cell's own size)
and by ``bench/tests`` (the same at a size a test run holds).  Each is a
context manager that patches the system under test for its duration and
clears JAX's caches on the way in and out, so no program traced before it
(or with it) is replayed.

* ``control`` -- the plain reference computed in bfloat16, the nearest
  precision below the float32 the configurations state, answers in place
  of the program's (labels and reported modularity);
* ``stale`` -- the local-move step returns its state unchanged (every
  vertex stays in its own community);
* ``altered`` -- every answer altered where it is produced: a tenth of the
  vertices take another vertex's community, the reported modularity kept;
* ``half`` -- half of each engine batch left out: the second half of the
  batch gets the answers of the first half (batched cells only).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib

import ml_dtypes
import numpy as np

from harness import checks, reference

KINDS = ("control", "stale", "altered", "half")


def _edge_list(g):
    """The undirected edge list of a program graph (host copy)."""
    n = int(np.asarray(g.n_nodes))
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    w = np.asarray(g.w, np.float64)
    keep = (src < dst) & (dst < n)
    return n, src[keep].astype(np.int64), dst[keep].astype(np.int64), w[keep]


def _bf16_answer(g, nv: int):
    n, lo, hi, w = _edge_list(g)
    dt = ml_dtypes.bfloat16
    lab = reference.louvain(n, lo, hi, w, dtype=dt)
    q = checks.modularity(n, lo, hi, w, lab, dt) if lo.size else 0.0
    out = np.full(nv, nv - 1, np.int32)
    out[:n] = lab
    return out, q


def _alter(labels, n: int, seed: int = 0):
    lab = np.array(labels, copy=True)
    if n < 2:
        return lab
    rng = np.random.default_rng(seed)
    k = max(1, n // 10)
    who = rng.choice(n, size=k, replace=False)
    lab[who] = lab[rng.integers(0, n, size=k)]
    return lab


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _clear():
    import jax

    jax.clear_caches()


@contextlib.contextmanager
def planted(kind: str):
    """Plant fault ``kind`` (one of :data:`KINDS`) for the duration."""
    import repro.core as core
    from repro.service.engine import BatchedLouvainEngine

    real_detect = core.detect
    real_batch = BatchedLouvainEngine.detect_batch

    def detect(g, **kw):
        res = real_detect(g, **kw)
        nv = int(np.asarray(res.labels).shape[0])
        if kind == "control":
            lab, q = _bf16_answer(g, nv)
            return dataclasses.replace(res, labels=lab, modularity=q)
        return dataclasses.replace(
            res, labels=_alter(res.labels, int(np.asarray(g.n_nodes))))

    def detect_batch(self, graphs, **kw):
        graphs = list(graphs)
        if kind == "half":
            h = -(-len(graphs) // 2)
            res = real_batch(self, graphs[:h], **kw)
            return [res[i % h] for i in range(len(graphs))]
        res = real_batch(self, graphs, **kw)
        out = []
        for g, r in zip(graphs, res):
            if kind == "control":
                lab, q = _bf16_answer(g, r.C.shape[0])
                out.append(dataclasses.replace(r, C=lab, q=q))
            else:
                out.append(dataclasses.replace(
                    r, C=_alter(r.C, int(np.asarray(g.n_nodes)))))
        return out

    def stale_local_move(src, dst, w, C0, K, Sigma0, two_m, **_):
        import jax.numpy as jnp

        return C0, Sigma0, jnp.int32(1)

    with contextlib.ExitStack() as stack:
        if kind == "stale":
            louvain_mod = importlib.import_module("repro.core.louvain")
            stack.enter_context(_patched(louvain_mod, "local_move",
                                         stale_local_move))
        elif kind in ("control", "altered"):
            stack.enter_context(_patched(core, "detect", detect))
            stack.enter_context(_patched(BatchedLouvainEngine,
                                         "detect_batch", detect_batch))
        elif kind == "half":
            stack.enter_context(_patched(BatchedLouvainEngine,
                                         "detect_batch", detect_batch))
        else:
            raise ValueError(f"unknown fault {kind!r}; known: {KINDS}")
        _clear()
        stack.callback(_clear)
        yield
