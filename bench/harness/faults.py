"""Faults planted under the timed path, and the control put in the
program's place: each must turn a run's ``correct`` false.

Used by ``bench/controls.py`` (readings on the chip at a cell's own size)
and by ``bench/tests`` (the same at a size a test run holds).  Each kind is
one file, ``faults/<kind>.py``, whose ``plant()`` is a context manager that
patches the seam it names for its duration (with :func:`patched`);
:func:`planted` clears JAX's caches on the way in and out, so no program
traced before it (or with it) is replayed.  A cell's kinds are the list
``faults`` of its traffic mix, so a new cell's faults are new files and a
list in its mix.

This file keeps what several kinds share: the patch itself, the control's
bfloat16 answer and the alteration of an answer.
"""
from __future__ import annotations

import contextlib
import dataclasses
import pathlib

import ml_dtypes
import numpy as np

from harness import checks, manifest, reference


def edge_list(g):
    """The undirected edge list of a program graph (host copy)."""
    n = int(np.asarray(g.n_nodes))
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    w = np.asarray(g.w, np.float64)
    keep = (src < dst) & (dst < n)
    return n, src[keep].astype(np.int64), dst[keep].astype(np.int64), w[keep]


def bf16_answer(g, nv: int):
    """The plain reference's labels (padded to ``nv``) and modularity for
    the program graph ``g``, every weight and sum in bfloat16."""
    n, lo, hi, w = edge_list(g)
    dt = ml_dtypes.bfloat16
    lab = reference.louvain(n, lo, hi, w, dtype=dt)
    q = checks.modularity(n, lo, hi, w, lab, dt) if lo.size else 0.0
    out = np.full(nv, nv - 1, np.int32)
    out[:n] = lab
    return out, q


def alter(labels, n: int, seed: int = 0):
    """``labels`` with a tenth of the first ``n`` vertices given another
    vertex's community."""
    lab = np.array(labels, copy=True)
    if n < 2:
        return lab
    rng = np.random.default_rng(seed)
    k = max(1, n // 10)
    who = rng.choice(n, size=k, replace=False)
    lab[who] = lab[rng.integers(0, n, size=k)]
    return lab


@contextlib.contextmanager
def patched(obj, name, value):
    """``obj.name`` is ``value`` for the duration."""
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def each_detection(replace):
    """Every answer of ``repro.core.detect`` and of the engine's
    ``detect_batch`` passes through ``replace(g, labels, q) -> (labels,
    q)`` where it is produced."""
    import repro.core as core
    from repro.service.engine import BatchedLouvainEngine

    real_detect = core.detect
    real_batch = BatchedLouvainEngine.detect_batch

    def detect(g, **kw):
        res = real_detect(g, **kw)
        lab, q = replace(g, res.labels, res.modularity)
        return dataclasses.replace(res, labels=lab, modularity=q)

    def detect_batch(self, graphs, **kw):
        graphs = list(graphs)
        out = []
        for g, r in zip(graphs, real_batch(self, graphs, **kw)):
            lab, q = replace(g, r.C, r.q)
            out.append(dataclasses.replace(r, C=lab, q=q))
        return out

    with patched(core, "detect", detect), \
            patched(BatchedLouvainEngine, "detect_batch", detect_batch):
        yield


def _clear():
    import jax

    jax.clear_caches()


@contextlib.contextmanager
def planted(kind: str, bench_dir: pathlib.Path = manifest.BENCH_DIR):
    """Plant the fault of ``faults/<kind>.py`` for the duration."""
    mod = manifest.load_module(bench_dir, "faults", kind)
    with contextlib.ExitStack() as stack:
        stack.enter_context(mod.plant())
        _clear()
        stack.callback(_clear)
        yield
