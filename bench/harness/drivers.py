"""Pieces the traffic drivers share.

A mix file (``traffic/<mix>.json``) names its driver, ``drivers/<driver>.py``,
which defines ``Driver(cell, seed, annotate_on=...)`` with:

* ``setup()`` -- build the inputs from the seed and warm every shape the
  window uses;
* ``window(t0, seconds, on_start=None)`` -- measure; returns the
  :class:`harness.window.Request` list (``on_start``, where the driver
  runs an event loop, is a coroutine function it runs beside the window);
* ``work_each()`` -- the work one request stands for (``edges_per_s``);
* ``release()`` -- free the program's state before the checks;
* ``check(reqs, tally)`` -- compare every answer with the host checks and
  answers with the plain reference.
"""
from __future__ import annotations

import contextlib
import sys
import time

import numpy as np

from harness import checks, reference


def log(*args):
    print(*args, file=sys.stderr, flush=True)


class Phases:
    """Host-clock seconds of each set-up phase, logged as one line."""

    def __init__(self):
        self.t = time.perf_counter()
        self.parts = []

    def done(self, name: str):
        now = time.perf_counter()
        self.parts.append(f"{name} {now - self.t:.2f} s")
        self.t = now

    def log(self):
        log("setup phases: " + ", ".join(self.parts))


def annotate(name: str, on: bool = True):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def annotated(name: str, fn):
    def wrapped(*a, **kw):
        with annotate(name):
            return fn(*a, **kw)
    return wrapped


def program_graph(graph):
    """The program's graph container for one canonical edge list."""
    from repro.graph.container import from_undirected

    n, lo, hi, w = graph
    return from_undirected(n, lo, hi, w.astype(np.float32))


def reference_q(graph, cfg: dict, dtype=np.float64):
    n, lo, hi, w = graph
    labels = reference.louvain(n, lo, hi, w, dtype=dtype,
                               **cfg.get("reference", {}))
    return labels, checks.modularity(n, lo, hi, w, labels, dtype)
