"""Back-to-back warm ``repro.core.detect`` calls, one caller, no queue.

The mix's ``graphs`` graphs of the configuration's family, in the seed's
order, are held on the device; the window makes one detection of each,
back to back, while it is open: a fixed amount of work, every request due
when it is called and done when its labels are on the host.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from harness import checks
from harness.drivers import Phases, annotate, program_graph, reference_q
from harness.window import Request


class Driver:
    def __init__(self, cell, seed: int, *, annotate_on: bool = False):
        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.seed = seed
        self.ann = annotate_on

    def setup(self):
        import jax
        from repro.core import DetectOptions

        ph = Phases()
        with annotate("bench.generate", self.ann):
            self.graphs = self.cell.make_graphs(self.seed,
                                                self.traffic["graphs"])
            self.device_graphs = [jax.device_put(program_graph(g))
                                  for g in self.graphs]
        ph.done("generate")
        self.opts = DetectOptions(**self.config.get("detect", {}))
        self._detect(self.device_graphs[0])
        ph.done("first detection")
        ph.log()

    def _detect(self, g) -> dict:
        from repro.core import detect

        with annotate("bench.detect", self.ann):
            res = detect(g, options=self.opts)
        with annotate("bench.fetch", self.ann):
            labels = np.asarray(res.labels)
        return {"labels": labels, "q": res.modularity,
                "sweeps": int(res.stats["li_total"]),
                "passes": int(res.stats["passes"])}

    def window(self, t0: float, seconds: float, on_start=None):
        """One detection of each graph, in order, while the window is open."""
        reqs = []
        for i, g in enumerate(self.device_graphs):
            if time.perf_counter() >= t0 + seconds:
                break
            r = Request(t_due=time.perf_counter(), info={"graph": i})
            reqs.append(r)
            try:
                r.info.update(self._detect(g))
            except Exception as e:  # noqa: BLE001 -- counted as failed
                r.error = repr(e)
                break
            r.t_done = time.perf_counter()
        return reqs

    def work_each(self) -> float:
        return float(2 * self.graphs[0][1].size)

    def release(self):
        del self.device_graphs
        gc.collect()

    def check(self, reqs, tally: checks.CheckTally):
        """Every answer against the host checks and against the reference
        on the first graph (the others are relabellings of it)."""
        _, q_ref = reference_q(self.graphs[0], self.config)
        for r in reqs:
            if r.error is None:
                q = tally.answer(self.graphs[r.info["graph"]],
                                 r.info["labels"], r.info["q"])
                tally.against_reference(q, r.info["q"], q_ref)
