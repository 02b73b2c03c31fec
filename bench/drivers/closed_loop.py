"""Closed-loop callers of ``AsyncCommunityService``.

A pool of the mix's ``pool`` graphs of the configuration's family, in the
seed's order; ``clients`` callers each send their next detect request when
the last one resolves, so a request is due when its caller sends it.
Caller ``c``'s ``k``-th request is for graph ``(c + k * clients) % pool``.

The callers start in set-up, after every program the pool's buckets need
is built, and run on into the window without a pause, so the window opens
on a service in its steady state.  At the close they stop sending and every
request still out gets a minute to come back: an answer that comes later,
or never, counts as failed.
"""
from __future__ import annotations

import asyncio
import gc
import math
import time

from harness import checks
from harness.drivers import (
    Phases, annotate, annotated, program_graph, reference_q,
)
from harness.window import Request
from traffic.generators import rng_for


class Driver:
    def __init__(self, cell, seed: int, *, annotate_on: bool = False):
        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.seed = seed
        self.ann = annotate_on
        self.reqs: list = []
        self.t_end = math.inf      # callers send no request from here on

    def setup(self):
        from repro.core import DetectOptions
        from repro.service import AsyncCommunityService, ServiceConfig
        from repro.service.buckets import Bucket, choose_bucket

        ph = Phases()
        with annotate("bench.generate", self.ann):
            self.pool = self.cell.make_graphs(self.seed,
                                              self.traffic["pool"])
            self.graphs = [program_graph(g) for g in self.pool]
        ph.done("generate")
        svc_cfg = dict(self.config.get("service", {}))
        svc_cfg["detect"] = DetectOptions(**self.config.get("detect", {}))
        if "buckets" in svc_cfg:
            svc_cfg["buckets"] = tuple(Bucket(*b) for b in svc_cfg["buckets"])
        self.svc_config = ServiceConfig(**svc_cfg)
        self.loop = asyncio.new_event_loop()
        self.svc = AsyncCommunityService(self.svc_config)
        self.loop.run_until_complete(self.svc.start())
        ph.done("service start")
        eng = self.svc.engine
        used = sorted({choose_bucket(n, 2 * lo.size, self.svc_config.buckets)
                       for n, lo, _, _ in self.pool})
        for bucket in used:
            eng.warm(bucket, self.svc_config.batch_size)
            ph.done(f"warm {bucket.n_cap}x{bucket.m_cap}")
        if self.ann:
            fe = self.svc.frontend
            eng.detect_batch = annotated("bench.engine", eng.detect_batch)
            fe.store.put = annotated("bench.commit", fe.store.put)
            fe.collect = annotated("bench.compose", fe.collect)
        # the callers start here and run on into the window
        self.tasks = [self.loop.create_task(self._client(c))
                      for c in range(self.traffic["clients"])]
        self.loop.run_until_complete(
            asyncio.sleep(float(self.traffic["warm_seconds"])))
        ph.done("warm traffic")
        ph.log()

    async def _client(self, c: int):
        clients, pool = self.traffic["clients"], len(self.graphs)
        k = 0
        while time.perf_counter() < self.t_end:
            i = (c + k * clients) % pool
            k += 1
            r = Request(t_due=time.perf_counter(), info={"graph": i})
            self.reqs.append(r)
            try:
                with annotate("bench.submit", self.ann):
                    fut = await self.svc.submit_detect(f"g{i}", self.graphs[i])
                entry = await fut
            except Exception as e:  # noqa: BLE001 -- counted as failed
                r.error = repr(e)
                continue
            r.t_done = time.perf_counter()
            r.info.update(labels=entry.C, q=entry.q, spans=[
                (s.name, s.t_start, s.t_end) for s in fut.trace.spans])

    async def _serve(self, t0: float, seconds: float, on_start=None):
        side = asyncio.ensure_future(on_start()) if on_start else None
        await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        self.t_end = t0 + seconds
        # answers due in the window get a minute past its close
        done, pending = await asyncio.wait(self.tasks, timeout=60.0)
        for t in pending:
            t.cancel()
        for t in done:
            t.result()
        if side is not None:
            await side

    def window(self, t0: float, seconds: float, on_start=None):
        """Every request of the run, the warm traffic's among them: the
        window's arithmetic picks those that completed inside it."""
        self.loop.run_until_complete(self._serve(t0, seconds, on_start))
        return self.reqs

    def work_each(self) -> float:
        return 1.0

    def release(self):
        self.loop.run_until_complete(self.svc.close())
        self.loop.close()
        del self.svc, self.graphs
        gc.collect()

    def check(self, reqs, tally: checks.CheckTally):
        """Every answer against the host checks; the answers for a sample
        of the served graphs, drawn from the seed and with the largest
        served graph in it, against the reference."""
        by_graph: dict = {}
        for r in reqs:
            if r.error is None and r.t_done is not None:
                i = r.info["graph"]
                q = tally.answer(self.pool[i], r.info["labels"], r.info["q"])
                by_graph.setdefault(i, []).append((q, r.info["q"]))
        ids = sorted(by_graph)
        if not ids:
            return
        k = min(int(self.traffic["reference_sample"]), len(ids))
        pick = set(rng_for(self.seed, 7).choice(ids, size=k, replace=False)
                   .tolist())
        pick.add(max(ids, key=lambda i: self.pool[i][1].size))
        for i in sorted(pick):
            _, q_ref = reference_q(self.pool[i], self.config)
            for q, q_reported in by_graph[i]:
                tally.against_reference(q, q_reported, q_ref)
