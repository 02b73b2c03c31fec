"""Service benchmark: batched engine vs sequential single-graph calls.

Eleven sections:

1. **Engine throughput, one bucket** — an ego-net workload in the
   (64, 2048) bucket.  The sequential baseline is the repo's public
   ``louvain()`` + detector per padded graph (what a service without the
   engine would run per request).  The engine is measured at batch sizes
   1 / 8 / 32; results are asserted to match the sequential partitions
   exactly.  Acceptance: batch-32 engine throughput >= 3.5x sequential.
   (The bar was 5x until the fused segment-reduction backend landed: the
   baseline IS the public sortscan ``louvain()``, which that PR made
   ~1.4x faster, so the engine's *relative* win re-based downward while
   its absolute graphs/s — recorded in the snapshot — slightly improved.
   A bar riding the old baseline would have rewarded reverting the
   fusion.)

2. **The async futures front end** — the same 32-graph workload submitted
   through ``AsyncCommunityService`` (admission + DRR + dispatcher task +
   store writes included).  Acceptance: the async path keeps >= 3.5x over
   sequential (same re-based bar as section 1) and still matches
   ``louvain()`` partitions exactly — the front end must not eat the
   engine's win.

3. **Batched warm updates** — 32 mixed add/delete edge batches against
   the detected graphs, served by the vmapped warm path
   (``engine.update_batch``) vs serving each update as its own request
   through the staged per-request warm path (what a service without the
   batched update engine runs — see ``bench_update_path``).  All sides
   include the host-side COO rewrite.  Acceptance: batch-32 warm updates
   >= 3x sequential with exact per-graph partition match.

3b. **Update mix with vertex churn** — the same three-way comparison for
   combined ``GraphUpdate`` batches (remove a vertex + compact ids, add
   a wired one, plus mixed edge deltas): the staged per-request baseline
   vs the fused immediate path vs the vmapped batched path, all
   including the host-side step-0 vertex rewrite.  Acceptance: batch-32
   vertex-churn updates >= 3x sequential with exact partition match
   (gated as ``speedup_vchurn_batch32``).

4. **Bucket mixes through the full service** — the mixed three-bucket
   traffic of launch/serve_communities.py at service batch 32 vs a
   batch-1 service (per-request dispatch), reporting graphs/s and
   aggregate directed edges/s.  The closed-loop driver submits faster
   than the road bucket computes, so at batch 32 it saturates: p50 there
   is head-of-line queueing behind full batches (throughput mode, ~4x
   the graphs/s), while the batch-1 row shows the latency mode.

5. **Fused sortscan backend** — end-to-end ``louvain()`` on the suite's
   largest synthetic graph (web_rmat, scale 12) with the fused
   segment-reduction backend (``seg_impl='auto'``) vs the pre-backend
   scatter formulation (``seg_impl='scatter'``), paired best-of-5.
   Acceptance: >= 1.2x, with bit-identical partitions.

6. **Telemetry tax** — the section-2 workload through two front ends,
   telemetry (per-request span tracing + in-memory aggregation sinks)
   enabled vs disabled, measured paired.  Acceptance: the instrumented
   path keeps >= 0.95x the disabled path's throughput — observability
   must cost < ~5%.  The enabled run's queue/engine/host phase shares
   are emitted as ``# phase_share_*`` markers, recorded in the snapshot
   informationally (they describe where time goes, not how fast it is).

7. **Stream ingest (temporal tracking)** — a removal-heavy external-id
   event stream (40% vertex deletions) folded into windowed snapshots
   through ``ServiceFrontend.ingest_window`` (translate + immediate warm
   update + matcher + timeline store per window), deferred compaction
   (``compact_window=32``, so flushes actually amortize) vs immediate
   (``compact_window=0``), measured paired over the identical
   pre-materialized window list.  Deferral is a *stability* knob — it
   keeps internal ids fixed between flushes so downstream id-map folds
   are no-ops — and at this scale it costs a little ingest throughput
   (the tombstone pass rewrites incident edges each window, like the
   compaction it defers).  Acceptance: deferred keeps >= 0.8x immediate
   throughput (the knob must stay cheap enough to leave on), with zero
   internally-disconnected communities at every snapshot and the same
   live external-id set in both modes.  Events/s end-to-end is recorded
   informationally (``service_stream_ingest``).

8. **Sharded single-graph detection** — ``louvain_sharded`` on a
   2-device forced-host CPU mesh vs the single-device ``louvain()`` on
   the same SBM graph, measured paired best-of-3 in a subprocess (jax
   pins the host device count at first init).  The partition is asserted
   bit-identical — that is the acceptance bar.  The paired time ratio is
   recorded informationally (``speedup_sharded_2dev``): forced-host
   "devices" share the same cores, so on this runner it reports the
   sharding machinery's overhead ceiling, not a speedup; it becomes one
   on real multi-chip meshes.

9. **Resilience tax** — the section-6 workload through two front ends:
   one with the full resilience stack armed but idle (retry policy +
   watchdog, per-bucket circuit breaker, degraded fallbacks enabled —
   ``fault_plan=None``, so nothing ever fires) vs a plain front end,
   measured paired.  Acceptance: the armed path keeps >= 0.95x the
   plain path's throughput — fault-tolerance must be close to free when
   nothing is failing (the breaker bookkeeping and the policy wrapper
   sit on every dispatch and commit).

10. **Quality-tier portfolio** — the three SLO tiers (``fast`` LPA /
    ``standard`` GSP-Louvain / ``max-quality`` Leiden-style refine,
    core/portfolio.py) over the tier-1 graph families of
    launch/serve_communities.py, two seeds each.  Per tier the bench
    emits mean modularity, total internally-disconnected communities
    and per-graph latency as ``# tier_*`` markers.  In-bench asserts
    pin the structural relations (per-graph max-quality modularity >=
    standard, zero disconnected for both contract-bearing tiers, the
    producing tier's QualityContract on every result);
    ``scripts/check_bench.py`` re-gates the quality axis absolutely
    from the markers: max-quality >= standard, standard within 2% of
    max-quality, disconnected == 0 for both.  The latency markers are
    informational — the fast tier sells a cheaper *contract*, and its
    wall-clock edge on a shared CPU host understates what an
    accelerator sees.

CSV rows use the suite convention ``name,us_per_call,derived`` (run.py);
``scripts/check_bench.py`` parses the ``# <metric>,<value>`` lines into
``benchmarks/BENCH_service.json`` and enforces the regression gate.
"""
from __future__ import annotations

import asyncio
import time

import jax
import numpy as np

from benchmarks.common import row, timeit
from repro.core import (
    DetectOptions, LouvainConfig, disconnected_communities, louvain,
    modularity,
)
from repro.graph import sbm_graph
from repro.launch.compile_cache import enable_compile_cache
from repro.service import (
    AsyncCommunityService, BatchedLouvainEngine, ServiceConfig,
)
from repro.service.buckets import Bucket, admit


BUCKET = Bucket(64, 2048)
B = 32


def timeit_best(fn, *args, repeats=5, **kw):
    """Best-of-N: the acceptance asserts in this file ride on ~5-8%
    margins and the suite default median-of-3 flakes under load."""
    return timeit(fn, *args, repeats=repeats, agg=np.min, **kw)


def accept_speedup(name, attempt, bar=3.5, attempts=3):
    """Assert ``attempt() >= bar``, re-measuring on failure.

    The container shares host CPU (cgroup cpu-shares): neighbors can
    shave >10% off any one measurement window without showing in local
    load, and the engine's true margin over the bar is only ~5-8%.  The
    bar is a claim about achievable throughput, so a pass on any paired
    re-measurement is a pass; a genuine regression fails all attempts.
    """
    best = 0.0
    for k in range(attempts):
        r = attempt()
        best = max(best, r)
        if best >= bar:
            break
        print(f"# {name} attempt {k + 1}: {r:.2f}x < {bar:g}x, "
              f"re-measuring")
    print(f"# {name},{best:.2f}")
    assert best >= bar, (
        f"{name} speedup {best:.2f}x < {bar:g}x acceptance bar")
    return best


def workload(n_graphs: int = B, seed0: int = 0):
    """Dense ego-net-like graphs, all admitted into the (64, 2048) bucket."""
    gs = []
    for s in range(n_graphs):
        g = sbm_graph(n_nodes=56, n_blocks=4, p_in=0.7, p_out=0.08,
                      seed=seed0 + s)[0]
        padded, bucket = admit(g, [BUCKET])
        assert bucket == BUCKET
        gs.append(padded)
    return gs


def sequential_detect(graphs, cfg):
    """Per-request work without the engine: partition + disconnected stats
    + modularity through the public single-graph API (same outputs the
    engine produces per graph)."""
    outs = []
    for g in graphs:
        C, stats = louvain(g, cfg)
        det = disconnected_communities(g.src, g.dst, g.w, C, g.n_nodes)
        q = modularity(g.src, g.dst, g.w, C)
        outs.append((C, stats, det, q))
    jax.block_until_ready(outs[-1][0])
    return outs


def bench_engine():
    cfg = LouvainConfig()
    graphs = workload()
    engine = BatchedLouvainEngine(cfg)

    # -- sequential baseline: public per-graph API ------------------------
    t_seq = timeit_best(sequential_detect, graphs, cfg)
    row("service_sequential_32", t_seq, f"{B / t_seq:.1f} graphs/s")

    # -- exactness: the engine must reproduce louvain() bit for bit ------
    seq = sequential_detect(graphs, cfg)
    res = engine.detect_batch(graphs)
    for i, (r, (C, stats, det, _)) in enumerate(zip(res, seq)):
        assert np.array_equal(r.C, np.asarray(C)), f"partition mismatch @{i}"
        assert r.n_communities == int(stats["n_communities"])
        assert r.n_disconnected == int(det["n_disconnected"]) == 0
    print("# batched results match per-graph louvain() exactly (32/32)")

    # -- engine at batch sizes -------------------------------------------
    ratios = {}
    for nb in (1, 8, 32):
        chunk = graphs[:nb]
        t = timeit_best(engine.detect_batch, chunk)
        per_graph = t / nb
        ratios[nb] = (t_seq / B) / per_graph
        row(f"service_engine_batch{nb}", t,
            f"{nb / t:.1f} graphs/s,{ratios[nb]:.2f}x_vs_sequential")
    m_edges = float(np.mean([int(np.asarray(g.src < g.n_cap).sum())
                             for g in graphs]))
    t32 = timeit_best(engine.detect_batch, graphs)
    row("service_engine_edges", t32,
        f"{B * m_edges / t32:,.0f} directed edges/s")

    def attempt():
        t_s = timeit_best(sequential_detect, graphs, cfg, repeats=3)
        t_b = timeit_best(engine.detect_batch, graphs)
        return (t_s / B) / (t_b / B)

    accept_speedup("speedup_batch32", attempt)
    return graphs, t_seq, seq


def bench_async_frontend(graphs, t_seq, seq):
    """Batch-32 through the futures front end: submit 32 detects as a
    tenant, await all futures, compare against the sequential baseline.

    The baseline is re-measured adjacent to the async rounds (paired
    measurement): container load drifts over the minutes between
    sections, and a ratio across regimes flakes the acceptance assert
    both ways."""
    config = ServiceConfig(
        detect=DetectOptions(louvain=LouvainConfig()),
            buckets=(BUCKET,), batch_size=B,
        max_delay_s=2.0, max_pending_per_tenant=B)
    # one engine across attempts: the compile cache is per-engine, and a
    # re-measurement attempt should not pay XLA compilation again
    shared_engine = None
    state = {}

    async def run():
        nonlocal shared_engine
        async with AsyncCommunityService(config) as svc:
            if shared_engine is None:
                shared_engine = svc.frontend.engine
            else:
                svc.frontend.engine = shared_engine

            async def once(tag):
                futs = [await svc.submit_detect(f"{tag}-g{i}", g)
                        for i, g in enumerate(graphs)]
                return list(await asyncio.gather(*futs))

            await once("warm")                    # compile outside timing
            ts, entries = [], None
            for r in range(5):
                t0 = time.perf_counter()
                entries = await once(f"r{r}")
                ts.append(time.perf_counter() - t0)
            return entries, float(np.min(ts))

    def attempt():
        entries, t_async = asyncio.run(run())
        state["entries"], state["t_async"] = entries, t_async
        # paired baseline: same noise regime as the async rounds
        t_s = timeit_best(sequential_detect, graphs, LouvainConfig(),
                          repeats=3)
        return t_s / t_async

    ratio = accept_speedup("speedup_async_batch32", attempt)
    for i, (e, (C, stats, det, _)) in enumerate(zip(state["entries"], seq)):
        assert np.array_equal(e.C, np.asarray(C)), \
            f"async partition mismatch @{i}"
        assert e.n_disconnected == int(det["n_disconnected"]) == 0
    print("# async front-end results match per-graph louvain() "
          "exactly (32/32)")
    t_async = state["t_async"]
    row("service_async_batch32", t_async,
        f"{B / t_async:.1f} graphs/s,{ratio:.2f}x_vs_sequential")
    return ratio


def bench_update_path(graphs):
    """Batch-32 warm updates: the vmapped engine path vs serving updates
    one request at a time.

    Mixed fully-dynamic batches (delete two live edges, add two new ones)
    against each detected graph, three implementations:

    * **sequential** — the per-request warm path a service *without* the
      batched update engine runs (and what ``store.apply_update`` ran
      before batching existed): per request, the host COO rewrite, then
      warm local-move / split / renumber / detector / modularity as
      separate jitted stages with the per-request host syncs the store
      needs for its entry fields.  The update analogue of section 1's
      per-request ``louvain()`` baseline.
    * **immediate** — the current single-request path
      (``store.apply_update``): same host rewrite, ONE fused
      ``warm_update`` call per request.  Reported for transparency: the
      fusion is where most of the win lives on a 2-core CPU host.
    * **batched** — the service's queued path: all host rewrites, then
      ONE vmapped engine call (``engine.update_batch``).

    All three produce bit-identical partitions (asserted).  Acceptance:
    batched >= 3x sequential.  On accelerator backends the batched call
    additionally gains lane parallelism over immediate (same argument as
    the engine sub_batch policy); on CPU it mostly amortizes dispatch.
    """
    from functools import partial

    import jax.numpy as jnp

    from repro.core import _segments as seg
    from repro.core.dynamic import (
        affected_mask, apply_edge_updates, directed_deltas, touched_mask,
        warm_local_move, warm_update,
    )
    from repro.core.split import split_labels

    cfg = LouvainConfig()
    engine = BatchedLouvainEngine(cfg)
    res = engine.detect_batch(graphs)
    scan = engine.scan_for(BUCKET)
    impl = "dense" if scan == "dense" else "coo"
    rng = np.random.default_rng(11)
    Cs = [np.asarray(r.C) for r in res]
    upds = []
    for g in graphs:
        src = np.asarray(g.src)
        dst = np.asarray(g.dst)
        w = np.asarray(g.w)
        live = (src < g.n_cap) & (src < dst)
        idx = rng.choice(int(live.sum()), 2, replace=False)
        n = int(g.n_nodes)
        au = rng.integers(0, n, 2)
        av = rng.integers(0, n, 2)
        u = np.concatenate([src[live][idx], au])
        v = np.concatenate([dst[live][idx], av])
        d = np.concatenate([-w[live][idx],
                            np.ones(2, np.float32)]).astype(np.float32)
        keep = u != v
        upds.append((u[keep], v[keep], d[keep]))

    _split = jax.jit(partial(split_labels, impl=impl))
    _detect = partial(disconnected_communities, impl=impl)

    def one_request_staged(g, C, u, v, d):
        """The pre-batching per-request warm path (staged dispatches +
        the host syncs the store's entry fields force per request)."""
        g_new = apply_edge_updates(g, *directed_deltas(u, v, d))
        C_prev = jnp.asarray(C)
        tm = jnp.asarray(touched_mask(g.nv, u, v))
        active0 = affected_mask(g_new, C_prev, tm)
        C1, _, it = warm_local_move(
            g_new.src, g_new.dst, g_new.w, C_prev,
            g_new.total_weight_2m(), active0, scan=scan)
        labels, _ = _split(g_new.src, g_new.dst, g_new.w, C1)
        C_new, n_comms = seg.renumber(labels, g_new.node_mask(), g_new.nv)
        det = _detect(g_new.src, g_new.dst, g_new.w, C_new, g_new.n_nodes)
        q = float(modularity(g_new.src, g_new.dst, g_new.w, C_new))
        return (np.asarray(C_new), int(n_comms),
                int(det["n_disconnected"]), q)

    def sequential_update():
        return [one_request_staged(g, C, *upd)
                for g, C, upd in zip(graphs, Cs, upds)]

    def immediate_update():
        outs = []
        for g, C, (u, v, d) in zip(graphs, Cs, upds):
            g_new = apply_edge_updates(g, *directed_deltas(u, v, d))
            out = warm_update(g_new, jnp.asarray(C),
                              jnp.asarray(touched_mask(g.nv, u, v)),
                              scan=scan)
            outs.append((np.asarray(out["C"]), int(out["n_communities"]),
                         int(out["n_disconnected"]), float(out["q"])))
        return outs

    def batched_update():
        items = []
        for g, C, (u, v, d) in zip(graphs, Cs, upds):
            g_new = apply_edge_updates(g, *directed_deltas(u, v, d))
            items.append((g_new, C, touched_mask(g.nv, u, v)))
        return engine.update_batch(items)

    # -- exactness: all three paths agree bit for bit --------------------
    seq = sequential_update()
    imm = immediate_update()
    bat = batched_update()
    for i, (s, m, b) in enumerate(zip(seq, imm, bat)):
        assert np.array_equal(s[0], b.C), f"update C @{i}"
        assert np.array_equal(m[0], b.C), f"immediate C @{i}"
        # immediate and batched run the same jitted compute: bit equal.
        # The staged baseline's eager modularity sum may differ by ulps.
        assert m[3] == b.q, f"update q @{i}"
        assert abs(s[3] - b.q) <= 1e-6, f"staged q @{i}"
        assert b.n_disconnected == 0
    print("# batched warm updates match the sequential warm path exactly "
          f"({B}/{B})")

    t_seq = timeit_best(sequential_update)
    row("service_update_sequential_32", t_seq, f"{B / t_seq:.1f} graphs/s")
    t_imm = timeit_best(immediate_update)
    row("service_update_immediate_32", t_imm,
        f"{B / t_imm:.1f} graphs/s,{t_seq / t_imm:.2f}x_vs_sequential")

    def attempt():
        t_s = timeit_best(sequential_update, repeats=3)
        t_b = timeit_best(batched_update)
        return t_s / t_b

    ratio = accept_speedup("speedup_update_batch32", attempt, bar=3.0)
    t_bat = timeit_best(batched_update)
    row("service_update_batch32", t_bat,
        f"{B / t_bat:.1f} graphs/s,{ratio:.2f}x_vs_sequential,"
        f"{t_imm / t_bat:.2f}x_vs_immediate")


def bench_vertex_churn(graphs):
    """Section 3b: batch-32 *vertex-churn* updates — combined GraphUpdate
    batches (remove one vertex, add one wired into a surviving community,
    plus an edge delete + insert) through the same three paths as section
    3.  Every path pays the identical host-side step-0 vertex rewrite
    (``prepare_graph_update``), so the ratio isolates the dispatch win.
    """
    from functools import partial

    import jax.numpy as jnp

    from repro.core import _segments as seg
    from repro.core.dynamic import (
        GraphUpdate, affected_mask, prepare_graph_update, warm_local_move,
        warm_update,
    )
    from repro.core.split import split_labels

    cfg = LouvainConfig()
    engine = BatchedLouvainEngine(cfg)
    res = engine.detect_batch(graphs)
    scan = engine.scan_for(BUCKET)
    impl = "dense" if scan == "dense" else "coo"
    rng = np.random.default_rng(23)
    Cs = [np.asarray(r.C) for r in res]
    upds = []
    for g, C in zip(graphs, Cs):
        n = int(g.n_nodes)
        src, dst, w = (np.asarray(g.src), np.asarray(g.dst), np.asarray(g.w))
        rem = int(rng.integers(0, n))
        anchor = int(rng.choice([i for i in range(n) if i != rem]))
        peers = [i - (i > rem) for i in range(n)
                 if C[i] == C[anchor] and i != rem][:3]
        # plus one live-edge delete and one fresh insert (post-rewrite ids)
        live = (src < g.n_cap) & (src < dst) & (src != rem) & (dst != rem)
        j = int(rng.integers(0, int(live.sum())))
        du = src[live][j] - (src[live][j] > rem)
        dv = dst[live][j] - (dst[live][j] > rem)
        u = np.concatenate([np.full(len(peers), n - 1), [du]])
        v = np.concatenate([peers, [dv]])
        d = np.concatenate([np.ones(len(peers)),
                            [-w[live][j]]]).astype(np.float32)
        upds.append(GraphUpdate(u=u.astype(np.int64), v=v.astype(np.int64),
                                dw=d, add=1, remove=np.array([rem])))

    _split = jax.jit(partial(split_labels, impl=impl))
    _detect = partial(disconnected_communities, impl=impl)

    def one_request_staged(g, C, upd):
        """The pre-batching per-request path: host vertex+edge rewrite +
        staged warm stages with per-request host syncs."""
        g_new, C_prev, tm, _ = prepare_graph_update(g, C, upd)
        C_prev = jnp.asarray(C_prev)
        active0 = affected_mask(g_new, C_prev, jnp.asarray(tm))
        C1, _, it = warm_local_move(
            g_new.src, g_new.dst, g_new.w, C_prev,
            g_new.total_weight_2m(), active0, scan=scan)
        labels, _ = _split(g_new.src, g_new.dst, g_new.w, C1)
        C_new, n_comms = seg.renumber(labels, g_new.node_mask(), g_new.nv)
        det = _detect(g_new.src, g_new.dst, g_new.w, C_new, g_new.n_nodes)
        q = float(modularity(g_new.src, g_new.dst, g_new.w, C_new))
        return (np.asarray(C_new), int(n_comms),
                int(det["n_disconnected"]), q)

    def sequential_update():
        return [one_request_staged(g, C, upd)
                for g, C, upd in zip(graphs, Cs, upds)]

    def immediate_update():
        outs = []
        for g, C, upd in zip(graphs, Cs, upds):
            g_new, C_prev, tm, _ = prepare_graph_update(g, C, upd)
            out = warm_update(g_new, jnp.asarray(C_prev), jnp.asarray(tm),
                              scan=scan)
            outs.append((np.asarray(out["C"]), int(out["n_communities"]),
                         int(out["n_disconnected"]), float(out["q"])))
        return outs

    def batched_update():
        items = []
        for g, C, upd in zip(graphs, Cs, upds):
            g_new, C_prev, tm, _ = prepare_graph_update(g, C, upd)
            items.append((g_new, C_prev, tm))
        return engine.update_batch(items)

    # -- exactness: all three paths agree, zero disconnected -------------
    seq = sequential_update()
    imm = immediate_update()
    bat = batched_update()
    for i, (s, m, b) in enumerate(zip(seq, imm, bat)):
        assert np.array_equal(s[0], b.C), f"vchurn C @{i}"
        assert np.array_equal(m[0], b.C), f"vchurn immediate C @{i}"
        assert m[3] == b.q, f"vchurn q @{i}"
        assert abs(s[3] - b.q) <= 1e-6, f"vchurn staged q @{i}"
        assert b.n_disconnected == 0
    print("# batched vertex-churn updates match the sequential warm path "
          f"exactly ({B}/{B})")

    t_seq = timeit_best(sequential_update)
    row("service_vchurn_sequential_32", t_seq, f"{B / t_seq:.1f} graphs/s")

    def attempt():
        t_s = timeit_best(sequential_update, repeats=3)
        t_b = timeit_best(batched_update)
        return t_s / t_b

    ratio = accept_speedup("speedup_vchurn_batch32", attempt, bar=3.0)
    t_bat = timeit_best(batched_update)
    row("service_vchurn_batch32", t_bat,
        f"{B / t_bat:.1f} graphs/s,{ratio:.2f}x_vs_sequential")


def bench_bucket_mix():
    from repro.launch.serve_communities import run_traffic
    from repro.service import CommunityService

    for name, batch, sub in (("service_mix_batch32", 32, None),
                             ("service_mix_batch1", 1, 1)):
        svc = CommunityService(LouvainConfig(), batch_size=batch,
                               max_delay_s=0.05, sub_batch=sub)
        t0 = time.perf_counter()
        rep = run_traffic(svc, n_requests=60, update_frac=0.25, seed=7,
                          verbose=False)
        dt = time.perf_counter() - t0
        row(name, dt,
            f"{rep['graphs_per_s']:.1f} graphs/s,"
            f"{rep['edges_per_s']:,.0f} edges/s,"
            f"p50 {rep['p50_ms']:.0f} ms,p99 {rep['p99_ms']:.0f} ms")


def bench_fused_backend():
    """Section 5: the segment-reduction backend's end-to-end win.

    One graph object, both seg_impls measured back to back per attempt
    (paired — host noise hits numerator and denominator alike); partitions
    asserted bit-identical so the speedup is never bought with drift.
    """
    from repro.graph import rmat_graph

    g = rmat_graph(scale=12, edge_factor=8, seed=1)  # == common.dataset web
    cfg = LouvainConfig()
    fused_opts = DetectOptions(louvain=cfg, seg_impl="auto")
    scatter_opts = DetectOptions(louvain=cfg, seg_impl="scatter")
    C_fused, _ = louvain(g, options=fused_opts)
    C_scatter, _ = louvain(g, options=scatter_opts)
    assert np.array_equal(np.asarray(C_fused), np.asarray(C_scatter)), (
        "fused backend partition diverged from the scatter path")
    print("# fused and scatter backends bit-identical on web_rmat")

    state = {}

    def attempt():
        t_scatter = timeit_best(
            lambda: louvain(g, options=scatter_opts)[0])
        t_fused = timeit_best(lambda: louvain(g, options=fused_opts)[0])
        state["t_fused"] = t_fused
        return t_scatter / t_fused

    accept_speedup("speedup_louvain_fused", attempt, bar=1.2)
    m = int(g.num_edges())
    row("service_louvain_fused_rmat", state["t_fused"],
        f"{m / state['t_fused']:,.0f} edges/s")


def bench_telemetry_overhead(graphs):
    """Section 6: what the span/sink instrumentation costs on the hot
    serving path.

    Two ServiceFrontends over the same batch-32 workload — one with the
    in-memory telemetry sink attached (every request pays trace
    allocation, ten span marks, and sink aggregation at resolve), one
    with ``telemetry_enabled=False`` (the hub's emission early-outs on
    the empty sink tuple).  Each frontend owns its engine, so both warm
    their compile caches outside the timed region; the ratio is measured
    paired (disabled immediately before enabled, each attempt).
    """
    from repro.service.frontend import ServiceFrontend

    def make(enabled):
        fe = ServiceFrontend(ServiceConfig(
            detect=DetectOptions(louvain=LouvainConfig()),
            buckets=(BUCKET,), batch_size=B,
            max_delay_s=2.0, max_pending_per_tenant=B,
            telemetry_enabled=enabled))
        run_once(fe)                      # compile outside timing
        return fe

    def run_once(fe):
        futs = [fe.submit_detect(f"g{i}", g)
                for i, g in enumerate(graphs)]
        fe.dispatch(force=True)
        for f in futs:
            f.result()

    fe_off = make(False)
    fe_on = make(True)

    def attempt():
        t_off = timeit_best(run_once, fe_off, repeats=3)
        t_on = timeit_best(run_once, fe_on, repeats=3)
        return t_off / t_on

    ratio = accept_speedup("speedup_telemetry_on", attempt, bar=0.95)
    t_on = timeit_best(run_once, fe_on, repeats=3)
    row("service_telemetry_on_batch32", t_on,
        f"{B / t_on:.1f} graphs/s,{ratio:.2f}x_vs_disabled")
    # where the instrumented run's time went — informational markers for
    # the snapshot, never gated (shares describe shape, not speed)
    bd = fe_on.mem_sink.phase_breakdown()
    for group in ("queue", "engine", "host"):
        print(f"# phase_share_{group},{bd[group]:.4f}")


def bench_resilience_tax(graphs):
    """Section 9: what the armed-but-idle resilience stack costs on the
    hot serving path.

    Two ServiceFrontends over the same batch-32 workload — one with the
    retry policy (watchdog included), the per-bucket circuit breaker and
    degraded fallbacks all configured but no fault plan (so every
    dispatch pays the policy wrapper, the watchdog thread, breaker
    bookkeeping and the wrapped commit, yet nothing ever fails), one
    plain.  Each frontend owns its engine, so both warm their compile
    caches outside the timed region; the ratio is measured paired.
    """
    from repro.resilience import BreakerConfig, RetryPolicy
    from repro.service.frontend import ServiceFrontend

    def make(armed):
        kw = {}
        if armed:
            kw = dict(retry=RetryPolicy(max_attempts=3, backoff_s=0.01,
                                        watchdog_s=30.0),
                      breaker=BreakerConfig(failure_threshold=5,
                                            cooldown_s=1.0),
                      degrade_enabled=True)
        fe = ServiceFrontend(ServiceConfig(
            detect=DetectOptions(louvain=LouvainConfig()),
            buckets=(BUCKET,), batch_size=B,
            max_delay_s=2.0, max_pending_per_tenant=B, **kw))
        run_once(fe)                      # compile outside timing
        return fe

    def run_once(fe):
        futs = [fe.submit_detect(f"g{i}", g)
                for i, g in enumerate(graphs)]
        fe.dispatch(force=True)
        for f in futs:
            f.result()

    fe_off = make(False)
    fe_on = make(True)

    def attempt():
        t_off = timeit_best(run_once, fe_off, repeats=3)
        t_on = timeit_best(run_once, fe_on, repeats=3)
        return t_off / t_on

    ratio = accept_speedup("speedup_resilience_on", attempt, bar=0.95)
    t_on = timeit_best(run_once, fe_on, repeats=3)
    row("service_resilience_on_batch32", t_on,
        f"{B / t_on:.1f} graphs/s,{ratio:.2f}x_vs_plain")
    assert fe_on.resilience.n_retries == 0, \
        "idle fault-free run recorded retries"


def bench_stream_ingest():
    """Section 7: events/s through the windowed temporal-tracking path,
    deferred vs immediate vertex compaction.

    The window list is materialized once from the synthetic stream and
    replayed against fresh frontends, so both modes fold the IDENTICAL
    events.  Each replay warms its frontend's compile caches by running
    the seed detect plus two windows against a throwaway graph id first;
    the timed region is pure steady-state ingest (translate -> immediate
    warm update -> matcher -> timeline store).
    """
    from repro.data.streams import graph_event_stream
    from repro.graph import ring_of_cliques
    from repro.service.frontend import ServiceFrontend

    g0 = ring_of_cliques(n_cliques=6, clique_size=6)
    horizon, window = 12.0, 1.0
    windows, buf, end = [], [], window
    for e in graph_event_stream(
            g0, rate=60.0, seed=11,
            mix=(("edge_add", 0.3), ("edge_del", 0.1), ("vertex_add", 0.2),
                 ("vertex_del", 0.4)),
            min_vertices=12):
        if e.t >= horizon:
            break
        while e.t >= end:
            windows.append((end, buf))
            buf, end = [], end + window
        buf.append(e)
    windows.append((end, buf))
    n_events = sum(len(b) for _, b in windows)

    def replay(compact_window):
        fe = ServiceFrontend(ServiceConfig(
            detect=DetectOptions(louvain=LouvainConfig()),
            batch_size=4, max_delay_s=0.0,
            update_batch_size=1, timeline_enabled=True,
            compact_window=compact_window))
        # warm compiles on a throwaway graph (same bucket, same window
        # shapes; unknown external ids just drop in translate)
        fe.submit_detect("w", g0)
        fe.dispatch(force=True)
        for t, evs in windows[:2]:
            fe.ingest_window("w", evs, t=t)
        fe.submit_detect("g", g0)
        fe.dispatch(force=True)
        fe.timelines.set_time("g", 0.0)
        t0 = time.perf_counter()
        for t, evs in windows:
            fe.ingest_window("g", evs, t=t)
        dt = time.perf_counter() - t0
        snaps = fe.timelines.snapshots("g")
        assert all(s.n_disconnected == 0 for s in snaps), \
            [(s.t, s.n_disconnected) for s in snaps]
        live = frozenset(snaps[-1].ext.tolist())
        fe.close()
        return dt, live

    def attempt():
        t_imm, live_imm = replay(0)
        t_def, live_def = replay(32)
        assert live_imm == live_def, \
            f"live external sets diverged: {sorted(live_imm ^ live_def)}"
        attempt.t_def = t_def
        return t_imm / t_def

    ratio = accept_speedup("speedup_stream_deferred", attempt, bar=0.8)
    t_def = attempt.t_def
    row("service_stream_ingest", t_def / n_events,
        f"{n_events / t_def:.1f} events/s,{len(windows)}_windows,"
        f"{ratio:.2f}x_vs_immediate")


def _sharded_pair(n_dev: int):
    """Paired single-device vs ``n_dev``-device sharded timing on one
    larger graph; returns ``(t_single, t_sharded, parity)``."""
    from repro.core.distributed import louvain_sharded

    g = sbm_graph(n_nodes=1500, n_blocks=24, p_in=0.08, p_out=0.002,
                  seed=7)[0]
    cfg = LouvainConfig()
    # warm both compile caches before timing
    C1 = np.asarray(louvain(g, cfg)[0])
    Cs = np.asarray(louvain_sharded(g, cfg, mesh=n_dev)[0])
    parity = int(np.array_equal(C1, Cs))

    def best_of(fn, repeats=3):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            np.asarray(fn()[0])
            best = min(best, time.perf_counter() - t0)
        return best

    t_single = best_of(lambda: louvain(g, cfg))
    t_sharded = best_of(lambda: louvain_sharded(g, cfg, mesh=n_dev))
    return t_single, t_sharded, parity


def _sharded_child():
    """Runs in the 2-device CPU subprocess of :func:`bench_sharded`."""
    t_single, t_sharded, parity = _sharded_pair(2)
    print(f"SHARDED_CHILD {t_single:.6f} {t_sharded:.6f} {parity}")


def bench_sharded():
    """Section 8: sharded single-graph detection on a 2-device mesh vs
    the single-device driver.  On the CPU the pair is measured in a
    subprocess with two forced-host devices (jax pins the host device
    count at first init); on an accelerator it runs in this process on
    the devices it already holds — a child could not reach them — and is
    skipped with fewer than two.  The partition is asserted
    bit-identical — that is the acceptance bar; the speedup is recorded
    informationally (``speedup_sharded_2dev``): two forced-host CPU
    "devices" share the same cores, so there the ratio reports the
    sharding machinery's overhead ceiling."""
    import os
    import subprocess
    import sys

    if jax.default_backend() != "cpu":
        if len(jax.devices()) < 2:
            print(f"# sharded: skipped, one {jax.default_backend()} device")
            return
        t_single, t_sharded, parity = _sharded_pair(2)
    else:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (f"{env.get('XLA_FLAGS', '')} "
                            "--xla_force_host_platform_device_count=2"
                            ).strip()
        proc = subprocess.run(
            [sys.executable, __file__, "--sharded-child"],
            capture_output=True, text=True, env=env, timeout=1200)
        if proc.returncode != 0:
            raise SystemExit("sharded bench child failed:\n"
                             + proc.stdout + proc.stderr)
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("SHARDED_CHILD")][-1]
        _, t_single, t_sharded, parity = line.split()
        t_single, t_sharded = float(t_single), float(t_sharded)
        parity = int(parity)
    assert parity == 1, "sharded partition diverged from single-device"
    print("# sharded 2-device partition matches single-device exactly")
    row("service_sharded_single", t_single, f"{1.0 / t_single:.2f} graphs/s")
    row("service_sharded_2dev", t_sharded, f"{1.0 / t_sharded:.2f} graphs/s")
    print(f"# speedup_sharded_2dev,{t_single / t_sharded:.2f}")
    print(f"# sharded_parity,{parity:.1f}")


def bench_tiers():
    """Section 10: the SLO-tiered algorithm portfolio over the tier-1
    graph families — per-tier modularity / disconnected / latency.

    Quality is gated, not trended: scripts/check_bench.py checks the
    emitted ``tier_*`` markers absolutely (max-quality >= standard,
    standard within 2% of max-quality, zero disconnected for both),
    while the per-tier latencies are informational — the fast tier's
    point is a cheaper *contract*, and its wall-clock edge over
    standard on a 2-core CPU host understates what an accelerator
    sees."""
    from repro.core import detect
    from repro.core.portfolio import ALGORITHMS, contract_for
    from repro.launch.serve_communities import FAMILIES, synth_graph

    graphs = [synth_graph(fam, seed) for fam in FAMILIES
              for seed in (0, 1)]
    key = {"fast": "fast", "standard": "standard", "max-quality": "maxq"}
    qs = {}
    for alg in ALGORITHMS:
        opts = DetectOptions(louvain=LouvainConfig(), algorithm=alg)
        dets = [detect(g, options=opts) for g in graphs]  # warms compiles
        for d in dets:
            assert d.contract is not None and d.contract.tier == alg, \
                f"{alg}: result carries contract {d.contract!r}"
        n_disc = sum(int(d.n_disconnected) for d in dets)
        if contract_for(alg).zero_disconnected:
            assert n_disc == 0, \
                f"{alg}: contract promises zero disconnected, got {n_disc}"
        qs[alg] = [float(d.modularity) for d in dets]

        def once():
            out = [detect(g, options=opts) for g in graphs]
            jax.block_until_ready(out[-1].labels)

        t = timeit_best(once, repeats=3)
        k = key[alg]
        row(f"service_tier_{k}", t / len(graphs),
            f"{len(graphs) / t:.1f} graphs/s,{alg}")
        print(f"# tier_modularity_{k},{float(np.mean(qs[alg])):.4f}")
        print(f"# tier_disconnected_{k},{n_disc:.1f}")
        print(f"# tier_latency_ms_{k},{1e3 * t / len(graphs):.2f}")

    for i, (q_s, q_m) in enumerate(zip(qs["standard"], qs["max-quality"])):
        assert q_m >= q_s - 1e-9, \
            f"graph {i}: max-quality {q_m:.4f} < standard {q_s:.4f}"
    print(f"# max-quality modularity >= standard on every graph "
          f"({len(graphs)}/{len(graphs)})")


def main():
    enable_compile_cache()
    print("name,us_per_call,derived")
    graphs, t_seq, seq = bench_engine()
    bench_async_frontend(graphs, t_seq, seq)
    bench_update_path(graphs)
    bench_vertex_churn(graphs)
    bench_bucket_mix()
    bench_fused_backend()
    bench_telemetry_overhead(graphs)
    bench_stream_ingest()
    bench_sharded()
    bench_resilience_tax(graphs)
    bench_tiers()


if __name__ == "__main__":
    import sys as _sys

    if "--sharded-child" in _sys.argv:
        _sharded_child()
    else:
        main()
