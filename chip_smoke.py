#!/usr/bin/env python3
"""End-to-end smoke of GSP-Louvain community detection on a TPU.

Drives the system's main paths once through the entry points users call,
checks the results by means independent of the code under test, and
prints one JSON line last::

    python3 chip_smoke.py              # one chip: phases "service", "large"
    python3 chip_smoke.py --chips 4    # four chips: sharded vs single only

Phases:

* ``service`` — the many-small-graph deployment: ``AsyncCommunityService``
  with a default ``ServiceConfig`` (``seg_impl='auto'`` resolves to the
  compiled Pallas kernels) plus batched warm updates.  About 64 detect
  requests from two tenants cover every bucket of ``DEFAULT_BUCKETS``;
  then one round of edge adds/deletes and one vertex add/remove go
  through the batched update path.  Every future must resolve to a
  committed entry; a few graphs per bucket must equal sequential
  ``louvain()`` (batched = sequential); the dense = sort and
  pallas = xla parities are printed for one graph per bucket.
* ``large`` — one-shot detection, the paper's headline job: ``detect()``
  on a Graph500 R-MAT graph (initiator 0.57/0.19/0.19/0.05, edge factor
  16) at scale 18, warmed once, then timed to ``block_until_ready``.
  At scale 14 the same ``detect()`` also runs on the host CPU backend in
  this process; its modularity must agree within 1%.
* ``sharded`` (``--chips 4`` only) — ``detect(g, mesh=4)`` against
  single-device ``detect(g)`` on the same scale-18 graph: labels and
  modularity bit-identical, no disconnected community.

Independent checks: internally-disconnected communities counted on the
host with scipy (one ``connected_components`` over intra-community
edges) must be 0, and modularity recomputed in numpy (float64) must match
the reported value to 1e-4.

Exits non-zero, before printing any result, when JAX finds no TPU (or
fewer chips than ``--chips``), and exits non-zero when any phase fails.
Everything runs in this one process: a chip belongs to the process that
holds it.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Graph500 R-MAT initiator (a, b, c; d = 1 - a - b - c) and edge factor
RMAT_ABC = (0.57, 0.19, 0.19)
EDGE_FACTOR = 16
# the large phase's scale: 2^18 vertices, ~7.6M directed COO entries.
# Graph500's scale 20 takes ~8x longer per detection than this on one
# v5e chip, which with warm-up and compilation overruns the smoke's time
# budget (PERF.md, "Where the time goes")
DEFAULT_SCALE = 18
REF_SCALE = 14
TENANTS = ("feed", "ads")


def log(*args):
    print(*args, flush=True)


# -- device -----------------------------------------------------------------

def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu(chips: int) -> dict:
    """Print the device; exit non-zero unless JAX sees ``chips`` TPUs."""
    import jax

    dev = device_info()
    log(f"jax {jax.__version__}")
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev['platform']} devices; "
                         f"this smoke runs on the chip only")
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU devices, "
                         f"JAX found {dev['count']}")
    return dev


# -- independent checks (host, numpy/scipy) ----------------------------------

def _live(g, labels):
    n = int(np.asarray(g.n_nodes))
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    w = np.asarray(g.w, np.float64)
    live = (src < n) & (dst < n)
    return n, src[live], dst[live], w[live], np.asarray(labels)[:n]


def host_disconnected(g, labels) -> int:
    """Communities whose members do not form one connected component of
    the subgraph induced by the community's own edges."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    n, src, dst, _, lab = _live(g, labels)
    keep = lab[src] == lab[dst]
    adj = coo_matrix((np.ones(int(keep.sum()), np.int8),
                      (src[keep], dst[keep])), shape=(n, n))
    _, comp = connected_components(adj, directed=False)
    pairs = np.unique(np.stack([lab, comp]), axis=1)
    _, parts = np.unique(pairs[0], return_counts=True)
    return int((parts > 1).sum())


def host_modularity(g, labels) -> float:
    """Newman modularity of ``labels`` in float64 over the directed COO
    (both directions stored): Q = sum_c [in_c / 2m - (tot_c / 2m)^2]."""
    n, src, dst, w, lab = _live(g, labels)
    two_m = w.sum()
    inside = w[lab[src] == lab[dst]].sum()
    k = np.bincount(src, weights=w, minlength=n)
    tot = np.bincount(lab, weights=k)
    return float(inside / two_m - np.sum((tot / two_m) ** 2))


def check_partition(g, labels, q_reported: float, what: str) -> None:
    """Both independent checks; raises AssertionError naming ``what``."""
    disc = host_disconnected(g, labels)
    assert disc == 0, f"{what}: {disc} internally-disconnected communities"
    q = host_modularity(g, labels)
    assert abs(q - q_reported) <= 1e-4, \
        f"{what}: modularity {q_reported} reported, {q} recomputed"


# -- phase "service" ----------------------------------------------------------

def bucket_graph(bucket: int, seed: int):
    """A request graph that lands in ``DEFAULT_BUCKETS[bucket]``."""
    from repro.graph import grid_graph, sbm_graph

    rng = np.random.default_rng(seed)
    if bucket == 0:        # sparse ego-net -> (64, 512)
        return sbm_graph(n_nodes=int(rng.integers(28, 52)), n_blocks=3,
                         p_in=0.35, p_out=0.03, seed=seed)[0]
    if bucket == 1:        # dense ego-net -> (64, 2048)
        return sbm_graph(n_nodes=int(rng.integers(48, 60)), n_blocks=4,
                         p_in=0.7, p_out=0.08, seed=seed)[0]
    if bucket == 2:        # road-like grid -> (256, 2048)
        return grid_graph(int(rng.integers(10, 15)), 16)
    if bucket == 3:        # mid-size social -> (256, 8192)
        return sbm_graph(n_nodes=int(rng.integers(180, 250)), n_blocks=5,
                         p_in=0.4, p_out=0.01, seed=seed)[0]
    # sparse large neighbourhood -> (1024, 16384), the sortscan bucket
    return sbm_graph(n_nodes=int(rng.integers(600, 900)), n_blocks=16,
                     p_in=0.25, p_out=0.002, seed=seed)[0]


def service_requests(seed: int, per_bucket: int, buckets=None):
    """``(graph_id, tenant, graph, bucket)`` round-robin over buckets and
    tenants; every graph is checked to land in its bucket."""
    from repro.service.buckets import DEFAULT_BUCKETS, choose_bucket, \
        live_edges

    idx = range(len(DEFAULT_BUCKETS)) if buckets is None else buckets
    reqs = []
    for i in range(per_bucket):
        for b in idx:
            g = bucket_graph(b, seed * 1000 + 10 * i + b)
            got = choose_bucket(int(g.n_nodes), live_edges(g))
            assert got == DEFAULT_BUCKETS[b], (b, got)
            reqs.append((f"b{b}-g{i}", TENANTS[len(reqs) % 2], g, got))
    return reqs


async def _serve(config, reqs, n_update: int, seed: int):
    """Submit every detect, then two update rounds on the first
    ``n_update`` graphs of each bucket; returns what the checks need."""
    from repro.launch.serve_communities import (
        synth_churn_updates, synth_vertex_churn,
    )
    from repro.service import AsyncCommunityService

    async with AsyncCommunityService(config) as svc:
        t0 = time.perf_counter()
        futs = [await svc.submit_detect(gid, g, tenant=t)
                for gid, t, g, _ in reqs]
        detected = await asyncio.gather(*futs, return_exceptions=True)
        t_detect = time.perf_counter() - t0
        upd_ids = []
        for b in dict.fromkeys(bk for *_, bk in reqs):
            upd_ids += [(gid, t) for gid, t, _, bk in reqs if bk == b
                        ][:n_update]
        t0 = time.perf_counter()
        rounds = []
        for r, make in enumerate((synth_churn_updates, synth_vertex_churn)):
            futs = [await svc.submit_update(
                gid, make(svc.result(gid), seed + 31 * r + i), tenant=t)
                for i, (gid, t) in enumerate(upd_ids)]
            rounds.append(await asyncio.gather(*futs,
                                               return_exceptions=True))
        await svc.drain()
        return dict(detected=detected, updated=rounds, upd_ids=upd_ids,
                    t_detect=t_detect, t_update=time.perf_counter() - t0,
                    report=svc.metrics.report(),
                    sink=svc.frontend.mem_sink, engine=svc.engine)


def _committed(result, what: str):
    from repro.resilience.degrade import DegradedResult
    from repro.service.store import StoreEntry

    assert not isinstance(result, BaseException), \
        f"{what}: failed future: {result!r}"
    assert not isinstance(result, DegradedResult), f"{what}: degraded"
    assert isinstance(result, StoreEntry), f"{what}: got {result!r}"
    return result


def phase_service(seed: int = 0, *, per_bucket: int = 13,
                  n_sequential: int = 2, n_update: int = 2,
                  buckets=None) -> dict:
    """The many-small-graph deployment through ``AsyncCommunityService``.
    Raises AssertionError on any failed contract; returns the report."""
    from repro.core import DetectOptions, louvain
    from repro.kernels import segsum
    from repro.service import ServiceConfig
    from repro.service.buckets import admit, calibrated_min_density, \
        choose_scan

    reqs = service_requests(seed, per_bucket, buckets)
    config = ServiceConfig(update_batch_size=8)
    out = asyncio.run(_serve(config, reqs, n_update, seed))
    engine = out["engine"]
    seg_impl = engine.seg_impl
    interpret = segsum._default_interpret(None)
    log(f"service: seg_impl={seg_impl} interpret={interpret} "
        f"sub_batch={engine.sub_batch} dense_min_density="
        f"{calibrated_min_density()}")

    entries = {}
    for (gid, _, g, _), res in zip(reqs, out["detected"]):
        e = _committed(res, gid)
        check_partition(e.graph, e.C, e.q, gid)
        entries[gid] = e
    for r, results in enumerate(out["updated"]):
        for (gid, _), res in zip(out["upd_ids"], results):
            e = _committed(res, f"update round {r} of {gid}")
            assert e.version == 2 + r, (gid, e.version)
            check_partition(e.graph, e.C, e.q, f"update round {r} of {gid}")

    # batched = sequential, and the on-chip parities, per bucket
    t0 = time.perf_counter()
    parity = {}
    for b in dict.fromkeys(bk for *_, bk in reqs):
        mine = [(gid, g) for gid, _, g, bk in reqs if bk == b][:n_sequential]
        for k, (gid, g) in enumerate(mine):
            padded, _ = admit(g)
            C_seq = np.asarray(louvain(padded)[0])
            assert np.array_equal(entries[gid].C, C_seq), \
                f"{gid}: batched partition != sequential louvain()"
            if k == 0:
                dense = np.asarray(louvain(padded, options=DetectOptions(
                    scan="dense"))[0])
                xla = np.asarray(louvain(padded, options=DetectOptions(
                    seg_impl="xla"))[0])
                parity[f"{b.n_cap}x{b.m_cap}"] = dict(
                    scan=choose_scan(b.nv, b.m_cap),
                    dense_eq_sort=bool(np.array_equal(dense, C_seq)),
                    pallas_eq_xla=bool(np.array_equal(xla, C_seq)))
    log(f"service: sequential louvain() and parity checks "
        f"{time.perf_counter() - t0:.1f} s")
    for name, p in parity.items():
        log(f"  bucket {name}: served scan={p['scan']} "
            f"dense==sort {p['dense_eq_sort']} "
            f"pallas==xla {p['pallas_eq_xla']}")

    rep, sink = out["report"], out["sink"]
    compiles = {}
    for (name, lk), v in sink.counters.items():
        lab = dict(lk)
        if name == "engine_compile" and lab["result"] == "miss":
            field = "misses"
        elif name == "engine_compile_seconds":
            field = "seconds"
        else:
            continue
        compiles.setdefault(lab["bucket"], {"misses": 0, "seconds": 0.0}
                            )[field] += v
    n_served = rep["n_detect"] + rep["n_update"]
    log(f"service: served {n_served} ({rep['n_detect']} detect + "
        f"{rep['n_update']} update), failed {rep['n_failed']}, "
        f"p50 {rep['p50_ms']} ms p99 {rep['p99_ms']} ms, "
        f"detect wave {out['t_detect']:.3f} s, update rounds "
        f"{out['t_update']:.3f} s (compilation included)")
    for name in sorted(compiles, key=lambda s: tuple(map(int,
                                                         s.split("x")))):
        c = compiles[name]
        log(f"  bucket {name}: compile misses {int(c['misses'])} "
            f"compile seconds {c['seconds']:.3f}")
    assert rep["n_failed"] == 0, rep
    assert rep["n_detect"] == len(reqs), rep
    return dict(seg_impl=seg_impl, interpret=interpret, served=n_served,
                p50_ms=rep["p50_ms"], p99_ms=rep["p99_ms"],
                compiles=compiles, parity=parity)


# -- phase "large" -------------------------------------------------------------

def rmat(scale: int, seed: int):
    from repro.graph import rmat_graph

    a, b, c = RMAT_ABC
    return rmat_graph(scale=scale, edge_factor=EDGE_FACTOR, a=a, b=b, c=c,
                      seed=seed)


def _timed_detect(g, options=None, telemetry=None):
    import jax
    from repro.core import detect

    t0 = time.perf_counter()
    res = detect(g, options=options, telemetry=telemetry)
    jax.block_until_ready(res.labels)
    return res, time.perf_counter() - t0


def phase_large(scale: int = DEFAULT_SCALE, seed: int = 0, *,
                ref_scale: int = REF_SCALE) -> dict:
    """One-shot ``detect()`` on an R-MAT graph on the default device, plus
    the CPU-backend reference at ``ref_scale``."""
    import jax
    from repro.core import DetectOptions

    t0 = time.perf_counter()
    g = rmat(scale, seed)
    m = int((np.asarray(g.src) < g.n_cap).sum())
    log(f"large: scale {scale}: {g.n_cap} vertices, {m} directed COO "
        f"entries, generated in {time.perf_counter() - t0:.1f} s")
    seg_impl = DetectOptions().resolved_seg_impl()
    _, t_first = _timed_detect(g)
    res, t = _timed_detect(g)
    labels = np.asarray(res.labels)
    log(f"large: seg_impl={seg_impl} first call {t_first:.3f} s, "
        f"timed {t:.3f} s, {m / t:.0f} directed edges/s "
        f"({m / 2 / t:.0f} undirected), q={res.modularity:.6f} "
        f"communities={res.n_communities} passes={int(res.stats['passes'])}"
        f" sweeps={int(res.stats['li_total'])}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"large: peak device memory {stats.get('peak_bytes_in_use')} bytes")
    assert res.n_disconnected == 0, res.n_disconnected
    check_partition(g, labels, res.modularity, f"scale {scale}")

    # the same detection on the host CPU backend, in this process
    gr = rmat(ref_scale, seed)
    dev, _ = _timed_detect(gr)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref, t_cpu = _timed_detect(jax.device_put(gr, cpu),
                                   DetectOptions(seg_impl="xla"))
    same = bool(np.array_equal(np.asarray(ref.labels),
                               np.asarray(dev.labels)))
    rel = abs(ref.modularity - dev.modularity) / abs(ref.modularity)
    log(f"large: scale {ref_scale} reference: device q={dev.modularity:.6f}"
        f" cpu q={ref.modularity:.6f} (rel diff {rel:.2e}, cpu "
        f"{t_cpu:.1f} s), labels identical {same}")
    check_partition(gr, dev.labels, dev.modularity, f"scale {ref_scale}")
    assert rel <= 0.01, (dev.modularity, ref.modularity)
    return dict(scale=scale, m=m, seconds=t, first_seconds=t_first,
                edges_per_s=m / t, q=res.modularity, seg_impl=seg_impl,
                ref_q=(dev.modularity, ref.modularity), ref_same=same)


# -- phase "sharded" (--chips 4) ---------------------------------------------

def phase_sharded(scale: int = DEFAULT_SCALE, seed: int = 0, *,
                  chips: int = 4) -> dict:
    """``detect(g, mesh=chips)`` against single-device ``detect(g)``."""
    from repro.core import DetectOptions
    from repro.telemetry.sinks import InMemorySink, Telemetry

    g = rmat(scale, seed)
    log(f"sharded: scale {scale}: {g.n_cap} vertices, {g.m_cap} COO slots")
    one, t_one = _timed_detect(g)
    tel = Telemetry()
    sink = tel.register(InMemorySink())
    many, t_many = _timed_detect(g, DetectOptions(mesh=chips), tel)
    halo = sink.counter_total("sharded_halo_bytes")
    same = bool(np.array_equal(np.asarray(one.labels),
                               np.asarray(many.labels)))
    log(f"sharded: single-device {t_one:.3f} s, {chips}-chip {t_many:.3f} s"
        f" (first calls, compilation included), halo bytes {halo:.0f}, "
        f"labels identical {same}, q {one.modularity!r} vs "
        f"{many.modularity!r}")
    assert same, "sharded labels differ from single-device"
    assert many.modularity == one.modularity
    assert many.n_disconnected == 0, many.n_disconnected
    check_partition(g, many.labels, many.modularity, f"{chips}-chip")
    return dict(scale=scale, t_single=t_one, t_sharded=t_many,
                halo_bytes=halo)


# -- driver ----------------------------------------------------------------------

def run_phases(phases) -> list:
    """Run ``(name, fn)`` pairs; returns the names that failed."""
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAILED after {time.perf_counter() - t0:.1f} s")
        else:
            log(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-vs-single comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(seed=args.seed))]
    else:
        def service():
            rep = phase_service(args.seed)
            assert rep["seg_impl"] == "pallas" and not rep["interpret"], rep

        def large():
            rep = phase_large(seed=args.seed)
            assert rep["seg_impl"] == "pallas", rep

        phases = [("service", service), ("large", large)]
    failed = run_phases(phases)
    log(f"total {time.perf_counter() - t0:.1f} s")
    if failed:
        log(f"failed phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
