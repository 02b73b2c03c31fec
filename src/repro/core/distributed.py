"""Distributed GSP-Louvain: vertex-aligned edge shards over a device mesh.

The production layout (DESIGN.md §4):
  * edges are partitioned by **source vertex** (graph/partition.py) into
    ``n_devices`` shards of static size ``m_shard`` — every per-vertex
    reduction (community scan, label-min, Sigma) is exact shard-locally;
  * vertex state (C, K, Sigma, labels) is replicated; each half-sweep
    merges owned updates with one int32 ``psum`` over [nv], each split
    round with one ``pmin`` — these are the collectives the roofline
    counts (grep collectives.py call sites).

Two drivers live here:

* :func:`louvain_sharded` — the production path: a host-driven multi-pass
  driver whose every pass runs local-move + split + renumber under
  ``shard_map``, **bit-identical** to single-device
  :func:`repro.core.louvain.louvain` (tests/test_sharded.py pins equality
  float-for-float).  The exactness argument, term by term:

  - the edge partition is vertex-aligned AND order-preserving: each
    shard's slice is contiguous in the container's ``(src, dst)``-sorted
    edge array, so every per-vertex segment reduction folds the exact
    same values in the exact same order as its single-device twin;
  - float state merges only ever ``psum`` *disjoint-support* vectors —
    per-vertex (K, refine's K_in) or per-global-edge-slot (the per-sweep
    modularity's masked weights, placed at their ``gidx`` slots so the
    replicated vector IS the single-device one): each slot is
    owner's-value + zeros, and ``x + 0.0 == x`` in IEEE f32 for the
    non-negative values here — exact, any shard count;
  - Sigma is NOT merged at all: (K, C_new) are replicated after the
    label merge, so every shard recomputes the full Sigma with the same
    in-order scatter the single-device sweep uses (local_move.py);
  - label/flag merges are integer ``psum`` of disjoint one-hot rows and
    boolean ``pmax``/``pmin`` — exactly associative by construction;
  - scalar convergence logic (tau ladder, shrink test) runs once on the
    host in the same f32 ops ``louvain_impl`` traces, and aggregation
    runs single-device on the gathered (replicated, identical) labels —
    bit-identical super-graphs feed every pass on every shard.

* :func:`run_louvain_multidevice` (+ :func:`community_pass` /
  :func:`build_community_step`) — the earlier approximate scale path:
  pass 1 sharded with *shard-local* aggregation (cross-shard duplicate
  super-edges kept as parallel edges — all-to-all-free but fold-order
  different from single-device), remaining passes replicated.  Kept as
  the roofline/scaling harness; use ``louvain_sharded`` when parity with
  the single-device partition matters.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import _segments as seg
from repro.core.aggregate import aggregate
from repro.core.local_move import local_move
from repro.core.split import split_labels
from repro.kernels import ops

SDS = jax.ShapeDtypeStruct


def community_pass(src, dst, w, v_lo, v_hi, two_m, n_nodes, *,
                   nv: int, axis, move_iters: int, split_iters: int,
                   tau: float = 1e-2, split_mode: str = "pj",
                   prune: bool = True):
    """One GSP-Louvain pass on this shard's edges (runs under shard_map).

    Returns (C_dense replicated, n_comms, new shard-local edges).
    """
    ids = jnp.arange(nv, dtype=jnp.int32)
    owned = (ids >= v_lo) & (ids < v_hi)
    node_valid = ids < n_nodes

    from repro.distributed import collectives as col

    K = col.psum(jax.ops.segment_sum(w, src, num_segments=nv), axis)
    C0 = ids
    C, _, li = local_move(
        src, dst, w, C0, K, K, two_m,
        tau=tau, max_iters=move_iters, axis=axis, owned=owned,
        prune=prune,
    )
    labels, _ = split_labels(
        src, dst, w, C, mode=split_mode, max_iters=split_iters, axis=axis,
    )
    C_dense, n_comms = seg.renumber(labels, node_valid, nv)
    nsrc, ndst, nw = aggregate(src, dst, w, C_dense)
    return C_dense, n_comms, li, nsrc, ndst, nw


def build_community_step(mesh, *, n_cap: int, m_shard: int,
                         move_iters: int = 4, split_iters: int = 8,
                         split_mode: str = "pj", prune: bool = True):
    """Build the jit-able distributed pass for a mesh.

    Args are stacked shard arrays: src/dst [S, m_shard] int32, w [S, m_shard]
    f32, v_lo/v_hi [S] int32 (owned vertex ranges), plus replicated scalars
    two_m, n_nodes.  S = total device count of the mesh.
    """
    axes = tuple(mesh.axis_names)
    S = int(np.prod([mesh.shape[a] for a in axes]))
    nv = n_cap + 1

    def shard_fn(src, dst, w, v_lo, v_hi, two_m, n_nodes):
        out = community_pass(
            src[0], dst[0], w[0], v_lo[0], v_hi[0], two_m, n_nodes,
            nv=nv, axis=axes, move_iters=move_iters,
            split_iters=split_iters, split_mode=split_mode, prune=prune,
        )
        C_dense, n_comms, li, nsrc, ndst, nw = out
        return C_dense, n_comms, li, nsrc[None], ndst[None], nw[None]

    edge_spec = P(axes, None)
    scal_spec = P(axes)
    step = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(edge_spec, edge_spec, edge_spec, scal_spec, scal_spec,
                  P(), P()),
        out_specs=(P(), P(), P(), edge_spec, edge_spec, edge_spec),
        check_vma=False,
    )

    args = (
        SDS((S, m_shard), jnp.int32),
        SDS((S, m_shard), jnp.int32),
        SDS((S, m_shard), jnp.float32),
        SDS((S,), jnp.int32),
        SDS((S,), jnp.int32),
        SDS((), jnp.float32),
        SDS((), jnp.int32),
    )
    e_sh = NamedSharding(mesh, edge_spec)
    s_sh = NamedSharding(mesh, scal_spec)
    r_sh = NamedSharding(mesh, P())
    in_shardings = (e_sh, e_sh, e_sh, s_sh, s_sh, r_sh, r_sh)
    out_shardings = (r_sh, r_sh, r_sh, e_sh, e_sh, e_sh)
    return dict(fn=step, args=args, in_shardings=in_shardings,
                out_shardings=out_shardings, nv=nv, n_shards=S)


def run_louvain_multidevice(g, mesh, cfg=None):
    """Full multi-pass GSP-Louvain on a real mesh (host-scale validation).

    Pass 1 runs sharded via :func:`build_community_step`; the aggregated
    graph (whose per-shard deduped edges fit one shard comfortably after
    the first pass) is gathered and the remaining passes run replicated
    through the single-device driver — the capacity switch described in
    DESIGN.md §4.
    """
    from repro.core.louvain import LouvainConfig, louvain
    from repro.graph.container import Graph
    from repro.graph.partition import partition_edges_by_src

    cfg = cfg or LouvainConfig()
    axes = tuple(mesh.axis_names)
    S = int(np.prod([mesh.shape[a] for a in axes]))
    parts = partition_edges_by_src(g, S)
    m_shard = parts["src"].shape[1]
    plan = build_community_step(
        mesh, n_cap=g.n_cap, m_shard=m_shard,
        move_iters=cfg.max_iters, split_iters=0,
        split_mode=cfg.split.split("-")[1] if "-" in cfg.split else "pj",
    )
    fn = jax.jit(plan["fn"], in_shardings=plan["in_shardings"],
                 out_shardings=plan["out_shardings"])
    two_m = jnp.float32(g.total_weight_2m())
    C1, n1, li, nsrc, ndst, nw = fn(
        jnp.asarray(parts["src"]), jnp.asarray(parts["dst"]),
        jnp.asarray(parts["w"]), jnp.asarray(parts["v_lo"]),
        jnp.asarray(parts["v_hi"]), two_m, g.n_nodes.astype(jnp.int32),
    )
    # gather the super graph (cross-shard duplicates are fine: they act as
    # parallel edges == summed weights for all downstream ops)
    flat_src = nsrc.reshape(-1)
    flat_dst = ndst.reshape(-1)
    flat_w = nw.reshape(-1)
    order = jnp.argsort(flat_src, stable=True)
    g2 = Graph(
        src=flat_src[order], dst=flat_dst[order], w=flat_w[order],
        n_nodes=n1.astype(jnp.int32), n_cap=g.n_cap, m_cap=flat_src.shape[0],
    )
    C2, stats = louvain(g2, cfg)
    Cfinal = C2[C1]
    stats = dict(stats, first_pass_li=li, first_pass_comms=n1)
    return Cfinal, stats


# --------------------------------------------------------------------------
# Bit-exact sharded driver (the production path — see module docstring)
# --------------------------------------------------------------------------

_PASS_CACHE: dict = {}


def build_sharded_pass(mesh, *, nv: int, m_shard: int, m_total: int, cfg,
                       seg_impl: str = "xla", block_m: int = 0):
    """One jitted GSP-Louvain pass under shard_map, mirroring the body of
    :func:`repro.core.louvain.louvain_impl` statement for statement.

    Traced scalars (two_m, n_cur, tau) are arguments, so one compile per
    (mesh, nv, m_shard, cfg, backend) serves every pass of every graph at
    those capacities.  Returns replicated ``(C_dense, n_comms, li, moved)``.
    """
    key = (mesh, nv, m_shard, m_total, cfg, seg_impl, block_m)
    hit = _PASS_CACHE.get(key)
    if hit is not None:
        return hit

    axes = tuple(mesh.axis_names)
    do_sp = cfg.split.startswith("sp")
    mode = cfg.split.split("-")[1] if "-" in cfg.split else "pj"

    from repro.core.louvain import refine_labels
    from repro.distributed import collectives as col

    def shard_fn(src, dst, w, gidx, v_lo, v_hi, two_m, n_cur, tau):
        src, dst, w, gidx = src[0], dst[0], w[0], gidx[0]
        v_lo, v_hi = v_lo[0], v_hi[0]
        ids = jnp.arange(nv, dtype=jnp.int32)
        owned = (ids >= v_lo) & (ids < v_hi)
        node_valid = ids < n_cur
        # K: shard-local in-order fold over owned vertices, then a
        # disjoint-support psum — bit-identical to the single-device fold
        if seg_impl == "scatter":
            K = jax.ops.segment_sum(w, src, num_segments=nv)
        else:
            K = ops.segreduce_sorted(w, src, nv, op="sum",
                                     impl=seg_impl, block_m=block_m)
        K = col.psum(K, axes)
        C0 = ids
        C, _, li = local_move(
            src, dst, w, C0, K, K, two_m,
            tau=tau, max_iters=cfg.max_iters, sync=cfg.sync,
            prune=cfg.prune, axis=axes, owned=owned, scan="sort",
            seg_impl=seg_impl, block_m=block_m,
            gidx=gidx, m_total=m_total,
        )
        if cfg.split == "refine":
            labels = refine_labels(
                src, dst, w, C, two_m,
                tau=tau, max_iters=cfg.max_iters, axis=axes, owned=owned,
                scan="sort", seg_impl=seg_impl, block_m=block_m,
                gidx=gidx, m_total=m_total,
            )
        elif do_sp:
            labels, _ = split_labels(
                src, dst, w, C,
                mode=mode, max_iters=cfg.split_max_iters, axis=axes,
                impl="coo", seg_impl=seg_impl, block_m=block_m,
            )
        else:
            labels = C
        moved = jnp.sum((labels != C) & node_valid).astype(jnp.int32)
        C_dense, n_comms = seg.renumber(labels, node_valid, nv)
        return C_dense, n_comms, li, moved

    edge_spec = P(axes, None)
    scal_spec = P(axes)
    step = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(edge_spec, edge_spec, edge_spec, edge_spec, scal_spec,
                  scal_spec, P(), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    e_sh = NamedSharding(mesh, edge_spec)
    s_sh = NamedSharding(mesh, scal_spec)
    r_sh = NamedSharding(mesh, P())
    fn = jax.jit(
        step,
        in_shardings=(e_sh, e_sh, e_sh, e_sh, s_sh, s_sh, r_sh, r_sh, r_sh),
        out_shardings=(r_sh, r_sh, r_sh, r_sh),
    )
    _PASS_CACHE[key] = fn
    return fn


def _pad_shards(a, cap, fill):
    S, m = a.shape
    if m == cap:
        return a
    out = np.full((S, cap), fill, a.dtype)
    out[:, :m] = a
    return out


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def louvain_sharded(g, cfg=None, *, mesh, seg_impl: str = "auto",
                    block_m: int = 0, telemetry=None):
    """Multi-pass GSP-Louvain sharded over ``mesh``, bit-identical to the
    single-device :func:`repro.core.louvain.louvain` partition.

    Host-driven: each pass re-partitions the live super-graph by owner
    vertex, runs one shard_map'd pass (local-move halo merges + split +
    renumber), then mirrors ``louvain_impl``'s convergence scalars in the
    same f32 arithmetic and aggregates single-device on the gathered
    (replicated) labels.  The 'sl-*' epilogue and the final community
    count also run single-device, exactly as the jitted driver traces
    them with ``axis=None``.

    ``telemetry``: optional :class:`repro.telemetry.Telemetry` hub; emits
    per-shard ghost/cut-edge gauges, halo-exchange byte counters (the
    replicated-state merges each sweep), per-device sweep counters, and
    per-pass latency spans (``sharded-partition`` / ``sharded-pass``).

    ``mesh`` may be a concrete ``jax.sharding.Mesh`` or an int (first-N
    host devices on a 1-D axis — the test/driver convenience).

    Returns ``(C, stats)`` with the single-device stats keys plus
    ``n_shards`` / ``m_shard`` / ``ghost_vertices``.
    """
    from repro.core.api import DetectOptions
    from repro.core.louvain import LouvainConfig
    from repro.graph.container import Graph
    from repro.graph.partition import partition_edges_by_src, shard_vertex_roles
    from repro.telemetry.spans import Span

    cfg = cfg or LouvainConfig()
    mesh = DetectOptions(mesh=mesh).resolved_mesh()
    S = int(np.prod(list(mesh.shape.values())))
    nv = g.nv
    seg_impl = ops.resolve_impl(seg_impl)
    two_m = jnp.float32(np.asarray(g.total_weight_2m()))

    esrc = np.asarray(g.src)
    edst = np.asarray(g.dst)
    ew = np.asarray(g.w)
    Ctop = np.arange(nv, dtype=np.int32)
    n_cur = np.int32(np.asarray(g.n_nodes))
    tau = np.float32(cfg.tolerance)
    drop = np.float32(cfg.tolerance_drop)
    agg_tol = np.float32(cfg.aggregation_tolerance)

    passes = li_last = li_total = split_moved = 0
    ghost_total = 0
    m_shard = 0
    emit = telemetry is not None and getattr(telemetry, "enabled", False)

    for lp in range(cfg.max_passes):
        t0 = time.perf_counter()
        cur = Graph(src=esrc, dst=edst, w=ew, n_nodes=n_cur,
                    n_cap=g.n_cap, m_cap=g.m_cap)
        parts = partition_edges_by_src(cur, S)
        # pad shard capacity to a power of two: one pass-fn compile serves
        # graphs/passes of similar size instead of one per exact m_shard
        m_shard = _next_pow2(parts["src"].shape[1])
        t1 = time.perf_counter()
        if emit:
            ghosts = [shard_vertex_roles(parts, s) for s in range(S)]
            ghost_total = sum(r["n_ghosts"] for r in ghosts)
            for s, r in enumerate(ghosts):
                lbl = {"shard": str(s)}
                telemetry.gauge("sharded_ghost_vertices", r["n_ghosts"], lbl)
                telemetry.gauge("sharded_cut_edges", r["n_cut_edges"], lbl)
            telemetry.span(Span("sharded-partition", t0, t1,
                                labels={"pass": str(lp)}))

        m_total = int(parts["m_cap"])
        fn = build_sharded_pass(mesh, nv=nv, m_shard=m_shard,
                                m_total=m_total, cfg=cfg,
                                seg_impl=seg_impl, block_m=block_m)
        C_dense, n_comms, li, moved = jax.block_until_ready(fn(
            _pad_shards(parts["src"], m_shard, np.int32(g.n_cap)),
            _pad_shards(parts["dst"], m_shard, np.int32(g.n_cap)),
            _pad_shards(parts["w"], m_shard, np.float32(0.0)),
            _pad_shards(parts["gidx"], m_shard, np.int32(m_total)),
            parts["v_lo"], parts["v_hi"],
            two_m, jnp.int32(n_cur), jnp.float32(tau),
        ))
        t2 = time.perf_counter()
        C_dense = np.asarray(C_dense)
        n_comms = np.int32(n_comms)
        li = int(li)
        moved = int(moved)

        Ctop = C_dense[Ctop]
        passes = lp + 1
        li_last = li
        li_total += li
        split_moved += moved
        if emit:
            # replicated-state halo traffic per local-move sweep: the C_new
            # int32 psum + want pmax (both [nv]) and the modularity
            # edge-slot psum ([m_total + 1] f32) + split-round pmin[nv]
            # per fixpoint round (bounded by sweeps); counted once per
            # participating device
            per_sweep = (2 * nv + m_total + 1) * 4
            telemetry.counter("sharded_halo_bytes",
                              S * li * 2 * per_sweep + S * nv * 4)
            telemetry.span(Span("sharded-pass", t1, t2,
                                labels={"pass": str(lp)}))
            for s in range(S):
                telemetry.counter("sharded_device_sweeps", li,
                                  {"shard": str(s)})

        converged = li <= 1
        low_shrink = bool(
            np.float32(n_comms) > agg_tol * np.float32(n_cur))
        if converged or low_shrink:
            break
        nsrc, ndst, nw = aggregate(
            jnp.asarray(esrc), jnp.asarray(edst), jnp.asarray(ew),
            jnp.asarray(C_dense), impl="sort", seg_impl=seg_impl,
            block_m=block_m)
        esrc, edst, ew = (np.asarray(nsrc), np.asarray(ndst),
                          np.asarray(nw))
        n_cur = n_comms
        tau = np.float32(tau / drop)

    Ctop = jnp.asarray(Ctop)
    if cfg.split.startswith("sl"):
        mode = cfg.split.split("-")[1]
        labels, _ = split_labels(
            g.src, g.dst, g.w, Ctop, mode=mode,
            max_iters=cfg.split_max_iters, impl="coo", seg_impl=seg_impl,
            block_m=block_m,
        )
        split_moved += int(jnp.sum((labels != Ctop) & g.node_mask()))
        Ctop, _ = seg.renumber(labels, g.node_mask(), nv)
    n_final = seg.count_communities(Ctop, g.node_mask(), nv)
    stats = dict(
        passes=passes, li_last=li_last, li_total=li_total,
        split_moved=split_moved, n_communities=n_final,
        n_shards=S, m_shard=m_shard, ghost_vertices=ghost_total,
    )
    return Ctop, stats
