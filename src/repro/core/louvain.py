"""GSP-Louvain multi-pass driver (paper Algorithm 3).

One fully-jitted ``lax.while_loop`` over passes; each pass is
local-moving -> splitting (SP variants) -> convergence checks -> renumber ->
dendrogram lookup -> aggregation -> threshold scaling, exactly the paper's
ordering (split happens *before* the ``l_i <= 1`` global-convergence break,
so the returned partition is always split-clean for every ``sp-*`` mode).

Split policies (``LouvainConfig.split``):
  'none'   — plain parallel Louvain (GVE-Louvain baseline).
  'sp-lp' / 'sp-lpp' / 'sp-pj' — Split Pass with LP / LPP / pointer-jumping
             (the paper's SP approach; 'sp-pj' ~ the paper's SP-BFS slot =
             **GSP-Louvain**, our default).
  'sl-lp' / 'sl-lpp' / 'sl-pj' — Split Last (post-processing, prior work).
  'refine' — Leiden-style refinement in the same slot (Traag et al. 2019):
             a constrained local-move from singletons over the community-
             masked graph; the greedy theta->0 variant (our Figure-4
             comparison baseline, "GVE-Leiden"-like).

Each phase runs under its device scope (``repro.telemetry.spans.SCOPES``):
``local_move``, ``split`` (``refine`` in that slot), ``renumber`` and
``aggregate``, so a profiler trace splits device time by phase (the paper's
Figure 5 phase split).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import _segments as seg
from repro.core.aggregate import aggregate
from repro.core.local_move import local_move
from repro.core.split import split_labels
from repro.graph.container import Graph
from repro.kernels import ops
from repro.telemetry.spans import scope


@dataclasses.dataclass(frozen=True)
class LouvainConfig:
    max_passes: int = 10
    max_iters: int = 20
    tolerance: float = 1e-2
    tolerance_drop: float = 10.0
    aggregation_tolerance: float = 0.8
    split: str = "sp-pj"          # none | {sp,sl}-{lp,lpp,pj} | refine
    sync: str = "handshake"       # handshake | parity | all
    prune: bool = True
    split_max_iters: int = 0      # 0 = graph-size bound


class PassState(NamedTuple):
    esrc: jax.Array
    edst: jax.Array
    ew: jax.Array
    Ctop: jax.Array       # int32[nv] original vertex -> current community
    n_cur: jax.Array      # int32[] vertices in current graph
    tau: jax.Array
    lp: jax.Array         # passes completed
    li_last: jax.Array
    li_total: jax.Array   # local-move sweeps summed over passes
    split_moved: jax.Array  # vertices relabelled by split/refine, all passes
    done: jax.Array


def _split_mode(split: str) -> str:
    return split.split("-")[1] if "-" in split else "pj"


def refine_labels(src, dst, w, C, two_m, *, tau, max_iters=10, axis=None,
                  owned=None, scan="sort", skip=None, seg_impl="auto",
                  block_m=0, gidx=None, m_total=None):
    """Leiden refinement: local-move from singletons restricted to each
    community's bound — implemented as local_move over the community-masked
    edge set (cross-community weights zeroed), scored against the full-graph
    2m.  Returns a refinement of C whose parts are connected (moves require
    a positive in-community edge)."""
    nv = C.shape[0]
    w_in = jnp.where(C[src] == C[dst], w, 0.0)
    if seg_impl == "scatter":
        K_in = jax.ops.segment_sum(w_in, src, num_segments=nv)
    else:
        K_in = ops.segreduce_sorted(w_in, src, nv, op="sum", impl=seg_impl,
                                    block_m=block_m)
    if axis is not None:
        from repro.distributed import collectives as col
        K_in = col.psum(K_in, axis)
    C0 = jnp.arange(nv, dtype=jnp.int32)
    R, _, _ = local_move(
        src, dst, w_in, C0, K_in, K_in, two_m,
        tau=tau, max_iters=max_iters, axis=axis, owned=owned, scan=scan,
        skip=skip, seg_impl=seg_impl, block_m=block_m,
        gidx=gidx, m_total=m_total,
    )
    return R


def louvain_impl(g: Graph, cfg: LouvainConfig = LouvainConfig(), *, axis=None,
                 owned=None, scan: str = "sort", seg_impl: str = "auto",
                 block_m: int = 0):
    """Run GSP-Louvain (unjitted — vmap/jit-compose freely).

    Returns (C int32[nv] dense top-level membership, stats dict).
    Ghost/padding vertices map to the trailing community ids; mask with
    ``g.node_mask()`` downstream.

    ``scan`` selects the phase implementations: 'sort' is the general
    sortscan formulation; 'dense' routes local-move/split/aggregate through
    the small-``nv`` dense community-matrix kernels (bit-identical results,
    single-device only — the batched service engine's path).

    ``seg_impl`` selects the sortscan's segment-reduction backend for
    every phase ('auto' | 'xla' | 'pallas' | 'scatter' — kernels/ops.py;
    'auto' is backend-keyed: XLA sorted path on CPU, Pallas on TPU;
    'scatter' is the pre-backend formulation kept for paired benchmarks).
    ``block_m`` is the Pallas kernel block size (0 = default; the service
    engine passes the per-bucket autotuned value).  Partitions are
    bit-identical across every (scan, seg_impl) combination.
    """
    nv = g.nv
    two_m = g.total_weight_2m()
    do_sp = cfg.split.startswith("sp")
    mode = _split_mode(cfg.split)
    split_impl = "dense" if scan == "dense" else "coo"
    agg_impl = "dense" if scan == "dense" else "sort"
    seg_impl = ops.resolve_impl(seg_impl)

    def body(st: PassState) -> PassState:
        node_valid = jnp.arange(nv) < st.n_cur
        # aggregation emits run-sorted super-edges, so esrc keeps the
        # container's sorted invariant across passes
        if seg_impl == "scatter":
            K = jax.ops.segment_sum(st.ew, st.esrc, num_segments=nv)
        else:
            K = ops.segreduce_sorted(st.ew, st.esrc, nv, op="sum",
                                     impl=seg_impl, block_m=block_m)
        C0 = jnp.arange(nv, dtype=jnp.int32)
        # one adjacency scatter per pass, shared by local-move pruning and
        # the split fixpoint (dense scan only)
        adj = (jnp.zeros((nv, nv), bool).at[st.esrc, st.edst].set(True)
               if scan == "dense" else None)
        with scope("local_move"):
            C, _, li = local_move(
                st.esrc, st.edst, st.ew, C0, K, K, two_m,
                tau=st.tau, max_iters=cfg.max_iters, sync=cfg.sync,
                prune=cfg.prune, axis=axis, owned=owned, scan=scan,
                skip=st.done, adj=adj, seg_impl=seg_impl, block_m=block_m,
            )
        if cfg.split == "refine":
            with scope("refine"):
                labels = refine_labels(
                    st.esrc, st.edst, st.ew, C, two_m,
                    tau=st.tau, max_iters=cfg.max_iters, axis=axis,
                    owned=owned, scan=scan, skip=st.done, seg_impl=seg_impl,
                    block_m=block_m,
                )
        elif do_sp:
            with scope("split"):
                labels, _ = split_labels(
                    st.esrc, st.edst, st.ew, C,
                    mode=mode, max_iters=cfg.split_max_iters, axis=axis,
                    impl=split_impl, skip=st.done, adj=adj,
                    seg_impl=seg_impl, block_m=block_m,
                )
        else:
            labels = C
        # split-pass trigger count: vertices the split/refine slot moved
        # out of their local-move community this pass (telemetry)
        moved = jnp.sum((labels != C) & node_valid).astype(jnp.int32)
        with scope("renumber"):
            C_dense, n_comms = seg.renumber(labels, node_valid, nv)
        Ctop = C_dense[st.Ctop]

        converged = li <= 1
        low_shrink = n_comms.astype(jnp.float32) > (
            cfg.aggregation_tolerance * st.n_cur.astype(jnp.float32)
        )
        done = converged | low_shrink

        with scope("aggregate"):
            nsrc, ndst, nw = aggregate(st.esrc, st.edst, st.ew, C_dense,
                                       impl=agg_impl, seg_impl=seg_impl,
                                       block_m=block_m)
        # freeze the graph if we're done (avoids dead aggregation writes)
        esrc = jnp.where(done, st.esrc, nsrc)
        edst = jnp.where(done, st.edst, ndst)
        ew = jnp.where(done, st.ew, nw)
        return PassState(
            esrc=esrc, edst=edst, ew=ew, Ctop=Ctop,
            n_cur=jnp.where(done, st.n_cur, n_comms),
            tau=st.tau / cfg.tolerance_drop,
            lp=st.lp + 1, li_last=li, li_total=st.li_total + li,
            split_moved=st.split_moved + moved, done=done,
        )

    def cond(st: PassState):
        return (~st.done) & (st.lp < cfg.max_passes)

    init = PassState(
        esrc=g.src, edst=g.dst, ew=g.w,
        Ctop=jnp.arange(nv, dtype=jnp.int32),
        n_cur=g.n_nodes.astype(jnp.int32),
        tau=jnp.float32(cfg.tolerance),
        lp=jnp.int32(0), li_last=jnp.int32(0), li_total=jnp.int32(0),
        split_moved=jnp.int32(0),
        done=jnp.bool_(False),
    )
    out = jax.lax.while_loop(cond, body, init)

    Ctop = out.Ctop
    split_moved = out.split_moved
    if cfg.split.startswith("sl"):
        with scope("split"):
            labels, _ = split_labels(
                g.src, g.dst, g.w, Ctop, mode=mode,
                max_iters=cfg.split_max_iters, axis=axis, impl=split_impl,
                seg_impl=seg_impl, block_m=block_m,
            )
        split_moved = split_moved + jnp.sum(
            (labels != Ctop) & g.node_mask()).astype(jnp.int32)
        with scope("renumber"):
            Ctop, _ = seg.renumber(labels, g.node_mask(), nv)
    n_final = seg.count_communities(Ctop, g.node_mask(), nv)
    stats = dict(passes=out.lp, li_last=out.li_last,
                 li_total=out.li_total, split_moved=split_moved,
                 n_communities=n_final)
    return Ctop, stats


_louvain_jit = partial(
    jax.jit, static_argnames=("cfg", "axis", "scan", "seg_impl", "block_m")
)(louvain_impl)


def louvain(g: Graph, cfg: LouvainConfig | None = None, *, options=None,
            mesh=None, telemetry=None, axis=None, owned=None, scan=None,
            seg_impl=None, block_m=None, _no_warn: bool = False):
    """Jitted GSP-Louvain — the public driver.

    Preferred call shapes:
      ``louvain(g, cfg)``                      — single device, defaults;
      ``louvain(g, options=DetectOptions(...))`` — full knob record;
      ``louvain(g, cfg, mesh=mesh_or_int)``    — sharded single-graph path
        (core/distributed.py): bit-identical partition to single-device.

    Flat keywords ``scan=``/``seg_impl=``/``block_m=`` keep working via
    the deprecation shim (warns once; see core/api.py).  ``axis``/
    ``owned`` are the expert shard_map pass-throughs and stay silent.
    """
    from repro.core.api import fold_legacy_kwargs
    if options is not None:
        if cfg is not None:
            raise TypeError(
                "louvain(): pass the config inside options= "
                "(DetectOptions(louvain=cfg)), not both")
        opts = options
    else:
        opts = fold_legacy_kwargs(
            None, dict(scan=scan, seg_impl=seg_impl, block_m=block_m),
            where="louvain()", warn=not _no_warn)
        if cfg is not None:
            opts = opts.replace(louvain=cfg)
    if mesh is not None:
        opts = opts.replace(mesh=mesh)
    if opts.resolved_mesh() is not None and (
            axis is not None or owned is not None):
        raise ValueError(
            "louvain(mesh=...) is incompatible with axis=/owned=")
    if opts.algorithm != "standard":
        # non-default portfolio tiers ('fast' LPA / 'max-quality' refine)
        # route through the shared dispatch — one switch for every caller
        from repro.core.portfolio import partition
        return partition(g, opts, axis=axis, owned=owned,
                         telemetry=telemetry)
    mesh = opts.resolved_mesh()
    if mesh is not None:
        if opts.scan == "dense":
            raise ValueError("scan='dense' is single-device only")
        from repro.core.distributed import louvain_sharded
        return louvain_sharded(g, opts.louvain, mesh=mesh,
                               seg_impl=opts.seg_impl, block_m=opts.block_m,
                               telemetry=telemetry)
    # 'auto' keeps the historical direct-call default: the sortscan layout
    # (the dense crossover is the service engine's bucketed decision —
    # resolve via DetectOptions.resolved_scan there)
    scan = "sort" if opts.scan == "auto" else opts.scan
    return _louvain_jit(g, opts.louvain, axis=axis, owned=owned, scan=scan,
                        seg_impl=opts.seg_impl, block_m=opts.block_m)
