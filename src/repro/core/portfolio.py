"""SLO-tiered algorithm portfolio: one dispatch, three quality contracts.

The paper's GSP-Louvain exists because Louvain and Leiden sit at different
quality/latency points — GSP-Louvain matches Leiden's zero-internally-
disconnected guarantee at Louvain-like speed.  This module turns that
spectrum into a first-class serving feature: every detection entry point
(`detect()` / `louvain()` / `lpa()` / the batched service engine) routes
through :func:`partition_impl`, selected by ``DetectOptions.algorithm``:

  'fast'        — pure LPA (core/lpa.py, Raghavan et al. 2007).  Cheapest
                  tier; labels converge but NO structural guarantee
                  (communities may be internally disconnected).
  'standard'    — GSP-Louvain (the paper; split='sp-pj' by default).
                  Zero internally-disconnected communities by
                  construction, modularity-converged.
  'max-quality' — Leiden-style mode (Traag et al. 2019): the same
                  multi-pass driver with refine-from-singletons
                  (``refine_labels``) run in the split slot every pass, so
                  every part is internally connected by construction —
                  AND the plain GSP candidate, selecting whichever
                  partition scores higher modularity.  The selection makes
                  ``q(max-quality) >= q(standard)`` structural rather than
                  empirical (greedy refinement occasionally lands in a
                  different local optimum); both candidates carry the
                  zero-disconnected guarantee, so the contract is the
                  union of both.

Each tier stamps a frozen :class:`QualityContract` on its results — the
guarantee flags tenants buy when they pick a tier — and the contract shape
is identical whether the tier was requested or served as a breaker
degrade (resilience/degrade.py routes through this module too).

Stats dicts are shape-uniform across tiers (passes / li_last / li_total /
split_moved / n_communities, all int32 scalars) so the batched engine can
swap algorithms per compile key without changing its unpacking.

A detection runs three device programs, each under its scope
(``repro.telemetry.spans.SCOPES``): ``partition``, ``detector`` and
``modularity``.  :func:`run_detection` also opens the host annotations
``repro.partition`` / ``repro.detector`` / ``repro.modularity`` around
their dispatch and ``repro.fetch`` around the blocking reads of the
results.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import _segments as seg
from repro.core.detect import disconnected_communities_impl
from repro.core.louvain import LouvainConfig, louvain_impl
from repro.core.lpa import lpa_run
from repro.core.modularity import modularity
from repro.telemetry.spans import annotate, scope

ALGORITHMS = ("fast", "standard", "max-quality")


@dataclasses.dataclass(frozen=True)
class QualityContract:
    """What a tier guarantees about the partition it returns.

    tier:                  the algorithm that produced the result.
    zero_disconnected:     no community has >1 internal component
                           (the paper's headline invariant).
    connected_parts:       every returned part is internally connected by
                           construction of the moves (split/refine slot
                           runs before the convergence break every pass).
    modularity_converged:  the local-move phase ran to its tolerance
                           ladder (LPA converges labels, not modularity).
    """

    tier: str
    zero_disconnected: bool
    connected_parts: bool
    modularity_converged: bool


_CONTRACTS = {
    "fast": QualityContract(
        tier="fast", zero_disconnected=False, connected_parts=False,
        modularity_converged=False),
    "standard": QualityContract(
        tier="standard", zero_disconnected=True, connected_parts=True,
        modularity_converged=True),
    "max-quality": QualityContract(
        tier="max-quality", zero_disconnected=True, connected_parts=True,
        modularity_converged=True),
}


def contract_for(algorithm: str) -> QualityContract:
    """The :class:`QualityContract` a tier promises (by construction —
    results additionally carry the *measured* ``n_disconnected``)."""
    try:
        return _CONTRACTS[algorithm]
    except KeyError:
        raise ValueError(
            f"algorithm must be one of {ALGORITHMS}, got {algorithm!r}"
        ) from None


def tier_config(algorithm: str, cfg: LouvainConfig) -> LouvainConfig:
    """The LouvainConfig a tier actually runs (fast ignores it; standard
    runs it as-is; max-quality's refined candidate swaps the split slot)."""
    contract_for(algorithm)
    if algorithm == "max-quality":
        return dataclasses.replace(cfg, split="refine")
    return cfg


def _standard_config(cfg: LouvainConfig) -> LouvainConfig:
    """max-quality's GSP candidate: the base config, never 'refine' (if the
    caller already asked for refine, the paper default is the comparator)."""
    if cfg.split == "refine":
        return dataclasses.replace(cfg, split="sp-pj")
    return cfg


def partition_impl(g, algorithm: str, cfg: LouvainConfig, *,
                   scan: str = "sort", seg_impl: str = "auto",
                   block_m: int = 0, axis=None, owned=None,
                   lpa_max_iters: int = 50):
    """Run one portfolio tier on one graph (unjitted — vmap/jit-compose
    freely; the batched engine maps this under lax.map(vmap(...))).

    Returns ``(C int32[nv], stats)`` with tier-uniform stats keys:
    passes / li_last / li_total / split_moved / n_communities (int32
    scalars).  For 'fast', li_* report LPA rounds and passes is 1.
    """
    if algorithm == "fast":
        C, iters = lpa_run(g, max_iters=lpa_max_iters, seg_impl=seg_impl,
                           block_m=block_m)
        n = seg.count_communities(C, g.node_mask(), g.nv)
        stats = dict(passes=jnp.int32(1), li_last=iters, li_total=iters,
                     split_moved=jnp.int32(0), n_communities=n)
        return C, stats
    if algorithm == "standard":
        return louvain_impl(g, cfg, axis=axis, owned=owned, scan=scan,
                            seg_impl=seg_impl, block_m=block_m)
    contract_for(algorithm)  # validates; only 'max-quality' remains
    kw = dict(axis=axis, owned=owned, scan=scan, seg_impl=seg_impl,
              block_m=block_m)
    C_r, st_r = louvain_impl(g, tier_config(algorithm, cfg), **kw)
    C_s, st_s = louvain_impl(g, _standard_config(cfg), **kw)
    q_r = modularity(g.src, g.dst, g.w, C_r, g.nv, seg_impl=seg_impl,
                     block_m=block_m)
    q_s = modularity(g.src, g.dst, g.w, C_s, g.nv, seg_impl=seg_impl,
                     block_m=block_m)
    take_r = q_r >= q_s
    C = jnp.where(take_r, C_r, C_s)
    stats = {k: jnp.where(take_r, st_r[k], st_s[k]) for k in st_r}
    return C, stats


@partial(jax.jit, static_argnames=("algorithm", "cfg", "axis", "scan",
                                   "seg_impl", "block_m"))
def _partition_jit(g, algorithm, cfg, *, axis=None, owned=None,
                   scan="sort", seg_impl="auto", block_m=0):
    with scope("partition"):
        return partition_impl(g, algorithm, cfg, axis=axis, owned=owned,
                              scan=scan, seg_impl=seg_impl, block_m=block_m)


@partial(jax.jit, static_argnames=("seg_impl", "block_m"))
def _detector_jit(g, C, *, seg_impl, block_m):
    with scope("detector"):
        return disconnected_communities_impl(
            g.src, g.dst, g.w, C, g.n_nodes, seg_impl=seg_impl,
            block_m=block_m)


@partial(jax.jit, static_argnames=("seg_impl", "block_m"))
def _modularity_jit(g, C, *, seg_impl, block_m):
    with scope("modularity"):
        return modularity(g.src, g.dst, g.w, C, seg_impl=seg_impl,
                          block_m=block_m)


def partition(g, options, *, axis=None, owned=None, telemetry=None):
    """Public single-graph tier dispatch: ``(C, stats)`` under jit.

    ``options`` is a :class:`repro.core.api.DetectOptions`; mesh routing
    (sharded single-graph, standard/max-quality only) happens here so
    ``louvain()``/``detect()`` share one switch.
    """
    mesh = options.resolved_mesh()
    if mesh is not None:
        if options.algorithm == "fast":
            raise ValueError(
                "algorithm='fast' (LPA) is single-device only — drop mesh=")
        if options.scan == "dense":
            raise ValueError("scan='dense' is single-device only")
        from repro.core.distributed import louvain_sharded
        return louvain_sharded(
            g, tier_config(options.algorithm, options.louvain), mesh=mesh,
            seg_impl=options.seg_impl, block_m=options.block_m,
            telemetry=telemetry)
    return _partition_jit(g, axis=axis, owned=owned,
                          **_partition_static(options))


def _partition_static(options) -> dict:
    """The static arguments of ``_partition_jit`` for ``options``."""
    return dict(algorithm=options.algorithm, cfg=options.louvain,
                scan="sort" if options.scan == "auto" else options.scan,
                seg_impl=options.seg_impl, block_m=options.block_m)


def detection_programs(graph, options):
    """The three single-device programs of a detection of ``graph``, with
    ``options`` resolved for it: ``partition(g)``, ``detector(g, C)`` and
    ``modularity(g, C)``.  Each is a ``functools.partial`` of a jitted
    function, so ``p.func.lower(*args, **p.keywords)`` compiles it alone.
    """
    run = options.replace(scan=options.resolved_scan(graph.nv, graph.m_cap))
    kw = dict(seg_impl=run.resolved_seg_impl(), block_m=run.block_m)
    return (partial(_partition_jit, **_partition_static(run)),
            partial(_detector_jit, **kw), partial(_modularity_jit, **kw))


def run_detection(graph, options, *, telemetry=None):
    """Full single-graph detection for one tier: partition + detector +
    modularity + contract — the body of :func:`repro.core.api.detect`.

    Returns a :class:`repro.core.api.Detection` with the tier's
    :class:`QualityContract` stamped on it.  ``n_disconnected`` is always
    *measured* (the detector runs even for tiers that guarantee zero, so
    the contract is checked, not assumed — and reported for 'fast').
    """
    from repro.core.api import Detection

    part, detector, modularity_of = detection_programs(graph, options)
    with annotate("partition"):
        if options.resolved_mesh() is None:
            C, stats = part(graph)
        else:
            C, stats = partition(graph, options, telemetry=telemetry)
    with annotate("detector"):
        det = detector(graph, C)
    with annotate("modularity"):
        q = modularity_of(graph, C)
    with annotate("fetch"):
        return Detection(
            labels=C,
            n_communities=int(stats["n_communities"]),
            n_disconnected=int(det["n_disconnected"]),
            modularity=float(q),
            stats=dict(stats),
            contract=contract_for(options.algorithm),
        )
