"""Batched GSP-Louvain engine: one jitted vmap call per request batch.

The engine owns the compile cache.  For a bucket ``(n_cap, m_cap)``, a
sub-batch width ``b`` and the engine's :class:`LouvainConfig`, it compiles

    jit(vmap(louvain_impl + disconnected_communities_impl + modularity))

once and replays it for every batch the bucket ever serves.  Results are
**exactly** the partitions `louvain()` returns per graph (same config): the
batched path reuses the very same pass driver under ``vmap``, and the dense
scan it selects for small buckets is bit-equivalent to the sortscan (see
core/local_move.py).

The engine also owns the **batched warm-update path**
(:meth:`BatchedLouvainEngine.update_batch`): same-bucket delta-screened
updates — graphs already rewritten host-side by
:func:`repro.core.dynamic.prepare_graph_update` (vertex removals
compacted, additions claimed, signed edge deltas applied) — run as one
jitted ``lax.map(vmap(warm_update_impl))`` call, the exact compute the
store's immediate path runs per graph, so batched and sequential
partitions agree exactly.  Vertex churn never perturbs the compile
cache: ``nv`` is bucket-static and ``n_nodes`` is a traced array leaf,
so a batch mixing grown and shrunk graphs replays one executable.

Sub-batching: inside the one jitted call, the batch is laid out as
``[n_tiles, sub_batch, ...]`` and processed by ``lax.map`` over vmapped
tiles.  Two reasons: (1) a vmapped ``while_loop`` runs every element for
the max trip count in the call, so narrower tiles waste less on
iteration-count variance; (2) on CPU backends the dense [b, nv, nv] sweep
state should stay cache-resident — measured on the dev container, b=1
beats b=32 by ~1.4x end-to-end (no per-op lane parallelism exists to buy
back the sync cost).  On accelerator backends lane parallelism wants wide
tiles instead, so the auto policy keys on the jax backend.  Either way the
whole batch remains ONE jitted call: tiles run under ``lax.map`` inside it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    LouvainConfig, QualityContract, contract_for,
    disconnected_communities_impl, modularity,
)
from repro.core.api import DetectOptions, fold_legacy_kwargs
from repro.core.portfolio import partition_impl, tier_config
from repro.core.dynamic import warm_update_impl
from repro.graph.container import Graph, stack_graphs
from repro.kernels import ops
from repro.kernels.autotune import autotune_block_m
from repro.service.buckets import Bucket, bucket_of, filler
from repro.telemetry.sinks import Telemetry
from repro.telemetry.spans import annotate, scope


@dataclasses.dataclass
class DetectResult:
    """Per-graph detection output (host-side)."""

    C: np.ndarray                # int32[nv] dense membership (ghost masked)
    n_communities: int
    n_disconnected: int
    fraction: float              # disconnected fraction (paper metric)
    passes: int
    q: float                     # modularity of the returned partition
    sweeps: int = 0              # local-move sweeps summed over passes
    split_moved: int = 0         # vertices the split pass relabelled
    algorithm: str = "standard"  # portfolio tier that produced this result
    contract: Optional[QualityContract] = None  # the tier's guarantees


@dataclasses.dataclass
class UpdateResult:
    """Per-graph warm-update output (host-side)."""

    C: np.ndarray                # int32[nv] dense membership after the update
    n_communities: int
    n_disconnected: int          # 0 by construction (split pass re-runs)
    fraction: float
    iterations: int              # warm local-move sweeps
    q: float
    n_affected: int = 0          # delta-screening affected vertices
    split_moved: int = 0         # vertices the split pass relabelled


@dataclasses.dataclass
class DispatchInfo:
    """Timing of one engine dispatch, for span attribution.

    Monotonic-clock stamps bracket the phases the front end turns into
    batch-level spans: ``compile`` = (t_call0, t_call1) on a cache miss
    (jit compiles lazily at the first call) and empty on a hit;
    ``engine-dispatch`` = (t_start, t_call1) minus compile, with ``stack``
    = (t_stack0, t_stacked) inside it (filler, stacking, reshape and the
    transfer); ``device-sync`` = (t_call1, t_sync), the device->host
    conversion that blocks on the async dispatch; ``unpack`` = (t_sync,
    t_unpacked), the per-result conversion and the engine's counters.
    The engine opens the matching ``repro.<span>`` profiler annotations
    around the same work.  ``fill`` is the live fraction of the padded
    batch (filler slots excluded) — the bucket fill-factor gauge.
    """

    kind: str                    # "detect" | "update"
    bucket: Bucket
    n: int                       # live requests in the batch
    capacity: int                # n_tiles * sub_batch (padded width)
    compile_hit: bool
    t_start: float               # dispatch entry (host prep begins)
    t_stack0: float              # program looked up, stacking begins
    t_stacked: float             # batch stacked and on the device
    t_call0: float               # jitted call begins
    t_call1: float               # jitted call returned (async dispatch)
    t_sync: float                # device->host conversion finished
    t_unpacked: float            # per-result conversion + counters done
    algorithm: str = "standard"  # portfolio tier the batch ran

    @property
    def fill(self) -> float:
        return self.n / self.capacity if self.capacity else 0.0


def _tiled(gb: Graph, n_tiles: int, b: int) -> Graph:
    """A stacked ``[n_tiles * b, ...]`` batch laid out as ``[n_tiles, b,
    ...]`` for the ``lax.map`` over vmapped tiles."""
    return Graph(
        src=gb.src.reshape(n_tiles, b, -1),
        dst=gb.dst.reshape(n_tiles, b, -1),
        w=gb.w.reshape(n_tiles, b, -1),
        n_nodes=gb.n_nodes.reshape(n_tiles, b),
        n_cap=gb.n_cap, m_cap=gb.m_cap,
    )


# (bucket-padded updated graph — vertex+edge rewrites applied, previous
#  membership int32[nv] in the post-rewrite id space, screening-seed mask
#  bool[nv]) — see ResultStore.prepare_update
UpdateItem = Tuple[Graph, np.ndarray, np.ndarray]


class BatchedLouvainEngine:
    """Vmapped GSP-Louvain over stacked same-bucket graphs."""

    def __init__(self, cfg: Optional[LouvainConfig] = None, *,
                 options: Optional[DetectOptions] = None,
                 algorithms: Optional[Tuple[str, ...]] = None,
                 sub_batch: Optional[int] = None,
                 telemetry: Optional[Telemetry] = None,
                 profile_dir: Optional[str] = None,
                 faults=None,
                 dense_max_nv: Optional[int] = None,
                 dense_small_nv: Optional[int] = None,
                 dense_min_density: Optional[float] = None,
                 seg_impl: Optional[str] = None,
                 seg_block_m: Optional[int] = None):
        """Args:
          cfg: the one Louvain config this engine serves (part of every
            compile key; run several engines for several configs).
            Convenience positional for ``options.louvain`` — pass one or
            the other, not both.
          options: the :class:`repro.core.DetectOptions` record selecting
            the default portfolio tier (``algorithm``), scan strategy,
            dense crossover, segment-reduction backend, Pallas block and
            (for :meth:`detect_sharded`) the device mesh.  Compile keys
            derive from it via :meth:`DetectOptions.cache_key` — the
            algorithm is part of every key, so each tier compiles and
            batches separately.
          algorithms: every portfolio tier this engine serves (``warm()``
            pre-compiles each); None = just ``options.algorithm``.
            Per-dispatch tiers outside this set still work — they just
            compile lazily on first use.
          sub_batch: dispatch width; None = auto (cache-sized on CPU, wide
            on accelerators).
          telemetry: optional hub for compile-cache hit/miss counters,
            algorithm counters (passes/sweeps/affected/split-moves) and
            the bucket fill-factor gauge; None = no emission.
          profile_dir: when set, every dispatch runs inside
            ``jax.profiler.trace(profile_dir)`` for on-device deep dives
            (TensorBoard-viewable; expensive — opt-in only).
          faults: optional :class:`repro.resilience.faults.FaultPlan`
            consulted at dispatch entry (``engine.detect[.hang]`` /
            ``engine.update[.hang]`` seams).  Warm-up pre-compiles
            bypass it — injected chaos must not fire during startup.
          dense_max_nv / dense_small_nv / dense_min_density / seg_impl /
            seg_block_m: DEPRECATED flat spellings of the DetectOptions
            fields; folded through the shim (one warning per process).
        """
        legacy = dict(dense_max_nv=dense_max_nv,
                      dense_small_nv=dense_small_nv,
                      dense_min_density=dense_min_density,
                      seg_impl=seg_impl, seg_block_m=seg_block_m)
        opts = fold_legacy_kwargs(options, legacy,
                                  where="BatchedLouvainEngine")
        if cfg is not None:
            if options is not None:
                raise TypeError(
                    "BatchedLouvainEngine: pass the algorithm config inside "
                    "options=DetectOptions(louvain=...), not both cfg= and "
                    "options=")
            opts = opts.replace(louvain=cfg)
        # resolve 'auto' once: the resolved backend is what compile keys,
        # kernels and the autotuner must agree on for this engine's lifetime
        self.options = opts.replace(seg_impl=ops.resolve_impl(opts.seg_impl))
        self.cfg = self.options.louvain
        if algorithms is None:
            algorithms = (self.options.algorithm,)
        for a in algorithms:
            contract_for(a)  # validates tier names
        self.algorithms = tuple(dict.fromkeys(algorithms))  # dedup, ordered
        if sub_batch is None:
            sub_batch = 1 if jax.default_backend() == "cpu" else 8
        self.sub_batch = max(1, int(sub_batch))
        self.seg_impl = self.options.seg_impl
        self.telemetry = telemetry or Telemetry()
        self.profile_dir = profile_dir
        self.faults = faults
        self.n_compile_hits = 0
        self.n_compile_misses = 0
        self.last_detect_info: Optional[DispatchInfo] = None
        self.last_update_info: Optional[DispatchInfo] = None
        self._seg_blocks: dict = {}
        self._compiled: dict = {}

    def _profiled(self):
        if self.profile_dir is None:
            return contextlib.nullcontext()
        return jax.profiler.trace(self.profile_dir)

    def _note_compile(self, info: DispatchInfo):
        hit = info.compile_hit
        if hit:
            self.n_compile_hits += 1
        else:
            self.n_compile_misses += 1
        labels = {"kind": info.kind,
                  "bucket": f"{info.bucket.n_cap}x{info.bucket.m_cap}",
                  "tier": info.algorithm}
        self.telemetry.counter("engine_compile", 1,
                               {**labels, "result": "hit" if hit else "miss"})
        if not hit:
            # jit compiles lazily inside the first call
            self.telemetry.counter("engine_compile_seconds",
                                   info.t_call1 - info.t_call0, labels)

    def _note_dispatch(self, info: DispatchInfo, flat: dict, n: int):
        """Emit algorithm counters + fill gauge for a finished batch."""
        tel = self.telemetry
        if not tel.enabled:
            return
        bl = {"bucket": f"{info.bucket.n_cap}x{info.bucket.m_cap}",
              "tier": info.algorithm}
        tel.gauge("batch_fill_factor", info.fill, bl)
        if info.kind == "detect":
            tel.counter("louvain_passes",
                        float(flat["passes"][:n].sum()), bl)
            tel.counter("local_move_sweeps",
                        float(flat["sweeps"][:n].sum()), bl)
        else:
            tel.counter("local_move_sweeps",
                        float(flat["iterations"][:n].sum()), bl)
            tel.counter("affected_vertices",
                        float(flat["n_affected"][:n].sum()), bl)
        tel.counter("split_moves", float(flat["split_moved"][:n].sum()), bl)

    # -- compile cache ----------------------------------------------------
    def scan_for(self, bucket: Bucket) -> str:
        return self.options.resolved_scan(bucket.nv, bucket.m_cap)

    def seg_block_for(self, bucket: Bucket) -> int:
        """The Pallas block size for a bucket: the pinned
        ``options.block_m`` if nonzero, else the autotuned value for the
        bucket's edge capacity (cached on disk; 0 — i.e.
        backend-irrelevant — for non-Pallas impls).  Recorded in the
        compile key either way so an impl or block change recompiles."""
        if self.seg_impl != "pallas":
            return 0
        if self.options.block_m:
            return int(self.options.block_m)
        blk = self._seg_blocks.get(bucket)
        if blk is None:
            blk = autotune_block_m(bucket.m_cap, 2, impl=self.seg_impl)
            self._seg_blocks[bucket] = blk
        return blk

    def _one(self, g: Graph, scan: str, block_m: int, algorithm: str):
        with scope("partition"):
            C, stats = partition_impl(g, algorithm, self.cfg, scan=scan,
                                      seg_impl=self.seg_impl,
                                      block_m=block_m)
        with scope("detector"):
            det = disconnected_communities_impl(
                g.src, g.dst, g.w, C, g.n_nodes,
                impl="dense" if scan == "dense" else "coo",
                seg_impl=self.seg_impl, block_m=block_m,
            )
        with scope("modularity"):
            q = modularity(g.src, g.dst, g.w, C, seg_impl=self.seg_impl,
                           block_m=block_m)
        return dict(
            C=C,
            n_communities=stats["n_communities"],
            passes=stats["passes"],
            sweeps=stats["li_total"],
            split_moved=stats["split_moved"],
            n_disconnected=det["n_disconnected"],
            fraction=det["fraction"],
            q=q,
        )

    def _resolve_algorithm(self, algorithm: Optional[str]) -> str:
        if algorithm is None:
            return self.options.algorithm
        contract_for(algorithm)  # validates
        return algorithm

    def _detect_key(self, bucket: Bucket, n_tiles: int,
                    algorithm: Optional[str] = None):
        return self.options.cache_key(
            bucket, n_tiles, self.sub_batch,
            algorithm=self._resolve_algorithm(algorithm),
            scan=self.scan_for(bucket), block_m=self.seg_block_for(bucket))

    def compiled_fn(self, bucket: Bucket, n_tiles: int,
                    algorithm: Optional[str] = None):
        """The jitted executable for (bucket, n_tiles x sub_batch, tier):
        a ``lax.map`` of the vmapped per-graph pipeline over tiles — one
        compile per (bucket, batch, tier, config, seg-backend), replayed
        for the bucket's whole lifetime."""
        scan = self.scan_for(bucket)
        alg = self._resolve_algorithm(algorithm)
        key = self._detect_key(bucket, n_tiles, alg)
        fn = self._compiled.get(key)
        if fn is None:
            tile = jax.vmap(partial(self._one, scan=scan,
                                    block_m=self.seg_block_for(bucket),
                                    algorithm=alg))
            fn = jax.jit(lambda gt: jax.lax.map(tile, gt))
            self._compiled[key] = fn
        return fn

    def _update_key(self, bucket: Bucket, n_tiles: int, tau, max_iters):
        return self.options.cache_key(
            bucket, n_tiles, self.sub_batch, "update", float(tau),
            int(max_iters),
            scan=self.scan_for(bucket), block_m=self.seg_block_for(bucket))

    def update_fn(self, bucket: Bucket, n_tiles: int, *, tau: float = 1e-3,
                  max_iters: int = 10):
        """The jitted executable for a (bucket, n_tiles x sub_batch) batch
        of warm updates: ``lax.map`` of the vmapped
        :func:`repro.core.dynamic.warm_update_impl` — the same compute the
        store's immediate path runs, batched."""
        scan = self.scan_for(bucket)
        key = self._update_key(bucket, n_tiles, tau, max_iters)
        fn = self._compiled.get(key)
        if fn is None:
            one = partial(warm_update_impl, tau=tau, max_iters=max_iters,
                          scan=scan, seg_impl=self.seg_impl,
                          block_m=self.seg_block_for(bucket))
            tile = jax.vmap(lambda g, C, t: one(g, C, t))
            fn = jax.jit(lambda gt, Ct, Tt: jax.lax.map(
                lambda args: tile(*args), (gt, Ct, Tt)))
            self._compiled[key] = fn
        return fn

    def cache_keys(self):
        return list(self._compiled)

    def warm(self, bucket: Bucket, max_batch: int, *,
             algorithms: Optional[Sequence[str]] = None) -> int:
        """Pre-compile the pow2 tile-count ladder for a bucket (1..max
        batch) for every configured tier (``algorithms`` overrides
        ``self.algorithms``); returns the number of executables compiled.
        Long-running services call this at startup so steady-state latency
        never pays XLA compilation."""
        n = 0
        pad = filler(bucket)
        # warm-up dispatches bypass any installed fault plan: injected
        # chaos is for live traffic, not startup pre-compiles
        faults, self.faults = self.faults, None
        try:
            for alg in (algorithms if algorithms is not None
                        else self.algorithms):
                tiles = 1
                while True:
                    key = self._detect_key(bucket, tiles, alg)
                    if key not in self._compiled:
                        self.detect_batch([pad] * (tiles * self.sub_batch),
                                          algorithm=alg)
                        n += 1
                    # cover the rounded-up rung too: a full batch of
                    # max_batch dispatches at the next power of two, not
                    # at max_batch
                    if tiles * self.sub_batch >= max(max_batch,
                                                     self.sub_batch):
                        break
                    tiles *= 2
        finally:
            self.faults = faults
        return n

    # -- execution --------------------------------------------------------
    def detect_batch(self, graphs: Sequence[Graph], *,
                     algorithm: Optional[str] = None,
                     fault_ids: Optional[Sequence[str]] = None
                     ) -> list[DetectResult]:
        """Detect communities for a homogeneous (same-bucket, same-tier)
        batch with one jitted call.

        ``algorithm`` selects the portfolio tier for the whole batch
        (None = the engine default); the DRR scheduler composes batches
        per (bucket, tier), so mixed-tier batches never reach here.  The
        stack is shaped [n_tiles, sub_batch, ...]; the tail tile is
        padded with filler graphs whose results are dropped.
        ``fault_ids`` (the batch's graph ids) scope any installed fault
        plan's per-graph poison specs to this dispatch.
        """
        graphs = list(graphs)
        if not graphs:
            return []
        alg = self._resolve_algorithm(algorithm)
        if self.faults is not None:
            self.faults.perturb("engine.detect.hang", ids=fault_ids)
            self.faults.perturb("engine.detect", ids=fault_ids)
        t_start = time.perf_counter()
        bucket = bucket_of(graphs[0])
        b = self.sub_batch
        n = len(graphs)
        # round the tile count up to a power of two: deadline flushes hand
        # us arbitrary partial batches, and an executable per exact size
        # would recompile constantly.  <= log2(batch) executables per
        # bucket, filler slots are cheap (they converge in one pass).
        n_tiles = 1 << (-(-n // b) - 1).bit_length()

        def compiled():
            hit = self._detect_key(bucket, n_tiles, alg) in self._compiled
            return self.compiled_fn(bucket, n_tiles, alg), hit

        def stack():
            padded = graphs + [filler(bucket)] * (n_tiles * b - n)
            return (_tiled(stack_graphs(padded), n_tiles, b),)

        flat, hit, stamps = self._run(t_start, compiled, stack, n_tiles * b)
        with annotate("unpack"):
            info = DispatchInfo(
                "detect", bucket, n, n_tiles * b, hit, *stamps,
                t_unpacked=stamps[-1], algorithm=alg)
            self._note_compile(info)
            self._note_dispatch(info, flat, n)
            contract = contract_for(alg)
            results = [
                DetectResult(
                    C=flat["C"][i],
                    n_communities=int(flat["n_communities"][i]),
                    n_disconnected=int(flat["n_disconnected"][i]),
                    fraction=float(flat["fraction"][i]),
                    passes=int(flat["passes"][i]),
                    q=float(flat["q"][i]),
                    sweeps=int(flat["sweeps"][i]),
                    split_moved=int(flat["split_moved"][i]),
                    algorithm=alg,
                    contract=contract,
                )
                for i in range(n)
            ]
            info.t_unpacked = time.perf_counter()
        self.last_detect_info = info
        return results

    def _run(self, t_start: float, compiled, stack, width: int):
        """One dispatch that entered at ``t_start``: ``compiled()`` gives
        the jitted program and whether it was cached, ``stack()`` builds
        the call's arguments on the device, the program runs them, and
        the outputs come back to the host as ``[width, ...]`` numpy
        arrays.  Each step runs under the profiler annotation of the span
        it becomes (``engine-dispatch`` with ``stack`` inside, ``compile``
        for the call on a cache miss, ``device-sync``).  Returns ``(flat,
        hit, (t_start, t_stack0, t_stacked, t_call0, t_call1, t_sync))``.
        """
        with self._profiled(), contextlib.ExitStack() as phase:
            phase.enter_context(annotate("engine-dispatch"))
            fn, hit = compiled()
            with annotate("stack"):
                t_stack0 = time.perf_counter()
                args = stack()
                t_stacked = time.perf_counter()
            t_call0 = time.perf_counter()
            if not hit:
                # jit compiles inside the first call: that is not dispatch
                phase.close()
                phase.enter_context(annotate("compile"))
            out = fn(*args)
            t_call1 = time.perf_counter()
            phase.close()
            with annotate("device-sync"):
                flat = {k: np.asarray(v).reshape((width,) + v.shape[2:])
                        for k, v in out.items()}
                t_sync = time.perf_counter()
        return flat, hit, (t_start, t_stack0, t_stacked, t_call0, t_call1,
                           t_sync)

    def detect_one(self, g: Graph, *,
                   algorithm: Optional[str] = None) -> DetectResult:
        return self.detect_batch([g], algorithm=algorithm)[0]

    def detect_sharded(self, g: Graph) -> DetectResult:
        """Single-graph detection sharded over ``options.mesh`` — the
        one-giant-graph mode for requests that dwarf the bucket ladder.

        Routes through :func:`repro.core.distributed.louvain_sharded`
        (vertex-aligned edge partitioning + halo exchange), which produces
        the EXACT partition the single-device path returns for the same
        config; the detector and modularity run single-device on the
        reassembled labeling.  Telemetry (halo bytes, ghost counts,
        per-device sweeps) flows through the engine's hub.
        """
        mesh = self.options.resolved_mesh()
        if mesh is None:
            raise ValueError(
                "detect_sharded requires a mesh: construct the engine with "
                "options=DetectOptions(mesh=...)")
        alg = self.options.algorithm
        if alg == "fast":
            raise ValueError(
                "algorithm='fast' (LPA) is single-device only — "
                "detect_sharded serves standard/max-quality")
        from repro.core.distributed import louvain_sharded
        from repro.core.portfolio import _standard_config
        t_start = time.perf_counter()
        C, stats = louvain_sharded(
            g, tier_config(alg, self.cfg), mesh=mesh,
            seg_impl=self.options.seg_impl,
            block_m=self.options.block_m, telemetry=self.telemetry)
        q = modularity(g.src, g.dst, g.w, jnp.asarray(C),
                       seg_impl=self.seg_impl, block_m=self.options.block_m)
        if alg == "max-quality":
            # same best-of-two selection as the single-device dispatch:
            # the refined candidate above vs the plain GSP partition
            C_s, st_s = louvain_sharded(
                g, _standard_config(self.cfg), mesh=mesh,
                seg_impl=self.options.seg_impl,
                block_m=self.options.block_m, telemetry=self.telemetry)
            q_s = modularity(g.src, g.dst, g.w, jnp.asarray(C_s),
                             seg_impl=self.seg_impl,
                             block_m=self.options.block_m)
            if float(q_s) > float(q):
                C, stats, q = C_s, st_s, q_s
        t_call1 = time.perf_counter()
        det = disconnected_communities_impl(
            g.src, g.dst, g.w, jnp.asarray(C), g.n_nodes,
            seg_impl=self.seg_impl, block_m=self.options.block_m)
        t_sync = time.perf_counter()
        info = DispatchInfo(
            kind="detect", bucket=bucket_of(g), n=1,
            capacity=1, compile_hit=True, t_start=t_start,
            t_stack0=t_start, t_stacked=t_start, t_call0=t_start,
            t_call1=t_call1, t_sync=t_sync, t_unpacked=t_sync,
            algorithm=alg)
        self.last_detect_info = info
        return DetectResult(
            C=np.asarray(C),
            n_communities=int(stats["n_communities"]),
            n_disconnected=int(det["n_disconnected"]),
            fraction=float(det["fraction"]),
            passes=int(stats["passes"]),
            q=float(q),
            sweeps=int(stats["li_total"]),
            split_moved=int(stats["split_moved"]),
            algorithm=alg,
            contract=contract_for(alg),
        )

    # -- batched warm updates ---------------------------------------------
    def update_batch(self, items: Sequence[UpdateItem], *, tau: float = 1e-3,
                     max_iters: int = 10,
                     fault_ids: Optional[Sequence[str]] = None
                     ) -> list[UpdateResult]:
        """Run a homogeneous (same-bucket) batch of delta-screened warm
        updates with one jitted call.

        ``items``: (updated graph, previous membership int32[nv], touched
        mask bool[nv]) triples — the graphs already carry the applied
        rewrites, vertex ops included
        (:func:`repro.core.dynamic.prepare_graph_update`); this method
        batches the device side: screening, warm local move, split,
        renumber, detector, modularity.  Partitions are exactly what the
        sequential warm path produces per graph, and per-graph ``n_nodes``
        may differ freely within the bucket (it is a traced leaf, not a
        compile key).
        """
        items = list(items)
        if not items:
            return []
        if self.faults is not None:
            self.faults.perturb("engine.update.hang", ids=fault_ids)
            self.faults.perturb("engine.update", ids=fault_ids)
        t_start = time.perf_counter()
        bucket = bucket_of(items[0][0])
        b = self.sub_batch
        n = len(items)
        n_tiles = 1 << (-(-n // b) - 1).bit_length()

        def compiled():
            hit = self._update_key(bucket, n_tiles, tau, max_iters) \
                in self._compiled
            return self.update_fn(bucket, n_tiles, tau=tau,
                                  max_iters=max_iters), hit

        def stack():
            padded = items + ([self._filler_update(bucket)]
                              * (n_tiles * b - n))
            nv = bucket.nv
            Cb = jnp.asarray(np.stack([np.asarray(C, np.int32)
                                       for _, C, _ in padded]))
            Tb = jnp.asarray(np.stack([np.asarray(t, bool)
                                       for _, _, t in padded]))
            return (_tiled(stack_graphs([g for g, _, _ in padded]),
                           n_tiles, b),
                    Cb.reshape(n_tiles, b, nv), Tb.reshape(n_tiles, b, nv))

        flat, hit, stamps = self._run(t_start, compiled, stack, n_tiles * b)
        with annotate("unpack"):
            info = DispatchInfo("update", bucket, n, n_tiles * b, hit,
                                *stamps, t_unpacked=stamps[-1])
            self._note_compile(info)
            self._note_dispatch(info, flat, n)
            results = [
                UpdateResult(
                    C=flat["C"][i],
                    n_communities=int(flat["n_communities"][i]),
                    n_disconnected=int(flat["n_disconnected"][i]),
                    fraction=float(flat["fraction"][i]),
                    iterations=int(flat["iterations"][i]),
                    q=float(flat["q"][i]),
                    n_affected=int(flat["n_affected"][i]),
                    split_moved=int(flat["split_moved"][i]),
                )
                for i in range(n)
            ]
            info.t_unpacked = time.perf_counter()
        self.last_update_info = info
        return results

    def _filler_update(self, bucket: Bucket) -> UpdateItem:
        """Bucket-shaped no-op update padding a partial batch: the filler
        graph at its identity partition with nothing touched."""
        nv = bucket.nv
        return (filler(bucket), np.arange(nv, dtype=np.int32),
                np.zeros((nv,), bool))

    def warm_updates(self, bucket: Bucket, max_batch: int, *,
                     tau: float = 1e-3, max_iters: int = 10) -> int:
        """Pre-compile the pow2 tile ladder for the batched update path
        (mirror of :meth:`warm` for detections)."""
        n = 0
        tiles = 1
        faults, self.faults = self.faults, None  # see warm()
        try:
            while True:
                key = self._update_key(bucket, tiles, tau, max_iters)
                if key not in self._compiled:
                    self.update_batch(
                        [self._filler_update(bucket)]
                        * (tiles * self.sub_batch),
                        tau=tau, max_iters=max_iters)
                    n += 1
                if tiles * self.sub_batch >= max(max_batch, self.sub_batch):
                    break
                tiles *= 2
        finally:
            self.faults = faults
        return n
