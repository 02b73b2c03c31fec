"""Unified detection API: :class:`DetectOptions` + :func:`detect`.

Before this module, callers picked among ``louvain`` / ``louvain_impl`` /
``disconnected_communities`` and threaded ~8 flat knobs (``scan``,
``seg_impl``, ``block_m``, ``dense_max_nv``, ...) through every layer.
Now one frozen, hashable record carries the whole detection
configuration — algorithm config, scan strategy, segment-reduction
backend, dense-crossover thresholds, and the device mesh for the sharded
single-graph path — and every entry point accepts it as a single
keyword-only ``options=``:

    from repro.core import DetectOptions, detect
    res = detect(g, options=DetectOptions(seg_impl="xla"))
    res.labels, res.modularity, res.n_disconnected

Legacy flat keywords keep working everywhere (``detect(g, seg_impl=...)``,
``louvain(g, cfg, scan=...)``, flat ``ServiceConfig`` fields) through
:func:`fold_legacy_kwargs`, which emits ONE :class:`DeprecationWarning`
per process and folds them into a ``DetectOptions`` — results are
identical by construction (regression-tested in tests/test_detect_api.py).

Compile-cache keying for the service engine/store also lives here
(:meth:`DetectOptions.cache_key`): the hashable backend identity that
used to be re-assembled by hand at three call sites.
"""
from __future__ import annotations

import dataclasses
import threading
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.louvain import LouvainConfig
from repro.core.portfolio import ALGORITHMS, QualityContract, contract_for

_SCANS = ("auto", "sort", "dense")
_SEG_IMPLS = ("auto", "xla", "pallas", "scatter")

# names the deprecation shim recognizes, in DetectOptions field terms
LEGACY_KWARG_MAP = {
    "cfg": "louvain",
    "louvain": "louvain",
    "scan": "scan",
    "seg_impl": "seg_impl",
    "block_m": "block_m",
    "seg_block_m": "block_m",
    "dense_max_nv": "dense_max_nv",
    "dense_small_nv": "dense_small_nv",
    "dense_min_density": "dense_min_density",
    "mesh": "mesh",
}

_warned = threading.Lock()
_warned_once = False


def _warn_once(where: str, names) -> None:
    """One DeprecationWarning per process, whatever the call site."""
    global _warned_once
    with _warned:
        if _warned_once:
            return
        _warned_once = True
    warnings.warn(
        f"{where}: flat keyword(s) {sorted(names)} are deprecated — pass "
        f"options=DetectOptions(...) instead (README: API migration table)",
        DeprecationWarning,
        stacklevel=3,
    )


def fold_legacy_kwargs(options, legacy: dict, *, where: str,
                       warn: bool = True):
    """Fold flat legacy keywords into a :class:`DetectOptions`.

    ``legacy`` maps old kwarg name -> value (``None`` values are treated
    as "not passed").  Mixing ``options=`` with explicit legacy keywords
    is an error — the whole point is one source of truth.
    """
    given = {k: v for k, v in legacy.items() if v is not None}
    unknown = set(given) - set(LEGACY_KWARG_MAP)
    if unknown:
        raise TypeError(f"{where}: unexpected keyword(s) {sorted(unknown)}")
    if not given:
        return options if options is not None else DetectOptions()
    if options is not None:
        raise TypeError(
            f"{where}: pass either options= or legacy keyword(s) "
            f"{sorted(given)}, not both")
    if warn:
        _warn_once(where, given)
    fields = {LEGACY_KWARG_MAP[k]: v for k, v in given.items()}
    return DetectOptions(**fields)


@dataclasses.dataclass(frozen=True)
class DetectOptions:
    """Everything that selects *how* detection runs (not *what* graph).

    Frozen and hashable: the service engine/store key their jit caches on
    (subsets of) this record via :meth:`cache_key`.

    Fields:
      algorithm: 'fast' | 'standard' | 'max-quality' — which portfolio
                tier runs (core/portfolio.py): pure LPA, GSP-Louvain
                (the paper; default), or the Leiden-style refine mode.
                Folded into every cache key, so the batched engine
                compiles/batches each tier separately.
      louvain:  the algorithm config (passes, tolerance ladder, split
                mode — the refinement policy lives here as ``split=``).
      scan:     'auto' | 'sort' | 'dense' — community-scan layout; 'auto'
                resolves per shape via the service's calibrated density
                crossover (:meth:`resolved_scan`).
      seg_impl: 'auto' | 'xla' | 'pallas' | 'scatter' — segment-reduction
                backend (kernels/ops.py; all bit-identical).
      block_m:  Pallas kernel block rows (0 = default/autotuned).
      dense_max_nv / dense_small_nv / dense_min_density: the dense-scan
                crossover thresholds 'auto' consults.
      mesh:     None (single device) | int (host-device count) |
                jax.sharding.Mesh — the sharded single-graph path
                (core/distributed.py; bit-identical partitions).
    """

    algorithm: str = "standard"
    louvain: LouvainConfig = LouvainConfig()
    scan: str = "auto"
    seg_impl: str = "auto"
    block_m: int = 0
    dense_max_nv: int = 1025
    dense_small_nv: int = 129
    dense_min_density: Optional[float] = None
    mesh: Any = None

    def __post_init__(self):
        if self.algorithm not in ALGORITHMS:
            raise ValueError(
                f"algorithm must be one of {ALGORITHMS}, "
                f"got {self.algorithm!r}")
        if self.scan not in _SCANS:
            raise ValueError(f"scan must be one of {_SCANS}, got {self.scan!r}")
        if self.seg_impl not in _SEG_IMPLS:
            raise ValueError(
                f"seg_impl must be one of {_SEG_IMPLS}, got {self.seg_impl!r}")
        if self.block_m < 0:
            raise ValueError("block_m must be >= 0")
        if isinstance(self.louvain, dict):  # tolerate config-dict loading
            object.__setattr__(self, "louvain", LouvainConfig(**self.louvain))

    def replace(self, **kw) -> "DetectOptions":
        return dataclasses.replace(self, **kw)

    # -- resolution --------------------------------------------------------
    def resolved_scan(self, nv: int, m_cap: int) -> str:
        """Concrete 'sort' | 'dense' for a shape ('auto' consults the
        calibrated service crossover; lazy import keeps core below the
        service layer for non-auto options)."""
        if self.scan != "auto":
            return self.scan
        from repro.service.buckets import choose_scan
        return choose_scan(nv, m_cap, dense_max_nv=self.dense_max_nv,
                           dense_small_nv=self.dense_small_nv,
                           dense_min_density=self.dense_min_density)

    def resolved_seg_impl(self) -> str:
        from repro.kernels import ops
        return ops.resolve_impl(self.seg_impl)

    def resolved_mesh(self):
        """None, or a concrete jax.sharding.Mesh (int = first-N devices)."""
        if self.mesh is None or isinstance(self.mesh, jax.sharding.Mesh):
            return self.mesh
        n = int(self.mesh)
        devs = jax.devices()
        if n > len(devs):
            raise ValueError(
                f"mesh={n} devices requested, {len(devs)} available "
                f"(set XLA_FLAGS=--xla_force_host_platform_device_count)")
        import numpy as np
        return jax.sharding.Mesh(np.array(devs[:n]), ("data",))

    # -- cache keying ------------------------------------------------------
    def cache_key(self, *parts, algorithm: Optional[str] = None,
                  scan: Optional[str] = None,
                  block_m: Optional[int] = None) -> tuple:
        """THE compile-cache key: shape/phase ``parts`` + the backend
        identity (algorithm tier included, so the engine batches and
        compiles each tier separately).  ``algorithm``/``scan``/
        ``block_m`` override with per-request / per-bucket resolved
        values (engine buckets resolve 'auto' and autotune blocks)."""
        return (*parts,
                self.algorithm if algorithm is None else algorithm,
                self.scan if scan is None else scan,
                self.seg_impl,
                self.block_m if block_m is None else block_m)

    def result_key(self, algorithm: Optional[str] = None) -> tuple:
        """Hashable identity of *what produced a stored partition*: the
        tier + full LouvainConfig + backend identity.  The result store
        stamps this on every entry and refuses warm updates whose current
        key mismatches (continuing a partition computed under different
        options silently corrupts it — re-detect instead)."""
        return self.cache_key(self.louvain, algorithm=algorithm)


@dataclasses.dataclass(frozen=True)
class Detection:
    """Result of :func:`detect` — one record instead of tuple juggling."""

    labels: jax.Array          # int32[nv] dense community membership
    n_communities: int
    n_disconnected: int        # paper invariant: 0 for every sp-*/refine run
    modularity: float
    stats: dict                # driver stats (passes, li_total, ...)
    contract: Optional[QualityContract] = None  # tier guarantee flags


def detect(graph, *, options: Optional[DetectOptions] = None,
           telemetry=None, **legacy) -> Detection:
    """Run community detection on one graph — the unified entry point.

    ``options.algorithm`` selects the portfolio tier ('fast' LPA /
    'standard' GSP-Louvain / 'max-quality' Leiden-style refine —
    core/portfolio.py); the returned :class:`Detection` carries the
    tier's :class:`QualityContract`.  Single-device by default;
    ``options.mesh`` routes through the sharded driver (bit-identical
    partition; standard/max-quality only).  Legacy flat keywords
    (``cfg=``, ``scan=``, ``seg_impl=``, ``block_m=``, ``mesh=``,
    ``dense_*=``) fold through the deprecation shim.

    Returns a :class:`Detection`; ``labels`` includes ghost/padding slots
    (mask with ``graph.node_mask()`` downstream, as before).
    """
    opts = fold_legacy_kwargs(options, legacy, where="detect()")
    from repro.core.portfolio import run_detection
    return run_detection(graph, opts, telemetry=telemetry)
