"""Per-shape block-size autotuner for the Pallas segment-reduction kernels.

The engine's compile cache is keyed by bucket; the right kernel block size
for a bucket depends on the backend generation (VMEM per core, DMA grain),
so it cannot be a constant.  This module measures the candidate ladder once
per ``(backend, op, m, d, impl)`` shape on the live backend and persists
the winner to an on-disk JSON cache — the kernel-level analogue of the
service engine's in-memory tile ladder, living next to it in the serving
stack (``BatchedLouvainEngine`` consults it when a bucket's executable is
first built).

The cache file defaults to ``~/.cache/repro/autotune.json`` and can be
redirected with ``REPRO_AUTOTUNE_CACHE`` (CI points it into the workspace
so runs are hermetic).  Entries record all measured timings, not just the
winner, so regressions in a candidate are visible in the artifact.
"""
from __future__ import annotations

import json
import os
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

# whole multiples of the kernel's block granule (kernels/segsum.py), each of
# which fits the TPU's scalar memory at the widths the core uses (D <= 2)
DEFAULT_CANDIDATES = (1024, 2048, 4096, 8192, 16384)
_ENV = "REPRO_AUTOTUNE_CACHE"
_lock = threading.Lock()
_mem_cache: dict = {}


def cache_path() -> pathlib.Path:
    p = os.environ.get(_ENV)
    if p:
        return pathlib.Path(p)
    return pathlib.Path.home() / ".cache" / "repro" / "autotune.json"


def _load() -> dict:
    path = cache_path()
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


def _save(cache: dict) -> None:
    path = cache_path()
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(cache, indent=2, sort_keys=True) + "\n")
    except OSError:
        pass  # read-only filesystem: fall back to the in-memory cache


def _measure(fn, *args, repeats: int = 3) -> float:
    # flush compilation AND the warm-up execution before the first timed
    # sample: dispatch is async, and leftover warm-up work pollutes sample
    # one — enough to flip the winner at repeats=3
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts))


def autotune_block_m(m: int, d: int = 1, *, op: str = "sum",
                     impl: str = "pallas",
                     candidates=DEFAULT_CANDIDATES,
                     force: bool = False) -> int:
    """Best ``block_m`` for ``segreduce_sorted`` at shape ``[m, d]``.

    Returns the cached winner when available; otherwise times every
    candidate (clamped to ``m`` rounded up to the block granule) on the
    current backend with a synthetic sorted-run workload and persists the
    result.  A candidate that fails to compile or run raises: the ladder
    holds only sizes the kernel supports, so a failure is a kernel fault
    to surface, not a size to skip.  ``impl='xla'`` shapes
    are block-size-free: 0 is returned without measuring (the engine still
    records it in its compile key so a backend switch recompiles).
    """
    if impl != "pallas":
        return 0
    backend = jax.default_backend()
    key = f"{backend}|segreduce|{op}|m{m}|d{d}"
    with _lock:
        if not force and key in _mem_cache:
            return _mem_cache[key]
        cache = _load()
        if not force and key in cache:
            best = int(cache[key]["block_m"])
            _mem_cache[key] = best
            return best

    from repro.kernels import ops
    from repro.kernels.segsum import BLOCK_GRANULE

    rng = np.random.default_rng(0)
    ids = jnp.asarray(np.sort(rng.integers(0, max(m // 8, 1), m))
                      .astype(np.int32))
    vals = jnp.asarray(rng.random((m, d), np.float32))
    nseg = max(m // 8, 1)
    timings = {}
    m_pad = -(-m // BLOCK_GRANULE) * BLOCK_GRANULE
    cands = sorted({min(c, m_pad) for c in candidates})
    for c in cands:
        fn = jax.jit(lambda v, i, c=c: ops.segreduce_sorted(
            v, i, nseg, op=op, impl="pallas", block_m=c))
        timings[c] = _measure(fn, vals, ids)
    best = min(timings, key=timings.get)
    with _lock:
        cache = _load()
        cache[key] = {
            "block_m": int(best),
            "backend": backend,
            "us": {str(c): round(t * 1e6, 1) for c, t in timings.items()},
        }
        _save(cache)
        _mem_cache[key] = int(best)
    return int(best)
