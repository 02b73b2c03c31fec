"""The persistent XLA compilation cache every entry point shares.

Compiling the detection programs for a TPU takes tens of seconds per
bucket, so entry points (``chip_smoke.py``, ``repro.launch.
serve_communities``, ``benchmarks/bench_service.py``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once at start-up, before the first
compile.  The cache directory is part of every entry's key, so it is one
fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself; nothing else is set here), else
``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
