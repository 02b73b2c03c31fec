"""The device a run measures: guard, peak table, memory and compile counts.

A run measures the chip or nothing: :func:`require_chips` refuses any
platform but ``tpu`` and fewer devices than the cell asks for, and
:func:`peaks_for` refuses a ``device_kind`` the peak table does not hold.
"""
from __future__ import annotations

import json
import os
import pathlib


def configure(bench: pathlib.Path, root: pathlib.Path) -> None:
    """Process-wide settings of a benchmark process, before any compile:
    the autotuner's cache in ``bench/.autotune/``, and JAX's persistent
    compilation cache where ``JAX_COMPILATION_CACHE_DIR`` says, else in
    ``<root>/.jax_cache``, holding every program however quick to build."""
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(
        bench / ".autotune" / "autotune.json")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class NoChip(RuntimeError):
    """The chip the cell asks for is missing: the run prints no result."""


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def require_chips(devices, chips: int, *, platform: str = "tpu") -> dict:
    """The device record, or :class:`NoChip` unless ``devices`` are at
    least ``chips`` devices of ``platform``."""
    if not devices:
        raise NoChip("JAX found no devices")
    dev = describe(devices)
    if dev["platform"] != platform:
        raise NoChip(f"no {platform.upper()}: JAX found {dev['platform']} "
                     f"devices; the benchmark runs on the chip only")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} {platform.upper()} devices, "
                     f"JAX found {dev['count']}")
    return dev


def peaks_for(kind: str, table_path: pathlib.Path) -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``kind``; an unknown kind is
    an error, never a default."""
    table = json.loads(table_path.read_text())["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {table_path.name}; "
                     f"known: {sorted(table)}")
    return table[kind]


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileMonitor:
    """Counts program builds (a compilation or a read from the persistent
    cache), persistent-cache hits and entries written, through
    ``jax.monitoring``; :meth:`mark` snapshots the counts so a window can
    be checked for builds inside it."""

    HIT = "/jax/compilation_cache/cache_hits"
    # JAX records its "cache_misses" event when it writes a new entry
    WRITE = "/jax/compilation_cache/cache_misses"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self._monitoring = monitoring
        self.counts = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
                       "cache_writes": 0}
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == self.HIT:
            self.counts["cache_hits"] += 1
        elif event == self.WRITE:
            self.counts["cache_writes"] += 1

    def _duration(self, event, secs, **_):
        if event == self.COMPILE:
            self.counts["compiles"] += 1
            self.counts["compile_s"] += float(secs)

    def mark(self) -> dict:
        return dict(self.counts)

    def since(self, mark: dict) -> dict:
        return {k: self.counts[k] - mark[k] for k in self.counts}

    def close(self):
        self._monitoring.unregister_event_listener(self._event)
        self._monitoring.unregister_event_duration_listener(self._duration)
