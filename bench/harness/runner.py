"""One run of one cell: set-up, the measured window, metrics, checks.

``run`` returns the result object; ``bench/run.py`` prints it.  Order:
set-up (inputs from the seed, warm-up of every shape the window uses),
the window (traced when ``trace``), the device's memory peak, the metrics,
then the program's state is freed and the answers are checked against the
host checks and the plain reference.
"""
from __future__ import annotations

import dataclasses
import math
import pathlib
import shutil
import tempfile
import time

from harness import checks, device, manifest, window
from harness.drivers import log
from harness.trace import (
    WINDOW_ANNOTATION, TraceSummary, load, profile_options,
)


@dataclasses.dataclass
class Context:
    """What a metric reader (``metrics/<name>.py``) may read."""

    requests: list          # window.Request completed inside the window
    all_requests: list      # every request of the run
    t0: float               # the window's start (host clock)
    seconds: float
    work_each: float        # the work one request stands for
    setup_s: float
    trace: TraceSummary | None
    peaks: dict


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: pathlib.Path, t_start: float,
        bench_dir: pathlib.Path = manifest.BENCH_DIR) -> dict:
    """Run ``workload`` once; the result object of the contract."""
    return run_with_tally(workload, seed, seconds, trace, root=root,
                          t_start=t_start, bench_dir=bench_dir)[0]


def run_with_tally(workload: str, seed: int, seconds: float, trace: bool, *,
                   root: pathlib.Path, t_start: float,
                   bench_dir: pathlib.Path = manifest.BENCH_DIR):
    """:func:`run`, and the :class:`checks.CheckTally` of its answers."""
    import jax

    cell = manifest.load_cell(workload, root, bench_dir)
    devices = jax.devices()
    dev = device.require_chips(devices, cell.chips)
    log(f"jax {jax.__version__} device {dev}")
    peaks = device.peaks_for(dev["kind"], bench_dir / "peaks.json")
    wanted = cell.per_layer if trace else cell.end_to_end
    readers = {m["name"]: manifest.load_reader(m["name"], bench_dir)
               for m in wanted}

    monitor = device.CompileMonitor()
    try:
        drv = cell.driver(seed, annotate_on=trace)
        drv.setup()
        at_setup = monitor.mark()
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        log(f"setup {setup_s:.3f} s; programs built {at_setup['compiles']} "
            f"({at_setup['compile_s']:.1f} s), persistent cache hits "
            f"{at_setup['cache_hits']}, entries written "
            f"{at_setup['cache_writes']}")
        reqs, summary = _measure(drv, cell, t0, seconds, trace)
        in_win = monitor.since(at_setup)
    finally:
        monitor.close()
    done = window.in_window(reqs, t0, seconds)
    log(f"window: {len(reqs)} requests, {len(done)} completed inside it, "
        f"programs built inside {in_win['compiles']}")
    mem = device.memory_peak(devices)

    ctx = Context(done, reqs, t0, seconds, drv.work_each(), setup_s,
                  summary, peaks)
    metrics = {}
    for m in wanted:
        v = readers[m["name"]](ctx)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    drv.release()
    t_chk = time.perf_counter()
    tally = checks.CheckTally(cell.config["checks"])
    drv.check(reqs, tally)
    log(f"checked {tally.n_checked} answers, {tally.n_reference} against "
        f"the reference, in {time.perf_counter() - t_chk:.1f} s")

    dev = dict(dev, memory_peak_bytes=mem)
    if trace and summary is not None:
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
    # an answer that never came (still pending a minute past the close)
    # fails like one that raised
    failed = sum(1 for r in reqs if r.error is not None or r.t_done is None)
    result = {"correct": tally.correct() and failed == 0 and bool(done),
              "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": dev}
    if trace and summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = tally.as_dict()
    for line in tally.lines():
        log(line)
    return result, tally


def _measure(drv, cell, t0: float, seconds: float, trace: bool):
    """The window, and its trace reduction when ``trace``."""
    import jax

    if not trace:
        return drv.window(t0, seconds), None
    start = float(cell.traffic.get("trace_start_s", 0.0))
    length = cell.traffic.get("trace_seconds") or seconds
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        if start <= 0:
            jax.profiler.start_trace(log_dir,
                                     profiler_options=profile_options())
            with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
                reqs = drv.window(t0, seconds)
            jax.profiler.stop_trace()
        else:
            reqs = drv.window(t0, seconds,
                              on_start=lambda: _traced_slice(
                                  log_dir, t0 + start, length))
        summary = TraceSummary(load(log_dir))
        log(f"trace: window {summary.window_s:.3f} s, busy "
            f"{summary.busy_s:.3f} s, {sum(map(len, summary.ops))} device "
            f"operations")
        return reqs, summary
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


async def _traced_slice(log_dir: str, t_from: float, length: float):
    """Trace ``length`` seconds of a window that an event loop drives."""
    import asyncio

    import jax

    await asyncio.sleep(max(0.0, t_from - time.perf_counter()))
    jax.profiler.start_trace(log_dir, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
        await asyncio.sleep(length)
    jax.profiler.stop_trace()
