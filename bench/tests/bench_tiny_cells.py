"""Tiny copies of the benchmark's cells, built from data files alone, for
tests that drive a whole run on the CPU, with the look for a chip faked.

A tiny copy is a file ``tests/tiny/<dir>/<name>.json`` beside these tests:
its keys replace those of the benchmark's ``<dir>/<name>.json`` (a
configuration or a traffic mix) in the copy."""
import contextlib
import json
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_root(tmp: pathlib.Path, source: pathlib.Path = ROOT) -> pathlib.Path:
    """A checkout-shaped directory whose cells are those of ``source``'s
    ``BENCHMARK.json`` and ``bench/``, each cut to the size its tiny files
    give."""
    src = source / "bench"
    bench = tmp / "bench"
    shutil.copytree(src, bench, ignore=shutil.ignore_patterns(
        "tests", "harness", "__pycache__", ".autotune"))
    shutil.copy(source / "BENCHMARK.json", tmp / "BENCHMARK.json")
    tiny = src / "tests" / "tiny"
    for change in sorted(tiny.rglob("*.json")):
        path = bench / change.relative_to(tiny)
        data = json.loads(path.read_text())
        data.update(json.loads(change.read_text()))
        path.write_text(json.dumps(data))
    return tmp


@contextlib.contextmanager
def no_chip_look():
    """The run's look for a TPU and its peak table, faked for the CPU."""
    from unittest import mock

    from harness import device

    with mock.patch.object(device, "require_chips",
                           lambda devices, chips: device.describe(devices)), \
            mock.patch.object(device, "peaks_for", lambda kind, path: {}):
        yield


def run_tiny(root: pathlib.Path, workload: str, seed: int = 5,
             seconds: float = 1.5, trace: bool = False) -> dict:
    from harness import runner

    with no_chip_look():
        return runner.run(workload, seed, seconds, trace, root=root,
                          bench_dir=root / "bench",
                          t_start=time.perf_counter())
