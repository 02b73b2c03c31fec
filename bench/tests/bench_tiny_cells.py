"""Tiny copies of the benchmark's cells, built from data files alone, for
tests that drive a whole run on the CPU, with the look for a chip faked."""
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

TINY_CONFIG = {
    "graph500-s15": {"scale": 8},
    "girvan-newman-128": {"groups": 2, "group": 16, "degree": 8,
                          "z_out": [1, 2],
                          "service": {"batch_size": 4,
                                      "buckets": [[64, 512], [64, 2048]]}},
}
TINY_TRAFFIC = {"closed64": {"clients": 8, "pool": 24, "warm_seconds": 0.5,
                             "reference_sample": 8}}


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped directory whose cells are the benchmark's own,
    cut to a size a test run holds."""
    bench = tmp / "bench"
    for sub in ("configs", "traffic", "metrics", "drivers", "families"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for name, change in TINY_CONFIG.items():
        path = bench / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(change)
        path.write_text(json.dumps(cfg))
    for name, change in TINY_TRAFFIC.items():
        path = bench / "traffic" / f"{name}.json"
        mix = json.loads(path.read_text())
        mix.update(change)
        path.write_text(json.dumps(mix))
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
