"""A run measures the chip or nothing: no TPU, no result."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness.device import NoChip, peaks_for, require_chips  # noqa: E402


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_cpu_is_refused():
    with pytest.raises(NoChip, match="no TPU"):
        require_chips([_Dev("cpu", "cpu")], 1)


def test_too_few_chips_are_refused():
    with pytest.raises(NoChip):
        require_chips([_Dev("tpu", "TPU v5 lite")], 4)
    dev = require_chips([_Dev("tpu", "TPU v5 lite")] * 4, 4)
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite", BENCH / "peaks.json")[
        "hbm_bytes_per_s"] == 819e9
    with pytest.raises(NoChip):
        peaks_for("TPU v9 imaginary", BENCH / "peaks.json")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron15-detect",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_without_the_system_under_test_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".autotune"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
