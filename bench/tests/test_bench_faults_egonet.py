"""Closed-loop service cell, driven whole at a tiny size with the chip check
skipped: a sound run is correct, and the control and every planted fault
the cell can have make ``correct`` false."""
import pytest

from bench_tiny_cells import run_tiny, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


WORKLOAD = "gn128-detect"
KINDS = ("control", "stale", "altered", "half")


def test_sound_run_is_correct(root):
    res = run_tiny(root, WORKLOAD)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("kind", KINDS)
def test_planted_fault_is_not_correct(root, kind):
    from harness import faults

    with faults.planted(kind):
        res = run_tiny(root, WORKLOAD)
    assert not res["correct"], (kind, res["checks"])
