"""Every cell, driven whole at a tiny size with the chip check skipped: a
sound run is correct, and the control and every planted fault the cell can
have (its mix's ``faults``, each a file ``faults/<kind>.py``) make
``correct`` false.  A new cell gets these cases from its files alone."""
import json

import pytest

from bench_tiny_cells import BENCH, ROOT, run_tiny, tiny_root
from harness.manifest import load_cell, names

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
PLANTED = [(cell, kind) for cell in CELLS
           for kind in load_cell(cell, ROOT).faults]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(root, workload):
    res = run_tiny(root, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("workload, kind", PLANTED)
def test_planted_fault_is_not_correct(root, workload, kind):
    from harness import faults

    with faults.planted(kind, root / "bench"):
        res = run_tiny(root, workload)
    assert not res["correct"], (kind, res["checks"])


def test_every_cell_names_faults_that_are_files():
    for cell in CELLS:
        kinds = load_cell(cell, ROOT).faults
        assert "control" in kinds
        assert set(kinds) <= set(names(BENCH, "faults"))
