"""Host checks and the plain reference on graphs whose answer is known."""
import pathlib
import sys

import ml_dtypes
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness import checks, reference  # noqa: E402
from traffic.generators import canonical, planted_edges, rng_for  # noqa: E402


def _two_triangles():
    # triangles {0,1,2} and {3,4,5} joined by the edge 2-3
    lo = np.array([0, 0, 1, 3, 3, 4, 2])
    hi = np.array([1, 2, 2, 4, 5, 5, 3])
    return 6, lo, hi, np.ones(7)


def test_modularity_of_two_triangles():
    n, lo, hi, w = _two_triangles()
    # in_c = 6 each, tot_c = 7 each, 2m = 14: Q = 12/14 - 2 * (7/14)^2
    q = checks.modularity(n, lo, hi, w, [0, 0, 0, 1, 1, 1])
    assert q == pytest.approx(12 / 14 - 0.5)


def test_disconnected_counts_split_communities():
    n, lo, hi, _ = _two_triangles()
    assert checks.disconnected(n, lo, hi, [0, 0, 0, 1, 1, 1]) == 0
    # {0, 4} share a label without an edge between them
    assert checks.disconnected(n, lo, hi, [0, 1, 1, 2, 0, 2]) == 1


def test_reference_finds_planted_groups():
    n, u, v = planted_edges(128, 32, 12, 1, rng_for(3, 0))
    lo, hi, w = canonical(n, u, v)
    lab = reference.louvain(n, lo, hi, w)
    assert checks.disconnected(n, lo, hi, lab) == 0
    truth = np.arange(n) // 32
    assert checks.modularity(n, lo, hi, w, lab) >= \
        checks.modularity(n, lo, hi, w, truth) - 1e-9
    assert len(np.unique(lab)) == 4


def test_bfloat16_modularity_departs_from_float64():
    n, u, v = planted_edges(512, 32, 12, 4, rng_for(4, 0))
    lo, hi, w = canonical(n, u, v)
    lab = np.arange(n) // 32
    q64 = checks.modularity(n, lo, hi, w, lab)
    q16 = checks.modularity(n, lo, hi, w, lab, ml_dtypes.bfloat16)
    assert abs(q16 - q64) > 1e-3


def test_tally_keeps_the_worst_and_judges_by_limits():
    t = checks.CheckTally({"disconnected": 0, "q_gap": 1e-4,
                           "q_shortfall": 0.1})
    assert not t.correct()                  # nothing read yet
    g = _two_triangles()
    q = t.answer(g, [0, 0, 0, 1, 1, 1], 12 / 14 - 0.5 + 1e-6)
    assert t.as_dict()["q_gap"]["value"] == pytest.approx(1e-6)
    t.against_reference(q, q, q + 0.05)
    assert t.correct()
    t.against_reference(q, q, q + 0.2)
    assert not t.correct()
    assert t.as_dict()["q_shortfall"]["value"] == pytest.approx(0.2)
    # read but not compared
    assert "q_ref_gap" not in t.as_dict()
    assert t.readings()["q_ref_gap"] == pytest.approx(0.2)


@pytest.mark.parametrize("reported, ok", [(0.40, True), (0.62, False),
                                          (-0.1, False)])
def test_reference_gap_reads_the_reported_modularity_either_way(reported,
                                                                 ok):
    t = checks.CheckTally({"disconnected": 0, "q_ref_gap": 0.1})
    g = _two_triangles()
    t.answer(g, [0, 0, 0, 1, 1, 1], reported)
    t.against_reference(12 / 14 - 0.5, reported, 0.45)
    assert t.correct() is ok


def test_unknown_check_is_refused():
    with pytest.raises(KeyError):
        checks.CheckTally({"q_typo": 1.0})
