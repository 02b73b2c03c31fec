"""The four check files read, value for value, what the tally's fixed
arithmetic read before the checks became files (kept here as the
reference), on seeded graphs, answers handed over as the drivers hand
them, NaN included."""
import math
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness import checks, reference  # noqa: E402
from traffic.generators import canonical, planted_edges, rng_for  # noqa: E402

NAMES = ("disconnected", "q_gap", "q_shortfall", "q_ref_gap")


def fixed_arithmetic(answers):
    """The worst reading of each number as the tally computed it, for
    ``(graph, labels, q_reported, q_ref or None)`` answers."""
    worst = dict.fromkeys(NAMES)

    def note(name, value):
        cur = worst[name]
        if cur is None or value > cur or np.isnan(value):
            worst[name] = float(value)

    for (n, lo, hi, w), labels, q_reported, q_ref in answers:
        note("disconnected", checks.disconnected(n, lo, hi, labels))
        q = checks.modularity(n, lo, hi, w, labels)
        note("q_gap", abs(float(q_reported) - q))
        if q_ref is not None:
            note("q_shortfall", q_ref - q)
            note("q_ref_gap", abs(q_ref - float(q_reported)))
    return worst


def _answers(seed, nan_at):
    """Answers on planted-partition graphs of ``seed``: the reference's
    labels, the planted ones and random ones, with reported modularities
    off by seeded amounts; ``nan_at`` names a field read as NaN in one
    answer."""
    rng = rng_for(seed, 0)
    out = []
    for k in range(4):
        n, u, v = planted_edges(96, 24, 8, 1 + k, rng_for(seed, 1 + k))
        lo, hi, w = canonical(n, u, v)
        g = (n, lo, hi, w)
        q_ref = checks.modularity(n, lo, hi, w, reference.louvain(n, lo, hi,
                                                                  w))
        for labels in (reference.louvain(n, lo, hi, w), np.arange(n) // 24,
                       rng.integers(0, 6, n)):
            q = checks.modularity(n, lo, hi, w, labels)
            q_reported = q + float(rng.normal(0, 1e-3))
            out.append([g, labels, q_reported,
                        q_ref if rng.random() < 0.7 else None])
    if nan_at == "q_reported":
        out[5][2] = math.nan
    elif nan_at == "q_ref":
        out[4][3] = math.nan
    return [tuple(a) for a in out]


def _same(a, b):
    return a == b or (a is not None and b is not None
                      and math.isnan(a) and math.isnan(b))


@pytest.mark.parametrize("nan_at", [None, "q_reported", "q_ref"])
@pytest.mark.parametrize("seed", [3, 2_200_000_011])
def test_check_files_read_what_the_fixed_arithmetic_read(seed, nan_at):
    answers = _answers(seed, nan_at)
    want = fixed_arithmetic(answers)
    limits = {"disconnected": 0, "q_gap": 1e-4, "q_shortfall": 0.02}

    # as the drivers hand answers over: answer(), then against_reference()
    tally = checks.CheckTally(limits)
    for graph, labels, q_reported, q_ref in answers:
        q = tally.answer(graph, labels, q_reported)
        if q_ref is not None:
            tally.against_reference(q, q_reported, q_ref)

    got = tally.readings()
    assert set(got) >= set(NAMES)
    for name in NAMES:
        assert _same(got[name], want[name]), (name, got[name], want[name])
    assert tally.n_checked == len(answers)
    assert tally.n_reference == sum(a[3] is not None for a in answers)
    if nan_at is not None:
        assert not tally.correct()
