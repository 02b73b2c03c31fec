"""Window arithmetic: a rate over the whole window, tails from due times
over every request."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness.window import (  # noqa: E402
    Request, count_rate, in_window, latency_percentile, work_rate,
)


def test_work_rate_runs_to_the_last_completion_in_the_window():
    reqs = [Request(10.0, 20.0), Request(20.0, 30.0), Request(30.0, 41.0)]
    # window [10, 40]: two completions, the last at 30
    assert work_rate(reqs, 10.0, 30.0, 100.0) == pytest.approx(200 / 20)
    assert work_rate(reqs, 10.0, 5.0, 100.0) is None


def test_count_rate_divides_by_the_window():
    reqs = [Request(0.0, t) for t in (0.5, 1.0, 1.5, 2.5)]
    assert count_rate(reqs, 0.0, 2.0) == pytest.approx(3 / 2.0)


def test_requests_sent_before_the_window_count_where_they_complete():
    # steady state: one request in flight at the open completes inside,
    # one completed before the open does not count
    reqs = [Request(-3.0, -1.0), Request(-2.0, 1.0), Request(0.5, 1.5)]
    assert len(in_window(reqs, 0.0, 2.0)) == 2
    assert count_rate(reqs, 0.0, 2.0) == pytest.approx(1.0)
    assert latency_percentile(reqs, 0.0, 2.0, 100) == pytest.approx(3000.0)


def test_failed_and_late_requests_are_not_served():
    reqs = [Request(0.0, 1.0), Request(0.0, 1.0, error="boom"),
            Request(0.0, None), Request(0.0, 9.0)]
    assert len(in_window(reqs, 0.0, 5.0)) == 1


def test_p95_from_due_times_over_all_requests():
    # a stall: requests were due every 10 ms but all answered at 1.0 s;
    # latency counts from the due time, so the wait shows
    reqs = [Request(0.01 * i, 1.0) for i in range(100)]
    lat = [(1.0 - 0.01 * i) * 1e3 for i in range(100)]
    assert latency_percentile(reqs, 0.0, 2.0, 95) == pytest.approx(
        float(np.percentile(lat, 95)))
    assert latency_percentile([], 0.0, 2.0, 95) is None
