"""Arithmetic of the measured window: rates over all of it, tails over all
requests, every request timed from when it was due."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    """One request of the window: when it was due (its client sent it or
    the schedule said so), when its answer came back (None: never), and
    whatever the checks and the per-layer readers need."""

    t_due: float
    t_done: float | None = None
    error: str | None = None
    info: dict = dataclasses.field(default_factory=dict)


def in_window(reqs, t0: float, seconds: float):
    """Requests that completed, without error, inside ``[t0, t0+seconds]``;
    one sent before ``t0`` counts, timed from when it was due."""
    t1 = t0 + seconds
    return [r for r in reqs
            if r.error is None and r.t_done is not None
            and t0 <= r.t_done <= t1]


def work_rate(reqs, t0: float, seconds: float, work_each: float):
    """All the work completed in the window over the time from its start to
    its last completion (None when nothing completed)."""
    done = in_window(reqs, t0, seconds)
    if not done:
        return None
    return work_each * len(done) / (max(r.t_done for r in done) - t0)


def count_rate(reqs, t0: float, seconds: float) -> float:
    """Requests completed in the window over the window."""
    return len(in_window(reqs, t0, seconds)) / seconds


def latency_percentile(reqs, t0: float, seconds: float, pct: float):
    """``pct``-th percentile, in ms, of due-to-done latency over every
    request completed in the window (None when none completed)."""
    lat = [(r.t_done - r.t_due) * 1e3 for r in in_window(reqs, t0, seconds)]
    return float(np.percentile(lat, pct)) if lat else None
