"""engine_host_ms_per_batch.detect: the stack and unpack spans of each
distinct engine dispatch, averaged over the dispatches."""
import pathlib
import sys
import types

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness.manifest import load_reader  # noqa: E402
from harness.window import Request  # noqa: E402

READ = load_reader("engine_host_ms_per_batch.detect")


def _req(t, stack, unpack, extra=()):
    """A request served by the batch dispatched at ``t`` (seconds): its
    engine spans, the stack span inside engine-dispatch."""
    spans = [("queue-wait", t - 1.0, t),
             ("engine-dispatch", t, t + 0.010),
             ("stack", t, t + stack),
             ("device-sync", t + 0.010, t + 0.030),
             ("unpack", t + 0.030, t + 0.030 + unpack), *extra]
    return Request(t - 1.0, t + 0.05, info={"spans": spans})


def _ctx(requests):
    return types.SimpleNamespace(requests=requests)


def test_each_batch_counts_once_whatever_its_size():
    # batch A (three requests): 4 ms stack + 1 ms unpack; batch B (one
    # request): 2 ms + 1 ms -> (5 + 3) / 2 batches
    reqs = [_req(10.0, 0.004, 0.001) for _ in range(3)]
    reqs.append(_req(20.0, 0.002, 0.001))
    assert READ(_ctx(reqs)) == pytest.approx(4.0)


def test_no_reading_without_the_spans():
    # a program without stack/unpack spans (older trees) and no requests
    old = Request(0.0, 1.0, info={"spans": [("engine-dispatch", 0.0, 0.1),
                                            ("device-sync", 0.1, 0.2)]})
    assert READ(_ctx([old])) is None
    assert READ(_ctx([])) is None
    assert READ(_ctx([Request(0.0, 1.0)])) is None
