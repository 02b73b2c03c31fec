"""The trace -> metrics reduction on a small synthetic trace."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness.trace import (  # noqa: E402
    Event, Plane, TraceSummary, hlo_bytes, merge, result_elements, roofline,
)

PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
SCAN = ("%segscan_blocked.7 = f32[2,1024]{1,0:T(2,128)} custom-call("
        "s32[1,1024]{1,0:T(1,128)} %a, f32[2,1024]{1,0:T(2,128)} %b), "
        "custom_call_target=\"tpu_custom_call\", backend_config=\"f32[9]\"")
SORT = "%sort.3 = s32[64]{0} sort(s32[64]{0} %x), dimensions={0}"
GATHER = "%fusion.1 = s32[64]{0} fusion(s32[64]{0} %sort.3), kind=kCustom"
LOOP = "%while.9 = (s32[64]{0}, f32[]) while((s32[64]{0}, f32[]) %t)"


def _trace():
    # window 0..100 us; device ops cover 0-30 (sort and a gather
    # overlapping it), 50-60 (scan kernel), 90-100 (sort); idle 30-50 and
    # 60-90, the second while the host fetches results
    us = 1e3
    ops = [Event(LOOP, 0, 30 * us),
           Event(SORT, 0, 20 * us), Event(GATHER, 10 * us, 20 * us),
           Event(SCAN, 50 * us, 10 * us), Event(SORT, 90 * us, 10 * us)]
    host = [Event("bench.window", 0, 100 * us),
            Event("bench.detect", 0, 40 * us),
            Event("bench.fetch", 60 * us, 35 * us)]
    return [Plane("/device:TPU:0", {"XLA Ops": ops, "XLA Modules": [
                Event("jit_detect", 0, 100 * us)]}),
            Plane("/host:CPU", {"python": host})]


def test_busy_is_union_and_idle_share():
    t = TraceSummary(_trace(), min_gap_ns=1e3)
    assert t.window_s == pytest.approx(100e-6)
    assert t.busy_s == pytest.approx(50e-6)      # 30 + 10 + 10 us
    assert t.idle_share() == pytest.approx(50.0)


def test_op_class_shares_leave_containers_out():
    t = TraceSummary(_trace())
    # the while loop spans the sort and the gather: it counts once, as them
    assert t.op_seconds(r"sort") == pytest.approx(30e-6)
    assert t.op_seconds() == pytest.approx(60e-6)
    assert t.top_ops(2) == [
        ["%sort.3 = s32[64] sort(s32[64] %x)", pytest.approx(30e-6)],
        ["%fusion.1 = s32[64] fusion(s32[64] %sort.3)",
         pytest.approx(20e-6)]]


def test_gaps_labelled_by_host_annotation():
    t = TraceSummary(_trace(), min_gap_ns=1e3)
    gaps = t.idle_gaps()
    assert gaps[0] == ["bench.fetch", pytest.approx(30e-6)]
    assert gaps[1] == ["bench.detect", pytest.approx(20e-6)]


def test_hlo_bytes_and_kernel_roofline():
    # result f32[2,1024] + operands s32[1,1024] and f32[2,1024]; layouts
    # and the backend config are no shapes
    assert hlo_bytes(SCAN) == 4 * (2048 + 1024 + 2048)
    assert result_elements(SCAN) == 2048
    t = TraceSummary(_trace())
    share, bound = roofline(t.matching("segscan"), PEAKS)
    least = hlo_bytes(SCAN) / PEAKS["hbm_bytes_per_s"]
    assert share == pytest.approx(100 * least / 10e-6)
    assert bound == "bandwidth"
    share, bound = roofline(t.matching("segscan"), PEAKS,
                            flops_of=lambda e: 1e8)
    assert bound == "compute" and share == pytest.approx(100 * 1e-4 / 10e-6)


def test_no_shapes_reads_nothing():
    t = TraceSummary(_trace())
    assert roofline(t.matching("no-such-op"), PEAKS) == (None, None)
    assert TraceSummary([Plane("/host:CPU", {})]).idle_share() is None


def test_merge():
    assert merge([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
