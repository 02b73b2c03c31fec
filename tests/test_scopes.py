"""Device scopes and profiler annotations: every detection phase is named in
the compiled programs' op-name metadata, and the program's spans appear in a
profiler trace on its clock."""
import glob
import json
import os
import pathlib
import re
import sys
import time

import jax
import numpy as np
import pytest

from repro.core import DetectOptions, LouvainConfig, detect
from repro.core.portfolio import detection_programs
from repro.graph import sbm_graph
from repro.graph.container import stack_graphs
from repro.service import ServiceConfig
from repro.service.buckets import Bucket, admit, filler
from repro.service.engine import BatchedLouvainEngine, _tiled
from repro.service.frontend import ServiceFrontend
from repro.telemetry import SCOPES, scope

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import scope_breakdown  # noqa: E402

pytestmark = pytest.mark.service

BUCKETS = (Bucket(64, 512), Bucket(64, 2048))
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the scopes a program of each kind must carry (sort scan: the sort step
# exists; dense scan: a community-matrix scatter replaces it)
PASS = {"local_move", "gain", "move", "renumber", "aggregate", "segreduce"}
DETECT = {"partition", "detector", "modularity"} | PASS
UPDATE = {"local_move", "gain", "move", "split", "renumber", "detector",
          "modularity", "segreduce"}


def _graph(seed=1):
    return sbm_graph(60, 3, p_in=0.3, p_out=0.02, seed=seed)[0]


def _paths(*compiled_texts):
    """Op-name paths of every instruction, without the primitive's own
    (last) component and without transform wrappers (``vmap(detector)``
    is the ``detector`` scope)."""
    return [tuple(scope_breakdown.bare(c) for c in p.split("/")[:-1])
            for t in compiled_texts for p in _OP_NAME.findall(t)]


def _components(paths):
    return {c for p in paths for c in p}


def _detect_paths(scan, split):
    g = _graph()
    opts = DetectOptions(scan=scan, louvain=LouvainConfig(split=split))
    C = detect(g, options=opts).labels
    return _paths(*(p.func.lower(*a, **p.keywords).compile().as_text()
                    for p, a in zip(detection_programs(g, opts),
                                    ((g,), (g, C), (g, C)))))


_CACHE = {}


def _paths_cache(scan, split):
    if (scan, split) not in _CACHE:
        _CACHE[(scan, split)] = _detect_paths(scan, split)
    return _CACHE[(scan, split)]


@pytest.mark.parametrize("scan,split,expect", [
    ("sort", "sp-pj", DETECT | {"sort", "split"}),
    ("dense", "sp-pj", DETECT | {"split"}),
    ("sort", "refine", DETECT | {"sort", "refine"}),
])
def test_detect_programs_carry_every_phase_scope(scan, split, expect):
    paths = _paths_cache(scan, split)
    found = _components(paths) & set(SCOPES)
    assert found == expect
    # the split slot and the detector both run split_labels: each call
    # counts as the phase that made it
    for p in paths:
        if "jit(split_labels)" in p:
            assert ("split" in p) != ("detector" in p), p


def test_sorted_reductions_carry_segreduce_in_every_phase():
    paths = _paths_cache("sort", "sp-pj")
    phases = {c for p in paths if "segreduce" in p for c in p
              if c in ("local_move", "split", "aggregate", "detector",
                       "modularity")}
    assert phases == {"local_move", "split", "aggregate", "detector",
                      "modularity"}


def test_sort_scan_places_pass_a_and_aggregation_folds_by_end_mark():
    # local move's pass A and the aggregation reduce over as many run ids
    # as entries: their folds are placed from run-end marks, inside the
    # segreduce scope (the Pallas path; 'xla' has no placement step)
    g = _graph()
    part = detection_programs(g, DetectOptions(scan="sort",
                                               seg_impl="pallas"))[0]
    paths = _paths(part.func.lower(g, **part.keywords).compile().as_text())
    marked = [set(p) for p in paths if "ends_mark" in p]
    assert any({"local_move", "gain"} <= p for p in marked)
    assert any("aggregate" in p for p in marked)
    assert all("segreduce" in p for p in paths
               if {"ends_mark", "ends_search"} & set(p))


def test_scopes_hold_every_name_a_call_site_opens():
    src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    opened = {n for f in src.rglob("*.py")
              for n in re.findall(r'\bscope\("(\w+)"\)', f.read_text())}
    assert {"segreduce", "ends_mark", "ends_search"} <= opened
    assert opened <= set(SCOPES)


def test_engine_detect_and_update_programs_carry_the_scopes():
    eng = BatchedLouvainEngine(options=DetectOptions())
    bucket = BUCKETS[0]
    b = eng.sub_batch
    tiled = _tiled(stack_graphs([filler(bucket)] * b), 1, b)
    det = eng.compiled_fn(bucket, 1).lower(tiled).compile().as_text()
    expect = DETECT | {"split"}
    if eng.scan_for(bucket) == "sort":
        expect |= {"sort"}
    assert _components(_paths(det)) & set(SCOPES) == expect
    nv = bucket.nv
    C = np.tile(np.arange(nv, dtype=np.int32), (1, b, 1))
    T = np.zeros((1, b, nv), bool)
    upd = eng.update_fn(bucket, 1).lower(tiled, C, T).compile().as_text()
    found = _components(_paths(upd)) & set(SCOPES)
    assert found == UPDATE | ({"sort"} if "sort" in found else set())


def test_scope_names_come_from_the_one_tuple():
    assert len(set(SCOPES)) == len(SCOPES)
    with pytest.raises(ValueError, match="SCOPES"):
        scope("staged")


def _host_events(log_dir):
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name[len("repro."):], e.start_ns, e.duration_ns)
            for p in data.planes for line in p.lines for e in line.events
            if e.name.startswith("repro.")]


def test_service_spans_are_profiler_events_on_its_clock(tmp_path):
    fe = ServiceFrontend(ServiceConfig(buckets=BUCKETS, batch_size=2,
                                       max_delay_s=0.01))
    graphs = [_graph(s) for s in (3, 4)]
    jax.profiler.start_trace(str(tmp_path))
    futs = [fe.submit_detect(f"g{i}", g) for i, g in enumerate(graphs)]
    fe.drain()
    jax.profiler.stop_trace()
    fe.close()
    events = _host_events(str(tmp_path))
    spans = [s for f in futs for s in f.trace.spans]
    annotated = {name for name, _, _ in events}
    assert annotated == {"repad", "admission", "drr-compose", "compile",
                         "engine-dispatch", "stack", "device-sync",
                         "unpack", "store-commit"}
    # every event agrees with a span of its name, and every span of an
    # annotated phase with an event, within 1 ms
    for name, _, dur in events:
        assert any(s.name == name and abs(s.duration_s * 1e9 - dur) < 1e6
                   for s in spans), name
    for s in spans:
        if s.name in annotated:
            assert any(n == s.name and abs(s.duration_s * 1e9 - d) < 1e6
                       for n, _, d in events), s
    # stack sits inside engine-dispatch on the profiler's clock
    (disp,) = [(t, t + d) for n, t, d in events if n == "engine-dispatch"]
    (stack,) = [(t, t + d) for n, t, d in events if n == "stack"]
    assert disp[0] <= stack[0] and stack[1] <= disp[1]


def test_detect_annotates_its_programs_and_the_fetch(tmp_path):
    g = _graph(5)
    detect(g)                                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    res = detect(g)
    jax.profiler.stop_trace()
    events = sorted(_host_events(str(tmp_path)), key=lambda e: e[1])
    assert [n for n, _, _ in events] == ["partition", "detector",
                                         "modularity", "fetch"]
    for (_, t0, d0), (_, t1, _) in zip(events, events[1:]):
        assert t0 + d0 <= t1
    assert res.n_disconnected == 0


def test_detect_runs_the_programs_detection_programs_names():
    g = _graph(6)
    opts = DetectOptions(scan="sort")
    res = detect(g, options=opts)
    progs = detection_programs(g, opts)
    sizes = [p.func._cache_size() for p in progs]
    C, stats = progs[0](g)
    det = progs[1](g, C)
    q = progs[2](g, C)
    # the same compiled programs: calling them compiles nothing new
    assert [p.func._cache_size() for p in progs] == sizes
    np.testing.assert_array_equal(np.asarray(C), np.asarray(res.labels))
    assert int(det["n_disconnected"]) == res.n_disconnected
    assert float(q) == res.modularity
    assert int(stats["li_total"]) == int(res.stats["li_total"])


def test_engine_dispatch_starts_at_batch_entry_and_stack_after_lookup():
    # the program lookup (on a bucket's first use: autotune and compile
    # key) is dispatch time, inside engine-dispatch and before stack
    eng = BatchedLouvainEngine(options=DetectOptions())
    lookup = eng.compiled_fn

    def slow_lookup(*a, **kw):
        time.sleep(0.05)
        return lookup(*a, **kw)

    eng.compiled_fn = slow_lookup
    padded = [admit(_graph(s), BUCKETS)[0] for s in (7, 8)]
    t0 = time.perf_counter()
    eng.detect_batch(padded)
    info = eng.last_detect_info
    assert t0 <= info.t_start <= info.t_stack0 - 0.05
    assert info.t_stack0 <= info.t_stacked <= info.t_call0 <= info.t_call1
    assert info.t_call1 <= info.t_sync <= info.t_unpacked


HLO = """HloModule jit_prog, entry_computation_layout={()->()}
  %sort.3 = s32[64]{0} sort(s32[64]{0} %x), metadata={op_name="jit(p)/partition/local_move/sort/sort"}
  %fusion.7 = s32[64]{0} fusion(s32[64]{0} %y), kind=kLoop, metadata={op_name="jit(p)/vmap(detector)/jit(split_labels)/segreduce/min"}
  %fusion.8 = s32[64]{0} fusion(s32[64]{0} %y), kind=kLoop, metadata={op_name="jit(p)/partition/jit(split_labels)/split"}
  ROOT %while.2 = (s32[64]{0}) while((s32[64]{0}) %t), metadata={op_name="jit(p)/partition/while"}
"""


def test_breakdown_joins_operations_to_whole_scope_components():
    paths = {"jit_prog": scope_breakdown.op_paths(HLO)}
    assert paths["jit_prog"]["while.2"][0] == "while"
    us = 1e3
    ops = [("jit_prog", "sort.3", "sort", 0, 3 * us, "tpu"),
           ("jit_prog", "fusion.7", "fusion", 3 * us, 4 * us, "tpu"),
           ("jit_prog", "fusion.8", "", 4 * us, 5 * us, "tpu"),   # no opcode
           ("jit_prog", "while.2", "", 0, 9 * us, "tpu"),     # a container
           ("jit_other", "fusion.1", "fusion", 10 * us, 15 * us, "tpu")]
    out = scope_breakdown.breakdown(ops, paths, SCOPES)
    assert out["op_seconds"] == pytest.approx(10e-6)
    sc = out["scopes"]
    # vmap(detector) is the detector scope; jit(split_labels) is no split
    # scope, and a trailing "split" names the primitive, not the scope
    assert sc["detector"] == pytest.approx(10.0)
    assert sc["segreduce"] == pytest.approx(10.0)
    assert sc["split"] == 0.0
    assert sc["partition"] == pytest.approx(40.0)
    assert sc["local_move"] == sc["sort"] == pytest.approx(30.0)
    # the other program's operation has no known path
    assert out["unscoped"] == out["no_path"] == pytest.approx(50.0)
    assert out["top"][0][:2] == ["jit_other", "fusion.1"]
    assert out["programs"] == pytest.approx({"jit_prog": 5e-6,
                                             "jit_other": 5e-6})


STRUNG = """HloModule jit_prog, entry_computation_layout={()->()}
  %while.1 = s32[] while(s32[] %a), metadata={op_name="jit(p)/partition/local_move/segreduce/jit(searchsorted)/while"}
  %while.2 = s32[] while(s32[] %b), metadata={op_name="jit(p)/partition/aggregate/segreduce/jit(searchsorted)/while"}
  %fusion.9 = s32[8]{0} fusion(s32[8]{0} %c), kind=kLoop, metadata={op_name="jit(p)/partition/aggregate/jit(p)/partition/local_move/segreduce/jit(searchsorted)/while/body/gather"}
"""


def test_breakdown_places_a_shared_loop_body_by_its_loop():
    # one loop body shared by two call sites carries both prefixes; each
    # run of it belongs to the loop that ran it
    paths = {"jit_prog": scope_breakdown.op_paths(STRUNG)}
    ops = [("jit_prog", "while.1", "while", 0, 10, "tpu"),
           ("jit_prog", "fusion.9", "fusion", 1, 4, "tpu"),
           ("jit_prog", "while.2", "while", 20, 30, "tpu"),
           ("jit_prog", "fusion.9", "fusion", 21, 22, "tpu")]
    out = scope_breakdown.breakdown(ops, paths, SCOPES)
    assert out["scopes"]["local_move"] == pytest.approx(75.0)
    assert out["scopes"]["aggregate"] == pytest.approx(25.0)
    assert out["scopes"]["segreduce"] == pytest.approx(100.0)
    assert {row[2] for row in out["top"]} == {
        "jit(p)/partition/local_move/segreduce/jit(searchsorted)/while/"
        "body/gather",
        "jit(p)/partition/aggregate/segreduce/jit(searchsorted)/while/"
        "body/gather"}


def test_breakdown_script_splits_a_cpu_trace_by_scope(capsys):
    assert scope_breakdown.main(["--scale", "10", "--edge-factor", "8"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(out["scopes"]) == set(SCOPES)
    sc = out["scopes"]
    # the three programs of a detection split the time with what carries
    # no scope, and the phases of a pass lie inside the partition
    assert sc["partition"] + sc["detector"] + sc["modularity"] \
        + out["unscoped"] == pytest.approx(100.0)
    assert sc["local_move"] + sc["split"] + sc["aggregate"] \
        <= sc["partition"] + 1e-9
    assert out["unscoped"] < 20.0
