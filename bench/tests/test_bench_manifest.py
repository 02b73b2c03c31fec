"""Cells, configurations, mixes, metrics, checks, faults and tiny copies
are found by name, and a cell can be added from data files alone."""
import json
import pathlib
import re
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness.manifest import load_cell, load_module, load_reader  # noqa: E402
from bench_tiny_cells import run_tiny, tiny_root  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_loads_with_its_files():
    for w in MANIFEST["workloads"]:
        cell = load_cell(w["name"], ROOT)
        assert cell.chips == w["chips"]
        assert load_module(BENCH, "drivers", cell.traffic["driver"]).Driver
        assert load_module(BENCH, "families", cell.config["family"]).make
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        # every per-layer metric moves an end-to-end metric of its cell
        for m in cell.per_layer:
            assert m["moves"] in names


def test_every_metric_has_a_reader():
    for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]:
        assert callable(load_reader(m["name"]))


def test_names_and_entries_follow_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in MANIFEST["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        load_cell("no-such-cell", ROOT)


def test_cell_added_from_data_files_alone(tmp_path):
    root = tiny_root(tmp_path)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "graph500-s15.json").read_text())
    cfg.update(scale=6, edge_factor=8)
    (bench / "configs" / "graph500-s6.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "oneshot-short.json").write_text(
        json.dumps({"driver": "oneshot", "trace_seconds": 1.0}))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "graph500-s6", "source": "x",
                                "file": "bench/configs/graph500-s6.json",
                                "reduced": ["scale"], "why": "x"})
    manifest["workloads"].append({"name": "kron6-detect",
                                  "config": "graph500-s6",
                                  "traffic": "oneshot-short", "chips": 1,
                                  "why": "x"})
    manifest["end_to_end"][1]["workloads"].append("kron6-detect")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell = load_cell("kron6-detect", root, bench)
    assert cell.config["scale"] == 6
    assert cell.traffic["trace_seconds"] == 1.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "edges_per_s"]


RING_FAMILY = '''"""Rings of cliques: a family added as one file."""
import numpy as np

from traffic.generators import canonical


def make(spec, seed, count=1):
    k, c = spec["clique"], spec["cliques"]
    n = k * c
    iu, ju = np.triu_indices(k, k=1)
    u = np.concatenate([iu + k * j for j in range(c)] + [np.arange(c) * k])
    v = np.concatenate([ju + k * j for j in range(c)]
                       + [(np.arange(c) * k + k + 1) % n])
    return [(n, *canonical(n, u, v)) for _ in range(count)]
'''

SEQUENTIAL_DRIVER = '''"""One caller, one detect() after another: a driver added as one file."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "oneshot_base", pathlib.Path(__file__).with_name("oneshot.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)


class Driver(_base.Driver):
    pass
'''


def test_family_driver_mix_and_metric_added_from_files_alone(tmp_path):
    root = tiny_root(tmp_path)
    bench = root / "bench"
    (bench / "families" / "ring_of_cliques.py").write_text(RING_FAMILY)
    (bench / "drivers" / "sequential.py").write_text(SEQUENTIAL_DRIVER)
    (bench / "metrics" / "detections_done.py").write_text(
        "def read(ctx):\n    return float(len(ctx.requests))\n")
    cfg = json.loads((bench / "configs" / "graph500-s15.json").read_text())
    cfg.update(family="ring_of_cliques", clique=5, cliques=6)
    (bench / "configs" / "ring-30.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "two-calls.json").write_text(
        json.dumps({"driver": "sequential", "graphs": 2}))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "ring-30", "source": "x",
                                "file": "bench/configs/ring-30.json",
                                "reduced": [], "why": "x"})
    manifest["workloads"].append({"name": "ring-detect", "config": "ring-30",
                                  "traffic": "two-calls", "chips": 1,
                                  "why": "x"})
    manifest["end_to_end"].append({"name": "detections_done", "unit": "n",
                                   "better": "higher", "bound": 0.01,
                                   "source": "host_clock",
                                   "workloads": ["ring-detect"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    res = run_tiny(root, "ring-detect", seconds=5.0)
    assert res["correct"], res["checks"]
    assert res["metrics"]["detections_done"]["value"] == 2.0
    assert set(res["metrics"]) == {"setup_s", "detections_done"}


REWEIGH_DRIVER = '''"""Warm updates to one held graph: a driver added as one file.

Each request adds weight 1 to ``per_batch`` existing edges drawn from the
seed through ``repro.core.update_communities``; its answer is the new
partition, the modularity the program reports for it and the program's
edge set, checked against the bench's host mirror of the graph."""
import time

import numpy as np

from harness.drivers import program_graph
from harness.faults import edge_list
from harness.window import Request
from traffic.generators import rng_for


class Driver:
    def __init__(self, cell, seed, *, annotate_on=False):
        self.cell, self.traffic = cell, cell.traffic
        self.rng = rng_for(seed, 3)
        (self.graph,) = cell.make_graphs(seed, 1)

    def _batch(self):
        lo, hi = self.graph[1], self.graph[2]
        pick = self.rng.choice(lo.size, size=self.traffic["per_batch"],
                               replace=False)
        return pick, (lo[pick], hi[pick], np.ones(pick.size, np.float32))

    def setup(self):
        import jax
        import repro.core as core

        self.g = jax.device_put(program_graph(self.graph))
        self.C = core.detect(self.g).labels
        _, batch = self._batch()
        np.asarray(core.update_communities(self.g, self.C, batch)[1])

    def window(self, t0, seconds, on_start=None):
        import repro.core as core

        n, lo, hi, w = self.graph
        w = w.copy()
        reqs = []
        for _ in range(self.traffic["batches"]):
            pick, batch = self._batch()
            r = Request(t_due=time.perf_counter())
            self.g, self.C, stats = core.update_communities(self.g, self.C,
                                                            batch)
            w[pick] += 1.0
            r.info.update(graph=(n, lo, hi, w.copy()),
                          labels=np.asarray(self.C), q=float(stats["q"]),
                          edges=edge_list(self.g))
            r.t_done = time.perf_counter()
            reqs.append(r)
        return reqs

    def work_each(self):
        return 1.0

    def release(self):
        del self.g, self.C

    def check(self, reqs, tally):
        for r in reqs:
            tally.answer(r.info["graph"], r.info["labels"], r.info["q"],
                         edges=r.info["edges"])
'''

EDGE_SET_CHECK = '''"""Edges, with their weights, in which the program's graph after an
update differs from the bench's host mirror: a check added as one file."""


def read(rec):
    if "edges" not in rec.extra:
        return None
    _, lo, hi, w = rec.graph
    _, plo, phi, pw = rec.extra["edges"]
    mirror = set(zip(lo.tolist(), hi.tolist(), w.tolist()))
    program = set(zip(plo.tolist(), phi.tolist(), pw.tolist()))
    return float(len(mirror ^ program))
'''

WARM_ALTERED_FAULT = '''"""Every warm update's labels altered where they are produced: a fault
added as one file (``repro.core.update_communities``)."""
import numpy as np

from harness.faults import alter, patched


def plant():
    import repro.core as core

    real = core.update_communities

    def update_communities(g_old, C_prev, updates, **kw):
        g, C, stats = real(g_old, C_prev, updates, **kw)
        return g, alter(C, int(np.asarray(g.n_nodes))), stats

    return patched(core, "update_communities", update_communities)
'''


def test_check_fault_and_tiny_copy_added_from_files_alone(tmp_path):
    source = tmp_path / "source"
    shutil.copytree(BENCH, source / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".autotune"))
    shutil.copy(ROOT / "BENCHMARK.json", source / "BENCHMARK.json")
    bench = source / "bench"
    (bench / "drivers" / "reweigh.py").write_text(REWEIGH_DRIVER)
    (bench / "checks" / "edge_set.py").write_text(EDGE_SET_CHECK)
    (bench / "faults" / "warm_altered.py").write_text(WARM_ALTERED_FAULT)
    cfg = json.loads((bench / "configs" / "graph500-s15.json").read_text())
    cfg.update(scale=12, checks={"disconnected": 0, "q_gap": 1e-4,
                                 "edge_set": 0})
    (bench / "configs" / "graph500-warm.json").write_text(json.dumps(cfg))
    (bench / "tests" / "tiny" / "configs" / "graph500-warm.json").write_text(
        json.dumps({"scale": 6}))
    (bench / "traffic" / "reweigh3.json").write_text(json.dumps(
        {"driver": "reweigh", "batches": 3, "per_batch": 4,
         "faults": ["warm_altered"]}))
    manifest = json.loads((source / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "graph500-warm", "source": "x",
                                "file": "bench/configs/graph500-warm.json",
                                "reduced": ["scale"], "why": "x"})
    manifest["workloads"].append({"name": "warm-update",
                                  "config": "graph500-warm",
                                  "traffic": "reweigh3", "chips": 1,
                                  "why": "x"})
    p95 = next(m for m in manifest["end_to_end"] if m["name"] == "p95_ms")
    p95["workloads"].append("warm-update")
    (source / "BENCHMARK.json").write_text(json.dumps(manifest))

    root = tiny_root(tmp_path / "tiny", source)
    cell = load_cell("warm-update", root, root / "bench")
    assert cell.config["scale"] == 6 and cell.faults == ("warm_altered",)
    res = run_tiny(root, "warm-update", seconds=30.0)
    assert res["correct"], res["checks"]
    assert res["checks"]["edge_set"] == {"value": 0.0, "limit": 0.0}
    assert set(res["metrics"]) == {"setup_s", "p95_ms"}

    from harness import faults

    with faults.planted("warm_altered", root / "bench"):
        res = run_tiny(root, "warm-update", seconds=30.0)
    assert not res["correct"], res["checks"]
    assert res["checks"]["edge_set"]["value"] == 0.0
