"""Cells, configurations, mixes and metrics are found by name, and a cell
can be added from data files alone."""
import json
import pathlib
import re
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
