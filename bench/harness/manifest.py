"""Find a cell and everything it names, by name, under the benchmark root.

``BENCHMARK.json`` lists cells (``workloads``), configurations and metrics.
Each name leads to a file of its own under the benchmark directory:

* a configuration: the ``file`` its entry names (``configs/<config>.json``);
* a traffic mix: ``traffic/<mix>.json``, whose ``driver`` names
  ``drivers/<driver>.py`` (a class ``Driver``);
* a graph family: the configuration's ``family`` names
  ``families/<family>.py`` (a function ``make(spec, seed, count)``);
* a metric, end-to-end or per-layer: ``metrics/<metric>.py`` (a function
  ``read(ctx)``);
* a check, compared or only read: ``checks/<check>.py`` (a function
  ``read(rec)``, ``harness/checks.py``); the configuration's ``checks``
  names the ones it compares, with their limits;
* a planted fault: ``faults/<kind>.py`` (a context manager ``plant()``,
  ``harness/faults.py``); the mix's ``faults`` lists the cell's kinds;
* a tiny copy for the CPU tests: ``tests/tiny/<dir>/<name>.json``, the keys
  that replace those of ``<dir>/<name>.json`` (``tests/bench_tiny_cells.py``).

So a cell, a mix, a driver, a family, a metric, a check, a fault or a tiny
copy is added by adding files and entries, with no edit to a file that is
already there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple
    bench_dir: pathlib.Path

    def driver(self, seed: int, *, annotate_on: bool = False):
        """The cell's traffic driver for ``seed``."""
        mod = load_module(self.bench_dir, "drivers", self.traffic["driver"])
        return mod.Driver(self, seed, annotate_on=annotate_on)

    def make_graphs(self, seed: int, count: int = 1):
        """``count`` graphs of the configuration's family from ``seed``:
        a list of ``(n, lo, hi, w)`` canonical undirected edge lists."""
        mod = load_module(self.bench_dir, "families", self.config["family"])
        return mod.make(self.config, seed, count)

    @property
    def faults(self) -> tuple:
        """The kinds of fault the cell can have (its mix's ``faults``)."""
        return tuple(self.traffic["faults"])


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path,
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration
    and traffic files read from ``bench_dir``."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in manifest["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in manifest["per_layer"]
                        if _reports(m, name)),
        bench_dir=bench_dir)


def names(bench_dir: pathlib.Path, kind: str) -> list:
    """The names of the files ``<bench_dir>/<kind>/<name>.py``."""
    return sorted(p.stem for p in (bench_dir / kind).glob("*.py"))


def load_module(bench_dir: pathlib.Path, kind: str, name: str):
    """The module ``<bench_dir>/<kind>/<name>.py``."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {path.name!r} in {path.parent}; "
                       f"found: {names(bench_dir, kind)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return load_module(bench_dir, "metrics", metric).read
