"""Device time of one ``detect()`` split by program scope, from a trace.

    PYTHONPATH=src python scripts/scope_breakdown.py [--workload kron15-detect]
        [--seed 1] [--scale N] [--edge-factor N]

Builds the benchmark cell's graph (``BENCHMARK.json``: the configuration
file and its graph family under ``bench/``, the first graph in ``--seed``'s
order, as ``bench/run.py`` would detect it first), traces one warm
``repro.core.detect`` of it with ``jax.profiler`` and prints one JSON
object: the share of device operation time under each scope of
``repro.telemetry.spans.SCOPES`` (a scope counts when it is a whole
component of the operation's op-name path), the share under none of them,
and the longest operations with their program, instruction and path.
``--scale`` / ``--edge-factor`` replace the configuration's for a smaller
graph of the same family.

``jax.profiler.ProfileData`` gives a device operation its instruction's
HLO text and timing, not its op-name path.  The path comes from the
optimized HLO text of the program that ran it (``Compiled.as_text()``,
which carries ``metadata={op_name=...}`` per instruction), joined on the
program's name and the instruction's name.  The program of a TPU
operation is the ``XLA Modules`` event that contains it; a CPU operation
names its program and instruction in its ``hlo_module`` / ``hlo_op``
stats.  The trace is read with the benchmark's own reader
(``bench/harness/trace.py``).

A computation that several call sites share (the body of
``jnp.searchsorted``'s loop, called from every sorted segment reduction)
gets one path that strings together every call site's prefix.  Such an
operation takes the path of the innermost loop or call around it in the
trace whose own path is whole, followed by the rest of its own path.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import pathlib
import re
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
from harness.trace import (  # noqa: E402
    CONTAINERS, OPS_LINE, inst_name, load, opcode, profile_options,
)

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\('
                    r'.*?metadata=\{[^}]*op_name="([^"]*)"', re.M)
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_TRANSFORM = re.compile(r"([a-z_]+)\((.*)\)")


def op_paths(hlo_text: str) -> dict:
    """Instruction name -> ``(opcode, op-name path)``, for every
    instruction of an HLO module's text that carries a path."""
    return {name: (code, path)
            for name, code, path in _INSTR.findall(hlo_text)}


def module_name(name: str) -> str:
    """``jit__partition_jit(1407...)`` -> ``jit__partition_jit``."""
    return name.split("(", 1)[0]


def bare(component: str) -> str:
    """A path component without transform wrappers: ``vmap(detector)`` ->
    ``detector``; a jitted function's ``jit(local_move)`` stays as it is."""
    m = _TRANSFORM.fullmatch(component)
    while m and m.group(1) != "jit":
        component = m.group(2)
        m = _TRANSFORM.fullmatch(component)
    return component


def scopes_of(path: str, scopes) -> tuple:
    """The scopes that are whole components of ``path`` (its last
    component names the primitive, not a scope)."""
    parts = {bare(c) for c in path.split("/")[:-1]}
    return tuple(s for s in scopes if s in parts)


def device_ops(planes):
    """``(module, instruction, opcode, start_ns, end_ns, lane)`` of every
    device operation of ``harness.trace.load``'s planes: TPU ``XLA Ops``
    events (program by containment in ``XLA Modules``) and CPU events that
    name their program and instruction; ``lane`` is the event's plane and
    line."""
    out = []
    for plane in planes:
        if OPS_LINE in plane.lines:
            mods = sorted((e.start_ns, e.end_ns, module_name(e.name))
                          for e in plane.lines.get("XLA Modules", ()))
            for e in plane.lines[OPS_LINE]:
                code = opcode(e.name)
                if not code or e.dur_ns <= 0:
                    continue
                mod = next((n for s, t, n in mods
                            if s <= e.start_ns <= t), "")
                out.append((mod, inst_name(e.name), code, e.start_ns,
                            e.end_ns, plane.name))
            continue
        for name, evs in plane.lines.items():
            for e in evs:
                st = e.stats
                if "hlo_op" in st and "hlo_module" in st and e.dur_ns > 0:
                    out.append((module_name(str(st["hlo_module"])),
                                str(st["hlo_op"]), "", e.start_ns,
                                e.end_ns, (plane.name, name)))
    return out


def _strung(path: str) -> bool:
    """Whether ``path`` strings several call sites' prefixes together
    (its first component comes back)."""
    parts = path.split("/")
    return parts.count(parts[0]) > 1


def _resolve(rows):
    """Give each operation with a strung path the path of the innermost
    container around it whose path is whole, followed by the rest of its
    own path (after that container's last component)."""
    conts = collections.defaultdict(list)
    for r in rows:
        if r[2] in CONTAINERS and r[6] and not _strung(r[6]):
            conts[r[5]].append((r[3], r[4], r[6]))
    for c in conts.values():
        c.sort()
    out = []
    for r in rows:
        path = r[6]
        if path and _strung(path):
            c = conts.get(r[5], [])
            i = bisect.bisect_right(c, (r[3], float("inf"), "")) - 1
            while i >= 0 and not (c[i][0] <= r[3] and r[4] <= c[i][1]):
                i -= 1
            if i >= 0:
                head = c[i][2]
                last = head.rsplit("/", 1)[-1]
                tail = path.rsplit("/" + last + "/", 1)[-1]
                path = head + "/" + tail
        out.append(r[:6] + (path,))
    return out


def breakdown(ops, paths: dict, scopes, top: int = 12) -> dict:
    """Shares (%) of the operations' time under each scope and under none,
    and each program's device seconds.

    ``ops``: :func:`device_ops` rows; ``paths``: module -> :func:`op_paths`.
    Containers (``while``/``conditional``/``call``) are left out, so no
    time counts twice."""
    full = []
    for mod, instr, code, t0, t1, lane in ops:
        code_hlo, path = paths.get(mod, {}).get(instr, ("", None))
        full.append((mod, instr, code or code_hlo, t0, t1, lane, path))
    rows = [(mod, instr, path, (t1 - t0) / 1e9)
            for mod, instr, code, t0, t1, _, path in _resolve(full)
            if code not in CONTAINERS]
    total = sum(r[3] for r in rows)
    per = collections.Counter()
    programs = collections.Counter()
    unscoped = unknown = 0.0
    longest = collections.Counter()
    for mod, instr, path, sec in rows:
        programs[mod] += sec
        if path is None:
            unknown += sec
        found = scopes_of(path or "", scopes)
        for s in found:
            per[s] += sec
        if not found:
            unscoped += sec
        longest[(mod, instr, path or "")] += sec
    pct = (lambda x: 100.0 * x / total) if total else (lambda x: 0.0)
    return {
        "op_seconds": total,
        "scopes": {s: pct(per[s]) for s in scopes},
        "unscoped": pct(unscoped),
        "no_path": pct(unknown),
        "programs": dict(programs.most_common()),
        "top": [[mod, instr, path, sec]
                for (mod, instr, path), sec in longest.most_common(top)],
    }


def trace_detect(g, options, log_dir: str):
    """Trace one warm ``detect(g)``: the planes of the trace, and each
    program's op-name paths from its optimized HLO."""
    import jax
    import numpy as np
    from repro.core import detect
    from repro.core.portfolio import detection_programs

    np.asarray(detect(g, options=options).labels)      # warm every program
    jax.profiler.start_trace(log_dir, profiler_options=profile_options())
    res = detect(g, options=options)
    np.asarray(res.labels)
    jax.profiler.stop_trace()
    planes = load(log_dir)

    args = ((g,), (g, res.labels), (g, res.labels))
    texts = [p.func.lower(*a, **p.keywords).compile().as_text()
             for p, a in zip(detection_programs(g, options), args)]
    paths = {_MODULE.search(t).group(1): op_paths(t) for t in texts}
    return planes, paths


def cell_graph(workload: str, seed: int, scale=None, edge_factor=None):
    """The first graph, in ``seed``'s order, of the cell ``workload``'s
    configuration (``scale`` / ``edge_factor`` replaced where given), and
    the configuration's ``DetectOptions``."""
    from harness.drivers import program_graph
    from harness.manifest import load_cell, load_module
    from repro.core import DetectOptions

    cell = load_cell(workload, ROOT)
    spec = dict(cell.config)
    for key, value in (("scale", scale), ("edge_factor", edge_factor)):
        if value is not None:
            spec[key] = value
    family = load_module(cell.bench_dir, "families", spec["family"])
    graphs = family.make(spec, seed, int(cell.traffic.get("graphs", 1)))
    return (program_graph(graphs[0]),
            DetectOptions(**spec.get("detect", {})))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="kron15-detect")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--scale", type=int)
    ap.add_argument("--edge-factor", type=int)
    args = ap.parse_args(argv)
    import jax
    from repro.telemetry.spans import SCOPES

    graph, options = cell_graph(args.workload, args.seed, args.scale,
                                args.edge_factor)
    g = jax.device_put(graph)
    with tempfile.TemporaryDirectory() as d:
        planes, paths = trace_detect(g, options, d)
        out = breakdown(device_ops(planes), paths, SCOPES)
    out["graph"] = {"workload": args.workload, "seed": args.seed,
                    "nv": int(g.n_nodes), "directed_entries": int(g.m_cap)}
    out["device"] = jax.devices()[0].device_kind
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
