"""Profiler trace capture and its reduction to device metrics.

A traced run wraps its window in ``jax.profiler`` tracing with the Python
tracer off, then reads the ``.xplane.pb`` back with ``jax.profiler.
ProfileData``.  The reduction works on plain :class:`Plane`/:class:`Event`
records, so the tests can feed it a synthetic trace:

* device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
  event per operation run on the device;
* busy time is the union of a device's operation intervals inside the
  traced window, averaged over the devices; idle share is 1 - busy/window;
* an idle gap is a stretch of the window in which a device ran nothing,
  labelled by the benchmark's own host annotation (``bench.*``) that
  overlaps it most;
* an operation's bytes come from the shapes in its HLO text (the result
  and every operand), counted by :func:`hlo_bytes`, whatever implements it.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import os
import re

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_ANNOTATION = "bench.window"
HOST_PREFIXES = ("bench.",)

# operations that contain others on the same line: their time is their
# children's, so they count towards busy time but not towards any share
CONTAINERS = ("while", "conditional", "call")

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
_SHAPE = re.compile(r"\b(pred|bf16|[suf](?:8|16|32|64))\[([0-9,]*)\]")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict          # line name -> list[Event]


# -- capture -------------------------------------------------------------------

def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def load(log_dir: str) -> list:
    """Every plane of the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for p in data.planes:
        lines = {}
        for line in p.lines:
            lines.setdefault(line.name, []).extend(
                Event(e.name, float(e.start_ns), float(e.duration_ns),
                      {k: v for k, v in e.stats})
                for e in line.events)
        planes.append(Plane(p.name, lines))
    return planes


# -- reduction -------------------------------------------------------------------

def merge(intervals):
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@functools.lru_cache(maxsize=1 << 14)
def _parse(text: str):
    """``(name, opcode, signature)`` of an HLO instruction's text; the
    signature drops layouts and stops after the operand list."""
    text = _LAYOUT.sub("", text)
    m = _OPCODE.match(text)
    if not m:
        return text, "", text
    depth = 0
    for j in range(m.end() - 1, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            return m.group(1), m.group(2), text[:j + 1]
    return m.group(1), m.group(2), text


def signature(text: str) -> str:
    """``%name = <result> opcode(<operands>)``, without layouts."""
    return _parse(text or "")[2]


def opcode(text: str) -> str:
    return _parse(text or "")[1]


def inst_name(text: str) -> str:
    """The instruction's name (``fusion.558``) without the ``%``."""
    return _parse(text or "")[0]


def _elements(dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def hlo_bytes(text: str) -> int:
    """Bytes of the result and the operands of an HLO instruction, from the
    shapes in its signature."""
    return sum(_elements(dims) * DTYPE_BYTES[dt]
               for dt, dims in _SHAPE.findall(signature(text)))


def result_elements(text: str) -> int:
    """Elements of the instruction's (first) result."""
    shapes = _SHAPE.findall(signature(text).split(" = ", 1)[-1])
    return _elements(shapes[0][1]) if shapes else 0


def hlo_text(ev: Event) -> str:
    """The HLO text of a device operation: the TPU trace names each event
    by its instruction's text."""
    return ev.name


class TraceSummary:
    """The reduction of one traced window."""

    def __init__(self, planes, window_ns=None, min_gap_ns: float = 1e5):
        self.devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
        self.host = [e for p in planes if not DEVICE_PLANE.match(p.name)
                     for evs in p.lines.values() for e in evs
                     if e.name.startswith(HOST_PREFIXES)]
        if window_ns is None:
            marks = [e for e in self.host if e.name == WINDOW_ANNOTATION]
            if marks:
                window_ns = (min(e.start_ns for e in marks),
                             max(e.end_ns for e in marks))
        self.all_ops = [[e for e in p.lines.get(OPS_LINE, [])
                         if e.dur_ns > 0] for p in self.devices]
        self.ops = [[e for e in ops if opcode(hlo_text(e)) not in CONTAINERS]
                    for ops in self.all_ops]
        if window_ns is None:
            spans = [(e.start_ns, e.end_ns) for ops in self.all_ops
                     for e in ops]
            window_ns = (min(s for s, _ in spans), max(e for _, e in spans)) \
                if spans else (0.0, 0.0)
        self.t0, self.t1 = window_ns
        self.min_gap_ns = min_gap_ns

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _busy(self, ops):
        return merge(clip([(e.start_ns, e.end_ns) for e in ops],
                          self.t0, self.t1))

    @property
    def busy_s(self) -> float:
        """Seconds some operation ran, averaged over the devices."""
        if not self.all_ops:
            return 0.0
        tot = [sum(e - s for s, e in self._busy(ops)) for ops in self.all_ops]
        return sum(tot) / len(tot) / 1e9

    def idle_share(self) -> float | None:
        if not self.ops or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def matching(self, pattern: str):
        """Operations (containers left out) whose instruction name matches
        ``pattern``."""
        rx = re.compile(pattern)
        return [e for ops in self.ops for e in ops
                if rx.search(inst_name(hlo_text(e)))]

    def op_seconds(self, pattern: str | None = None) -> float:
        """Device seconds of the operations whose instruction name matches
        ``pattern`` (all when None), summed over the devices; containers
        are left out, so nothing counts twice."""
        evs = self.matching(pattern) if pattern else [
            e for ops in self.ops for e in ops]
        return sum(e.dur_ns for e in evs) / 1e9

    def top_ops(self, k: int = 10, width: int = 160):
        """``[[signature, seconds], ...]`` of the operations that took most
        time, summed over their runs and the devices."""
        acc = collections.Counter()
        for ops in self.ops:
            for e in ops:
                acc[signature(hlo_text(e))[:width]] += e.dur_ns / 1e9
        return [[n, s] for n, s in acc.most_common(k)]

    def gaps(self):
        """Idle stretches of each device inside the window, longer than
        ``min_gap_ns``: ``[(start_ns, end_ns), ...]``."""
        out = []
        for ops in self.all_ops:
            cur = self.t0
            for s, e in self._busy(ops) + [[self.t1, self.t1]]:
                if s - cur > self.min_gap_ns:
                    out.append((cur, s))
                cur = max(cur, e)
        return out

    def label(self, gap) -> str:
        """The benchmark host annotation overlapping ``gap`` the most."""
        s, e = gap
        best, best_ov = "other", 0.0
        for h in self.host:
            if h.name == WINDOW_ANNOTATION:
                continue
            ov = min(e, h.end_ns) - max(s, h.start_ns)
            if ov > best_ov:
                best, best_ov = h.name, ov
        return best

    def idle_gaps(self, k: int = 10):
        """``[[label, seconds], ...]`` of the ``k`` longest idle gaps."""
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:k]
        return [[self.label(g), (g[1] - g[0]) / 1e9] for g in gaps]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def roofline(events, peaks: dict, flops_of=None):
    """Share (%) of the least time the chip could take for ``events`` over
    their device time, and which bound sets that least time.

    Bytes are the result and operands of each event's HLO instruction;
    ``flops_of(event)`` gives its operations (None: none counted).  Returns
    ``(None, None)`` when no event carries shapes."""
    least = device = 0.0
    t_mem = t_flop = 0.0
    for e in events:
        nbytes = hlo_bytes(hlo_text(e))
        if not nbytes:
            continue
        flops = flops_of(e) if flops_of else 0.0
        tm = nbytes / peaks["hbm_bytes_per_s"]
        tf = flops / peaks["flops_per_s"]
        least += max(tm, tf)
        t_mem += tm
        t_flop += tf
        device += e.dur_ns / 1e9
    if device <= 0 or least <= 0:
        return None, None
    return 100.0 * least / device, ("bandwidth" if t_mem >= t_flop
                                    else "compute")
