"""Span/trace layer: per-request lifecycle timing.

A request through the service passes a fixed set of phases::

    submit -> admission -> queue-wait -> drr-compose -> repad ->
    compile(hit/miss) -> engine-dispatch (stack inside) -> device-sync ->
    unpack -> store-commit -> resolve

Each phase is recorded as a :class:`Span` — a name plus monotonic-clock
``(t_start, t_end)`` — inside the request's :class:`RequestTrace`.  The
trace id is the request id (``d17-gid`` / ``u3-gid``), surfaced on
``DetectionFuture.trace`` so callers can inspect where their time went
without any global registry.

Per-request phases (``submit``, ``admission``, ``queue-wait``,
``repad``, ``store-commit``, ``resolve``) are marked individually;
batch-level phases (``drr-compose``, ``compile``, ``engine-dispatch``,
``device-sync``) happen once per dispatched batch and are stamped onto
every member request's trace with the same interval — a trace therefore
reads as "this request's batch spent X in the engine", which is the
number that matters for per-phase latency attribution.

Spans carry optional string labels (e.g. ``compile`` marks
``hit="true"|"false"``).  Completed traces are broadcast to the
telemetry hub (:mod:`repro.telemetry.sinks`) at resolve time.

One naming scheme serves the host and the device.  A span timed with
:meth:`RequestTrace.span`, and every batch-level span where the engine
and front end do its work, also opens the profiler annotation
``repro.<name>`` (:func:`annotate`; a no-op while no profiler trace is
active), so a ``jax.profiler`` trace shows the program's phases on the
profiler's own clock.  Inside the detection programs, :func:`scope` names
each phase with ``jax.named_scope``; the names are :data:`SCOPES`, and
the compiled operations carry them as components of their op-name path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax

# canonical phase taxonomy, in lifecycle order (docs + tests key off this)
PHASES = (
    "submit",          # entry-point work before enqueue (validate, repad..)
    "admission",       # bound check + locked enqueue
    "queue-wait",      # enqueue -> popped by DRR compose
    "drr-compose",     # weighted-DRR batch composition
    "repad",           # bucket padding (inside submit on the detect path)
    "compile",         # jit cache consult; labels: hit=true|false
    "engine-dispatch", # traced jax dispatch (host -> device)
    "stack",           # filler, stack, reshape, transfer (in engine-dispatch)
    "device-sync",     # device -> host transfer + np conversion
    "unpack",          # per-result conversion + engine counters
    "store-commit",    # versioned store write
    "resolve",         # future resolution fan-out
)

# phases that lie inside another phase's interval, so totals skip them
NESTED: Dict[str, str] = {"stack": "engine-dispatch"}

# device scopes of the detection programs (jax.named_scope), outermost
# first: the three programs of one detection, the phases of a pass, the
# steps of a local-move half-sweep, and every sorted segment reduction
SCOPES = (
    "partition",       # the multi-pass loop (every tier)
    "local_move",      # local-moving phase of a pass
    "sort",            # half-sweep: sort by (src, C[dst]) + payload gathers
    "gain",            # half-sweep: run sums, Eq.-2 gains, best target
    "move",            # half-sweep: apply moves, merge, recompute Sigma
    "split",           # split slot: connected-component labels
    "refine",          # split slot under 'refine' (Leiden refinement)
    "renumber",        # dense renumbering of the slot's labels
    "aggregate",       # super-graph construction
    "detector",        # disconnected-community detector
    "modularity",      # modularity of the returned partition
    "segreduce",       # kernels/ops.segreduce_sorted, in any phase
    "ends_mark",       # segreduce: segment folds placed from run-end marks
    "ends_search",     # segreduce: segment ends found by binary search
)

# prefix of the program's profiler annotations
ANNOTATION_PREFIX = "repro."

# phases grouped for the replay harness's breakdown report
PHASE_GROUPS: Dict[str, str] = {
    "queue-wait": "queue",
    "compile": "engine",
    "engine-dispatch": "engine",
    "stack": "engine",
    "device-sync": "engine",
    "unpack": "engine",
}


def phase_group(name: str) -> str:
    """queue / engine / host bucket for a span name."""
    return PHASE_GROUPS.get(name, "host")


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`SCOPES`, opened where a
    phase is called, so the phase's operations carry the name."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; add it to SCOPES")
    return jax.named_scope(name)


def annotate(name: str):
    """The profiler annotation ``repro.<name>`` (does nothing while no
    profiler trace is active)."""
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


@dataclasses.dataclass
class Span:
    """One timed phase of a request (monotonic-clock endpoints)."""

    name: str
    t_start: float
    t_end: float
    trace_id: str = ""
    labels: Optional[Dict[str, str]] = None

    @property
    def duration_s(self) -> float:
        return self.t_end - self.t_start

    def as_dict(self) -> dict:
        d = dict(name=self.name, trace_id=self.trace_id,
                 t_start=self.t_start, t_end=self.t_end,
                 duration_s=self.duration_s)
        if self.labels:
            d["labels"] = dict(self.labels)
        return d


class RequestTrace:
    """Ordered spans for one request; the trace id is the request id."""

    __slots__ = ("trace_id", "tenant", "kind", "spans", "clock")

    def __init__(self, trace_id: str, *, tenant: str = "default",
                 kind: str = "detect",
                 clock: Optional[Callable[[], float]] = None):
        self.trace_id = trace_id
        self.tenant = tenant
        self.kind = kind
        self.spans: List[Span] = []
        self.clock = clock or time.perf_counter

    def mark(self, name: str, t_start: float, t_end: float,
             **labels: str) -> Span:
        """Record a phase from externally-measured endpoints (used for
        batch-level phases stamped onto every member request)."""
        s = Span(name, float(t_start), float(t_end), self.trace_id,
                 labels or None)
        self.spans.append(s)
        return s

    @contextlib.contextmanager
    def span(self, name: str, **labels: str):
        """Context-manager phase: ``with trace.span("repad"): ...``; also
        the profiler annotation ``repro.<name>`` while it runs."""
        with annotate(name):
            t0 = self.clock()
            try:
                yield
            finally:
                self.mark(name, t0, self.clock(), **labels)

    def durations(self) -> Dict[str, float]:
        """Total seconds per phase name (a repeated phase accumulates)."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.duration_s
        return out

    def find(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def __len__(self) -> int:
        return len(self.spans)

    def __repr__(self):
        parts = ", ".join(f"{s.name}={s.duration_s * 1e3:.2f}ms"
                          for s in self.spans)
        return f"RequestTrace({self.trace_id!r}: {parts})"
