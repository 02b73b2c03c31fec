"""Structured telemetry for the community-detection service.

Four layers (see README "Observability"):

* :mod:`repro.telemetry.spans` — per-request lifecycle traces
  (``submit -> ... -> resolve``) with monotonic-clock spans, their
  ``repro.*`` profiler annotations, and the device scope names.
* :mod:`repro.telemetry.sinks` — the :class:`Telemetry` hub plus
  pluggable :class:`MetricSink` callbacks (in-memory aggregation, JSONL
  event log, custom).
* :mod:`repro.telemetry.histogram` — fixed-size streaming latency
  histograms (replaces the unbounded lists ``service/metrics.py`` used).
* :mod:`repro.telemetry.prometheus` — text-format exporter over stdlib
  ``http.server`` plus a parser for scrape assertions.
"""
from repro.telemetry.histogram import StreamingHistogram
from repro.telemetry.prometheus import (
    MetricsExporter, metric_names, parse_prometheus, render_prometheus,
)
from repro.telemetry.sinks import (
    InMemorySink, JsonlSink, MetricSink, Telemetry,
)
from repro.telemetry.spans import (
    PHASES, SCOPES, RequestTrace, Span, annotate, phase_group, scope,
)

__all__ = [
    "StreamingHistogram",
    "MetricsExporter", "metric_names", "parse_prometheus",
    "render_prometheus",
    "InMemorySink", "JsonlSink", "MetricSink", "Telemetry",
    "PHASES", "SCOPES", "RequestTrace", "Span", "annotate", "phase_group",
    "scope",
]
