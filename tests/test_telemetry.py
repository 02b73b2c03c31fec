"""Telemetry subsystem: streaming histograms, sink registry, Prometheus
exporter, per-request trace completeness/parity across all three front
ends (sync adapter / ServiceFrontend / async), engine counters, the
null-safe metrics report, and the load-replay harness."""
import asyncio
import io
import json
import urllib.request

import numpy as np
import pytest

from repro.core import LouvainConfig
from repro.graph import sbm_graph
from repro.service import (
    AsyncCommunityService, Bucket, CommunityService, ServiceConfig,
)
from repro.service.metrics import ServiceMetrics
from repro.telemetry import (
    InMemorySink, JsonlSink, MetricsExporter, PHASES, RequestTrace,
    StreamingHistogram, Telemetry, metric_names, parse_prometheus,
    render_prometheus,
)
from tests._service_helpers import overflow_updates

pytestmark = pytest.mark.service

CFG = LouvainConfig()
BUCKETS = (Bucket(64, 512), Bucket(64, 2048), Bucket(256, 2048))

# the three request shapes and the spans each must carry end to end; the
# engine's host spans (stack, unpack) come with every batched dispatch
ENGINE_HOST_PHASES = {"stack", "unpack"}
DETECT_PHASES = set(PHASES)
IMMEDIATE_UPDATE_PHASES = {"submit", "repad", "compile", "engine-dispatch",
                           "device-sync", "store-commit", "resolve"}
BATCHED_UPDATE_PHASES = (DETECT_PHASES - {"admission"})
assert ENGINE_HOST_PHASES <= DETECT_PHASES & BATCHED_UPDATE_PHASES
assert not ENGINE_HOST_PHASES & IMMEDIATE_UPDATE_PHASES


def _ego(seed, n=30):
    return sbm_graph(n_nodes=n, n_blocks=3, p_in=0.4, p_out=0.04,
                     seed=seed)[0]


def _cfg(**kw):
    kw.setdefault("louvain", CFG)
    kw.setdefault("buckets", BUCKETS)
    return ServiceConfig(**kw)


def _updates(entry, seed, n_edges=4):
    rng = np.random.default_rng(seed)
    n = int(entry.graph.n_nodes)
    u = rng.integers(0, n, n_edges)
    v = rng.integers(0, n, n_edges)
    keep = u != v
    return u[keep], v[keep], np.ones(int(keep.sum()), np.float32)


def _span_names(trace):
    return {s.name for s in trace.spans}


# ---------------------------------------------------------------------------
# streaming histogram: bounded memory, percentiles within 1%
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_1pct():
    rng = np.random.default_rng(7)
    xs = rng.lognormal(mean=-4.0, sigma=1.5, size=20_000)  # latency-like
    h = StreamingHistogram()
    for x in xs:
        h.add(float(x))
    for p in (50, 90, 99, 99.9):
        exact = float(np.percentile(xs, p))
        approx = h.percentile(p)
        assert abs(approx - exact) / exact <= 0.01, (p, approx, exact)
    assert h.n == len(xs)
    assert abs(h.sum - xs.sum()) / xs.sum() < 1e-9
    assert h.percentile(0) == pytest.approx(xs.min(), rel=0.01)
    assert h.percentile(100) == pytest.approx(xs.max(), rel=0.01)
    assert xs.min() <= h.percentile(0) <= h.percentile(100) <= xs.max()


def test_histogram_memory_is_bounded_and_merge_works():
    h1, h2 = StreamingHistogram(), StreamingHistogram()
    for i in range(10_000):
        h1.add(1e-3 * (1 + i % 7))
        h2.add(1e-2 * (1 + i % 5))
    assert len(h1.counts) == len(h2.counts)  # fixed bucket array, no growth
    n1 = h1.n
    h1.merge(h2)
    assert h1.n == n1 + h2.n
    assert h1.cumulative_le(1e2) == h1.n


def test_histogram_ignores_nan_and_handles_empty():
    h = StreamingHistogram()
    assert h.percentile(99) != h.percentile(99)  # NaN on empty
    h.add(float("nan"))
    assert h.n == 0
    h.add(0.0)                                   # underflow bucket
    h.add(1e9)                                   # overflow bucket
    assert h.n == 2
    assert h.cumulative_le(1e-7) == 1


# ---------------------------------------------------------------------------
# metrics report: JSON-safe nulls, never NaN (regression)
# ---------------------------------------------------------------------------

def test_empty_report_serializes_without_nan():
    rep = ServiceMetrics().report()
    # allow_nan=False raises on any NaN/Inf — the old report emitted NaN
    # percentiles before any traffic, which json.dumps silently wrote as
    # bare `NaN`, invalid JSON for every strict parser downstream
    json.dumps(rep, allow_nan=False)
    for key in ("p50_ms", "p99_ms", "p50_detect_ms", "p50_update_ms",
                "graphs_per_s", "edges_per_s", "update_batch_mean"):
        assert rep[key] is None, (key, rep[key])


def test_populated_report_stays_json_safe():
    m = ServiceMetrics()
    m.observe("detect", 0.010, 1.0, tenant="a")
    m.observe("update", 0.002, 1.5, tenant="b")
    m.reject("b")
    rep = m.report()
    json.dumps(rep, allow_nan=False)
    assert rep["p50_ms"] is not None and rep["p50_ms"] > 0
    assert rep["tenants"]["b"]["n_rejected"] == 1
    assert rep["tenants"]["b"]["p50_ms"] == pytest.approx(2.0, rel=0.02)
    m.reset()
    json.dumps(m.report(), allow_nan=False)


# ---------------------------------------------------------------------------
# sink registry: fan-out, error isolation, JSONL
# ---------------------------------------------------------------------------

def test_sink_registry_fanout_and_unregister():
    hub = Telemetry()
    assert not hub.enabled            # no sinks -> emission early-outs
    a, b = InMemorySink(), InMemorySink()
    hub.register(a)
    hub.register(b)
    assert hub.enabled
    hub.counter("x", 2, {"t": "u"})
    hub.gauge("g", 0.5)
    hub.observe("h", 0.01)
    assert a.counter_value("x", {"t": "u"}) == 2
    assert b.counter_value("x", {"t": "u"}) == 2
    hub.unregister(b)
    hub.counter("x", 1, {"t": "u"})
    assert a.counter_value("x", {"t": "u"}) == 3
    assert b.counter_value("x", {"t": "u"}) == 2


def test_broken_sink_is_isolated_and_recorded():
    class Broken(InMemorySink):
        def on_counter(self, *a, **kw):
            raise RuntimeError("sink exploded")

    hub = Telemetry()
    broken = hub.register(Broken())
    good = hub.register(InMemorySink())
    hub.counter("x", 1)               # must not raise
    hub.counter("x", 1)
    assert good.counter_value("x") == 2
    assert id(broken) in hub.sink_errors  # first failure recorded per sink


def test_jsonl_sink_emits_parseable_lines():
    buf = io.StringIO()
    hub = Telemetry()
    hub.register(JsonlSink(buf))
    hub.counter("served", 1, {"tenant": "a"})
    tr = RequestTrace("r1", tenant="a", kind="detect")
    tr.mark("submit", 0.0, 0.5)
    hub.trace(tr)
    lines = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    assert any(o.get("name") == "served" for o in lines)
    spans = [o for o in lines if o.get("ev") == "span"]
    assert spans and spans[0]["trace_id"] == "r1"
    assert spans[0]["duration_s"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# prometheus: render/parse round trip + live HTTP scrape
# ---------------------------------------------------------------------------

def test_prometheus_round_trip():
    sink = InMemorySink()
    sink.on_counter("requests_served", 3, {"tenant": "a", "kind": "detect"})
    sink.on_gauge("queue_depth", 2, {"tenant": "a"})
    for v in (0.001, 0.002, 0.04):
        sink.on_histogram("request_latency_seconds", v, {"kind": "detect"})
    parsed = parse_prometheus(render_prometheus(sink))
    names = metric_names(parsed)
    assert {"repro_requests_served_total", "repro_queue_depth",
            "repro_request_latency_seconds_bucket",
            "repro_request_latency_seconds_sum",
            "repro_request_latency_seconds_count"} <= names
    key = ("repro_requests_served_total",
           (("kind", "detect"), ("tenant", "a")))
    assert parsed[key] == 3
    cnt = ("repro_request_latency_seconds_count", (("kind", "detect"),))
    assert parsed[cnt] == 3
    # the cumulative ladder is monotone and ends at the count
    ladder = sorted(
        (dict(lk)["le"], v) for (n, lk), v in parsed.items()
        if n == "repro_request_latency_seconds_bucket")
    vals = [v for _, v in ladder]
    assert vals[-1] == 3 and all(a <= 3 for a in vals)


def test_parse_rejects_garbage():
    with pytest.raises(ValueError):
        parse_prometheus("this is { not prometheus\n")


def test_exporter_live_http_scrape():
    sink = InMemorySink()
    sink.on_counter("requests_served", 5, {"tenant": "t0"})
    exp = MetricsExporter(sink, port=0)
    try:
        body = urllib.request.urlopen(exp.url, timeout=10).read().decode()
        parsed = parse_prometheus(body)
        assert parsed[("repro_requests_served_total",
                       (("tenant", "t0"),))] == 5
        # scrape reflects live mutation, not a snapshot at bind time
        sink.on_counter("requests_served", 1, {"tenant": "t0"})
        body = urllib.request.urlopen(exp.url, timeout=10).read().decode()
        assert parse_prometheus(body)[("repro_requests_served_total",
                                       (("tenant", "t0"),))] == 6
    finally:
        exp.close()


# ---------------------------------------------------------------------------
# trace completeness + parity across the three front ends
# ---------------------------------------------------------------------------

def test_phase_breakdown_counts_a_nested_span_once():
    sink = InMemorySink()
    tel = Telemetry()
    tel.register(sink)
    tr = RequestTrace("d0-g")
    tr.mark("queue-wait", 0.0, 1.0)
    tr.mark("engine-dispatch", 1.0, 3.0)
    tr.mark("stack", 1.0, 2.0)          # inside engine-dispatch
    tr.mark("unpack", 3.0, 4.0)
    tel.trace(tr)
    assert sink.phase_breakdown() == pytest.approx(
        {"queue": 0.25, "engine": 0.75, "host": 0.0})


def test_sync_adapter_detect_trace_is_complete():
    svc = CommunityService(CFG, buckets=BUCKETS, batch_size=2,
                           max_delay_s=0.01)
    fut = svc.detect("g0", _ego(0))
    svc.drain()
    assert fut.done()
    assert _span_names(fut.trace) == DETECT_PHASES
    assert fut.trace.trace_id == fut.req_id
    # spans carry real durations and the lifecycle is ordered
    d = fut.trace.durations()
    assert all(v >= 0 for v in d.values())
    order = [s.name for s in fut.trace.spans]
    assert order.index("submit") < order.index("queue-wait") \
        < order.index("engine-dispatch") < order.index("resolve")
    # stack lies inside engine-dispatch; unpack runs from the end of
    # device-sync to before the store commit
    (disp,), (stack,) = fut.trace.find("engine-dispatch"), \
        fut.trace.find("stack")
    assert disp.t_start <= stack.t_start < stack.t_end <= disp.t_end
    (sync,), (unpack,), (commit,) = (fut.trace.find(n) for n in (
        "device-sync", "unpack", "store-commit"))
    assert unpack.t_start == sync.t_end and unpack.t_end <= commit.t_start


def test_frontend_and_async_traces_match_sync(tmp_path):
    from repro.service.frontend import ServiceFrontend

    fe = ServiceFrontend(_cfg(batch_size=2, max_delay_s=0.01))
    f1 = fe.submit_detect("g0", _ego(0))
    fe.drain()

    async def go():
        async with AsyncCommunityService(
                _cfg(batch_size=2, max_delay_s=0.01)) as svc:
            fut = await svc.submit_detect("g0", _ego(0))
            await fut
            return fut

    f2 = asyncio.run(go())
    assert _span_names(f1.trace) == _span_names(f2.trace) == DETECT_PHASES


def test_immediate_update_trace():
    svc = CommunityService(CFG, buckets=BUCKETS, batch_size=2,
                           max_delay_s=0.01)
    svc.detect("g0", _ego(0))
    svc.drain()
    fut = svc.frontend.submit_update("g0", _updates(svc.result("g0"), 1))
    assert fut.kind == "update" and fut.done()
    assert _span_names(fut.trace) == IMMEDIATE_UPDATE_PHASES
    (compile_span,) = fut.trace.find("compile")
    assert compile_span.labels["hit"] in ("true", "false")


def test_batched_update_trace():
    svc = CommunityService(
        CFG, config=_cfg(batch_size=2, max_delay_s=0.01,
                         update_batch_size=2))
    for i in range(2):
        svc.detect(f"g{i}", _ego(i))
    svc.drain()
    futs = [svc.frontend.submit_update(f"g{i}",
                                       _updates(svc.result(f"g{i}"), i))
            for i in range(2)]
    svc.drain()
    for fut in futs:
        assert fut.done()
        assert _span_names(fut.trace) == BATCHED_UPDATE_PHASES, \
            _span_names(fut.trace)


def test_rebucket_path_trace_is_complete():
    svc = CommunityService(CFG, buckets=BUCKETS, batch_size=2,
                           max_delay_s=0.01)
    svc.detect("g0", _ego(0))
    svc.drain()
    fut = svc.frontend.submit_update(
        "g0", overflow_updates(svc.result("g0").graph))
    assert fut.kind == "detect"       # overflow re-bucketed into a detect
    svc.drain()
    assert fut.done()
    assert _span_names(fut.trace) == DETECT_PHASES


def test_resolved_future_always_has_closed_trace():
    # a woken caller must never observe a trace still missing its resolve
    # span — the broadcast happens before set_result
    async def go():
        async with AsyncCommunityService(
                _cfg(batch_size=4, max_delay_s=0.005)) as svc:
            futs = [await svc.submit_detect(f"g{i}", _ego(i))
                    for i in range(4)]
            done = []

            async def watch(f):
                await f
                done.append(_span_names(f.trace))

            await asyncio.gather(*(watch(f) for f in futs))
            return done

    for names in asyncio.run(go()):
        assert "resolve" in names and names == DETECT_PHASES


# ---------------------------------------------------------------------------
# engine + algorithm counters through the sink
# ---------------------------------------------------------------------------

def test_engine_counters_compile_hit_miss_and_algorithm_totals():
    svc = CommunityService(CFG, buckets=BUCKETS, batch_size=2,
                           max_delay_s=0.01)
    sink = svc.frontend.mem_sink
    for i in range(2):
        svc.detect(f"g{i}", _ego(i))
    svc.drain()
    assert svc.engine.n_compile_misses >= 1
    miss0 = sink.counter_total("engine_compile")
    assert miss0 >= 1
    # same bucket + same batch width -> compiled executable reused
    for i in range(2):
        svc.detect(f"h{i}", _ego(10 + i))
    svc.drain()
    assert svc.engine.n_compile_hits >= 1
    hits = sum(v for (n, lk), v in sink.counters.items()
               if n == "engine_compile" and dict(lk)["result"] == "hit")
    assert hits >= 1
    assert sink.counter_total("louvain_passes") >= 4
    assert sink.counter_total("local_move_sweeps") >= 4
    # fill-factor gauge in (0, 1] for the dispatched bucket
    fills = [v for (n, lk), v in sink.gauges.items()
             if n == "batch_fill_factor"]
    assert fills and all(0 < v <= 1 for v in fills)


def test_tenant_metrics_mirrored_to_sink():
    svc = CommunityService(CFG, buckets=BUCKETS, batch_size=2,
                           max_delay_s=0.01)
    svc.detect("g0", _ego(0), tenant="alice")
    svc.detect("g1", _ego(1), tenant="bob")
    svc.drain()
    sink = svc.frontend.mem_sink
    assert sink.counter_value("requests_served",
                              {"tenant": "alice", "kind": "detect"}) == 1
    assert sink.counter_value("requests_served",
                              {"tenant": "bob", "kind": "detect"}) == 1
    h = sink.histogram("request_latency_seconds", {"kind": "detect"})
    assert h is not None and h.n == 2


def test_telemetry_disabled_leaves_no_sink_and_still_serves():
    svc = CommunityService(
        CFG, config=_cfg(batch_size=2, max_delay_s=0.01,
                         telemetry_enabled=False))
    assert svc.frontend.mem_sink is None
    fut = svc.detect("g0", _ego(0))
    svc.drain()
    assert fut.done() and fut.result().n_disconnected == 0
    json.dumps(svc.metrics.report(), allow_nan=False)


def test_exporter_config_requires_telemetry():
    with pytest.raises(ValueError):
        _cfg(telemetry_enabled=False, exporter_port=0)


# ---------------------------------------------------------------------------
# service + exporter end to end, and the replay harness
# ---------------------------------------------------------------------------

def test_service_exporter_scrapes_during_traffic():
    svc = CommunityService(
        CFG, config=_cfg(batch_size=2, max_delay_s=0.01, exporter_port=0))
    try:
        svc.detect("g0", _ego(0), tenant="a")
        svc.detect("g1", _ego(1), tenant="b")
        svc.drain()
        body = urllib.request.urlopen(
            svc.frontend.exporter.url, timeout=10).read().decode()
        parsed = parse_prometheus(body)
        names = metric_names(parsed)
        assert "repro_requests_served_total" in names
        assert "repro_span_duration_seconds_bucket" in names
        assert "repro_engine_compile_total" in names
        tenants = {dict(lk).get("tenant") for n, lk in parsed
                   if n == "repro_requests_served_total"}
        assert {"a", "b"} <= tenants
    finally:
        svc.close()


@pytest.mark.slow
def test_replay_mini_run_reports_phase_breakdown():
    from repro.service.replay import ReplayConfig, find_knee, run_replay

    rep = run_replay(
        ReplayConfig(rate=40.0, duration_s=0.75, pool_size=4, n_tenants=3,
                     update_frac=0.3, seed=5),
        _cfg(batch_size=4, max_delay_s=0.01))
    assert rep["offered"] > 0
    assert rep["served"] + rep["rejected"] + rep["failed"] >= rep["offered"]
    assert rep["failed"] == 0
    json.dumps(rep, allow_nan=False)
    bd = rep["phase_breakdown"]
    assert set(bd) == {"queue", "engine", "host"}
    assert sum(bd.values()) == pytest.approx(1.0)
    assert set(rep["phases"]) <= set(PHASES)
    # knee detection: a degenerate ladder where the second rate collapses
    good = dict(rate=10.0, goodput=1.0, p99_ms=5.0)
    bad = dict(rate=20.0, goodput=0.5, p99_ms=5.0)
    assert find_knee([good, bad]) == 20.0
    assert find_knee([good, dict(rate=20.0, goodput=1.0, p99_ms=100.0)]) \
        == 20.0
    assert find_knee([good]) is None
