"""Observability subsystem tests: flight-recorder trace store, span parent
linkage, labeled metrics + gauges, Prometheus text exposition, SLO watchdog,
batcher queue swap, and end-to-end trace propagation through a full (stub-
engine) runner stack over the in-proc bus.
"""

import asyncio
import json
import re
import urllib.request

import numpy as np
import pytest

from symbiont_tpu.obs import prometheus
from symbiont_tpu.obs.trace_store import SpanRecord, TraceStore, trace_store
from symbiont_tpu.obs.watchdog import SloWatchdog, parse_thresholds
from symbiont_tpu.utils.telemetry import (
    SPAN_HEADER,
    TRACE_HEADER,
    Metrics,
    _Histogram,
    child_headers,
    metrics,
    span,
)


def _rec(trace="t1", sid="s1", parent=None, name="svc.op", start=100.0,
         dur=5.0, status="ok"):
    return SpanRecord(trace_id=trace, span_id=sid, parent_id=parent,
                      name=name, start_s=start, duration_ms=dur,
                      status=status)


# --------------------------------------------------------------- trace store

def test_trace_tree_parent_linkage():
    ts = TraceStore(capacity=16)
    ts.record(_rec(sid="root", name="api.submit_url", start=1.0))
    ts.record(_rec(sid="c1", parent="root", name="perception.handle",
                   start=2.0))
    ts.record(_rec(sid="c2", parent="c1", name="preprocessing.handle",
                   start=3.0))
    ts.record(_rec(sid="c3", parent="c1", name="vector_memory.handle",
                   start=4.0, status="error"))
    tree = ts.trace_tree("t1")
    assert tree["span_count"] == 4
    assert tree["error_count"] == 1
    assert tree["services"] == ["api", "perception", "preprocessing",
                                "vector_memory"]
    (root,) = tree["roots"]
    assert root["name"] == "api.submit_url"
    (c1,) = root["children"]
    assert c1["name"] == "perception.handle"
    assert {c["name"] for c in c1["children"]} == {
        "preprocessing.handle", "vector_memory.handle"}


def test_trace_tree_orphan_parent_becomes_root():
    # parent evicted from the ring (or a hop through the native workers):
    # the span must surface as a root, not vanish
    ts = TraceStore(capacity=16)
    ts.record(_rec(sid="x", parent="never-recorded"))
    tree = ts.trace_tree("t1")
    assert len(tree["roots"]) == 1
    assert ts.trace_tree("missing") is None


def test_trace_store_ring_bound_and_recent_order():
    ts = TraceStore(capacity=8)
    for i in range(20):
        ts.record(_rec(trace=f"t{i}", sid=f"s{i}", start=float(i),
                       dur=float(i)))
    assert len(ts) == 8  # bounded: oldest 12 evicted
    ts.record(_rec(trace="terr", sid="serr", start=0.5, dur=0.1,
                   status="error"))
    recent = ts.recent(limit=3)
    # errored traces first, then slowest
    assert recent[0]["trace_id"] == "terr"
    durs = [r["duration_ms"] for r in recent[1:]]
    assert durs == sorted(durs, reverse=True)


# ---------------------------------------------------------------------- span

def test_span_records_parent_linkage_and_error_accounting():
    trace_store.clear()
    errors_before = metrics.get("span.obs_test.child.errors")
    with span("obs_test.root", None) as root_sp:
        ctx = child_headers(root_sp.headers)
        # child_headers PROPAGATES the active span id (a hop is an edge)
        assert ctx[SPAN_HEADER] == root_sp.span_id
        assert ctx[TRACE_HEADER] == root_sp.trace_id
        with pytest.raises(ValueError):
            with span("obs_test.child", ctx):
                raise ValueError("boom")
    assert metrics.get("span.obs_test.child.errors") == errors_before + 1
    spans = trace_store.spans_for(root_sp.trace_id)
    by_name = {s.name: s for s in spans}
    assert by_name["obs_test.root"].status == "ok"
    child = by_name["obs_test.child"]
    assert child.status == "error"
    assert child.parent_id == root_sp.span_id
    assert child.fields["error"] == "ValueError"
    tree = trace_store.trace_tree(root_sp.trace_id)
    (root_node,) = tree["roots"]
    assert [c["name"] for c in root_node["children"]] == ["obs_test.child"]


# ------------------------------------------------------------------- metrics

def test_histogram_exact_min_max_survive_decimation():
    h = _Histogram()
    values = list(np.random.default_rng(0).uniform(10.0, 100.0, 6000))
    values[137] = 1.25   # unique true min, early (decimation drops evens)
    values[5391] = 999.5  # unique true max
    for v in values:
        h.observe(v)
    s = h.summary()
    assert len(h.values) < 6000  # the reservoir actually decimated
    assert s["min"] == 1.25
    assert s["max"] == 999.5
    assert s["count"] == 6000


def test_labeled_metrics_and_gauges():
    m = Metrics()
    m.inc("bus.consumed", labels={"service": "api", "subject": "a.b"})
    m.inc("bus.consumed", labels={"subject": "a.b", "service": "api"})
    assert m.get("bus.consumed", labels={"service": "api",
                                         "subject": "a.b"}) == 2
    m.gauge_add("api.sse_clients", 1)
    m.gauge_add("api.sse_clients", -1)
    snap = m.snapshot()
    assert snap["counters"]['bus.consumed{service="api",subject="a.b"}'] == 2
    assert snap["gauges"]["api.sse_clients"] == 0


def test_callback_gauge_dropped_when_dead():
    m = Metrics()

    class Owner:
        pass

    import weakref

    owner = Owner()
    ref = weakref.ref(owner)
    m.register_gauge("x.depth", lambda: 7 if ref() is not None else None)
    assert m.snapshot()["gauges"]["x.depth"] == 7
    del owner
    assert "x.depth" not in m.snapshot()["gauges"]
    assert "x.depth" not in m.snapshot()["gauges"]  # stays dropped


# ---------------------------------------------------------------- prometheus

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' (-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?|NaN|[+-]Inf)$')


def test_prometheus_exposition_parses():
    m = Metrics()
    m.inc("perception.published", 3)
    m.inc("api.POST./api/submit-url")  # hostile chars in the name
    m.observe("span.api.search.ms", 12.0)
    m.observe("span.api.search.ms", 30.0)
    m.gauge_set("batcher.queue_depth", 4,
                labels={"service": "engine", "batcher": "embed"})
    out = prometheus.render(m)
    assert out.endswith("\n")
    declared_type = {}
    seen_samples = set()
    for line in out.splitlines():
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "summary", "histogram")
            declared_type[name] = kind
            continue
        match = _SAMPLE_RE.match(line)
        assert match, f"unparseable sample line: {line!r}"
        base = match.group(1)
        family = re.sub(r"_(sum|count|min|max|bucket)$", "", base)
        assert base in declared_type or family in declared_type, (
            f"sample {base} has no preceding TYPE")
        seen_samples.add(base)
    assert "symbiont_published_total" in seen_samples
    assert "symbiont_batcher_queue_depth" in seen_samples
    assert "symbiont_span_duration_ms" in seen_samples
    assert declared_type["symbiont_span_duration_ms"] == "summary"
    # service labels derived from dot names
    assert 'symbiont_published_total{service="perception"} 3' in out
    assert ('symbiont_span_duration_ms_count'
            '{service="api",span="api.search"} 2') in out
    # the REAL histogram family rides alongside the summary: cumulative
    # `le` buckets (12.0 counts by le=25, 30.0 by le=50), +Inf == count
    assert declared_type["symbiont_span_duration_ms_hist"] == "histogram"
    assert ('symbiont_span_duration_ms_hist_bucket'
            '{le="25.0",service="api",span="api.search"} 1') in out
    assert ('symbiont_span_duration_ms_hist_bucket'
            '{le="50.0",service="api",span="api.search"} 2') in out
    assert ('symbiont_span_duration_ms_hist_bucket'
            '{le="+Inf",service="api",span="api.search"} 2') in out
    assert ('symbiont_span_duration_ms_hist_count'
            '{service="api",span="api.search"} 2') in out
    # 0.0.4 rendering: no exemplar syntax, no EOF terminator
    assert " # {" not in out and "# EOF" not in out


def test_prometheus_label_escaping_roundtrip():
    hostile = 'a"b\\c\nd'
    m = Metrics()
    m.gauge_set("g", 1, labels={"k": hostile})
    out = prometheus.render(m)
    (line,) = [ln for ln in out.splitlines() if not ln.startswith("#")]
    assert "\n" not in line  # the raw newline must have been escaped
    escaped = line.split('k="', 1)[1].rsplit('"', 1)[0]
    unescaped = (escaped.replace("\\n", "\n").replace('\\"', '"')
                 .replace("\\\\", "\\"))
    assert prometheus.escape_label_value(hostile) == escaped
    # NB: naive sequential unescape is escape-order sensitive; exact
    # equality via the library's own escape is the contract under test
    assert unescaped.count("b") == 1


# ------------------------------------------------- histogram buckets/exemplars

def test_histogram_buckets_cumulative_and_le_inclusive():
    m = Metrics()
    m.set_bucket_bounds([10.0, 100.0])
    m.observe("span.x.y.ms", 10.0)   # le is INCLUSIVE: lands in le=10
    m.observe("span.x.y.ms", 10.001)
    m.observe("span.x.y.ms", 500.0)
    s = m.snapshot()["histograms"]["span.x.y.ms"]
    assert s["buckets"] == [(10.0, 1), (100.0, 2), ("+Inf", 3)]
    assert "exemplars" not in s  # exposition detail, stripped from JSON
    # bounds apply to NEW histograms only; invalid bounds fail loud
    with pytest.raises(ValueError):
        m.set_bucket_bounds([5.0, 5.0])
    with pytest.raises(ValueError):
        m.set_bucket_bounds([])


def test_openmetrics_exemplar_links_bucket_to_trace():
    m = Metrics()
    m.observe("span.api.search.ms", 12.0, exemplar={"trace_id": "tr-42"})
    om = prometheus.render(m, openmetrics=True)
    (ex_line,) = [ln for ln in om.splitlines()
                  if "_hist_bucket" in ln and " # {" in ln]
    assert 'le="25.0"' in ex_line  # 12ms lands in the 25ms bucket
    assert '# {trace_id="tr-42"} 12 ' in ex_line
    assert om.rstrip().endswith("# EOF")
    # span() itself attaches its trace id as the exemplar
    trace_store.clear()
    with span("obs_test.exemplar", None) as sp:
        pass
    om = prometheus.render()
    assert f'trace_id="{sp.trace_id}"' in prometheus.render(
        openmetrics=True)
    assert f'trace_id="{sp.trace_id}"' not in om  # 0.0.4 stays exemplar-free


def test_openmetrics_counter_families_drop_total_suffix():
    """OpenMetrics reserves `_total`: the counter FAMILY name must not end
    with it (samples must) — the reference parser rejects the clash and a
    failed parse loses the whole scrape (review finding). 0.0.4 keeps the
    historical family-name-includes-_total rendering."""
    m = Metrics()
    m.inc("perception.published", 3)
    m.inc("span.api.search.errors")
    om = prometheus.render(m, openmetrics=True)
    assert "# TYPE symbiont_published counter" in om
    assert "# TYPE symbiont_published_total counter" not in om
    assert "symbiont_published_total{" in om  # the sample keeps the suffix
    assert "# TYPE symbiont_span_errors counter" in om
    legacy = prometheus.render(m)
    assert "# TYPE symbiont_published_total counter" in legacy
    try:
        from prometheus_client.openmetrics import parser
    except ImportError:
        return
    names = {f.name for f in parser.text_string_to_metric_families(om)}
    assert {"symbiont_published", "symbiont_span_errors"} <= names


# ----------------------------------------------------- trace store (capacity)

def test_set_capacity_shrink_keeps_newest_and_len():
    ts = TraceStore(capacity=16)
    for i in range(12):
        ts.record(_rec(trace=f"t{i}", sid=f"s{i}", start=float(i)))
    ts.set_capacity(4)
    assert ts.capacity == 4 and len(ts) == 4
    # newest survive, eviction order is oldest-first
    kept = {r.trace_id for tid in (f"t{i}" for i in range(12))
            for r in ts.spans_for(tid)}
    assert kept == {"t8", "t9", "t10", "t11"}
    ts.record(_rec(trace="t12", sid="s12", start=12.0))
    assert len(ts) == 4
    assert not ts.spans_for("t8") and ts.spans_for("t12")


def test_trace_tree_parent_evicted_from_ring():
    # the orphan case the critical-path plane must survive: the PARENT
    # span was evicted by the ring, the child must surface as a root
    ts = TraceStore(capacity=2)
    ts.record(_rec(sid="root", name="api.submit_url", start=1.0))
    ts.record(_rec(sid="c1", parent="root", name="perception.handle",
                   start=2.0))
    ts.record(_rec(sid="c2", parent="c1", name="preprocessing.handle",
                   start=3.0))  # evicts "root"
    tree = ts.trace_tree("t1")
    assert tree["span_count"] == 2
    (root,) = tree["roots"]
    assert root["name"] == "perception.handle"
    assert [c["name"] for c in root["children"]] == ["preprocessing.handle"]


# ------------------------------------------------------------- critical path

from symbiont_tpu.obs import chrome_trace, critical_path  # noqa: E402


def _pipeline_store() -> TraceStore:
    """An ingest-shaped trace: causal children outliving their parents
    (bus semantics), one parallel fan-out, dominant hop = preprocessing."""
    ts = TraceStore(capacity=64)

    def rec(sid, parent, name, start, dur, status="ok"):
        ts.record(SpanRecord("t1", sid, parent, name, start, dur, status))

    rec("r", None, "api.submit_url", 100.0, 5.0)
    rec("c1", "r", "perception.handle", 100.010, 40.0)
    rec("c2", "c1", "preprocessing.handle", 100.060, 100.0)
    # parallel fan-out off preprocessing: only the blocker joins the chain;
    # c3 outlives its parent (causal bus semantics) and ends the trace
    rec("c3", "c2", "vector_memory.handle", 100.130, 60.0, status="error")
    rec("c4", "c2", "knowledge_graph.handle", 100.130, 10.0)
    return ts


def test_critical_path_self_time_chain_and_dominant():
    ts = _pipeline_store()
    report = critical_path.compute(ts, "t1")
    assert report is not None
    # e2e: 100.000 → 100.190 (c3's end) = 190ms
    assert report["e2e_ms"] == pytest.approx(190.0, abs=0.01)
    assert [h["name"] for h in report["chain"]] == [
        "api.submit_url", "perception.handle", "preprocessing.handle",
        "vector_memory.handle"]
    by = {h["name"]: h for h in report["chain"]}
    # api's causal child starts AFTER api already returned (bus hop): no
    # overlap to subtract, the full 5ms stays self-time
    assert by["api.submit_url"]["self_ms"] == pytest.approx(5.0, abs=0.01)
    # preprocessing [100.060, 100.160] with children covering
    # [100.130, 100.160] once merged (c3 clipped at parent end, c4 inside
    # c3): 100 - 30 = 70ms self
    assert by["preprocessing.handle"]["self_ms"] == pytest.approx(
        70.0, abs=0.01)
    # the chain + the untraced inter-hop gaps (5ms + 10ms) tile the e2e
    assert report["gap_ms"] == pytest.approx(15.0, abs=0.05)
    assert report["dominant"]["name"] == "preprocessing.handle"
    assert "preprocessing.handle" in report["verdict"]
    assert report["chain_self_ms"] + report["gap_ms"] == pytest.approx(
        report["e2e_ms"], abs=0.1)
    assert critical_path.compute(ts, "missing") is None


def test_critical_path_self_time_with_overlapping_children():
    ts = TraceStore(capacity=8)
    ts.record(SpanRecord("t2", "p", None, "svc.handle", 10.0, 100.0, "ok"))
    # overlapping children inside the parent: merged coverage, not summed
    ts.record(SpanRecord("t2", "a", "p", "svc.op_a", 10.010, 40.0, "ok"))
    ts.record(SpanRecord("t2", "b", "p", "svc.op_b", 10.030, 40.0, "ok"))
    tree = critical_path.annotate_self_times(ts.trace_tree("t2"))
    (root,) = tree["roots"]
    # union of [10,50] and [30,70] = 60ms covered, not 80
    assert root["child_ms"] == pytest.approx(60.0, abs=0.01)
    assert root["self_ms"] == pytest.approx(40.0, abs=0.01)


def test_stage_attribution_aggregates_and_exports_gauges():
    ts = _pipeline_store()
    attr = critical_path.aggregate_stage_attribution(ts)
    assert set(attr) == {"api.submit_url"}
    agg = attr["api.submit_url"]
    assert agg["count"] == 1
    fracs = agg["stages"]
    assert fracs["preprocessing.handle"] == pytest.approx(70 / 190,
                                                          abs=0.005)
    total = sum(fracs.values()) + agg["gap_frac"]
    assert total == pytest.approx(1.0, abs=0.02)
    m = Metrics()
    critical_path.export_stage_gauges(attr, registry=m)
    gauges = m.snapshot()["gauges"]
    assert gauges[
        'stage.fraction{pipeline="api.submit_url",'
        'stage="preprocessing.handle"}'] == pytest.approx(70 / 190,
                                                          abs=0.005)
    assert 'stage.e2e_ms{pipeline="api.submit_url"}' in gauges
    assert gauges['stage.traces{pipeline="api.submit_url"}'] == 1


# ------------------------------------------------------- chrome trace export

def _chrome_schema_check(doc: dict, expect_spans: int) -> None:
    """The golden-file schema, reusable against live exports: top-level
    shape, metadata-first ordering, complete events with µs timing."""
    assert set(doc) == {"displayTimeUnit", "otherData", "traceEvents"}
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(meta) + len(spans) == len(doc["traceEvents"])
    assert len(spans) == expect_spans == doc["otherData"]["span_count"]
    assert meta[0]["name"] == "process_name"
    tids = {e["args"]["name"]: e["tid"] for e in meta[1:]}
    for ev in spans:
        assert {"name", "cat", "pid", "tid", "ts", "dur",
                "args"} <= set(ev)
        assert ev["tid"] == tids[ev["cat"]]  # one track per service
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["dur"], (int, float))
        assert ev["args"]["span_id"]
        if ev["args"]["status"] != "ok":
            assert ev["cname"] == "terrible"  # error spans flagged


def test_chrome_trace_export_matches_golden():
    import pathlib

    ts = _pipeline_store()
    doc = chrome_trace.export_spans("t1", ts.spans_for("t1"))
    _chrome_schema_check(doc, expect_spans=5)
    golden_path = (pathlib.Path(__file__).parent / "goldens"
                   / "chrome_trace_golden.json")
    golden = json.loads(golden_path.read_text())
    assert doc == golden, (
        "Chrome Trace export drifted from the pinned golden — if the "
        "change is deliberate, regenerate: python -c \"from "
        "tests.test_observability import _write_chrome_golden; "
        "_write_chrome_golden()\"")


def _write_chrome_golden() -> None:
    import pathlib

    ts = _pipeline_store()
    doc = chrome_trace.export_spans("t1", ts.spans_for("t1"))
    p = (pathlib.Path(__file__).parent / "goldens"
         / "chrome_trace_golden.json")
    p.parent.mkdir(exist_ok=True)
    p.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")


# ------------------------------------------------------ device / host planes

def test_device_gauges_graceful_noop_on_cpu():
    from symbiont_tpu.obs.device import register_device_gauges

    m = Metrics()
    n = register_device_gauges(m)  # CPU jax: memory_stats() is None
    assert n == 0
    assert not [k for k in m.snapshot()["gauges"] if k.startswith("device.")]


def test_process_gauges_from_proc_self():
    from symbiont_tpu.obs.device import register_process_gauges

    m = Metrics()
    assert register_process_gauges(m) is True  # this suite runs on Linux
    g = m.snapshot()["gauges"]
    assert g["process.resident_memory_bytes"] > 1 << 20
    assert g["process.open_fds"] >= 3
    assert 0 <= g["process.uptime_seconds"] < 7 * 24 * 3600
    assert abs(g["process.start_time_seconds"]
               + g["process.uptime_seconds"] - __import__("time").time()) < 5
    out = prometheus.render(m)
    # the standard family keeps its ecosystem names: NO symbiont_ prefix
    assert "\nprocess_resident_memory_bytes" in out
    assert "symbiont_process_" not in out


def test_compile_events_land_on_the_timeline():
    from symbiont_tpu.obs.device import (COMPILE_TRACE_ID,
                                         record_compile_event)

    trace_store.clear()
    record_compile_event("engine.compile", 1.5, start_s=1000.0,
                         signature="embed[L=128,B=32]")
    (rec,) = trace_store.spans_for(COMPILE_TRACE_ID)
    assert rec.name == "engine.compile"
    assert rec.duration_ms == pytest.approx(1500.0)
    assert rec.fields["signature"] == "embed[L=128,B=32]"
    # and the timeline exports like any other trace
    doc = chrome_trace.export_spans(
        COMPILE_TRACE_ID, trace_store.spans_for(COMPILE_TRACE_ID))
    _chrome_schema_check(doc, expect_spans=1)


# ------------------------------------------------------------------ watchdog

def test_watchdog_threshold_parsing():
    assert parse_thresholds(["api.search=500", "x.y=1.5"]) == {
        "api.search": 500.0, "x.y": 1.5}
    for bad in (["api.search"], ["=5"], ["a=notanumber"], ["a=-3"]):
        with pytest.raises(ValueError):
            parse_thresholds(bad)


def test_watchdog_breach_emits_structured_event():
    m = Metrics()
    for v in (5.0, 6.0, 900.0):
        m.observe("span.api.search.ms", v)
    m.observe("span.api.healthy.ms", 1.0)
    wd = SloWatchdog({"api.search": 100.0, "api.healthy": 100.0,
                      "api.never_ran": 1.0}, registry=m)
    breaches = wd.evaluate()
    assert len(breaches) == 1
    ev = breaches[0]
    assert ev["event"] == "slo_breach" and ev["span"] == "api.search"
    assert ev["p99_ms"] > ev["threshold_ms"] == 100.0
    assert m.get("slo.breaches", labels={"span": "api.search"}) == 1
    # evaluated p99 exported for BOTH spans, breached or not
    gauges = m.snapshot()["gauges"]
    assert 'slo.p99_ms{span="api.search"}' in gauges
    assert 'slo.p99_ms{span="api.healthy"}' in gauges
    assert list(wd.events) == breaches
    # idle span (no new samples): no re-alert off the same old outlier
    assert wd.evaluate() == []
    assert m.get("slo.breaches", labels={"span": "api.search"}) == 1
    # fresh samples while still breached: the counter keeps counting
    m.observe("span.api.search.ms", 2.0)
    wd.evaluate()
    assert m.get("slo.breaches", labels={"span": "api.search"}) == 2


# ------------------------------------------------------- batcher queue swap

def test_batcher_deque_order_and_accounting():
    from symbiont_tpu.engine.batcher import _BatcherBase

    class Item:
        def __init__(self, tag, size):
            self.tag, self.size = tag, size
            self.future = None

    class B(_BatcherBase):
        def _size(self, item):
            return item.size

    b = B(max_batch=4, deadline_s=0.01)
    for i, size in enumerate([2, 1, 1, 3]):
        b._submit(Item(i, size))
    assert b._queued == 7
    chunk = b._take_chunk()
    # FIFO: 2+1+1 fits in max_batch=4; the 3-sized item stays queued
    assert [it.tag for it in chunk] == [0, 1, 2]
    assert b._queued == 3
    # requeue puts items back at the FRONT in original order
    b._requeue(chunk[1:])
    assert [it.tag for it in b._queue] == [1, 2, 3]
    assert b._queued == 5
    assert b._wake.is_set()
    # oversized head still moves alone (the "always at least one" contract)
    big = b._take_chunk()
    assert [it.tag for it in big] == [1, 2]  # 1+1 fits, then 3 would exceed
    assert [it.tag for it in b._take_chunk()] == [3]
    assert b._queued == 0


def test_batcher_gen_queue_survives_steal_and_requeue():
    # the GenBatcher steal pattern: list(queue) + clear + partial requeue
    from symbiont_tpu.engine.batcher import _BatcherBase

    class Item:
        def __init__(self, tag):
            self.tag = tag
            self.future = None

    class B(_BatcherBase):
        def _size(self, item):
            return 1

    b = B(max_batch=8, deadline_s=0.01)
    for i in range(5):
        b._submit(Item(i))
    candidates = list(b._queue)
    b._queue.clear()
    b._queued -= sum(b._size(c) for c in candidates)
    assert b._queued == 0
    b._submit(Item(99))  # arrives mid-steal
    b._requeue(candidates[3:])  # transient rejects go back to the front
    assert [it.tag for it in b._queue] == [3, 4, 99]
    assert b._queued == 3


# ----------------------------------------------------- SSE gauge (satellite)

def test_sse_clients_is_a_real_gauge():
    from symbiont_tpu.bus.inproc import InprocBus
    from symbiont_tpu.config import ApiConfig
    from symbiont_tpu.services.api import ApiService

    async def scenario():
        api = ApiService(InprocBus(), ApiConfig(port=0, sse_keepalive_s=0.2))
        await api.start()
        base_gauge = metrics.gauge_get("api.sse_clients")
        base_total = metrics.get("api.sse_clients_total")
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           api.port)
            writer.write(b"GET /api/events HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            await reader.readline()  # HTTP/1.1 200 OK
            for _ in range(50):
                if metrics.gauge_get("api.sse_clients") == base_gauge + 1:
                    break
                await asyncio.sleep(0.05)
            assert metrics.gauge_get("api.sse_clients") == base_gauge + 1
            assert metrics.get("api.sse_clients_total") == base_total + 1
            writer.close()
            await writer.wait_closed()
            for _ in range(100):
                if metrics.gauge_get("api.sse_clients") == base_gauge:
                    break
                await asyncio.sleep(0.05)
            # DECREMENTED on disconnect (the pre-obs counter only ever rose)
            assert metrics.gauge_get("api.sse_clients") == base_gauge
            assert metrics.get("api.sse_clients_total") == base_total + 1
        finally:
            await api.stop()

    asyncio.run(scenario())


# ------------------------------------------- e2e trace propagation (runner)

class _StubEngine:
    """Duck-typed engine: deterministic fake embeddings, no device, no
    compiles — the trace-propagation test is about span plumbing, not BERT."""

    class _ModelCfg:
        hidden_size = 16

    def __init__(self):
        from symbiont_tpu.config import EngineConfig

        self.config = EngineConfig(embedding_dim=16, max_batch=8,
                                   flush_deadline_ms=2.0)
        self.model_cfg = self._ModelCfg()
        self.cross_params = None
        self.stats = {"embed_calls": 0, "compiles": 0}

    def embed_texts(self, texts):
        self.stats["embed_calls"] += 1
        rng = np.random.default_rng(len(texts))
        return rng.standard_normal((len(texts), 16)).astype(np.float32)


def test_ingest_trace_spans_pipeline(tmp_path):
    """A submitted URL yields ONE trace id whose parent-linked tree spans
    the ingest pipeline (≥3 services) — the flight-recorder acceptance
    criterion, driven through the real runner + HTTP surface."""
    from symbiont_tpu.bus.inproc import InprocBus
    from symbiont_tpu.config import (
        ApiConfig,
        GraphStoreConfig,
        SymbiontConfig,
        TextGeneratorConfig,
        VectorStoreConfig,
    )
    from symbiont_tpu.runner import SymbiontStack

    page = ("<html><body><main><p>Tracing the pipeline end to end.</p>"
            "<p>Spans must link across services!</p></main></body></html>")

    cfg = SymbiontConfig(
        vector_store=VectorStoreConfig(dim=16,
                                       data_dir=str(tmp_path / "vs"),
                                       shard_capacity=64),
        graph_store=GraphStoreConfig(data_dir=str(tmp_path / "gs")),
        text_generator=TextGeneratorConfig(markov_state_path=None),
        api=ApiConfig(host="127.0.0.1", port=0),
    )
    cfg.runner.services = ("perception,preprocessing,vector_memory,"
                           "knowledge_graph,api")

    async def scenario():
        trace_store.clear()
        stack = SymbiontStack(cfg, bus=InprocBus(), engine=_StubEngine(),
                              fetcher=lambda url: page)
        await stack.start()
        port = stack.api.port
        loop = asyncio.get_running_loop()

        def http_get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                return r.status, json.loads(r.read())

        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/submit-url",
                data=json.dumps({"url": "http://fake/doc"}).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            status = (await loop.run_in_executor(
                None, lambda: urllib.request.urlopen(req, timeout=10))).status
            assert status == 200
            for _ in range(200):
                if (stack.vector_store.count() >= 2
                        and stack.graph_store.counts()["Document"] >= 1):
                    break
                await asyncio.sleep(0.05)
            assert stack.vector_store.count() >= 2

            status, body = await loop.run_in_executor(
                None, http_get, "/api/traces/recent")
            assert status == 200
            ingest = [t for t in body["traces"]
                      if t["root"] == "api.submit_url"]
            assert ingest, f"no ingest trace in {body['traces']}"
            summary = ingest[0]
            assert summary["error_count"] == 0
            assert len(summary["services"]) >= 3

            status, tree = await loop.run_in_executor(
                None, http_get, f"/api/traces/{summary['trace_id']}")
            assert status == 200
            services = set(tree["services"])
            assert {"api", "perception", "preprocessing",
                    "vector_memory"} <= services
            # parent-linked: ONE root (the submit span), everything else
            # hangs off it
            assert len(tree["roots"]) == 1
            root = tree["roots"][0]
            assert root["name"] == "api.submit_url"

            def names(node):
                out = {node["name"]}
                for c in node["children"]:
                    out |= names(c)
                return out

            reachable = names(root)
            assert "perception.handle" in reachable
            assert "preprocessing.handle" in reachable
            assert "vector_memory.handle" in reachable
            assert "vector_memory.upsert" in reachable
            # Prometheus exposition over the same run, with the engine-plane
            # gauges the acceptance criterion names
            def get_text(path):
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                    return r.status, r.headers["Content-Type"], \
                        r.read().decode()

            status, ctype, text = await loop.run_in_executor(
                None, get_text, "/metrics")
            assert status == 200 and ctype.startswith("text/plain")
            assert 'symbiont_batcher_queue_depth{batcher="embed"' in text
            assert ('symbiont_batcher_last_flush_fill_ratio'
                    '{batcher="embed",service="engine"}') in text
            assert ('symbiont_bus_consumed_total{service="perception"'
                    in text)
            # real histogram series ride alongside the summaries
            # (acceptance: /metrics exposes _bucket/le for span durations)
            assert "symbiont_span_duration_ms_hist_bucket{le=" in text
            assert "# TYPE symbiont_span_duration_ms_hist histogram" in text
            assert 'quantile="0.99"' in text  # summaries stay
            # the runner registered the standard process_* host gauges
            assert "\nprocess_resident_memory_bytes" in text

            # acceptance: critical path of the live ingest trace names a
            # dominant hop with self-time accounting
            status, cp = await loop.run_in_executor(
                None, http_get,
                f"/api/traces/{summary['trace_id']}/critical_path")
            assert status == 200
            assert cp["e2e_ms"] > 0
            chain_names = [h["name"] for h in cp["chain"]]
            assert chain_names[0] == "api.submit_url"
            assert cp["dominant"] is not None
            assert cp["dominant"]["self_ms"] <= cp["e2e_ms"]
            assert cp["dominant"]["name"] in chain_names
            assert cp["verdict"].startswith(cp["dominant"]["name"])
            for hop in cp["chain"]:
                assert hop["self_ms"] + hop["child_ms"] <= (
                    hop["duration_ms"] + 0.01)

            # acceptance: the same trace exports as Chrome Trace Format
            # that validates against the golden-file schema
            status, chrome = await loop.run_in_executor(
                None, http_get,
                f"/api/traces/{summary['trace_id']}/export?fmt=chrome")
            assert status == 200
            _chrome_schema_check(chrome,
                                 expect_spans=tree["span_count"])
            def http_code(path):
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}{path}",
                            timeout=10) as r:
                        return r.status
                except urllib.error.HTTPError as e:
                    return e.code

            assert await loop.run_in_executor(
                None, http_code,
                f"/api/traces/{summary['trace_id']}/export?fmt=bogus") == 400
            # unknown trace: 404 on the new endpoints too
            assert await loop.run_in_executor(
                None, http_code, "/api/traces/nope/critical_path") == 404
        finally:
            await stack.stop()

    asyncio.run(scenario())
