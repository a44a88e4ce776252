"""Decode-plane flight recorder + usage metering + tail-based retention.

Covers the PR-15 observability plane end to end:

- EngineTimeline: ring bounds, summary arithmetic, the prefix-share probe,
  the packing-opportunity estimate;
- chrome_trace.export_timeline: counter tracks + span lanes in ONE
  Perfetto document, pinned by tests/goldens/engine_timeline_golden.json;
- TraceStore tail retention: an errored trace survives 10x capacity of
  healthy churn (the ring-pressure proof), slowest-decile pinning,
  healthy-trace sampling, keep-set bounds;
- SloWatchdog two-window burn rates + breach-exemplar pinning;
- UsageMeter: per-tenant ledger, bounded tenant universe, registry
  counters;
- the REAL decode path: a GenBatcher session mix records steps/admits/
  TTFT and bills tenants exactly (engine/lm.py chunk-boundary hooks);
- the HTTP surfaces: GET /api/engine/timeline (json + chrome) and
  GET /api/tenants on a booted stub-engine stack.
"""

import asyncio
import json
import pathlib
import time

import numpy as np
import pytest

from symbiont_tpu.obs import chrome_trace
from symbiont_tpu.obs.engine_timeline import EngineTimeline
from symbiont_tpu.obs.trace_store import SpanRecord, TraceStore
from symbiont_tpu.obs.usage import UsageMeter
from symbiont_tpu.utils.telemetry import Metrics

GOLDEN = pathlib.Path(__file__).parent / "goldens" / "engine_timeline_golden.json"


# ------------------------------------------------------------ timeline core

def _tl(**kw) -> EngineTimeline:
    kw.setdefault("registry", Metrics())
    return EngineTimeline(**kw)


def test_timeline_ring_is_bounded_and_clearable():
    tl = _tl(capacity=8)
    for i in range(50):
        tl.note_decode_step(wall_ms=1.0, rows_live=1, rows_capacity=2,
                            kv_rows_live=1, kv_rows_allocated=2, steps=4)
    assert len(tl) == 8
    tl.clear()
    assert len(tl) == 0 and tl.summary()["decode_steps"] == 0


def test_timeline_summary_arithmetic():
    tl = _tl()
    # two steps: 3/8 and 5/8 occupancy; kv 8 allocated, 3 and 5 live
    tl.note_decode_step(wall_ms=4.0, rows_live=3, rows_capacity=8,
                        kv_rows_live=3, kv_rows_allocated=8, steps=8)
    tl.note_decode_step(wall_ms=2.0, rows_live=5, rows_capacity=8,
                        kv_rows_live=5, kv_rows_allocated=8, steps=8)
    tl.note_admit(rows=2, prefill_ms=10.0, prefix_share=0.5, kind="splice")
    tl.note_finish(tokens=7, ttft_ms=12.0)
    tl.note_cancel()
    s = tl.summary()
    assert s["decode_steps"] == 2
    assert s["decode_occupancy_pct"] == pytest.approx(50.0)
    assert s["decode_kv_stranded_pct"] == pytest.approx(50.0)
    assert s["decode_prefix_share_pct"] == pytest.approx(50.0)
    assert s["decode_admits"] == 1 and s["decode_finishes"] == 1
    assert s["decode_cancels"] == 1
    assert s["decode_ttft_ms_p50"] == pytest.approx(12.0)
    # tpot samples 0.5 and 0.25 ms/token; repo median convention takes
    # the upper of an even-length pair
    assert s["decode_tpot_ms_p50"] == pytest.approx(0.5)
    assert any(k in s["dominant_stall"]
               for k in ("stranded KV", "row underfill",
                         "admission prefills"))


def test_timeline_summary_paged_fields():
    # the paged-KV fields (PR 16) appear only when steps carry pages_*
    # and admits carry prompt_tokens — dense timelines stay unchanged
    tl = _tl()
    tl.note_decode_step(wall_ms=2.0, rows_live=2, rows_capacity=4,
                        kv_rows_live=2, kv_rows_allocated=4, steps=8,
                        pages_free=6, pages_live=2, pages_total=8)
    tl.note_admit(rows=1, prefill_ms=5.0, prefix_share=0.5, kind="splice",
                  hit_tokens=24, prompt_tokens=32)
    tl.note_finish(tokens=4, ttft_ms=2.0, radix_hit=True)
    tl.note_finish(tokens=4, ttft_ms=40.0, radix_hit=False)
    s = tl.summary()
    assert s["decode_radix_hit_pct"] == pytest.approx(75.0)
    assert s["decode_pages_live_pct"] == pytest.approx(25.0)
    assert s["decode_ttft_hit_ms_p50"] == pytest.approx(2.0)
    assert s["decode_ttft_cold_ms_p50"] == pytest.approx(40.0)
    # a dense timeline never grows the paged keys
    dense = _tl()
    dense.note_decode_step(wall_ms=2.0, rows_live=2, rows_capacity=4,
                           kv_rows_live=2, kv_rows_allocated=4, steps=8)
    dense.note_finish(tokens=4, ttft_ms=2.0)
    ds = dense.summary()
    assert "decode_pages_live_pct" not in ds
    assert "decode_radix_hit_pct" not in ds


def test_timeline_disabled_records_nothing():
    tl = _tl(capacity=0)
    tl.note_decode_step(wall_ms=1.0, rows_live=1, rows_capacity=1,
                        kv_rows_live=1, kv_rows_allocated=1, steps=1)
    tl.note_embed_flush(128, 8, 8, real_tokens=10, total_tokens=1024)
    assert tl.prompt_prefix_share([[1, 2, 3]]) == 0.0
    assert len(tl) == 0


def test_prefix_share_probe():
    tl = _tl()
    assert tl.prompt_prefix_share([[1, 2, 3, 4]]) == 0.0  # empty registry
    # identical prompt: full-prefix overlap
    assert tl.prompt_prefix_share([[1, 2, 3, 4]]) == pytest.approx(1.0)
    # half-prefix overlap
    assert tl.prompt_prefix_share([[1, 2, 9, 9]]) == pytest.approx(0.5)
    # disjoint
    assert tl.prompt_prefix_share([[7, 7, 7, 7]]) == 0.0
    # the windowed gauge landed
    g = tl.registry.snapshot()["gauges"]
    assert 'lm.prefix_share_ratio{service="lm"}' in g


def test_prefix_probe_registry_is_bounded():
    tl = _tl(prompt_window=4)
    for i in range(100):
        tl.prompt_prefix_share([[i, i + 1, i + 2]])
    assert len(tl._prompts) == 4


def test_packing_opportunity_gauge_from_flush_window():
    tl = _tl()
    tl.note_embed_flush(128, 8, 4, real_tokens=256, total_tokens=1024)
    g = tl.registry.snapshot()["gauges"]
    assert g['engine.packing_opportunity_pct{service="engine"}'] == \
        pytest.approx(75.0)
    s = tl.summary()
    assert s["packing_opportunity_pct"] == pytest.approx(75.0)
    assert s["embed_flushes"] == 1


# -------------------------------------------------------- chrome export

def _golden_inputs():
    """Deterministic engine-shaped spans + timeline events (fixed fake
    wall-clock seconds; no clocks, no randomness)."""
    ts = TraceStore(capacity=32)
    ts.record(SpanRecord("g1", "s0", None, "text_generator.generate",
                         100.0, 50.0, "ok"))
    ts.record(SpanRecord("g1", "s1", "s0", "engine.generate",
                         100.005, 40.0, "ok"))
    ts.record(SpanRecord("g2", "s2", None, "engine.compile",
                         100.010, 8.0, "error"))
    events = [
        {"kind": "admit", "t": 100.0, "rows": 4, "prefill_ms": 5.0,
         "prefix_share": 0.5, "admit_kind": "start"},
        {"kind": "step", "t": 100.010, "wall_ms": 4.0, "rows_live": 4,
         "rows_capacity": 8, "kv_rows_live": 4, "kv_rows_allocated": 8,
         "steps": 8, "sessions": 1},
        {"kind": "queue", "t": 100.012, "queue": "generate", "depth": 3},
        {"kind": "flush", "t": 100.015, "bucket": 128, "batch_rows": 8,
         "n_real": 5, "real_tokens": 100, "total_tokens": 1024},
        {"kind": "step", "t": 100.020, "wall_ms": 4.0, "rows_live": 6,
         "rows_capacity": 8, "kv_rows_live": 6, "kv_rows_allocated": 8,
         "steps": 8, "sessions": 1},
        {"kind": "finish", "t": 100.030, "tokens": 8, "ttft_ms": 14.0},
        {"kind": "cancel", "t": 100.032},
    ]
    return ts, events


def test_export_timeline_counters_and_span_lanes():
    ts, events = _golden_inputs()
    spans = ts.spans_for("g1") + ts.spans_for("g2")
    doc = chrome_trace.export_timeline("engine-timeline", spans, events)
    phs = {}
    for e in doc["traceEvents"]:
        phs.setdefault(e["ph"], []).append(e)
    assert len(phs["X"]) == 3                      # span lanes intact
    counters = phs["C"]
    # 2 counters per step event (rows + kv_rows) x 2 steps + queue + flush
    assert doc["otherData"]["counter_events"] == len(counters) == 6
    assert doc["otherData"]["instant_events"] == len(phs["i"]) == 3
    names = {e["name"] for e in counters}
    assert names == {"decode.rows", "decode.kv_rows",
                     "engine.queue.generate", "embed.flush_tokens"}
    by_name = {e["name"]: e for e in counters}
    assert by_name["decode.kv_rows"]["args"] in (
        {"live": 4, "stranded": 4}, {"live": 6, "stranded": 2})
    assert by_name["embed.flush_tokens"]["args"] == {"real": 100,
                                                     "padding": 924}
    # counter/instant events are chronologically sorted in document order
    # and share the span time axis (µs)
    cts = [e["ts"] for e in doc["traceEvents"] if e["ph"] in ("C", "i")]
    assert cts == sorted(cts)
    assert any(e["ts"] == pytest.approx(100.010 * 1e6) for e in counters)


def test_export_timeline_matches_golden():
    ts, events = _golden_inputs()
    spans = ts.spans_for("g1") + ts.spans_for("g2")
    doc = chrome_trace.export_timeline("engine-timeline", spans, events)
    golden = json.loads(GOLDEN.read_text())
    assert doc == golden, (
        "engine-timeline Perfetto export drifted from the pinned golden — "
        "if deliberate, regenerate: python -c \"from "
        "tests.test_engine_timeline import _write_timeline_golden; "
        "_write_timeline_golden()\"")


def _write_timeline_golden() -> None:
    ts, events = _golden_inputs()
    spans = ts.spans_for("g1") + ts.spans_for("g2")
    doc = chrome_trace.export_timeline("engine-timeline", spans, events)
    GOLDEN.parent.mkdir(exist_ok=True)
    GOLDEN.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")


def test_export_timeline_without_spans_still_has_counter_lane():
    _, events = _golden_inputs()
    doc = chrome_trace.export_timeline("engine-timeline", [], events)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "process_name"
    assert any(e["ph"] == "C" for e in doc["traceEvents"])


# ------------------------------------------------- tail-based retention

def _span(trace, sid, status="ok", parent=None, start=1.0, dur=1.0,
          name="api.handle"):
    return SpanRecord(trace, sid, parent, name, start, dur, status)


def test_errored_trace_survives_10x_ring_pressure():
    """The acceptance bar: one errored trace, then 10x the ring capacity
    of healthy churn — the errored trace must still be queryable whole."""
    ts = TraceStore(capacity=64)
    ts.record(_span("bad", "b0", start=1.0))
    ts.record(_span("bad", "b1", parent="b0", status="error", start=1.1))
    ts.record(_span("bad", "b2", parent="b0", start=1.2))
    for i in range(10 * 64):
        ts.record(_span(f"h{i}", f"h{i}", start=2.0 + i))
    # the ring itself evicted everything of "bad"
    assert all(r.trace_id != "bad" for r in ts._ring)
    spans = ts.spans_for("bad")
    assert {r.span_id for r in spans} == {"b0", "b1", "b2"}
    tree = ts.trace_tree("bad")
    assert tree["error_count"] == 1 and tree["span_count"] == 3
    # errored-first triage order still surfaces it
    assert any(s["trace_id"] == "bad" and s["error_count"]
               for s in ts.recent(limit=200))


def test_healthy_traces_keep_fifo_eviction():
    ts = TraceStore(capacity=4)
    for i in range(10):
        ts.record(_span(f"t{i}", f"s{i}", start=float(i)))
    assert not ts.spans_for("t0") and ts.spans_for("t9")
    assert ts.pinned_traces() == 0


def test_slowest_decile_root_pins():
    ts = TraceStore(capacity=16)
    for i in range(40):
        ts.record(_span(f"w{i}", f"w{i}", start=float(i), dur=1.0))
    ts.record(_span("slow", "slow0", start=100.0, dur=500.0))
    for i in range(200):
        ts.record(_span(f"x{i}", f"x{i}", start=200.0 + i, dur=1.0))
    assert ts.spans_for("slow")
    # uniform-duration traffic pinned nothing else
    assert ts.pinned_traces() == 1


def test_keep_set_is_bounded_and_counts_evictions():
    ts = TraceStore(capacity=16, keep_traces=3)
    for i in range(8):
        ts.record(_span(f"e{i}", f"e{i}", status="error", start=float(i)))
    assert ts.pinned_traces() == 3
    assert ts.pin_evictions == 5
    # churn the ring: an errored trace EVICTED from the bounded keep-set
    # is gone, the still-pinned ones survive
    for i in range(100):
        ts.record(_span(f"c{i}", f"c{i}", start=10.0 + i))
    assert not ts.spans_for("e0") and ts.spans_for("e7")


def test_healthy_sampling_keeps_configured_fraction():
    ts = TraceStore(capacity=1000)
    ts.configure_retention(sample_rate=0.25)
    for i in range(100):
        ts.record(_span(f"s{i}", f"s{i}", start=float(i)))
    assert len(ts) == 25 and ts.sampled_out == 75
    # fractional rates are NOT quantized to an integer period: 0.75 keeps
    # exactly 75%, not everything
    ts75 = TraceStore(capacity=1000)
    ts75.configure_retention(sample_rate=0.75)
    for i in range(100):
        ts75.record(_span(f"r{i}", f"r{i}", start=float(i)))
    assert len(ts75) == 75 and ts75.sampled_out == 25
    # a sampled-out trace that errors later is still pinned WITH the
    # errored span
    ts.record(_span("s1", "s1-err", status="error", start=500.0,
                    parent="s1"))
    assert any(r.span_id == "s1-err" for r in ts.spans_for("s1"))


def test_explicit_pin_keeps_future_spans():
    ts = TraceStore(capacity=4)
    ts.record(_span("keep", "k0", start=1.0))
    ts.pin("keep")
    for i in range(40):
        ts.record(_span(f"c{i}", f"c{i}", start=2.0 + i))
    ts.record(_span("keep", "k1", parent="k0", start=50.0))
    assert {r.span_id for r in ts.spans_for("keep")} == {"k0", "k1"}


# ------------------------------------------------------ watchdog burn rate

def test_watchdog_burn_rates_and_exemplar_pinning():
    from symbiont_tpu.obs.watchdog import SloWatchdog

    reg = Metrics()
    store = TraceStore(capacity=64)
    wd = SloWatchdog({"api.search": 10.0}, registry=reg,
                     burn_fast_s=60.0, burn_slow_s=600.0, store=store)
    # a FAST observation's bucket exemplar must never pin (healthy churn
    # through the bounded keep-set would evict the evidence it protects)
    reg.observe("span.api.search.ms", 1.0,
                exemplar={"trace_id": "fast-trace"})
    # breach: slow observations with a trace exemplar
    reg.observe("span.api.search.ms", 500.0,
                exemplar={"trace_id": "slow-trace"})
    breaches = wd.evaluate()
    assert len(breaches) == 1
    ev = breaches[0]
    assert ev["burn_rate_fast"] == 1.0 and ev["burn_rate_slow"] == 1.0
    # ONLY the breaching bucket's exemplar trace is pinned
    assert store.pinned_traces() == 1
    assert store.spans_for("slow-trace") == []  # pinned id, no spans yet
    store.record(_span("slow-trace", "late"))
    assert store.spans_for("slow-trace")
    assert "fast-trace" not in store._pinned
    # healthy pass dilutes the burn rate (fresh fast sample)
    reg.observe("span.api.search.ms", 1.0)
    # cumulative p99 still breaches; rates reflect breach fraction of
    # judged passes
    wd.evaluate()
    g = reg.snapshot()["gauges"]
    assert 'slo.burn_rate_fast{span="api.search"}' in g
    assert 'slo.burn_rate_slow{span="api.search"}' in g


def test_watchdog_burn_rate_clears_on_recovery():
    from symbiont_tpu.obs.watchdog import SloWatchdog

    reg = Metrics()
    wd = SloWatchdog({"api.x": 1000.0}, registry=reg, store=TraceStore(8))
    for _ in range(3):
        reg.observe("span.api.x.ms", 5.0)
        assert wd.evaluate() == []
    g = reg.snapshot()["gauges"]
    assert g['slo.burn_rate_fast{span="api.x"}'] == 0.0


# ------------------------------------------------- fleet tap retention

def test_fleet_exporter_tap_keeps_errored_spans_under_churn():
    from symbiont_tpu.obs.fleet import TelemetryExporter

    reg = Metrics()
    store = TraceStore(capacity=4096)
    exp = TelemetryExporter(lambda: None, role="r", pending_max=16,
                            spans_max=8, registry=reg, store=store)
    err = _span("t-err", "e0", status="error")
    exp._tap(err)
    for i in range(200):
        exp._tap(_span(f"t{i}", f"s{i}"))
    batch = exp._drain_spans()
    assert batch[0].span_id == "e0"  # errored first, never displaced
    assert reg.get("fleet.spans_dropped") > 0


# --------------------------------------------------------- usage metering

def test_usage_meter_ledger_and_registry():
    reg = Metrics()
    m = UsageMeter(registry=reg)
    m.note("acme", tokens_in=10, tokens_out=4)
    m.note("acme", kv_row_seconds=0.5)
    m.note(None, embed_rows=3)          # None → default tenant
    m.note("acme", search_queries=1)
    snap = m.snapshot()
    assert snap["acme"] == {"tokens_in": 10.0, "tokens_out": 4.0,
                            "kv_row_seconds": 0.5, "search_queries": 1.0}
    assert snap["default"]["embed_rows"] == 3.0
    assert reg.get("tenant.usage.tokens_in",
                   labels={"tenant": "acme"}) == 10
    with pytest.raises(ValueError):
        m.note("acme", bogus_kind=1)


def test_usage_meter_bounded_tenant_universe():
    m = UsageMeter(max_tenants=3, registry=Metrics())
    for i in range(10):
        m.note(f"tenant-{i}", tokens_in=1)
    snap = m.snapshot()
    assert "(overflow)" in snap
    # default + 2 named + overflow
    assert len(snap) <= 4
    assert snap["(overflow)"]["tokens_in"] == 8.0


# ------------------------------------------- real decode session (engine)

@pytest.fixture(scope="module")
def tiny_lm():
    from symbiont_tpu.config import LmConfig
    from symbiont_tpu.engine.lm import LmEngine

    return LmEngine(LmConfig(
        enabled=True, arch="gpt2", hidden_size=32, num_layers=1,
        num_heads=2, intermediate_size=64, max_positions=128,
        dtype="float32", prompt_buckets=[16], new_token_buckets=[16],
        stream_chunk=4, gen_max_batch=8, gen_flush_deadline_ms=5.0,
        session_min_rows=4, temperature=0.0))


def test_decode_session_records_timeline_and_usage(tiny_lm):
    from symbiont_tpu.obs.engine_timeline import engine_timeline
    from symbiont_tpu.obs.usage import usage

    engine_timeline.clear()
    usage.reset()
    sess = tiny_lm.start_session(
        ["shared prefix one", "shared prefix two"], [8, 8],
        tenants=["gold", "free"])
    while not sess.done():
        sess.step()
    s = engine_timeline.summary()
    assert s["decode_steps"] >= 1
    assert s["decode_admits"] >= 1
    assert s["decode_finishes"] == 2
    assert 0 < s["decode_occupancy_pct"] <= 100
    # both tenants billed: exact prompt tokens in, decoded tokens out,
    # and kv-row-seconds accrued
    snap = usage.snapshot()
    for tenant in ("gold", "free"):
        assert snap[tenant]["tokens_in"] > 0
        assert snap[tenant]["tokens_out"] > 0
        assert snap[tenant]["kv_row_seconds"] > 0
    # TTFT histogram fed
    from symbiont_tpu.utils.telemetry import metrics as gmetrics

    hist = gmetrics.histogram_summary("lm.ttft_ms",
                                      labels={"service": "lm"})
    assert hist is not None and hist["count"] >= 2
    # "shared prefix ..." prompts overlap: the probe saw it
    assert s["decode_prefix_share_pct"] > 0
    # kv stranded gauge is readable and consistent with no live sessions
    assert gmetrics.gauge_get(
        "lm.kv_stranded_rows",
        labels={"service": "lm",
                "kv_dtype": tiny_lm.model_cfg.dtype}) == 0


def test_decode_session_chrome_export_has_counters_and_spans(tiny_lm):
    from symbiont_tpu.obs.engine_timeline import engine_timeline

    engine_timeline.clear()
    sess = tiny_lm.start_session(["export me"], [8])
    while not sess.done():
        sess.step()
    events = engine_timeline.events()
    doc = chrome_trace.export_timeline("engine-timeline", [], events)
    phs = [e["ph"] for e in doc["traceEvents"]]
    assert "C" in phs and "i" in phs
    assert doc["otherData"]["counter_events"] >= 2


def test_cancelled_row_notes_cancel_and_bills_tokens(tiny_lm):
    from symbiont_tpu.obs.engine_timeline import engine_timeline
    from symbiont_tpu.obs.usage import usage

    engine_timeline.clear()
    usage.reset()
    sess = tiny_lm.start_session(["cancel target"], [16],
                                 tenants=["quitter"])
    sess.step()
    (tag,) = [r.tag for r in sess.rows if r is not None]
    assert sess.cancel_tag(tag)
    s = engine_timeline.summary()
    assert s["decode_cancels"] == 1
    assert usage.snapshot()["quitter"]["tokens_out"] >= 0


# --------------------------------------------------------- HTTP surfaces

class _StubEngine:
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
        rng = np.random.default_rng(len(texts))
        return rng.standard_normal((len(texts), 16)).astype(np.float32)


def test_timeline_and_tenants_endpoints(tmp_path):
    import urllib.request

    from symbiont_tpu.bus.inproc import InprocBus
    from symbiont_tpu.config import (
        ApiConfig,
        GraphStoreConfig,
        SymbiontConfig,
        TextGeneratorConfig,
        VectorStoreConfig,
    )
    from symbiont_tpu.obs.engine_timeline import engine_timeline
    from symbiont_tpu.obs.usage import usage
    from symbiont_tpu.runner import SymbiontStack

    engine_timeline.clear()
    usage.reset()
    page = ("<html><body><main><p>Timeline endpoint sentence one.</p>"
            "<p>Timeline endpoint sentence two!</p></main></body></html>")
    cfg = SymbiontConfig(
        vector_store=VectorStoreConfig(dim=16, data_dir=str(tmp_path / "vs"),
                                       shard_capacity=64),
        graph_store=GraphStoreConfig(data_dir=str(tmp_path / "gs")),
        text_generator=TextGeneratorConfig(markov_state_path=None),
        api=ApiConfig(host="127.0.0.1", port=0, fused_search=False),
    )
    cfg.runner.services = ("perception,preprocessing,vector_memory,"
                           "knowledge_graph,text_generator,api")

    async def scenario():
        stack = SymbiontStack(cfg, bus=InprocBus(), engine=_StubEngine(),
                              fetcher=lambda url: page)
        await stack.start()
        loop = asyncio.get_running_loop()
        port = stack.api.port

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                return json.loads(r.read())

        def post(path, body, headers=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json",
                         **(headers or {})}, method="POST")
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.loads(r.read())

        try:
            status, _ = await loop.run_in_executor(
                None, lambda: post("/api/submit-url",
                                   {"url": "http://fake/doc"},
                                   {"X-Symbiont-Tenant": "acme"}))
            assert status == 200
            for _ in range(200):
                if stack.vector_store.count() >= 2:
                    break
                await asyncio.sleep(0.05)
            assert stack.vector_store.count() >= 2
            status, _ = await loop.run_in_executor(
                None, lambda: post("/api/search/semantic",
                                   {"query_text": "timeline", "top_k": 2},
                                   {"X-Symbiont-Tenant": "acme"}))
            assert status == 200
            # a generation drives the text_generator span lane the chrome
            # export interleaves with the counter tracks
            status, _ = await loop.run_in_executor(
                None, lambda: post("/api/generate-text",
                                   {"task_id": "tl-gen", "prompt": "hi",
                                    "max_length": 8}))
            assert status == 200
            for _ in range(100):
                from symbiont_tpu.obs.trace_store import trace_store

                if any(r.name == "text_generator.generate"
                       for spans in trace_store.spans_by_trace().values()
                       for r in spans):
                    break
                await asyncio.sleep(0.05)
            body = await loop.run_in_executor(
                None, lambda: get("/api/engine/timeline"))
            # a stub engine records no real _note_padding flushes, but
            # the micro-batcher's queue-depth samples land regardless
            assert any(e["kind"] == "queue" for e in body["events"])
            assert "dominant_stall" in body["summary"]
            doc = await loop.run_in_executor(
                None, lambda: get("/api/engine/timeline?fmt=chrome"))
            # counter tracks AND span lanes in ONE Perfetto document
            assert any(e["ph"] == "C" for e in doc["traceEvents"])
            assert any(e["ph"] == "X"
                       and e["name"] == "text_generator.generate"
                       for e in doc["traceEvents"])
            tb = await loop.run_in_executor(
                None, lambda: get("/api/tenants"))
            assert tb["tenants"]["acme"]["search_queries"] == 1.0
            assert tb["tenants"]["acme"]["embed_rows"] >= 2.0
        finally:
            await stack.stop()

    asyncio.run(scenario())
