"""Compute-plane profiler (obs/xprof.py, ROADMAP item 5's instrument).

Covers the PR-17 profiling plane end to end:

- DispatchLedger: per-executable counts + host wall across recompiles,
  LRU bound, enable/disable, registry counters;
- live host-sync audit: counters per allowlisted site, and TWO-direction
  parity with the lint allowlist (every allowlisted site has a runtime
  counter call; no counter call names a site the lint rule doesn't know);
- cost_analysis_of: real-jit happy path, and the graceful None fallback
  when the backend exposes no cost model (None is "unknown", never zero);
- host-gap attribution: the engine-timeline summary's
  decode_dispatches_per_token / decode_host_gap_pct fields and the new
  `host-dispatch` dominant-stall verdict;
- roofline.grade_executable: cost-model work over measured dispatch wall;
- DeviceTraceCapture: bounded window, input validation, the busy path
  under telemetry's process-global profiler lock;
- the REAL decode path: an LmEngine session populates the ledger with
  prefill/decode-chunk signatures and nonzero host-gap summary fields;
- the HTTP surfaces: GET /api/engine/executables and a bounded
  POST /api/profile/device on a booted stub-engine stack.
"""

import asyncio
import json
import pathlib
import re
import types

import numpy as np
import pytest

from symbiont_tpu.bench.roofline import grade_executable
from symbiont_tpu.obs.xprof import (
    DeviceTraceCapture,
    DispatchLedger,
    compile_analysis_for,
    cost_analysis_of,
    known_sync_sites,
)
from symbiont_tpu.utils.telemetry import Metrics

REPO = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------- dispatch ledger

def _ledger(**kw) -> DispatchLedger:
    kw.setdefault("registry", Metrics())
    return DispatchLedger(**kw)


def test_ledger_counts_dispatches_and_recompiles():
    led = _ledger()
    led.note_compile("embed[L=128,B=8]", {"flops": 1e9,
                                         "bytes_accessed": 1e8})
    led.note_dispatch("embed[L=128,B=8]", 0.010)
    led.note_dispatch("embed[L=128,B=8]", 0.020)
    # a cache eviction recompiles the SAME signature: compiles accumulate
    led.note_compile("embed[L=128,B=8]", {"flops": 1e9,
                                         "bytes_accessed": 1e8})
    led.note_dispatch("embed[L=128,B=32]", 0.005)
    rows = {r["executable"]: r for r in led.snapshot()}
    r = rows["embed[L=128,B=8]"]
    assert r["dispatches"] == 2 and r["compiles"] == 2
    assert r["host_wall_ms"] == pytest.approx(30.0)
    assert r["mean_dispatch_us"] == pytest.approx(15000.0)
    assert r["flops"] == 1e9 and r["bytes_accessed"] == 1e8
    assert rows["embed[L=128,B=32]"]["dispatches"] == 1
    # snapshot orders by dispatch count (hottest executable first)
    assert led.snapshot()[0]["executable"] == "embed[L=128,B=8]"
    # the counter family carries the per-executable label
    assert led.registry.get(
        "xla.dispatches_total",
        labels={"executable": "embed[L=128,B=8]"}) == 2


def test_ledger_lru_bound_and_configure():
    led = _ledger(max_executables=4)
    for i in range(10):
        led.note_dispatch(f"sig{i}", 0.001)
    assert len(led) == 4
    assert {r["executable"] for r in led.snapshot()} == \
        {"sig6", "sig7", "sig8", "sig9"}
    led.configure(max_executables=2)  # shrinks in place, oldest out first
    assert len(led) == 2
    led.clear()
    assert len(led) == 0 and led.snapshot() == []


def test_ledger_disabled_records_nothing():
    led = _ledger()
    led.configure(enabled=False)
    led.note_dispatch("sig", 0.001)
    led.note_compile("sig", {"flops": 1.0, "bytes_accessed": 1.0})
    led.note_host_sync("TpuEngine.warmup")
    assert len(led) == 0
    assert led.registry.get("xla.dispatches_total",
                            labels={"executable": "sig"}) == 0


def test_cost_unknown_stays_none_not_zero():
    led = _ledger()
    led.note_compile("nocost", None)
    led.note_dispatch("nocost", 0.001)
    (r,) = led.snapshot()
    assert r["flops"] is None and r["bytes_accessed"] is None


# --------------------------------------------------- live host-sync audit

def test_sync_counters_fire_per_site():
    led = _ledger()
    led.note_host_sync("TpuEngine.warmup")
    led.note_host_sync("TpuEngine.embed_texts", n=3)
    assert led.registry.get("engine.host_syncs_total",
                            labels={"site": "TpuEngine.warmup"}) == 1
    assert led.registry.get("engine.host_syncs_total",
                            labels={"site": "TpuEngine.embed_texts"}) == 3


def test_register_zero_exports_every_allowlisted_site():
    led = _ledger()
    led.register_zero()
    counters = led.registry.snapshot()["counters"]
    assert counters['xla.dispatches_total{executable="all"}'] == 0
    for site in known_sync_sites():
        assert counters[f'engine.host_syncs_total{{site="{site}"}}'] == 0


def test_sync_site_parity_both_directions():
    """The static lint allowlist and the runtime counter sites are ONE
    inventory. Direction 1: known_sync_sites() mirrors every allowlist
    scope. Direction 2: every ``note_host_sync("...")`` call site in the
    engine plane names an allowlisted scope — a counter can never fire
    from a sync the ``jax-host-sync-in-loop`` rule doesn't know about."""
    from symbiont_tpu.lint.allowlist import JAX_HOST_SYNC_ALLOWED

    allow = {scope for (_f, scope) in JAX_HOST_SYNC_ALLOWED}
    assert set(known_sync_sites()) == allow
    called = set()
    for py in (REPO / "symbiont_tpu").rglob("*.py"):
        if py.name == "xprof.py":  # the definition, not a call site
            continue
        called |= set(re.findall(r'note_host_sync\(\s*"([^"]+)"',
                                 py.read_text()))
    assert called == allow, (
        "runtime host-sync counter sites drifted from the lint allowlist "
        f"(counters: {sorted(called)}, allowlist: {sorted(allow)})")


# ----------------------------------------------------------- cost analysis

def _fake_lowered(ca):
    """Stands in for jax.jit(fn).lower(*args): .cost_analysis() -> shape."""
    def cost_analysis():
        if isinstance(ca, Exception):
            raise ca
        return ca
    return types.SimpleNamespace(cost_analysis=cost_analysis)


def test_cost_analysis_fallback_when_unavailable():
    # backend has no cost model -> None (unknown)
    assert cost_analysis_of(
        _fake_lowered(RuntimeError("no cost model"))) is None
    # non-dict shapes -> None
    assert cost_analysis_of(_fake_lowered("nope")) is None
    assert cost_analysis_of(_fake_lowered([])) is None


def test_cost_analysis_normalizes_shapes_and_guards_values():
    out = cost_analysis_of(
        _fake_lowered({"flops": 10.0, "bytes accessed": 5.0}))
    assert out == {"flops": 10.0, "bytes_accessed": 5.0}
    # per-device LIST of dicts
    out = cost_analysis_of(_fake_lowered([{"flops": 7.0}]))
    assert out == {"flops": 7.0, "bytes_accessed": 0.0}
    # NaN / negative / non-numeric estimates -> 0.0, never poison
    out = cost_analysis_of(
        _fake_lowered({"flops": float("nan"), "bytes accessed": -3.0}))
    assert out == {"flops": 0.0, "bytes_accessed": 0.0}


def test_compile_errors_propagate_and_are_paid_once():
    """Only the ANALYSES are tolerant. A failed lower() or compile() raises
    out of compile_analysis_for — it is never swallowed into a second
    attempt under jit (a compile error paid for twice and reported never)."""
    calls = {"lower": 0, "compile": 0}

    class _Lowered:
        def cost_analysis(self):
            return {"flops": 1.0}

        def compile(self):
            calls["compile"] += 1
            raise RuntimeError("mosaic says no")

    class _Jitted:
        def __init__(self, lower_exc=None):
            self._exc = lower_exc

        def lower(self, *args):
            calls["lower"] += 1
            if self._exc is not None:
                raise self._exc
            return _Lowered()

    with pytest.raises(TypeError, match="bad trace"):
        compile_analysis_for(_Jitted(TypeError("bad trace")), ())
    with pytest.raises(RuntimeError, match="mosaic says no"):
        compile_analysis_for(_Jitted(), ())
    assert calls == {"lower": 2, "compile": 1}


def test_cost_analysis_real_jit_does_not_crash():
    import jax
    import jax.numpy as jnp

    jitted = jax.jit(lambda x: jnp.dot(x, x))
    out = cost_analysis_of(jitted.lower(np.ones((8, 8), dtype=np.float32)))
    # CPU backends may or may not expose a cost model — both are legal,
    # but a present one must carry the normalized keys
    if out is not None:
        assert set(out) == {"flops", "bytes_accessed"}
        assert out["flops"] >= 0.0


# ------------------------------------------------- host-gap attribution

def test_timeline_summary_host_gap_fields():
    from symbiont_tpu.obs.engine_timeline import EngineTimeline

    tl = EngineTimeline(registry=Metrics())
    # two 8-token chunks, 1 dispatch each, 4ms device + 1ms host gap
    for _ in range(2):
        tl.note_decode_step(wall_ms=4.0, rows_live=4, rows_capacity=8,
                            kv_rows_live=4, kv_rows_allocated=8, steps=8,
                            dispatches=1, host_gap_ms=1.0)
    s = tl.summary()
    assert s["decode_dispatches_per_token"] == pytest.approx(2 / 16)
    assert s["decode_host_gap_pct"] == pytest.approx(20.0)
    # a recorder that predates the profiler never grows the keys
    dense = EngineTimeline(registry=Metrics())
    dense.note_decode_step(wall_ms=4.0, rows_live=4, rows_capacity=8,
                           kv_rows_live=4, kv_rows_allocated=8, steps=8)
    ds = dense.summary()
    assert "decode_dispatches_per_token" not in ds
    assert "decode_host_gap_pct" not in ds


def test_host_dispatch_dominant_stall_verdict():
    from symbiont_tpu.obs.engine_timeline import EngineTimeline

    tl = EngineTimeline(registry=Metrics())
    # full occupancy, zero stranded KV, no admits: the ONLY measured waste
    # is the host gap between chunk dispatches (80% of chunk wall)
    tl.note_decode_step(wall_ms=2.0, rows_live=8, rows_capacity=8,
                        kv_rows_live=8, kv_rows_allocated=8, steps=8,
                        dispatches=8, host_gap_ms=8.0)
    s = tl.summary()
    assert s["decode_host_gap_pct"] == pytest.approx(80.0)
    assert "host-dispatch" in s["dominant_stall"]


# ------------------------------------------------------ roofline grading

def test_grade_executable_places_cost_model_on_roofline():
    g = grade_executable(flops=1e9, bytes_accessed=1e8, wall_s=0.01,
                         dispatches=10, ref_gbps=200.0)
    assert g["achieved_gflops_per_s"] == pytest.approx(1000.0)
    assert g["achieved_gbps"] == pytest.approx(100.0)
    assert g["arithmetic_intensity"] == pytest.approx(10.0)
    assert g["hbm_util_vs_ref_pct"] == pytest.approx(50.0)


def test_grade_executable_unknown_cost_is_all_none():
    for kw in (dict(flops=None, bytes_accessed=None, wall_s=0.01,
                    dispatches=10),
               dict(flops=1e9, bytes_accessed=1e8, wall_s=0.0,
                    dispatches=10),
               dict(flops=1e9, bytes_accessed=1e8, wall_s=0.01,
                    dispatches=0)):
        assert all(v is None for v in grade_executable(**kw).values())


# -------------------------------------------------- device trace capture

def test_device_trace_validates_and_reports_busy(tmp_path):
    from symbiont_tpu.obs import xprof

    cap = DeviceTraceCapture()
    cap.configure(trace_dir=str(tmp_path), max_s=0.2)
    with pytest.raises(ValueError):
        cap.capture(duration_s=-1.0)
    with pytest.raises(ValueError):
        cap.capture(duration_s="soon")
    # a capture already in flight holds the process-global profiler lock:
    # the request must report busy, never corrupt the in-flight trace
    assert xprof._profile_lock.acquire(blocking=False)
    try:
        res = cap.capture(duration_s=0.05)
    finally:
        xprof._profile_lock.release()
    assert res["status"] == "busy"
    assert cap.last_artifact is None


def test_device_trace_capture_is_bounded(tmp_path):
    cap = DeviceTraceCapture()
    cap.configure(trace_dir=str(tmp_path), max_s=0.1)
    res = cap.capture(duration_s=60.0)  # clamped to max_s, never 60s
    # a backend without profiler support reports error rather than
    # crashing; a working one returns the artifact dir
    assert res["status"] in ("captured", "error")
    if res["status"] == "captured":
        # sleep clamped to max_s=0.1; wall carries profiler start/stop
        # serialization overhead on top, but never the requested 60s
        assert res["window_s"] < 30.0
        assert res["artifact"].startswith(str(tmp_path))
        assert cap.last_artifact == res["artifact"]


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


def test_decode_session_feeds_ledger_and_host_gap(tiny_lm):
    from symbiont_tpu.obs.engine_timeline import engine_timeline
    from symbiont_tpu.obs.xprof import dispatch_ledger

    engine_timeline.clear()
    dispatch_ledger.clear()
    dispatch_ledger.configure(enabled=True)
    sess = tiny_lm.start_session(["ledger probe one", "ledger probe two"],
                                 [8, 8])
    while not sess.done():
        sess.step()
    sigs = {r["executable"]: r for r in dispatch_ledger.snapshot()}
    chunk = [s for s in sigs if s.startswith("lm.decode_chunk[")]
    prefill = [s for s in sigs if s.startswith("lm.prefill[")]
    assert chunk and prefill, sorted(sigs)
    assert sigs[chunk[0]]["dispatches"] >= 2  # 8 tokens / chunk=4
    assert sigs[chunk[0]]["host_wall_ms"] > 0
    # the chunk-boundary host-gap attribution reached the summary — and
    # the bench decode_timeline tier's two new primaries are NONZERO
    s = engine_timeline.summary()
    assert s["decode_dispatches_per_token"] > 0
    assert s["decode_host_gap_pct"] >= 0.0
    assert "decode_host_gap_pct" in s


def test_spec_session_feeds_ledger_without_new_sync_sites(tiny_lm):
    """The speculative-decode executables (draft plane + verify) land in
    the dispatch ledger like any other jitted dispatch, and the spec path
    introduces NO new host-sync site: the round's one materialization
    rides the pre-existing chunk-boundary scope, so the lint allowlist
    and the runtime counter inventory both stay unchanged."""
    from symbiont_tpu.config import LmConfig
    from symbiont_tpu.engine.lm import LmEngine
    from symbiont_tpu.lint.allowlist import JAX_HOST_SYNC_ALLOWED
    from symbiont_tpu.obs.xprof import dispatch_ledger, known_sync_sites

    donor = LmEngine(LmConfig(
        enabled=True, arch="gpt2", hidden_size=32, num_layers=1,
        num_heads=2, intermediate_size=64, max_positions=128,
        dtype="float32", prompt_buckets=[16], new_token_buckets=[16],
        stream_chunk=4, gen_max_batch=8, gen_flush_deadline_ms=5.0,
        session_min_rows=4, temperature=0.0, spec_k=4))
    spec = LmEngine(donor.config, draft_params=donor.params,
                    draft_model_cfg=donor.model_cfg)
    dispatch_ledger.clear()
    dispatch_ledger.configure(enabled=True)
    sess = spec.start_session(["ledger probe one", "ledger probe two"],
                              [8, 8])
    while not sess.done():
        sess.step()
    sigs = {r["executable"] for r in dispatch_ledger.snapshot()}
    for fam in ("lm.draft_prefill[", "lm.draft_chunk[", "lm.verify_chunk["):
        assert any(s.startswith(fam) for s in sigs), (fam, sorted(sigs))
    # two-direction parity with the lint allowlist is untouched by the
    # spec plane: every runtime counter site is allowlisted and vice versa
    allow = {scope for (_f, scope) in JAX_HOST_SYNC_ALLOWED}
    assert set(known_sync_sites()) == allow


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


def test_executables_and_profile_endpoints(tmp_path):
    import urllib.error
    import urllib.request

    from symbiont_tpu.bus.inproc import InprocBus
    from symbiont_tpu.config import (
        ApiConfig,
        GraphStoreConfig,
        SymbiontConfig,
        TextGeneratorConfig,
        VectorStoreConfig,
    )
    from symbiont_tpu.obs.xprof import device_trace, dispatch_ledger
    from symbiont_tpu.runner import SymbiontStack

    dispatch_ledger.clear()
    dispatch_ledger.configure(enabled=True)
    dispatch_ledger.note_compile("embed[L=128,B=8]",
                                 {"flops": 1e9, "bytes_accessed": 1e8})
    dispatch_ledger.note_dispatch("embed[L=128,B=8]", 0.010)
    cfg = SymbiontConfig(
        vector_store=VectorStoreConfig(dim=16, data_dir=str(tmp_path / "vs"),
                                       shard_capacity=64),
        graph_store=GraphStoreConfig(data_dir=str(tmp_path / "gs")),
        text_generator=TextGeneratorConfig(markov_state_path=None),
        api=ApiConfig(host="127.0.0.1", port=0, fused_search=False),
    )
    cfg.runner.services = ("perception,preprocessing,vector_memory,"
                           "knowledge_graph,text_generator,api")
    cfg.obs.xprof_trace_dir = str(tmp_path / "xprof")
    cfg.obs.xprof_trace_max_s = 0.1

    async def scenario():
        stack = SymbiontStack(cfg, bus=InprocBus(), engine=_StubEngine(),
                              fetcher=lambda url: "<html></html>")
        await stack.start()
        loop = asyncio.get_running_loop()
        port = stack.api.port

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                return json.loads(r.read())

        def post(path, body):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        try:
            body = await loop.run_in_executor(
                None, lambda: get("/api/engine/executables"))
            rows = {r["executable"]: r for r in body["executables"]}
            assert "embed[L=128,B=8]" in rows
            r = rows["embed[L=128,B=8]"]
            assert r["dispatches"] >= 1 and r["compiles"] == 1
            # the roofline grade rides each row (cost model present here)
            assert r["achieved_gbps"] is not None
            assert body["total_dispatches"] >= 1
            # bounded on-demand device trace: 60s clamps to max_s=0.1
            status, res = await loop.run_in_executor(
                None, lambda: post("/api/profile/device",
                                   {"duration_s": 60.0}))
            assert status in (200, 500)  # 500 = backend without profiler
            if status == 200:
                assert res["status"] == "captured"
                # the sleep is clamped to max_s=0.1; the wall additionally
                # carries profiler start/stop serialization, never 60s
                assert res["window_s"] < 30.0
                assert device_trace.last_artifact == res["artifact"]
                # the artifact cross-links from the Perfetto export
                from symbiont_tpu.obs.engine_timeline import engine_timeline

                engine_timeline.note_decode_step(
                    wall_ms=1.0, rows_live=1, rows_capacity=2,
                    kv_rows_live=1, kv_rows_allocated=2, steps=4)
                doc = await loop.run_in_executor(
                    None, lambda: get("/api/engine/timeline?fmt=chrome"))
                assert doc["otherData"]["device_trace_artifact"] == \
                    res["artifact"]
            # malformed body is a 400, not a traceback
            status, _ = await loop.run_in_executor(
                None, lambda: post("/api/profile/device", [1, 2, 3]))
            assert status == 400
        finally:
            await stack.stop()

    asyncio.run(scenario())
