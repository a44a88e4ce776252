"""HBM attribution plane (obs/hbm.py + the obs/device.py stats cache).

Covers the four surfaces end to end:

- HbmLedger: claim/replace, weakref retirement of dead owners, overlay
  exclusion from the attribution sum, ownerless static claims, the
  bounded row cache, enable/disable, and reconcile's live-arrays basis
  fallback on CPU;
- live-array census: aggregation by (shape, dtype, sharding), the
  "(other)" tail fold, and the LEAK test — census_diff pins a
  deliberately leaked buffer to its owning allocation group;
- per-executable static footprints: memory_analysis_of guards,
  compile_analysis_for on a real jit, the ledger snapshot carrying the
  footprint fields, and peak_temp_bytes' prefix filter;
- OOM forensics: an injected RESOURCE_EXHAUSTED out of a stream dispatch
  seam writes the bounded postmortem, counts engine.oom_total{site}, and
  the engine keeps serving afterwards; non-OOM errors pass untouched;
- the obs/device.py _DeviceStatsCache: one memory_stats() runtime call
  per TTL window shared across readers, raises propagate uncached;
- the admission forecast: can_admit on CPU (headroom unknown) is
  unchanged; _admit_bytes_forecast covers dense KV + peak temp;
- the HTTP surfaces: GET /api/memory, GET /api/memory/census (top,
  diff arming + delta, bad-int 400) and last_oom riding /api/fleet, on
  a booted stub-engine stack;
- the Perfetto export: a timeline "mem" event renders as one
  hbm.subsystem_bytes counter track sample.
"""

import asyncio
import gc
import json
import os

import numpy as np
import pytest

from symbiont_tpu.obs import hbm
from symbiont_tpu.obs.hbm import (
    HbmLedger,
    OomForensics,
    census,
    census_diff,
    guard_oom,
    is_oom,
)
from symbiont_tpu.utils.telemetry import Metrics


def _ledger(**kw) -> HbmLedger:
    kw.setdefault("registry", Metrics())
    return HbmLedger(**kw)


class _Owner:
    def __init__(self, nbytes):
        self.nbytes = nbytes


# ------------------------------------------------------------------ ledger

def test_ledger_claims_sum_and_overlay_is_excluded():
    led = _ledger()
    a, b, c = _Owner(100), _Owner(28), _Owner(40)
    led.claim("lm.params", a, lambda o: o.nbytes)
    led.claim("lm.params", b, lambda o: o.nbytes)   # second owner: sums
    led.claim("kv.radix_retained", c, lambda o: o.nbytes, overlay=True)
    rows = {r["subsystem"]: r for r in led.rows()}
    assert rows["lm.params"]["bytes"] == 128
    assert rows["lm.params"]["overlay"] is False
    assert rows["kv.radix_retained"]["overlay"] is True
    # overlay bytes are visible but never double-counted
    assert led.attributed_bytes() == 128


def test_ledger_weakref_retires_dead_owner():
    led = _ledger()
    a = _Owner(64)
    led.claim("lm.params", a, lambda o: o.nbytes)
    assert led.attributed_bytes() == 64
    del a
    gc.collect()
    assert led.rows() == []
    assert len(led) == 0  # the dead claim was dropped, not just skipped


def test_ledger_reader_none_retires_and_raise_skips():
    led = _ledger()
    a, b = _Owner(0), _Owner(32)
    led.claim("lm.drafter", a, lambda o: None)   # retire signal

    def flaky(o):
        raise RuntimeError("transient")

    led.claim("kv.page_pool", b, flaky)
    assert led.rows() == []
    assert len(led) == 1  # the raising claim survives for the next read
    led.claim("kv.page_pool", b, lambda o: o.nbytes)  # replace, same owner
    assert led.attributed_bytes() == 32


def test_ledger_static_claim_and_row_cache():
    led = _ledger()
    led.claim_value("engine.params", 512)
    calls = []
    a = _Owner(8)
    led.claim("lm.params", a, lambda o: calls.append(1) or o.nbytes)
    r1 = led.rows(max_age_s=60.0)
    r2 = led.rows(max_age_s=60.0)   # served from the bounded cache
    assert r1 == r2 and len(calls) == 1
    assert led.rows(max_age_s=0.0) and len(calls) == 2  # fresh read
    led.claim_value("engine.params", 0)  # 0 removes the static claim
    names = {r["subsystem"] for r in led.rows()}
    assert names == {"lm.params"}


def test_ledger_disabled_reports_nothing():
    led = _ledger()
    a = _Owner(64)
    led.claim("lm.params", a, lambda o: o.nbytes)
    led.configure(enabled=False)
    assert led.rows() == [] and led.attributed_bytes() == 0
    led.configure(enabled=True)
    assert led.attributed_bytes() == 64


def test_reconcile_cpu_falls_back_to_live_array_basis():
    import jax.numpy as jnp

    led = _ledger()
    anchor = jnp.zeros((128, 64), jnp.float32)
    led.claim("lm.params", led, lambda _: int(anchor.nbytes))
    rec = led.reconcile()
    # CPU reports no memory_stats: the basis is the live-array census
    assert rec["basis"] in ("live_arrays", "memory_stats")
    assert rec["attributed_bytes"] == anchor.nbytes
    assert rec["bytes_in_use"] >= anchor.nbytes
    assert rec["unattributed_bytes"] == \
        rec["bytes_in_use"] - rec["attributed_bytes"]
    assert 0.0 <= rec["unattributed_pct"] <= 100.0
    del anchor


def test_register_zero_exports_the_hbm_family():
    led = _ledger()
    led.register_zero()
    gauges = led.registry.snapshot()["gauges"]
    assert gauges['hbm.attributed_bytes{subsystem="all"}'] == 0


def test_register_gauges_serves_per_subsystem_series():
    led = _ledger()
    a = _Owner(96)
    led.claim("kv.page_pool", a, lambda o: o.nbytes)
    led.register_gauges()
    gauges = led.registry.snapshot()["gauges"]
    assert gauges['hbm.attributed_bytes{subsystem="kv.page_pool"}'] == 96


# ------------------------------------------------------------------ census

def test_census_groups_by_shape_dtype_and_diff_catches_leak():
    import jax.numpy as jnp

    before = census(top=0)
    assert before["available"]
    # the deliberate leak: a distinctive shape no other test allocates
    leaked = [jnp.ones((173, 37), jnp.float32) for _ in range(3)]
    after = census(top=0)
    diff = census_diff(before, after, top=8)
    assert diff["available"]
    assert diff["bytes_delta"] >= 3 * 173 * 37 * 4
    top_row = diff["groups"][0]   # growth sorts first
    assert top_row["shape"] == [173, 37]
    assert top_row["dtype"] == "float32"
    assert top_row["count_delta"] == 3
    assert top_row["bytes_delta"] == 3 * 173 * 37 * 4
    # freeing the leak shows up as shrink on the next diff
    del leaked
    gc.collect()
    diff2 = census_diff(after, census(top=0), top=8)
    shrink = {(tuple(r["shape"]), r["dtype"]): r["bytes_delta"]
              for r in diff2["groups"]}
    assert shrink.get(((173, 37), "float32")) == -(3 * 173 * 37 * 4)


def test_census_tail_folds_into_other_and_diff_ignores_it():
    import jax.numpy as jnp

    anchors = [jnp.zeros((7, i + 1), jnp.float32) for i in range(6)]
    c = census(top=2)
    assert len(c["groups"]) == 3  # 2 + "(other)"
    other = c["groups"][-1]
    assert other["dtype"] == "(other)"
    assert c["group_count"] > 2
    # bytes are conserved across the fold
    assert sum(g["bytes"] for g in c["groups"]) == c["bytes_total"]
    # "(other)" never participates in a diff (it is a fold, not a group)
    d = census_diff(c, c, top=8)
    assert d["available"] and d["groups"] == []
    del anchors


# ------------------------------------------------- executable footprints

class _FakeMemStats:
    temp_size_in_bytes = 1 << 20
    argument_size_in_bytes = 2048
    output_size_in_bytes = 512
    generated_code_size_in_bytes = float("nan")  # guarded -> absent


class _FakeCompiled:
    def memory_analysis(self):
        return _FakeMemStats()


def test_memory_analysis_guards_values():
    from symbiont_tpu.obs.xprof import memory_analysis_of

    out = memory_analysis_of(_FakeCompiled())
    assert out == {"temp_bytes": 1 << 20, "argument_bytes": 2048,
                   "output_bytes": 512}

    class _Broken:
        def memory_analysis(self):
            raise NotImplementedError

    assert memory_analysis_of(_Broken()) is None


def test_compile_analysis_real_jit_and_ledger_footprint_rows():
    import jax
    import jax.numpy as jnp

    from symbiont_tpu.obs.xprof import DispatchLedger, compile_analysis_for

    jitted = jax.jit(lambda x: (x @ x.T).sum())
    cost, mem, compiled = compile_analysis_for(
        jitted, (jnp.ones((16, 16), jnp.float32),))
    assert compiled is not None
    out = compiled(jnp.ones((16, 16), jnp.float32))
    assert float(out) == 16.0 * 16.0 * 16.0
    led = DispatchLedger(registry=Metrics())
    led.note_compile("probe[B=16]", cost, memory=mem)
    (row,) = led.snapshot()
    # memory fields ride the row: ints when the backend reports them,
    # None (unknown) otherwise — never a fabricated zero
    for f in ("temp_bytes", "argument_bytes", "output_bytes",
              "generated_code_bytes"):
        assert f in row
        assert row[f] is None or isinstance(row[f], int)


def test_peak_temp_bytes_prefix_filter():
    from symbiont_tpu.obs.xprof import dispatch_ledger

    dispatch_ledger.clear()
    dispatch_ledger.configure(enabled=True)
    dispatch_ledger.note_compile("lm.decode_chunk[P=32]", None,
                                 memory={"temp_bytes": 4096})
    dispatch_ledger.note_compile("lm.prefill[P=32]", None,
                                 memory={"temp_bytes": 1 << 20})
    dispatch_ledger.note_compile("embed[L=128,B=32]", None,
                                 memory={"temp_bytes": 1 << 30})
    assert hbm.peak_temp_bytes("lm.") == 1 << 20
    assert hbm.peak_temp_bytes() == 1 << 30
    dispatch_ledger.clear()


# ------------------------------------------------------- device stats cache

class _FakeDev:
    def __init__(self, stats=None, boom=False):
        self.calls = 0
        self._stats = stats if stats is not None else {}
        self._boom = boom

    def memory_stats(self):
        self.calls += 1
        if self._boom:
            raise RuntimeError("runtime down")
        return self._stats


def test_device_stats_cache_one_runtime_call_per_window():
    from symbiont_tpu.obs.device import _DeviceStatsCache

    cache = _DeviceStatsCache(max_age_s=60.0)
    dev = _FakeDev({"bytes_in_use": 7, "bytes_limit": 10})
    # three series readers + the hbm plane share ONE runtime call
    for _ in range(5):
        assert cache.stats(dev)["bytes_in_use"] == 7
    assert dev.calls == 1
    assert cache.stats(dev, max_age_s=0.0) and dev.calls == 2  # forced fresh
    # the empty (CPU) result is cached exactly like a real one
    cpu = _FakeDev({})
    assert cache.stats(cpu) == {} and cache.stats(cpu) == {}
    assert cpu.calls == 1
    cache.invalidate()
    assert cache.stats(dev)["bytes_limit"] == 10 and dev.calls == 3


def test_device_stats_cache_raise_propagates_uncached():
    from symbiont_tpu.obs.device import _DeviceStatsCache

    cache = _DeviceStatsCache(max_age_s=60.0)
    dev = _FakeDev(boom=True)
    for _ in range(2):
        with pytest.raises(RuntimeError):
            cache.stats(dev)
    assert dev.calls == 2  # a raise is never cached


# ------------------------------------------------------------ OOM forensics

def test_is_oom_matches_xla_status_not_pool_exhausted():
    from symbiont_tpu.kv.pool import PoolExhausted

    assert is_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 2147483648 bytes"))
    assert is_oom(RuntimeError("Allocator ran out of memory"))
    assert not is_oom(PoolExhausted("need 4 pages, 1 free"))
    assert not is_oom(ValueError("bad bucket"))


def test_forensics_postmortem_bounded_and_counter(tmp_path):
    fx = OomForensics(registry=Metrics())
    fx.configure(postmortem_dir=str(tmp_path), max_files=2, enabled=True)
    paths = [fx.record("lm.batch_step",
                       RuntimeError(f"RESOURCE_EXHAUSTED: alloc {i}"))
             for i in range(5)]
    assert all(p for p in paths)
    kept = sorted(f for f in os.listdir(tmp_path) if f.endswith(".json"))
    assert kept == ["oom_0004.json", "oom_0005.json"]  # newest win
    assert fx.registry.get("engine.oom_total",
                           labels={"site": "lm.batch_step"}) == 5
    report = json.loads((tmp_path / "oom_0005.json").read_text())
    assert report["site"] == "lm.batch_step"
    assert "RESOURCE_EXHAUSTED" in report["error"]
    assert "memory" in report and "census" in report  # forensic sections
    last = fx.last
    assert last["site"] == "lm.batch_step"
    assert last["postmortem"].endswith("oom_0005.json")


def test_forensics_disabled_still_counts(tmp_path):
    fx = OomForensics(registry=Metrics())
    fx.configure(postmortem_dir=str(tmp_path), enabled=False)
    assert fx.record("engine.embed", RuntimeError("RESOURCE_EXHAUSTED")) \
        is None
    assert os.listdir(tmp_path) == []
    assert fx.registry.get("engine.oom_total",
                           labels={"site": "engine.embed"}) == 1


def test_guard_oom_records_and_reraises_and_ignores_non_oom(tmp_path,
                                                           monkeypatch):
    from symbiont_tpu.obs.hbm import oom_forensics
    from symbiont_tpu.utils.telemetry import metrics

    oom_forensics.configure(postmortem_dir=str(tmp_path), max_files=2,
                            enabled=True)
    before = metrics.get("engine.oom_total",
                         labels={"site": "lm.generate_stream"}) or 0
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        with guard_oom("lm.generate_stream"):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
    assert metrics.get("engine.oom_total",
                       labels={"site": "lm.generate_stream"}) == before + 1
    assert os.listdir(tmp_path)  # postmortem landed
    # a non-OOM error passes straight through: no count, no file
    with pytest.raises(ValueError):
        with guard_oom("lm.generate_stream"):
            raise ValueError("not an allocator failure")
    assert metrics.get("engine.oom_total",
                       labels={"site": "lm.generate_stream"}) == before + 1


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


def test_engine_survives_injected_oom(tiny_lm, tmp_path, monkeypatch):
    """The acceptance path: a RESOURCE_EXHAUSTED out of the stream's
    dispatch seam writes the postmortem and counts the site, the error
    reaches the caller unchanged, and the SAME engine serves the next
    request normally."""
    from symbiont_tpu.obs.hbm import oom_forensics
    from symbiont_tpu.utils.telemetry import metrics

    oom_forensics.configure(postmortem_dir=str(tmp_path), max_files=4,
                            enabled=True)
    before = metrics.get("engine.oom_total",
                         labels={"site": "lm.generate_stream"}) or 0

    def exploding_impl(prompt, max_new_tokens, **kw):
        yield "warm"
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 8589934592 bytes")

    monkeypatch.setattr(tiny_lm, "_generate_stream_impl", exploding_impl)
    chunks = []
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        for chunk in tiny_lm.generate_stream("probe", 8):
            chunks.append(chunk)
    assert chunks == ["warm"]  # chunks before the OOM were delivered
    assert metrics.get("engine.oom_total",
                       labels={"site": "lm.generate_stream"}) == before + 1
    files = [f for f in os.listdir(tmp_path) if f.startswith("oom_")]
    assert len(files) == 1
    report = json.loads((tmp_path / files[0]).read_text())
    assert report["site"] == "lm.generate_stream"
    assert report["memory"]["subsystems"], "ledger missing from postmortem"
    monkeypatch.undo()
    # the engine still serves: its state was never touched by the OOM path
    text = "".join(tiny_lm.generate_stream("still serving", 8))
    assert isinstance(text, str) and text


def test_lm_claims_and_admission_forecast(tiny_lm):
    from symbiont_tpu.obs.hbm import hbm_ledger
    from symbiont_tpu.obs.xprof import dispatch_ledger

    rows = {r["subsystem"]: r["bytes"] for r in hbm_ledger.rows()}
    assert rows.get("lm.params", 0) > 0  # the engine claimed its params
    # on CPU the backend reports no memory accounting: headroom is
    # UNKNOWN (None), and can_admit must not treat that as zero
    assert tiny_lm.hbm_headroom_bytes() is None
    assert tiny_lm.can_admit(1, max_kv_rows=0)
    # the forecast itself: dense KV slab bytes per row + peak lm.* temp
    dispatch_ledger.clear()
    dispatch_ledger.configure(enabled=True)
    base = tiny_lm._admit_bytes_forecast(1)
    assert base > 0
    dispatch_ledger.note_compile("lm.decode_chunk[P=16]", None,
                                 memory={"temp_bytes": 1 << 16})
    assert tiny_lm._admit_bytes_forecast(1) == base + (1 << 16)
    # rows scale the KV slab term; the temp footprint is counted once
    assert tiny_lm._admit_bytes_forecast(2) - tiny_lm._admit_bytes_forecast(
        1) == base
    dispatch_ledger.clear()


# --------------------------------------------------------- Perfetto export

def test_mem_event_renders_as_counter_track():
    from symbiont_tpu.obs.chrome_trace import export_timeline

    doc = export_timeline("tl", [], [
        {"kind": "mem", "t": 10.0, "lm.params": 1024, "kv.page_pool": 2048},
        {"kind": "mem", "t": 10.5},   # empty sample: no track emitted
    ])
    counters = [e for e in doc["traceEvents"]
                if e.get("ph") == "C" and e["name"] == "hbm.subsystem_bytes"]
    assert len(counters) == 1
    assert counters[0]["args"] == {"lm.params": 1024, "kv.page_pool": 2048}
    assert counters[0]["ts"] == 10.0 * 1e6


def test_timeline_mem_sampling_is_rate_limited():
    from symbiont_tpu.obs.engine_timeline import EngineTimeline
    from symbiont_tpu.obs.hbm import hbm_ledger

    anchor = _Owner(4096)
    hbm_ledger.claim("lm.params", anchor, lambda o: o.nbytes)
    tl = EngineTimeline(capacity=256, registry=Metrics())
    for _ in range(20):
        tl.note_decode_step(wall_ms=1.0, rows_live=1, rows_capacity=2,
                            kv_rows_live=1, kv_rows_allocated=2, steps=4)
    mem = [e for e in tl.events() if e["kind"] == "mem"]
    # 20 back-to-back steps inside one 0.5s window: exactly one sample
    assert len(mem) == 1
    assert mem[0]["lm.params"] >= 4096
    # summary() is untouched by mem events
    assert tl.summary()["decode_steps"] == 20


# ------------------------------------------------------------ HTTP surfaces

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


def test_memory_endpoints(tmp_path):
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
    from symbiont_tpu.obs.hbm import hbm_ledger, oom_forensics
    from symbiont_tpu.runner import SymbiontStack

    cfg = SymbiontConfig(
        vector_store=VectorStoreConfig(dim=16, data_dir=str(tmp_path / "vs"),
                                       shard_capacity=64),
        graph_store=GraphStoreConfig(data_dir=str(tmp_path / "gs")),
        text_generator=TextGeneratorConfig(markov_state_path=None),
        api=ApiConfig(host="127.0.0.1", port=0, fused_search=False),
    )
    cfg.runner.services = ("perception,preprocessing,vector_memory,"
                           "knowledge_graph,text_generator,api")
    cfg.obs.hbm_postmortem_dir = str(tmp_path / "oom")
    anchor = _Owner(1 << 20)
    hbm_ledger.claim("engine.params", anchor, lambda o: o.nbytes)
    oom_forensics.record("engine.embed",
                         RuntimeError("RESOURCE_EXHAUSTED: probe"))

    async def scenario():
        stack = SymbiontStack(cfg, bus=InprocBus(), engine=_StubEngine(),
                              fetcher=lambda url: "<html></html>")
        await stack.start()
        loop = asyncio.get_running_loop()
        port = stack.api.port

        def get(path):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        try:
            status, mem = await loop.run_in_executor(
                None, lambda: get("/api/memory"))
            assert status == 200
            subs = {r["subsystem"]: r["bytes"]
                    for r in mem["local"]["subsystems"]}
            assert subs.get("engine.params") == 1 << 20
            assert mem["local"]["basis"] in ("live_arrays", "memory_stats",
                                             "none")
            assert mem["last_oom"]["site"] == "engine.embed"
            assert isinstance(mem["roles"], dict)

            status, cen = await loop.run_in_executor(
                None, lambda: get("/api/memory/census?top=4"))
            assert status == 200
            c = cen["census"]
            if c["available"]:
                assert len(c["groups"]) <= 5  # top=4 (+ the "(other)" fold)
                assert c["bytes_total"] >= 0

            # diff mode: first call arms the baseline, second reports it
            status, d1 = await loop.run_in_executor(
                None, lambda: get("/api/memory/census?diff=1"))
            assert status == 200 and d1.get("baseline_armed") is True
            import jax.numpy as jnp

            leak = jnp.ones((211, 13), jnp.float32)
            status, d2 = await loop.run_in_executor(
                None, lambda: get("/api/memory/census?diff=1&top=8"))
            assert status == 200 and "diff" in d2
            if d2["diff"]["available"]:
                grown = {(tuple(r["shape"]), r["dtype"])
                         for r in d2["diff"]["groups"]
                         if r["bytes_delta"] > 0}
                assert ((211, 13), "float32") in grown
            del leak

            status, _ = await loop.run_in_executor(
                None, lambda: get("/api/memory/census?top=abc"))
            assert status == 400

            # the OOM verdict rides /api/fleet on a fleet-less stack too
            status, fleet = await loop.run_in_executor(
                None, lambda: get("/api/fleet"))
            assert status == 200
            assert fleet["last_oom"]["site"] == "engine.embed"
        finally:
            await stack.stop()

    asyncio.run(scenario())
