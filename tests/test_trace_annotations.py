"""One span primitive on the profiler's clock (utils/telemetry.span):

- under a `jax.profiler` trace (the CPU profiler records host annotations
  too) every span is ONE `symbiont.<name>` host event with its interval
  and its trace id, held across an `await` or opened on a pool thread;
- with no profile running a span records as before;
- inner spans join the request that caused them (the open span is ambient;
  `carry_context` takes it onto a pool thread; a batcher flush rides its
  first item's trace);
- the wait/busy histograms are observed once per message / item / call;
- the programs carry their scope names and are still called `fn`.

No assertion here is a wall-clock bound tighter than the work it sleeps.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from symbiont_tpu.bus.core import Msg
from symbiont_tpu.config import EngineConfig, VectorStoreConfig
from symbiont_tpu.engine.batcher import MicroBatcher
from symbiont_tpu.engine.engine import TpuEngine
from symbiont_tpu.memory.vector_store import VectorStore
from symbiont_tpu.obs.trace_store import trace_store
from symbiont_tpu.services.coalesce import UpsertCoalescer
from symbiont_tpu.services.engine_service import EngineService
from symbiont_tpu.utils import telemetry
from symbiont_tpu.utils.telemetry import (
    SPAN_HEADER,
    TRACE_HEADER,
    carry_context,
    current_headers,
    metrics,
    span,
)


def _count(name: str) -> int:
    return sum(s["count"] for _, s in metrics.histogram_summaries(name))


def _sum(name: str) -> float:
    return sum(s["sum"] for _, s in metrics.histogram_summaries(name))


def _counters() -> dict:
    return metrics.snapshot()["counters"]


def _host_events(trace_dir) -> dict:
    """{event name: [(start_ns, duration_ns, {stat: value})]} of the
    `symbiont.*` host events in the one trace under `trace_dir`."""
    (path,) = list(trace_dir.rglob("*.xplane.pb"))
    out: dict = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("symbiont."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def engine():
    cfg = EngineConfig(embedding_dim=32, length_buckets=[8, 16],
                       batch_buckets=[2, 4], max_batch=4, dtype="float32",
                       data_parallel=False)
    return TpuEngine(cfg)


@pytest.fixture(scope="module")
def store(engine, tmp_path_factory):
    st = VectorStore(VectorStoreConfig(
        dim=32, data_dir=str(tmp_path_factory.mktemp("store")),
        shard_capacity=64))
    corpus = [f"sentence number {i} about topic {i % 5}" for i in range(12)]
    vecs = engine.embed_texts(corpus)
    st.upsert([(f"p{i}", vecs[i], {"sentence_text": corpus[i]})
               for i in range(len(corpus))])
    return st


# ------------------------------------------------- the profiler annotation

def _one_span(events: dict, name: str) -> tuple:
    """(start_ns, end_ns, trace ids) of the one span `name` made: its
    segments in order (a span the ticker found older than ROLL_S is closed
    and opened again under the same name and trace id), from the first
    one's start to the last one's end."""
    segments = sorted(events[f"symbiont.t_ann.{name}"])
    assert all(a[0] + a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    return (segments[0][0], segments[-1][0] + segments[-1][1],
            {stats["trace_id"] for _, _, stats in segments})


@pytest.mark.parametrize("roll_s", [None, 0.02])
def test_span_across_await_and_on_pool_thread_are_one_event_each(
        tmp_path, monkeypatch, roll_s):
    """Two spans of one request — one held across an `await` while another
    task's span opens and closes inside it, one on a pool thread — come
    out as one span each, covering the work, with the trace id. On a loaded
    machine the 0.05 s sleep can outlast ROLL_S and the ticker rolls the
    annotation over: the segments of one name and trace id are one span
    (`roll_s` 0.02 makes every run that machine)."""
    if roll_s is not None:
        monkeypatch.setattr(telemetry._annotations, "ROLL_S", roll_s)
    sleeps = {"held": 0.05, "other": 0.01, "pooled": 0.02}
    seen = {}

    def pooled():
        with span("t_ann.pooled") as sp:
            seen["pooled"] = sp
            time.sleep(sleeps["pooled"])

    async def other():
        with span("t_ann.other", {TRACE_HEADER: "trace-other"}):
            await asyncio.sleep(sleeps["other"])

    async def request():
        with span("t_ann.held", {TRACE_HEADER: "trace-held"}) as sp:
            seen["held"] = sp
            task = asyncio.create_task(other())
            with ThreadPoolExecutor(1) as pool:
                await asyncio.get_running_loop().run_in_executor(
                    pool, carry_context(pooled))
            await asyncio.sleep(sleeps["held"])
            await task

    jax.profiler.start_trace(str(tmp_path))
    try:
        asyncio.run(request())
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    got = {}
    for name, trace in (("held", "trace-held"), ("other", "trace-other"),
                        ("pooled", "trace-held")):
        start, end, traces = got[name] = _one_span(events, name)
        assert end - start >= sleeps[name] * 1e9, (name, end - start)
        assert traces == {trace}
    # the pool thread's span lies inside the span that caused it, on one
    # clock, and names it as its parent in the flight recorder
    assert got["held"][0] <= got["pooled"][0]
    assert got["pooled"][1] <= got["held"][1]
    assert seen["pooled"].parent_id == seen["held"].span_id
    assert seen["pooled"].trace_id == "trace-held"


def test_span_without_a_profile_records_as_before():
    trace_store.clear()
    before = _count("span.t_ann.plain.ms")
    with span("t_ann.plain", {TRACE_HEADER: "trace-plain",
                              SPAN_HEADER: "parent-1"}, rows=3) as sp:
        sp.fields["late"] = True
    assert _count("span.t_ann.plain.ms") == before + 1
    (rec,) = trace_store.spans_for("trace-plain")
    assert (rec.name, rec.parent_id, rec.status) == (
        "t_ann.plain", "parent-1", "ok")
    assert rec.fields == {"rows": 3, "late": True}
    errors = metrics.get("span.t_ann.plain.errors")
    with pytest.raises(ValueError):
        with span("t_ann.plain", {TRACE_HEADER: "trace-plain"}):
            raise ValueError("boom")
    assert metrics.get("span.t_ann.plain.errors") == errors + 1
    assert trace_store.spans_for("trace-plain")[-1].status == "error"


def test_a_span_longer_than_the_trace_is_covered_in_segments(
        tmp_path, monkeypatch):
    """The profiler keeps only annotations that began and ended inside the
    trace. A span open before the trace starts and after it stops is still
    there: the ticker gives it an annotation in the running trace and rolls
    it over every ROLL_S, so it shows as segments under one name and trace
    id. The ticks are made by hand here, on a registry with no thread."""
    import threading

    fresh = telemetry._ProfilerAnnotations()
    fresh.ROLL_S = 0.05
    fresh._ticker = threading.current_thread()  # taken: none is started
    monkeypatch.setattr(telemetry, "_annotations", fresh)
    with span("t_ann.long", {TRACE_HEADER: "trace-long"}):
        jax.profiler.start_trace(str(tmp_path))
        try:
            fresh.tick()                 # no annotation in this trace: one
            for _ in range(3):
                time.sleep(0.1)
                fresh.tick()             # older than ROLL_S: rolled over
        finally:
            jax.profiler.stop_trace()
    assert not fresh._open
    segments = sorted(_host_events(tmp_path)["symbiont.t_ann.long"])
    assert len(segments) == 3  # the fourth was open at the stop: lost
    assert {stats["trace_id"] for _, _, stats in segments} == {"trace-long"}
    assert all(a[0] + a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    assert sum(dur for _, dur, _ in segments) >= 0.3e9  # the three sleeps
    # the process's own registry runs its ticker as a daemon thread
    monkeypatch.undo()
    with span("t_ann.any"):
        pass
    ticker = telemetry._annotations._ticker
    assert ticker.daemon and ticker.is_alive()


def test_spans_and_the_ticker_share_the_registry_without_losing_one(tmp_path):
    """More threads than cores open and close spans under a running trace
    while the ticker rolls over whatever is open: every span still comes
    out (at least one event each, all closed), none left in the registry."""
    import sys
    import threading

    fresh = telemetry._ProfilerAnnotations()
    fresh.ROLL_S = 0.0
    fresh._ticker = threading.current_thread()  # taken: ticked from here
    n_threads, n_spans = 16, 40
    done = threading.Event()

    def ticker():
        while not done.is_set():
            fresh.tick()

    rolling = threading.Thread(target=ticker)
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    jax.profiler.start_trace(str(tmp_path))
    rolling.start()
    try:
        def work(t):
            for i in range(n_spans):
                h = telemetry.SpanHandle(f"trace-{t}", f"{t}-{i}", None, {})
                fresh.opened(h, f"t_ann.stress{t}")
                time.sleep(0.0005)
                fresh.closed(h)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        done.set()
        rolling.join(timeout=60)
        jax.profiler.stop_trace()
        sys.setswitchinterval(before)
    assert not rolling.is_alive()
    assert not fresh._open
    events = _host_events(tmp_path)
    for t in range(n_threads):
        got = events[f"symbiont.t_ann.stress{t}"]
        assert len(got) >= n_spans
        assert {stats["trace_id"] for _, _, stats in got} == {f"trace-{t}"}


def test_a_process_without_jax_gets_the_span_without_the_annotation(
        monkeypatch):
    """The annotations take jax from `sys.modules` and never import it;
    without it there is no ticker thread either."""
    import sys

    fresh = telemetry._ProfilerAnnotations()
    monkeypatch.setattr(telemetry, "_annotations", fresh)
    monkeypatch.delitem(sys.modules, "jax")
    before = _count("span.t_ann.nojax.ms")
    with span("t_ann.nojax"):
        assert fresh._tracing() is None and len(fresh._open) == 1
    assert _count("span.t_ann.nojax.ms") == before + 1
    assert "jax" not in sys.modules
    assert fresh._cls is None and fresh._ticker is None and not fresh._open


def test_the_log_line_is_built_only_when_info_is_taken(monkeypatch, caplog):
    def no_dumps(*a, **kw):
        raise AssertionError("json.dumps ran for a log line nobody takes")

    with caplog.at_level(logging.WARNING, logger="symbiont.trace"):
        monkeypatch.setattr(telemetry.json, "dumps", no_dumps)
        with span("t_ann.quiet"):
            pass
        monkeypatch.undo()
    with caplog.at_level(logging.INFO, logger="symbiont.trace"):
        with span("t_ann.loud", rows=2):
            pass
    (line,) = [r.getMessage() for r in caplog.records
               if r.name == "symbiont.trace"]
    assert json.loads(line)["span"] == "t_ann.loud"


# ----------------------------------------------------- the ambient parent

def test_inner_spans_join_the_open_span_unless_headers_say_otherwise():
    assert current_headers() is None
    with span("t_ann.outer", {TRACE_HEADER: "trace-outer"}) as outer:
        assert current_headers() == outer.headers
        with span("t_ann.inner") as inner:
            assert (inner.trace_id, inner.parent_id) == (
                "trace-outer", outer.span_id)
        with span("t_ann.elsewhere", {TRACE_HEADER: "trace-else",
                                      SPAN_HEADER: "p"}) as other:
            assert (other.trace_id, other.parent_id) == ("trace-else", "p")
        assert current_headers() == outer.headers  # restored on exit
        # a pool thread starts with an empty context: without
        # carry_context its span would be a trace of its own
        with ThreadPoolExecutor(1) as pool:
            bare = pool.submit(lambda: current_headers()).result()
            carried = pool.submit(carry_context(current_headers)).result()
        assert bare is None and carried == outer.headers
    assert current_headers() is None


# -------------------------------------------- wait and busy, where they are

def test_coalesce_wait_is_observed_once_per_message():
    labels = {"service": "t_ann_store"}

    async def run():
        flushed = []
        co = UpsertCoalescer(lambda ids, rows, p: flushed.append(len(ids))
                             or len(ids), max_rows=512, max_age_ms=5.0,
                             name="t_ann_store")
        await co.start()
        rows = np.ones((2, 4), np.float32)
        got = await asyncio.gather(*[
            co.add([f"a{i}", f"b{i}"], rows, [{}, {}],
                   headers={TRACE_HEADER: f"trace-msg-{i}"})
            for i in range(3)])
        await co.stop()
        return got, flushed

    trace_store.clear()
    before = metrics.histogram_summary("coalesce.wait_ms", labels=labels)
    got, flushed = asyncio.run(run())
    after = metrics.histogram_summary("coalesce.wait_ms", labels=labels)
    assert got == [2, 2, 2] and sum(flushed) == 6
    assert after["count"] - (before or {"count": 0})["count"] == 3
    assert after["min"] >= 0.0
    # the flush (busy) rides the first message's trace, as a span of its own
    assert _count("span.t_ann_store.flush.ms") == len(flushed)
    assert [r.name for r in trace_store.spans_for("trace-msg-0")] == [
        "t_ann_store.flush"]


class _StubEngine:
    class config:
        max_batch, flush_deadline_ms = 4, 5.0

    def __init__(self):
        self.seen = []

    def embed_texts(self, texts):
        self.seen.append((list(texts), current_headers()))
        return np.zeros((len(texts), 4), np.float32)


def test_batcher_wait_per_item_and_a_flush_span_on_the_first_items_trace():
    labels = {"service": "engine", "batcher": "embed"}
    eng = _StubEngine()

    async def submit(b, i):
        with span("t_ann.submit", {TRACE_HEADER: f"trace-item-{i}"}):
            return await b.embed([f"text {i}", f"more {i}"])

    async def run():
        b = MicroBatcher(eng)
        await b.start()
        out = await asyncio.gather(*[submit(b, i) for i in range(3)])
        await b.close()
        return out

    trace_store.clear()
    waits = metrics.histogram_summary("batcher.queue_wait_ms", labels=labels)
    flushes = _count("span.batcher.flush.ms")
    out = asyncio.run(run())
    assert [o.shape for o in out] == [(2, 4)] * 3
    after = metrics.histogram_summary("batcher.queue_wait_ms", labels=labels)
    assert after["count"] - (waits or {"count": 0})["count"] == 3
    # max_batch 4 takes two 2-text items per chunk: two flushes, each on its
    # first item's trace, and the engine call on the pool thread inside it
    assert _count("span.batcher.flush.ms") == flushes + 2
    assert [len(t) for t, _ in eng.seen] == [4, 2]
    for first, (_, ctx) in zip((0, 2), eng.seen):
        (flush,) = [r for r in trace_store.spans_for(f"trace-item-{first}")
                    if r.name == "batcher.flush"]
        assert flush.fields["batcher"] == "embed"
        assert ctx == {TRACE_HEADER: f"trace-item-{first}",
                       SPAN_HEADER: flush.span_id}


class _ReplyBus:
    def __init__(self):
        self.replies = []

    async def publish(self, subject, data, headers=None):
        self.replies.append((subject, json.loads(data)))


def test_a_fused_search_is_one_trace_with_every_stage_observed_once(
        engine, store):
    """EngineService's query.search handler -> pool thread -> the store ->
    the engine: executor wait, the store's span, the engine's span and its
    two stages, each once, all on the request's trace."""
    bus = _ReplyBus()
    svc = EngineService(bus, engine=engine, vector_store=store,
                        coalesce=False)
    names = ("engine.executor_wait_ms", "span.store.search_fused.ms",
             "span.engine.qsearch.ms", "engine.qsearch.host_ms",
             "engine.qsearch.device_wait_ms", "span.engine.query.search.ms")
    store.search_fused(engine, "topic 3", 3)  # compile outside the count
    trace_store.clear()
    before = {n: _count(n) for n in names}
    ms_before = {n: _sum(n) for n in names}
    msg = Msg("engine.query.search",
              json.dumps({"text": "topic 3", "top_k": 3}).encode(),
              reply="inbox.1", headers={TRACE_HEADER: "trace-search",
                                        SPAN_HEADER: "gateway-span"})
    asyncio.run(svc._query_search(msg))
    ((_, reply),) = bus.replies
    assert len(reply["hits"]) == 3 and not reply.get("error_message")
    assert {n: _count(n) - before[n] for n in names} == dict.fromkeys(names, 1)
    by_name = {r.name: r for r in trace_store.spans_for("trace-search")}
    assert set(by_name) == {"engine.query.search", "store.search_fused",
                            "engine.qsearch", "engine.qsearch.tokenize",
                            "engine.qsearch.dispatch"}
    for stage in ("engine.qsearch.tokenize", "engine.qsearch.dispatch"):
        assert (by_name[stage].parent_id
                == by_name["engine.qsearch"].span_id)
    assert by_name["engine.query.search"].parent_id == "gateway-span"
    assert (by_name["store.search_fused"].parent_id
            == by_name["engine.query.search"].span_id)
    assert (by_name["engine.qsearch"].parent_id
            == by_name["store.search_fused"].span_id)
    # the split is a split: each layer's time lies inside its caller's
    ms = {n: _sum(n) - ms_before[n] for n in names}
    assert (ms["engine.qsearch.host_ms"] + ms["engine.qsearch.device_wait_ms"]
            <= ms["span.engine.qsearch.ms"]
            <= ms["span.store.search_fused.ms"]
            <= ms["span.engine.query.search.ms"])


def test_the_batched_encoder_observes_its_span_and_stages_once_per_call(
        engine):
    names = ("span.engine.embed.ms", "engine.embed.host_ms",
             "engine.embed.device_wait_ms")
    before = {n: _count(n) for n in names}
    out = engine.embed_texts(["one two", "three four five", "six"])
    assert out.shape == (3, 32)
    assert {n: _count(n) - before[n] for n in names} == dict.fromkeys(names, 1)


# ------------------------------------- who holds the interpreter: CPU time

def _busy(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_a_cpu_span_around_a_busy_loop_reads_about_its_wall_time():
    """`cpu=True`: the thread's CPU time over the body, added to the
    counter and put on the record. A busy loop holds the thread for (nearly) all of its
    wall time; a descheduled attempt on a loaded machine is taken again."""
    trace_store.clear()
    for attempt in range(8):
        before = metrics.get("span.t_ann.busy.cpu_ms_total")
        wall0 = _sum("span.t_ann.busy.ms")
        with span("t_ann.busy", {TRACE_HEADER: f"trace-busy-{attempt}"},
                  cpu=True) as sp:
            _busy(0.05)
        cpu_ms = metrics.get("span.t_ann.busy.cpu_ms_total") - before
        wall_ms = _sum("span.t_ann.busy.ms") - wall0
        assert _count("span.t_ann.busy.cpu_ms") == 0  # no histogram of it
        assert sp.fields["cpu_ms"] == pytest.approx(cpu_ms)
        (rec,) = trace_store.spans_for(f"trace-busy-{attempt}")
        assert rec.fields["cpu_ms"] == sp.fields["cpu_ms"]
        assert 0.0 < cpu_ms <= wall_ms * 1.001
        if cpu_ms >= 0.8 * wall_ms:
            break
    else:
        pytest.fail(f"cpu {cpu_ms:.1f} ms of {wall_ms:.1f} ms wall, 8 times")


def test_a_cpu_span_around_a_sleep_reads_next_to_nothing():
    before = metrics.get("span.t_ann.asleep.cpu_ms_total")
    with span("t_ann.asleep", cpu=True):
        time.sleep(0.05)
    assert metrics.get("span.t_ann.asleep.cpu_ms_total") - before < 5.0
    assert _sum("span.t_ann.asleep.ms") >= 50.0


def test_a_cpu_span_closed_on_another_thread_records_no_cpu_number():
    """A thread's CPU clock says nothing about another thread's work: no
    number rather than a wrong one. The wall time and the record stay."""
    import threading

    trace_store.clear()
    before = _count("span.t_ann.moved.ms")
    cm = span("t_ann.moved", {TRACE_HEADER: "trace-moved"}, cpu=True)
    cm.__enter__()
    closer = threading.Thread(target=cm.__exit__, args=(None, None, None))
    closer.start()
    closer.join(timeout=30)
    assert not closer.is_alive()
    assert "span.t_ann.moved.cpu_ms_total" not in _counters()
    assert _count("span.t_ann.moved.ms") == before + 1
    (rec,) = trace_store.spans_for("trace-moved")
    assert "cpu_ms" not in rec.fields
    # without cpu=True nothing of the kind is recorded
    with span("t_ann.plain_cpu") as sp:
        pass
    assert "cpu_ms" not in sp.fields
    assert "span.t_ann.plain_cpu.cpu_ms_total" not in _counters()


def test_python_cpu_s_rises_by_a_pool_threads_busy_time():
    """The gauge sums the CPU clocks of the interpreter's live threads,
    whichever thread did the work."""
    with ThreadPoolExecutor(1) as pool:
        pool.submit(lambda: None).result()   # the thread exists
        for _ in range(8):
            cpu0, proc0 = telemetry.python_cpu_s(), time.process_time()
            pool.submit(_busy, 0.1).result()
            rose = telemetry.python_cpu_s() - cpu0
            proc = time.process_time() - proc0
            # the process's clock also counts threads the interpreter does
            # not know (the runtime's pools), so it bounds from above
            assert 0.0 < rose <= proc + 0.01
            if 0.08 <= rose <= 0.15:
                break
        else:
            pytest.fail(f"rose by {rose:.3f} s around 0.1 s of work, 8 times")


# ------------------------------------- the stage spans, through the stack

ENGINE = {"service": "engine"}
STAGES_PER_PAGE = ("perception.extract", "preprocessing.split",
                   "preprocessing.frame", "vector_memory.decode")
STAGES_PER_STORE_FLUSH = ("store.ingest_rows", "store.wal_encode",
                          "store.wal_sync")
STAGES_PER_QUERY = ("engine.qsearch.tokenize", "engine.qsearch.dispatch")
STAGES = (STAGES_PER_PAGE + STAGES_PER_STORE_FLUSH + STAGES_PER_QUERY
          + ("engine.embed.tokenize", "engine.embed.pack",
             "engine.embed.dispatch"))


def _stack(engine, tmp_path, services, fetcher=None):
    from symbiont_tpu.bus.inproc import InprocBus
    from symbiont_tpu.config import (
        GraphStoreConfig,
        SymbiontConfig,
    )
    from symbiont_tpu.runner import SymbiontStack

    cfg = SymbiontConfig(
        vector_store=VectorStoreConfig(dim=32, data_dir=str(tmp_path / "vs"),
                                       shard_capacity=4096),
        graph_store=GraphStoreConfig(data_dir=str(tmp_path / "gs")))
    cfg.runner.services = services
    return SymbiontStack(cfg, bus=InprocBus(), engine=engine,
                         fetcher=fetcher)


async def _until(cond, what: str, timeout_s: float = 120.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        await asyncio.sleep(0.02)


def test_every_stage_span_is_observed_as_often_as_its_stage_runs(
        engine, tmp_path):
    """Pages through perception -> preprocessing -> the embed batcher -> the
    engine -> vector_memory -> the coalescer -> the store, then fused
    queries through EngineService: each of the twelve stage spans once per
    page, store flush, embed call, dispatch or query, no more; all with a
    CPU reading; the store's three on the flush's trace, as its children."""
    from symbiont_tpu import subjects

    pages, queries = 5, 3
    html = {f"http://fake/{i}": "<html><body><main>" + "".join(
        f"<p>Page {i} sentence {j} about topic {j % 3}.</p>"
        for j in range(3)) + "</main></body></html>" for i in range(pages)}
    stack = _stack(engine, tmp_path,
                   "perception,preprocessing,vector_memory,engine",
                   fetcher=html.__getitem__)
    names = [f"span.{n}.ms" for n in STAGES] + [
        "span.engine.embed.ms", "span.vector_memory.flush.ms"]

    async def scenario():
        await stack.start()
        try:
            # the fused warm-up runs queries of its own: let it finish
            await _until(lambda: metrics.get(
                "engine.fused_warmups", labels={"result": "ok"}) >= 1,
                "the fused warm-up")
            trace_store.clear()
            before = {n: _count(n) for n in names}
            dispatches = metrics.get("engine.embed.dispatches", labels=ENGINE)
            for url in html:
                await stack.bus.publish(
                    subjects.TASKS_PERCEIVE_URL,
                    json.dumps({"url": url}).encode())
            await _until(lambda: stack.vector_store.count() >= 3 * pages,
                         "the pages' rows")
            await _until(lambda: _count("span.vector_memory.handle.ms") >= pages
                         and not stack.services[2]._coalescer._pending,
                         "the upsert handlers")
            for q in range(queries):
                reply = await stack.bus.request(
                    subjects.ENGINE_QUERY_SEARCH, json.dumps(
                        {"text": f"topic {q}", "top_k": 3}).encode(), 60.0)
                assert len(json.loads(reply.data)["hits"]) == 3
            got = {n: _count(n) - before[n] for n in names}
            return got, metrics.get("engine.embed.dispatches",
                                    labels=ENGINE) - dispatches
        finally:
            await stack.stop()

    got, dispatches = asyncio.run(scenario())
    embeds, flushes = (got["span.engine.embed.ms"],
                       got["span.vector_memory.flush.ms"])
    assert embeds >= 1 and flushes >= 1 and dispatches >= embeds
    want = {**dict.fromkeys(STAGES_PER_PAGE, pages),
            **dict.fromkeys(STAGES_PER_STORE_FLUSH, flushes),
            **dict.fromkeys(STAGES_PER_QUERY, queries),
            "engine.embed.tokenize": embeds,
            # once round the plan of a call, once round each dispatch's rows
            "engine.embed.pack": embeds + dispatches,
            "engine.embed.dispatch": dispatches}
    assert {n: got[f"span.{n}.ms"] for n in STAGES} == want
    # each with a CPU reading: summed in a counter, and on every record
    assert all(f"span.{n}.cpu_ms_total" in _counters() for n in STAGES)
    # one ingest trace shows the store's stages under the flush it rode
    by_id = {r.span_id: r for spans in
             trace_store.spans_by_trace().values() for r in spans}
    staged = [r for r in by_id.values() if r.name in STAGES]
    assert len(staged) == sum(want.values())
    assert all("cpu_ms" in r.fields for r in staged)
    store_spans = [r for r in by_id.values()
                   if r.name in STAGES_PER_STORE_FLUSH]
    assert len(store_spans) == 3 * flushes
    for r in store_spans:
        parent = by_id[r.parent_id]
        assert parent.name == "vector_memory.flush"
        assert parent.trace_id == r.trace_id and "cpu_ms" in r.fields
    # the engine's stages under the embed call, the handlers' under theirs
    for r in by_id.values():
        if r.name.startswith("engine.embed."):
            assert by_id[r.parent_id].name == "engine.embed"
        elif r.name in STAGES_PER_PAGE:
            assert by_id[r.parent_id].name == (
                r.name.split(".")[0] + ".handle")


# --------------------------------------------- the event-loop lag probe

def test_the_lag_probe_samples_the_loop_sees_a_block_and_stops(
        engine, tmp_path):
    """A sample every `LOOP_LAG_PROBE_S` while the stack is up; a handler
    that blocks the loop for 50 ms past the timer's next firing shows as
    >= 40 ms of lag (a block reads as what it overran a firing by); after `stop()` the timer
    is gone and nothing more is observed."""
    stack = _stack(engine, tmp_path, "preprocessing")

    async def scenario():
        await stack.start()
        try:
            assert "host.python_cpu_s" in metrics.snapshot()["gauges"]
            await asyncio.sleep(0.1)       # the probe's first firings
            n0, t0 = _count("loop.lag_ms"), time.monotonic()
            await asyncio.sleep(1.0)
            rate = (_count("loop.lag_ms") - n0) / (time.monotonic() - t0)
            lag0 = _sum("loop.lag_ms")
            # a handler that blocks the loop
            time.sleep(telemetry.LOOP_LAG_PROBE_S + 0.05)
            await asyncio.sleep(2.5 * telemetry.LOOP_LAG_PROBE_S)
            return rate, _sum("loop.lag_ms") - lag0
        finally:
            await stack.stop()

    async def after():
        n = _count("loop.lag_ms")
        await asyncio.sleep(3 * telemetry.LOOP_LAG_PROBE_S)
        return _count("loop.lag_ms") - n

    rate, blocked_ms = asyncio.run(scenario())
    # a timer overshoots a little, more on a loaded machine: never more
    # often than it is due, and not a fraction of that either
    due = 1.0 / telemetry.LOOP_LAG_PROBE_S
    assert 0.3 * due <= rate <= 1.005 * due, rate
    # the firing the block overran
    assert blocked_ms >= 40.0, blocked_ms
    assert stack._stop_lag_probe is None
    assert asyncio.run(after()) == 0


# ------------------------------------------------------ named scopes

def _lowered(monkeypatch, engine, kind, L, shape, args) -> str:
    """The lowered text of one of the engine's programs (traced, never
    compiled): the jitted function itself, without the first-call wrapper."""
    monkeypatch.setattr(TpuEngine, "_time_first_call",
                        lambda self, jitted, sig: jitted)
    fresh = TpuEngine(engine.config, params=engine.params,
                      model_cfg=engine.model_cfg, tokenizer=engine.tokenizer)
    return fresh._get_executable(kind, L, *shape).lower(*args).as_text(
        debug_info=True)


@pytest.mark.parametrize("kind", ["embed", "qsearch"])
def test_the_programs_hold_their_scopes_and_are_still_called_fn(
        monkeypatch, engine, kind):
    ids = np.ones((2, 8), engine._ids_dtype)
    if kind == "embed":
        # packed rows: [B, S] sentence lengths, S = segments_per_row(8) = 1
        text = _lowered(monkeypatch, engine, "embed", 8, (2,),
                        (engine.params, ids, np.full((2, 1), 8, np.int32)))
        phases = ("embeddings", "encoder", "pool")
    else:
        text = _lowered(monkeypatch, engine, "qsearch", 8, (64, 8, None),
                        (engine.params, ids[:1], np.ones((1, 8), np.int32),
                         jnp.zeros((64, 32), jnp.float32), 12))
        phases = ("embeddings", "encoder", "pool", "scan", "topk")
    assert "module @jit_fn " in text
    for phase in phases:
        assert f"jit(fn)/symbiont.{kind}/{phase}/" in text, phase
    if kind == "qsearch":
        assert "symbiont.qsearch/scan/dot_general" in text
        assert "symbiont.qsearch/topk/top_k" in text


def test_the_lm_programs_hold_their_top_scopes():
    from symbiont_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                        num_heads=2, intermediate_size=32,
                        max_position_embeddings=32, dtype="float32")
    params = gpt.init_params(jax.random.key(0), cfg)
    ids, mask = jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)
    text = gpt.prefill.lower(params, ids, mask, cfg,
                             max_new_tokens=4).as_text(debug_info=True)
    assert "module @jit_prefill " in text
    assert "jit(prefill)/symbiont.prefill/" in text
    cache, logits, kv_valid, plen = gpt.prefill(params, ids, mask, cfg,
                                                max_new_tokens=4)
    keys = jax.random.split(jax.random.key(1), 2)
    text = gpt._decode_chunk_jit.lower(
        params, cache, logits, plen, jnp.zeros((1,), bool), kv_valid, keys,
        jnp.ones((1,), jnp.float32), jnp.ones((1,), jnp.int32), cfg,
        top_k_bucket=1, eos_id=-1).as_text(debug_info=True)
    assert "jit(_decode_chunk_jit)/symbiont.decode/" in text
