"""Chaos suite (`pytest -m chaos`, scripts/chaos.sh): deterministic
fault-injection scenarios proving the resilience-plane acceptance criteria
— ZERO-LOSS ingest on the durable in-proc bus under every injected fault
class (handler exception, handler hang past the timeout, delivery drop,
store outage with recovery, TCP disconnect), and poison-message quarantine:
exactly `durable_max_deliver` attempts, then the DLQ, inspectable and
replayable through `GET /api/dlq`.

Every scenario runs under a seeded FaultPlan (resilience/faults.py) so the
faults fire at the same operations on every run — loss counts are asserted
exactly, not "usually". The suite doubles as a bench tier
(symbiont_tpu/bench/chaos.py) so loss-under-fault regressions gate like
perf regressions.
"""

import asyncio
import json
import struct
import urllib.error
import urllib.request

import numpy as np
import pytest

from symbiont_tpu import subjects
from symbiont_tpu.bus.core import subject_matches
from symbiont_tpu.bus.inproc import InprocBus
from symbiont_tpu.config import (
    ApiConfig,
    GraphStoreConfig,
    SymbiontConfig,
    TextGeneratorConfig,
    VectorStoreConfig,
)
from symbiont_tpu.resilience.breaker import CircuitBreaker
from symbiont_tpu.resilience.faults import FaultPlan, FaultRule
from symbiont_tpu.resilience.stores import ResilientVectorStore
from symbiont_tpu.runner import SymbiontStack

pytestmark = pytest.mark.chaos

PAGE = ("<html><body><main><p>Chaos testing the ingest pipeline.</p>"
        "<p>Every message must survive the faults!</p></main></body></html>")
SENTENCES_PER_DOC = 2
N_DOCS = 6


class _StubEngine:
    """Duck-typed engine (same shape as test_observability's): the chaos
    suite is about the failure paths, not BERT numerics."""

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


def _stack_config(tmp_path, *, services, ack_wait_s=0.3, max_deliver=5,
                  handler_timeout_s=0.0):
    cfg = SymbiontConfig(
        vector_store=VectorStoreConfig(dim=16,
                                       data_dir=str(tmp_path / "vs"),
                                       shard_capacity=64),
        graph_store=GraphStoreConfig(data_dir=str(tmp_path / "gs")),
        text_generator=TextGeneratorConfig(markov_state_path=None),
        api=ApiConfig(host="127.0.0.1", port=0),
    )
    cfg.runner.services = services
    cfg.bus.durable = True
    cfg.bus.durable_ack_wait_s = ack_wait_s
    cfg.bus.durable_max_deliver = max_deliver
    cfg.resilience.handler_timeout_s = handler_timeout_s
    cfg.resilience.supervisor_backoff_base_s = 0.05
    cfg.resilience.supervisor_backoff_max_s = 0.1
    return cfg


async def _ingest_docs(bus, n_docs=N_DOCS):
    from symbiont_tpu.schema import PerceiveUrlTask, to_json_bytes

    for i in range(n_docs):
        await bus.publish(subjects.TASKS_PERCEIVE_URL,
                          to_json_bytes(PerceiveUrlTask(url=f"http://d/{i}")))


async def _wait_for(cond, timeout=20.0, interval=0.05):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if cond():
            return True
        await asyncio.sleep(interval)
    return cond()


# ----------------------------------------------- fault class: handler crash

def test_zero_loss_under_handler_exceptions(tmp_path):
    """Injected exceptions in the vector-memory handler (fewer than
    max_deliver): every delivery redelivers until it sticks — the full
    document set lands, nothing lost."""
    plan = FaultPlan(seed=11, rules=[
        FaultRule(seam="handler", kind="error",
                  match="vector_memory:data.text.with_embeddings", times=3)])
    cfg = _stack_config(tmp_path,
                        services="perception,preprocessing,vector_memory")
    expected = N_DOCS * SENTENCES_PER_DOC

    async def scenario():
        bus = InprocBus()
        stack = SymbiontStack(cfg, bus=bus, engine=_StubEngine(),
                              fetcher=lambda url: PAGE)
        await stack.start()
        try:
            with plan.activate():
                await _ingest_docs(bus)
                ok = await _wait_for(
                    lambda: stack.vector_store.count() >= expected)
            assert ok, (f"lost ingest under handler faults: "
                        f"{stack.vector_store.count()}/{expected} points")
            assert stack.vector_store.count() == expected
            assert plan.fired[("handler", "error")] == 3
            assert bus.stats["redelivered"] >= 3
            assert len(bus.dlq) == 0  # transient faults never quarantine
        finally:
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


# ---------------------------------- fault class: crash under coalesced acks

def test_zero_loss_with_coalesced_acks_under_handler_faults(tmp_path):
    """Coalesced-ack semantics (services/coalesce.py) under chaos: rows
    from many messages share one flush and each durable delivery acks only
    after the flush carrying its rows commits. Injected handler crashes
    redeliver through the coalescer — the full document set lands exactly
    once (deterministic ids), and the coalescer demonstrably batched
    multiple messages per store call while the faults fired."""
    plan = FaultPlan(seed=15, rules=[
        FaultRule(seam="handler", kind="error",
                  match="vector_memory:data.text.with_embeddings", times=2)])
    cfg = _stack_config(tmp_path,
                        services="perception,preprocessing,vector_memory")
    cfg.vector_store.coalesce_max_rows = 8
    cfg.vector_store.coalesce_max_age_ms = 100.0
    expected = N_DOCS * SENTENCES_PER_DOC
    from symbiont_tpu.utils.telemetry import metrics

    labels = {"service": "vector_memory"}
    msgs0 = metrics.get("coalesce.messages", labels=labels)
    rows0 = metrics.get("coalesce.rows", labels=labels)

    async def scenario():
        bus = InprocBus()
        stack = SymbiontStack(cfg, bus=bus, engine=_StubEngine(),
                              fetcher=lambda url: PAGE)
        await stack.start()
        try:
            with plan.activate():
                await _ingest_docs(bus)
                ok = await _wait_for(
                    lambda: stack.vector_store.count() >= expected)
            assert ok, (f"lost ingest under coalesced acks: "
                        f"{stack.vector_store.count()}/{expected} points")
            assert stack.vector_store.count() == expected
            assert plan.fired[("handler", "error")] == 2
            assert bus.stats["redelivered"] >= 2
            assert len(bus.dlq) == 0
            # the coalescer really carried the load: every message went
            # through it, and at least one flush batched several messages
            assert metrics.get("coalesce.messages",
                               labels=labels) - msgs0 == N_DOCS
            assert metrics.get("coalesce.rows",
                               labels=labels) - rows0 == expected
            flush_hist = metrics.histogram_summary("coalesce.flush_rows",
                                                   labels=labels)
            assert flush_hist is not None and flush_hist["max"] >= \
                2 * SENTENCES_PER_DOC, flush_hist
        finally:
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


# ------------------------------------------------ fault class: handler hang

def test_zero_loss_under_handler_hang_past_timeout(tmp_path):
    """Injected hangs longer than the handler timeout: the handler is
    CANCELLED at the deadline (semaphore slot freed), the delivery stays
    unacked, redelivery completes the work — zero loss."""
    plan = FaultPlan(seed=12, rules=[
        FaultRule(seam="handler", kind="hang", delay_s=30.0,
                  match="vector_memory:data.text.with_embeddings", times=2)])
    cfg = _stack_config(tmp_path,
                        services="perception,preprocessing,vector_memory",
                        handler_timeout_s=0.2)
    expected = N_DOCS * SENTENCES_PER_DOC

    async def scenario():
        bus = InprocBus()
        stack = SymbiontStack(cfg, bus=bus, engine=_StubEngine(),
                              fetcher=lambda url: PAGE)
        await stack.start()
        try:
            with plan.activate():
                await _ingest_docs(bus)
                ok = await _wait_for(
                    lambda: stack.vector_store.count() >= expected)
            assert ok, (f"lost ingest under hang faults: "
                        f"{stack.vector_store.count()}/{expected} points")
            assert stack.vector_store.count() == expected
            assert plan.fired[("handler", "hang")] == 2
            from symbiont_tpu.utils.telemetry import metrics

            assert metrics.get("bus.handler_timeout",
                               labels={"service": "vector_memory",
                                       "subject":
                                       "data.text.with_embeddings"}) >= 2
            vm = next(s for s in stack.services
                      if s.name == "vector_memory")
            # no slot pinned by a hung handler (the last handlers free
            # theirs a moment after their rows are counted: wait, a pinned
            # slot never comes back)
            assert await _wait_for(lambda: vm._sem._value == 32, timeout=5.0)
        finally:
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


# --------------------------------------------- fault class: delivery drops

def test_zero_loss_under_delivery_drops(tmp_path):
    """Injected in-flight delivery drops on the durable pump: the delivery
    attempt is consumed but the message redelivers after ack_wait."""
    plan = FaultPlan(seed=13, rules=[
        FaultRule(seam="bus.deliver", kind="drop",
                  match="data.text.with_embeddings", times=3)])
    cfg = _stack_config(tmp_path,
                        services="perception,preprocessing,vector_memory",
                        ack_wait_s=0.2)
    expected = N_DOCS * SENTENCES_PER_DOC

    async def scenario():
        bus = InprocBus()
        stack = SymbiontStack(cfg, bus=bus, engine=_StubEngine(),
                              fetcher=lambda url: PAGE)
        await stack.start()
        try:
            with plan.activate():
                await _ingest_docs(bus)
                ok = await _wait_for(
                    lambda: stack.vector_store.count() >= expected)
            assert ok, (f"lost ingest under delivery drops: "
                        f"{stack.vector_store.count()}/{expected} points")
            assert stack.vector_store.count() == expected
            assert plan.fired[("bus.deliver", "drop")] == 3
        finally:
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


# ------------------------------------- fault class: store outage + recovery

def test_zero_loss_under_store_outage_with_recovery(tmp_path):
    """Mid-run vector-store outage: the first upserts fail, the breaker
    opens, writes SPILL to the WAL (handler keeps acking — the pipeline
    never backs up), and recovery replays the spill. Inner store ends with
    every point."""
    from symbiont_tpu.memory.vector_store import VectorStore
    from symbiont_tpu.schema import (
        SentenceEmbedding,
        TextWithEmbeddingsMessage,
        to_json_bytes,
    )
    from symbiont_tpu.services.vector_memory import VectorMemoryService

    inner = VectorStore(VectorStoreConfig(dim=4,
                                          data_dir=str(tmp_path / "inner"),
                                          shard_capacity=64))
    breaker = CircuitBreaker("chaos_vs", failure_threshold=2,
                             reset_timeout_s=0.2)
    store = ResilientVectorStore(inner, breaker=breaker,
                                 spill_path=str(tmp_path / "spill.jsonl"))
    plan = FaultPlan(seed=14, rules=[
        FaultRule(seam="store.upsert", kind="error", match="chaos_vs",
                  times=2)])
    n_msgs = 5

    async def scenario():
        bus = InprocBus()
        await bus.add_stream("pipeline",
                             [subjects.DATA_TEXT_WITH_EMBEDDINGS],
                             ack_wait_s=0.5, max_deliver=5)
        svc = VectorMemoryService(bus, store, durable_stream="pipeline")
        await svc.start()
        try:
            with plan.activate():
                for i in range(n_msgs):
                    msg = TextWithEmbeddingsMessage(
                        original_id=f"doc-{i}", source_url="http://d",
                        embeddings_data=[SentenceEmbedding(
                            sentence_text=f"s{i}",
                            embedding=[float(i), 1.0, 0.0, 0.0])],
                        model_name="stub", timestamp_ms=i)
                    await bus.publish(subjects.DATA_TEXT_WITH_EMBEDDINGS,
                                      to_json_bytes(msg))
                    await asyncio.sleep(0.12)  # spread across the outage
                # every message was ACKED (spill counts as durable): the
                # stream settles even while the backend is down
                stats_ok = await _wait_for_settled(bus, n_msgs)
                assert stats_ok, "durable stream did not settle"
                # recovery: drain whatever is still spilled
                drained = await _wait_for(
                    lambda: store.spill_pending() == 0, timeout=5.0)
                if not drained:
                    await asyncio.get_running_loop().run_in_executor(
                        None, store.replay_spill)
            assert inner.count() == n_msgs, (
                f"store outage lost writes: {inner.count()}/{n_msgs}")
            assert plan.fired[("store.upsert", "error")] == 2
            from symbiont_tpu.utils.telemetry import metrics

            assert metrics.get("store.spilled_points",
                               labels={"store": "chaos_vs"}) >= 1
        finally:
            await svc.stop()
            await bus.close()

    async def _wait_for_settled(bus, n):
        async def floor():
            stats = await bus.stream_stats()
            return stats["pipeline"]["groups"][
                subjects.QUEUE_VECTOR_MEMORY]["ack_floor"]

        deadline = asyncio.get_running_loop().time() + 20.0
        while asyncio.get_running_loop().time() < deadline:
            if await floor() >= n:
                return True
            await asyncio.sleep(0.05)
        return False

    asyncio.run(scenario())


# --------------------------------------------- fault class: TCP disconnect

class _MiniBroker:
    """~80-line in-test symbus broker speaking just enough of the wire
    protocol (native/symbus/protocol.hpp) to prove client reconnect: SUB /
    UNSUB / PUB / MSG routing plus auto-`{"ok": true}` replies on the
    `_SYMBUS.*` control subjects. `kill_connections()` resets every client
    socket without stopping the listener — the broker-restart story from
    the client's side."""

    def __init__(self):
        self.server = None
        self.conns = {}  # writer -> {sid: (subject, queue)}
        self.control_requests = []  # (subject, payload-dict)

    async def start(self) -> int:
        self.server = await asyncio.start_server(self._handle,
                                                 "127.0.0.1", 0)
        return self.server.sockets[0].getsockname()[1]

    async def stop(self):
        self.server.close()
        await self.server.wait_closed()
        await self.kill_connections()

    async def kill_connections(self):
        for w in list(self.conns):
            w.close()
        self.conns.clear()

    def _msg_frame(self, sid, subject, reply, headers, data):
        def s(x):
            b = x.encode()
            return struct.pack("<H", len(b)) + b

        body = struct.pack("<BI", 5, sid) + s(subject) + s(reply or "")
        body += struct.pack("<H", len(headers))
        for k, v in headers.items():
            body += s(k) + s(v)
        body += struct.pack("<I", len(data)) + data
        return struct.pack("<I", len(body)) + body

    async def _route(self, subject, reply, headers, data):
        for w, subs in list(self.conns.items()):
            for sid, (pattern, _queue) in subs.items():
                if subject_matches(pattern, subject):
                    w.write(self._msg_frame(sid, subject, reply, headers,
                                            data))
                    await w.drain()

    async def _handle(self, reader, writer):
        self.conns[writer] = {}
        try:
            while True:
                head = await reader.readexactly(4)
                (n,) = struct.unpack("<I", head)
                payload = await reader.readexactly(n)
                from symbiont_tpu.bus.tcp import _FrameReader

                r = _FrameReader(payload)
                op = r.u8()
                if op == 1:  # SUB
                    sid = r.u32()
                    self.conns[writer][sid] = (r.s(), r.s() or None)
                elif op == 2:  # UNSUB
                    self.conns[writer].pop(r.u32(), None)
                elif op == 3:  # PUB
                    subject = r.s()
                    reply = r.s()
                    headers = {r.s(): r.s() for _ in range(r.u16())}
                    data = r.data()
                    if subject.startswith("_SYMBUS.") and reply:
                        try:
                            self.control_requests.append(
                                (subject, json.loads(data)))
                        except ValueError:
                            self.control_requests.append((subject, None))
                        await self._route(reply, None, {},
                                          json.dumps({"ok": True}).encode())
                    else:
                        await self._route(subject, reply or None, headers,
                                          data)
                elif op == 4:  # PING
                    pass
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        finally:
            self.conns.pop(writer, None)
            writer.close()


def test_tcp_bus_reconnects_resubscribes_and_reattaches_consumers():
    """A connection reset mid-run: the client auto-reconnects with backoff,
    re-sends every SUB, re-issues add_stream, re-attaches durable
    consumers, and messages published after the reset arrive — the client
    no longer dies permanently on one disconnect."""
    from symbiont_tpu.bus.tcp import TcpBus

    async def scenario():
        broker = _MiniBroker()
        port = await broker.start()
        bus = TcpBus("127.0.0.1", port, reconnect_base_s=0.05,
                     reconnect_max_s=0.2, send_wait_s=5.0)
        await bus.connect()
        try:
            sub = await bus.subscribe("t.events")
            await bus.add_stream("s", ["t.>"], ack_wait_s=1.0)
            dsub = await bus.durable_subscribe("s", "g")
            assert [s for s, _ in broker.control_requests] == [
                "_SYMBUS.stream.create", "_SYMBUS.consumer.create"]

            await bus.publish("t.events", b"before")
            m = await sub.next(5.0)
            assert m is not None and m.data == b"before"

            # ---- the fault: every client connection reset
            await broker.kill_connections()
            assert await _wait_for(lambda: bus.stats["disconnects"] >= 1,
                                   timeout=5.0)
            # publish during/after the gap: waits for the reconnect, then
            # sends — no ConnectionError, no dead client
            await bus.publish("t.events", b"after")
            m = await sub.next(5.0)
            assert m is not None and m.data == b"after", \
                "subscription did not survive the reconnect"
            assert bus.stats["reconnects"] == 1
            # session restored: stream + consumer re-issued broker-side
            control = [s for s, _ in broker.control_requests]
            assert control.count("_SYMBUS.stream.create") == 2
            assert control.count("_SYMBUS.consumer.create") == 2
            assert not dsub._closed  # durable sub survived too
        finally:
            await bus.close()
            await broker.stop()

    asyncio.run(scenario())


# ------------------------------------ poison message -> DLQ -> HTTP replay

def test_poison_message_quarantined_and_replayed_via_api(tmp_path):
    """A poison message fails every delivery: after EXACTLY max_deliver
    attempts it is quarantined (not redelivered, not dropped), shows up in
    GET /api/dlq with its failure metadata, and POST /api/dlq/replay
    re-enters it into the durable flow — where the fixed handler finally
    processes it. Zero loss, bounded retries."""
    from symbiont_tpu.services.api import ApiService
    from symbiont_tpu.services.base import Service

    max_deliver = 3
    poisoned = [True]
    processed = []

    class _IngestService(Service):
        name = "ingest"

        async def _setup(self):
            await self._subscribe_loop("work.item", self._handle,
                                       queue="q.ingest",
                                       durable_stream="jobs")

        async def _handle(self, msg):
            if poisoned[0]:
                raise RuntimeError("poison payload")
            processed.append(msg.data)

    async def scenario():
        bus = InprocBus()
        await bus.add_stream("jobs", ["work.item"], ack_wait_s=0.1,
                             max_deliver=max_deliver)
        svc = _IngestService(bus)
        await svc.start()
        api = ApiService(bus, ApiConfig(host="127.0.0.1", port=0))
        await api.start()
        loop = asyncio.get_running_loop()
        port = api.port

        def http(method, path, body=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=json.dumps(body).encode() if body is not None else None,
                headers={"Content-Type": "application/json"}, method=method)
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.loads(r.read())

        try:
            await bus.publish("work.item", b'{"job": "poison"}')
            assert await _wait_for(lambda: len(bus.dlq) == 1), \
                "poison message was not quarantined"
            # exactly max_deliver attempts, then quarantine — never more
            entry = bus.dlq.list()[0]
            assert entry.deliveries == max_deliver
            assert entry.subject == "work.item"
            await asyncio.sleep(0.3)  # would-be extra redeliveries
            from symbiont_tpu.utils.telemetry import metrics

            failed = metrics.get("bus.failed",
                                 labels={"service": "ingest",
                                         "subject": "work.item"})
            assert failed == max_deliver

            # inspectable over HTTP
            status, body = await loop.run_in_executor(
                None, http, "GET", "/api/dlq")
            assert status == 200 and body["available"] and body["size"] == 1
            (e,) = body["entries"]
            assert e["deliveries"] == max_deliver
            assert e["stream"] == "jobs" and e["group"] == "q.ingest"
            assert "max_deliver exhausted" in e["reason"]
            assert json.loads(e["data_preview"]) == {"job": "poison"}

            # fix the handler, replay through the HTTP surface
            poisoned[0] = False
            status, body = await loop.run_in_executor(
                None, lambda: http("POST", "/api/dlq/replay",
                                   {"id": e["id"]}))
            assert status == 200 and body["replayed"] == 1
            assert await _wait_for(lambda: len(processed) == 1), \
                "replayed message was not processed"
            assert processed[0] == b'{"job": "poison"}'
            status, body = await loop.run_in_executor(
                None, http, "GET", "/api/dlq")
            assert body["size"] == 0
        except urllib.error.HTTPError as err:
            raise AssertionError(f"unexpected HTTP error: {err}") from err
        finally:
            await api.stop()
            await svc.stop()
            await bus.close()

    asyncio.run(scenario())


def test_dlq_replay_error_shapes():
    """/api/dlq/replay input validation: missing selector -> 400, unknown
    id -> 404 (already replayed / evicted)."""
    from symbiont_tpu.services.api import ApiService

    async def scenario():
        bus = InprocBus()
        api = ApiService(bus, ApiConfig(host="127.0.0.1", port=0))
        await api.start()
        loop = asyncio.get_running_loop()
        port = api.port

        def post(body):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/dlq/replay",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        try:
            status, _ = await loop.run_in_executor(None, post, {})
            assert status == 400
            status, _ = await loop.run_in_executor(None, post, {"id": 999})
            assert status == 404
            status, body = await loop.run_in_executor(
                None, post, {"all": True})
            assert status == 200 and body["replayed"] == 0
        finally:
            await api.stop()
            await bus.close()

    asyncio.run(scenario())
