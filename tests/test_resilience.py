"""Resilience-plane units: fault plan determinism, circuit breaker state
machine, DLQ quarantine store, store wrappers (spill + replay), loop
supervisor, retry jitter/async, handler timeout + retry, durable in-proc
streams. The end-to-end zero-loss proofs live in tests/test_chaos.py."""

import asyncio
import random

import pytest

from symbiont_tpu.bus.core import Msg
from symbiont_tpu.bus.inproc import InprocBus
from symbiont_tpu.resilience.breaker import (
    CircuitBreaker,
    CircuitOpenError,
)
from symbiont_tpu.resilience.dlq import DeadLetterStore
from symbiont_tpu.resilience.faults import FaultInjected, FaultPlan, FaultRule
from symbiont_tpu.resilience.stores import (
    ResilientGraphStore,
    ResilientVectorStore,
)
from symbiont_tpu.resilience.supervisor import jittered, supervise
from symbiont_tpu.services.base import Service
from symbiont_tpu.utils.retry import connect_retry, connect_retry_async
from symbiont_tpu.utils.telemetry import metrics


def _run(coro):
    return asyncio.run(coro)


# ------------------------------------------------------------- fault plan

def test_fault_rule_positional_determinism():
    plan = FaultPlan(seed=1, rules=[
        FaultRule(seam="handler", kind="error", match="svc:*",
                  after=1, times=2)])
    # op 0 skipped (after=1), ops 1-2 fire, op 3+ exhausted
    fired = [plan.check("handler", "svc:a") is not None for _ in range(5)]
    assert fired == [False, True, True, False, False]
    assert plan.fired[("handler", "error")] == 2
    # non-matching seam/key never counts
    assert plan.check("store.upsert", "svc:a") is None
    assert plan.check("handler", "other:a") is None


def test_fault_plan_seeded_probability_reproducible():
    def transcript(seed):
        plan = FaultPlan(seed=seed, rules=[
            FaultRule(seam="bus.publish", kind="drop", times=0, prob=0.5)])
        return [plan.check("bus.publish", "s") is not None
                for _ in range(32)]

    assert transcript(7) == transcript(7)
    assert transcript(7) != transcript(8)  # astronomically unlikely to tie


def test_fault_kinds_raise_or_sleep():
    plan = FaultPlan(rules=[
        FaultRule(seam="store.upsert", kind="error", times=1),
        FaultRule(seam="store.upsert", kind="reset", times=1),
    ])
    with pytest.raises(FaultInjected):
        plan.sync_fault("store.upsert", "x")
    with pytest.raises(ConnectionResetError):
        plan.sync_fault("store.upsert", "x")
    assert plan.sync_fault("store.upsert", "x") is None  # exhausted

    async def hang():
        p = FaultPlan(rules=[FaultRule(seam="handler", kind="hang",
                                       delay_s=0.01, times=1)])
        rule = await p.async_fault("handler", "k")
        assert rule is not None and rule.kind == "hang"

    _run(hang())


def test_fault_plan_activation_scoped():
    from symbiont_tpu.resilience import faults

    assert faults.active_plan() is None
    plan = FaultPlan()
    with plan.activate():
        assert faults.active_plan() is plan
        inner = FaultPlan()
        with inner.activate():
            assert faults.active_plan() is inner
        assert faults.active_plan() is plan
    assert faults.active_plan() is None


def test_fault_rule_rejects_unknown_kind():
    with pytest.raises(ValueError):
        FaultRule(seam="handler", kind="explode")


# --------------------------------------------------------- circuit breaker

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_breaker_opens_half_opens_and_recovers():
    clock = _Clock()
    br = CircuitBreaker("t", failure_threshold=3, reset_timeout_s=10.0,
                        clock=clock)
    boom = lambda: (_ for _ in ()).throw(RuntimeError("down"))  # noqa: E731
    for _ in range(3):
        with pytest.raises(RuntimeError):
            br.call(boom)
    assert br.state == "open"
    # open: refuse FAST with CircuitOpenError (a ConnectionError subclass)
    with pytest.raises(CircuitOpenError):
        br.call(lambda: "never runs")
    assert issubclass(CircuitOpenError, ConnectionError)
    # before the window: still open; after: one half-open probe admitted
    clock.t = 9.9
    assert not br.allow()
    clock.t = 10.1
    assert br.state == "half_open"
    assert br.allow()
    assert not br.allow()  # second concurrent probe refused
    br.record_success()
    assert br.state == "closed"
    assert br.allow()


def test_breaker_half_open_failure_reopens():
    clock = _Clock()
    br = CircuitBreaker("t2", failure_threshold=1, reset_timeout_s=5.0,
                        clock=clock)
    with pytest.raises(RuntimeError):
        br.call(lambda: (_ for _ in ()).throw(RuntimeError()))
    clock.t = 6.0
    with pytest.raises(RuntimeError):  # the probe fails
        br.call(lambda: (_ for _ in ()).throw(RuntimeError()))
    assert br.state == "open"
    assert br.retry_in_s() == pytest.approx(5.0, abs=0.01)


def test_breaker_fatal_exceptions_bypass_accounting():
    br = CircuitBreaker("t3", failure_threshold=1)
    with pytest.raises(ValueError):
        br.call(lambda: (_ for _ in ()).throw(ValueError("config")),
                fatal=(ValueError,))
    assert br.state == "closed"  # config errors never trip the breaker


# -------------------------------------------------------------------- DLQ

def test_dlq_bounded_with_eviction_and_replay():
    store = DeadLetterStore(capacity=2)
    for i in range(3):
        store.quarantine(f"s.{i}", f"payload{i}".encode(), {"h": "v"},
                         reason="max_deliver", deliveries=5)
    assert len(store) == 2  # oldest evicted
    subjects = [e.subject for e in store.list()]
    assert subjects == ["s.1", "s.2"]
    entry = store.list()[0]
    s = entry.summary()
    assert s["data_preview"] == "payload1"
    import base64

    assert base64.b64decode(s["data_b64"]) == b"payload1"

    class _FakeBus:
        def __init__(self):
            self.published = []

        async def publish(self, subject, data, headers=None):
            self.published.append((subject, data, headers))

    async def scenario():
        bus = _FakeBus()
        n = await store.replay(bus, entry.id)
        assert n == 1 and len(store) == 1
        subject, data, headers = bus.published[0]
        assert subject == "s.1" and data == b"payload1"
        assert headers["X-Symbiont-Replayed"] == "1"
        # replay-all drains the rest
        assert await store.replay(bus) == 1
        assert len(store) == 0

    _run(scenario())


# ---------------------------------------------------------- store wrappers

class _FlakyVectorStore:
    """Fails the first `fail_n` upserts, then recovers."""

    supports_fused = False

    def __init__(self, fail_n=0):
        self.fail_n = fail_n
        self.calls = 0
        self.points = {}

    def ensure_collection(self, dim=None):
        pass

    def upsert(self, points):
        self.calls += 1
        if self.calls <= self.fail_n:
            raise ConnectionError("backend down")
        for pid, vec, payload in points:
            self.points[pid] = (vec, payload)
        return len(points)

    def search(self, query, top_k):
        return []

    def count(self):
        return len(self.points)


def test_vector_wrapper_spills_and_replays(tmp_path):
    inner = _FlakyVectorStore(fail_n=2)
    br = CircuitBreaker("vtest", failure_threshold=10, reset_timeout_s=0.01)
    spill = tmp_path / "spill.jsonl"
    store = ResilientVectorStore(inner, breaker=br, spill_path=str(spill))
    # outage: both writes report success (spilled), nothing reaches inner
    assert store.upsert([("a", [1.0], {"k": 1})]) == 1
    assert store.upsert([("b", [2.0], {"k": 2})]) == 1
    assert inner.count() == 0 and store.spill_pending() == 2
    assert spill.exists()
    # recovery: the next write replays the spill FIRST, then lands itself
    assert store.upsert([("c", [3.0], {"k": 3})]) == 1
    assert inner.count() == 3 and store.spill_pending() == 0
    assert list(inner.points) == ["a", "b", "c"]  # rough arrival order kept
    assert not spill.exists()


def test_vector_wrapper_spill_survives_restart(tmp_path):
    spill = tmp_path / "spill.jsonl"
    down = ResilientVectorStore(_FlakyVectorStore(fail_n=99),
                                breaker=CircuitBreaker(
                                    "vp", failure_threshold=1,
                                    reset_timeout_s=30.0),
                                spill_path=str(spill))
    down.upsert([("a", [1.0], {})])
    assert down.spill_pending() == 1
    # process restart during the outage: the journal reloads from disk
    healthy_inner = _FlakyVectorStore()
    revived = ResilientVectorStore(healthy_inner,
                                   breaker=CircuitBreaker("vp2"),
                                   spill_path=str(spill))
    assert revived.spill_pending() == 1
    assert revived.replay_spill() == 1
    assert healthy_inner.count() == 1 and revived.spill_pending() == 0


def test_vector_wrapper_open_breaker_read_fallback():
    class _Hits:
        def search(self, query, top_k):
            return ["local-hit"]

    br = CircuitBreaker("vr", failure_threshold=1, reset_timeout_s=60.0)
    store = ResilientVectorStore(_FlakyVectorStore(fail_n=99), breaker=br,
                                 fallback=_Hits())
    br.record_failure()  # threshold 1 -> open
    assert store.search([1.0], 3) == ["local-hit"]
    no_fallback = ResilientVectorStore(_FlakyVectorStore(), breaker=br)
    with pytest.raises(CircuitOpenError):
        no_fallback.search([1.0], 3)


def test_vector_wrapper_config_errors_propagate():
    class _DimMismatch(_FlakyVectorStore):
        def upsert(self, points):
            raise ValueError("dim mismatch")

    store = ResilientVectorStore(_DimMismatch(), breaker=CircuitBreaker("vc"))
    with pytest.raises(ValueError):
        store.upsert([("a", [1.0], {})])
    assert store.spill_pending() == 0  # never spilled: replay can't fix it


def test_graph_wrapper_spills_and_replays(tmp_path):
    from symbiont_tpu.schema import TokenizedTextMessage

    class _FlakyGraph:
        def __init__(self, fail_n):
            self.fail_n = fail_n
            self.calls = 0
            self.saved = []

        def ensure_schema(self):
            pass

        def save_tokenized(self, msg):
            self.calls += 1
            if self.calls <= self.fail_n:
                raise ConnectionError("neo4j down")
            self.saved.append(msg.original_id)
            return 1

        def counts(self):
            return {"Document": len(self.saved)}

        def close(self):
            pass

    inner = _FlakyGraph(fail_n=1)
    store = ResilientGraphStore(inner, breaker=CircuitBreaker(
        "gtest", failure_threshold=10),
        spill_path=str(tmp_path / "graph.spill.jsonl"))

    def doc(i):
        return TokenizedTextMessage(original_id=f"d{i}", source_url="u",
                                    tokens=["a"], sentences=["a."],
                                    timestamp_ms=1)

    assert store.save_tokenized(doc(0)) == -1  # spilled
    assert store.spill_pending() == 1
    assert store.save_tokenized(doc(1)) == 1  # replays d0 first
    assert inner.saved == ["d0", "d1"]
    assert store.spill_pending() == 0


# -------------------------------------------------------------- supervisor

def test_supervisor_restarts_crashed_loop_until_clean_exit():
    async def scenario():
        runs = []

        async def loop():
            runs.append(1)
            if len(runs) < 3:
                raise RuntimeError("loop died")
            return  # clean exit on the 3rd run

        before = metrics.get("service.loop_restarts",
                             labels={"service": "t", "task": "t:x"})
        await supervise(loop, name="t:x", backoff_base_s=0.01,
                        backoff_max_s=0.02, labels={"service": "t"},
                        rng=random.Random(0))
        assert len(runs) == 3
        after = metrics.get("service.loop_restarts",
                            labels={"service": "t", "task": "t:x"})
        assert after - before == 2

    _run(scenario())


def test_supervisor_stops_when_no_longer_wanted():
    async def scenario():
        wanted = [True]
        runs = []

        async def loop():
            runs.append(1)
            wanted[0] = False
            raise RuntimeError("died while stopping")

        await supervise(loop, name="t:y", backoff_base_s=0.01,
                        still_wanted=lambda: wanted[0])
        assert len(runs) == 1  # no resurrection after stop

    _run(scenario())


def test_jittered_bounds():
    rng = random.Random(3)
    for _ in range(100):
        v = jittered(1.0, rng)
        assert 0.5 <= v <= 1.0


# ------------------------------------------------------------------ retry

def test_connect_retry_jitter_and_async():
    sleeps = []

    import symbiont_tpu.utils.retry as retry_mod

    orig_sleep = retry_mod.time.sleep
    retry_mod.time.sleep = sleeps.append
    try:
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionError("not yet")
            return "up"

        assert connect_retry(flaky, retries=5, delay_s=1.0, what="svc",
                             jitter=True, rng=random.Random(1)) == "up"
    finally:
        retry_mod.time.sleep = orig_sleep
    assert len(sleeps) == 2
    assert all(0.5 <= s <= 1.0 for s in sleeps)  # full-jitter window

    async def scenario():
        calls = []

        async def flaky_async():
            calls.append(1)
            if len(calls) < 2:
                raise ConnectionError("not yet")
            return "up"

        out = await connect_retry_async(flaky_async, retries=3,
                                        delay_s=0.01, what="svc",
                                        jitter=True)
        assert out == "up"

        async def hopeless():
            raise ConnectionError("never")

        with pytest.raises(ConnectionError):
            await connect_retry_async(hopeless, retries=2, delay_s=0.01,
                                      what="svc2")

    _run(scenario())


# ------------------------------------------- service timeout/retry/stop

class _OneShotService(Service):
    name = "oneshot"

    def __init__(self, bus, handler, subject="t.x", durable_stream=None):
        super().__init__(bus)
        self._handler = handler
        self._subject = subject
        self._durable = durable_stream

    async def _setup(self):
        await self._subscribe_loop(self._subject, self._handler,
                                   queue="q.oneshot",
                                   durable_stream=self._durable)


def test_handler_timeout_cancels_and_frees_slot():
    async def scenario():
        bus = InprocBus()
        cancelled = []

        async def hang_forever(msg):
            try:
                await asyncio.sleep(60)
            except asyncio.CancelledError:
                cancelled.append(1)
                raise

        svc = _OneShotService(bus, hang_forever)
        svc.handler_timeout_s = 0.1
        before = metrics.get("bus.handler_timeout",
                             labels={"service": "oneshot", "subject": "t.x"})
        await svc.start()
        await bus.publish("t.x", b"x")

        def timed_out() -> int:
            return metrics.get("bus.handler_timeout", labels={
                "service": "oneshot", "subject": "t.x"}) - before

        # what the test is about, waited for itself: the timeout is COUNTED
        # and the semaphore slot is back. Both happen in the service's own
        # task a loop turn or more AFTER the handler saw its cancellation,
        # so asserting them the moment `cancelled` fills raced that task on
        # a loaded machine. 30 s: the idle case takes 0.1 s.
        deadline = asyncio.get_running_loop().time() + 30
        while (asyncio.get_running_loop().time() < deadline
               and not (timed_out() and svc._sem._value == 32)):
            await asyncio.sleep(0.01)
        assert cancelled, "handler was not cancelled at the deadline"
        assert timed_out() == 1
        # the semaphore slot came back: no hung-handler pinning
        assert svc._sem._value == 32
        await svc.stop()
        await bus.close()

    _run(scenario())


def test_handler_retry_with_backoff_eventually_succeeds():
    async def scenario():
        bus = InprocBus()
        attempts = []
        done = asyncio.Event()

        async def flaky(msg):
            attempts.append(1)
            if len(attempts) < 3:
                raise RuntimeError("transient")
            done.set()

        svc = _OneShotService(bus, flaky)
        svc.handler_retries = 3
        svc.handler_backoff_base_s = 0.01
        svc.handler_backoff_max_s = 0.02
        await svc.start()
        await bus.publish("t.x", b"x")
        await asyncio.wait_for(done.wait(), 5)
        assert len(attempts) == 3
        await svc.stop()
        await bus.close()

    _run(scenario())


def test_stop_awaits_cancelled_loop_tasks():
    async def scenario():
        bus = InprocBus()

        async def noop(msg):
            pass

        svc = _OneShotService(bus, noop)
        await svc.start()
        loops = list(svc._loops)
        assert loops
        await svc.stop()
        # gathered, not just cancelled: every loop task is DONE now, so no
        # "Task was destroyed but it is pending" at interpreter exit
        assert all(t.done() for t in loops)
        assert svc._loops == []
        await bus.close()

    _run(scenario())


def test_subscribe_loop_is_supervised():
    async def scenario():
        bus = InprocBus()
        handled = asyncio.Event()

        async def ok(msg):
            handled.set()

        svc = _OneShotService(bus, ok)
        svc.supervisor_backoff_base_s = 0.01
        svc.supervisor_backoff_max_s = 0.02
        await svc.start()
        # sabotage the semaphore so the DISPATCH LOOP itself (not the
        # handler) crashes on the next message — the pre-resilience loop
        # died here silently, never consuming again
        real_sem = svc._sem

        class _Bomb:
            async def acquire(self):
                svc._sem = real_sem  # heal for the restarted loop
                raise RuntimeError("loop body bomb")

        svc._sem = _Bomb()
        await bus.publish("t.x", b"boom")
        await asyncio.sleep(0.1)
        # supervised restart: a later message is still consumed
        await bus.publish("t.x", b"fine")
        await asyncio.wait_for(handled.wait(), 5)
        await svc.stop()
        await bus.close()

    _run(scenario())


# -------------------------------------------- durable in-proc bus (units)

def test_inproc_durable_capture_ack_redeliver():
    async def scenario():
        bus = InprocBus()
        await bus.add_stream("ingest", ["data.raw_text.>"], ack_wait_s=0.15,
                             max_deliver=3)
        # capture with NO consumer connected (at-least-once)
        await bus.publish("data.raw_text.discovered", b"one")
        await bus.publish("data.other", b"not captured")
        sub = await bus.durable_subscribe("ingest", "workers")
        m = await sub.next(2.0)
        assert m is not None and m.data == b"one"
        assert m.subject == "data.raw_text.discovered"
        assert m.headers["X-Symbus-Stream"] == "ingest"
        assert m.headers["X-Symbus-Deliveries"] == "1"
        # unacked -> redelivers after ack_wait
        r = await sub.next(2.0)
        assert r is not None and int(r.headers["X-Symbus-Deliveries"]) == 2
        await bus.ack(r)
        assert await sub.next(0.4) is None  # settled, no more deliveries
        stats = await bus.stream_stats()
        g = stats["ingest"]["groups"]["workers"]
        assert g["ack_floor"] == 1 and g["inflight"] == 0
        await bus.close()

    _run(scenario())


def test_inproc_durable_group_shares_and_filter_auto_acks():
    async def scenario():
        bus = InprocBus()
        await bus.add_stream("p", ["a.x", "a.y"], ack_wait_s=5.0)
        got_x, got_y = [], []
        sub_x = await bus.durable_subscribe("p", "gx", filter_subject="a.x")
        sub_y = await bus.durable_subscribe("p", "gy", filter_subject="a.y")
        for i in range(4):
            await bus.publish("a.x" if i % 2 == 0 else "a.y",
                              str(i).encode())
        for _ in range(2):
            mx = await sub_x.next(2.0)
            assert mx is not None and mx.subject == "a.x"
            got_x.append(mx)
            await bus.ack(mx)
            my = await sub_y.next(2.0)
            assert my is not None and my.subject == "a.y"
            got_y.append(my)
            await bus.ack(my)
        # each group's filter auto-acked the other's subjects: floors at 4
        stats = await bus.stream_stats()
        assert stats["p"]["groups"]["gx"]["ack_floor"] == 4
        assert stats["p"]["groups"]["gy"]["ack_floor"] == 4
        # two members of ONE group share (queue-group semantics)
        a = await bus.durable_subscribe("p", "shared")
        b = await bus.durable_subscribe("p", "shared")
        for i in range(6):
            await bus.publish("a.x", str(i).encode())
        seen_a = seen_b = 0
        for _ in range(60):
            ma = await a.next(0.05)
            if ma is not None:
                seen_a += 1
                await bus.ack(ma)
            mb = await b.next(0.05)
            if mb is not None:
                seen_b += 1
                await bus.ack(mb)
            if seen_a + seen_b >= 6:
                break
        assert seen_a + seen_b == 6
        assert seen_a and seen_b  # both replicas participated
        await bus.close()

    _run(scenario())


def test_inproc_durable_mismatched_filter_rejected():
    async def scenario():
        bus = InprocBus()
        await bus.add_stream("s", ["a.>"])
        await bus.durable_subscribe("s", "g", filter_subject="a.x")
        with pytest.raises(RuntimeError):
            await bus.durable_subscribe("s", "g", filter_subject="a.y")
        with pytest.raises(RuntimeError):
            await bus.durable_subscribe("nope", "g")
        await bus.close()

    _run(scenario())


def test_handler_raised_timeout_is_a_failure_not_a_deadline():
    """A TimeoutError raised BY the handler (bus request timeout, socket
    read timeout — on 3.11+ asyncio.TimeoutError IS builtin TimeoutError)
    must hit the retry/accounting path; only OUR wait_for cancellation is
    the deadline. Regression: the first cut matched on exception type and
    misclassified both."""

    async def scenario(timeout_s):
        bus = InprocBus()
        attempts = []
        done = asyncio.Event()

        async def raises_timeout(msg):
            attempts.append(1)
            if len(attempts) < 3:
                raise TimeoutError("downstream request timed out")
            done.set()

        svc = _OneShotService(bus, raises_timeout)
        svc.handler_timeout_s = timeout_s
        svc.handler_retries = 3
        svc.handler_backoff_base_s = 0.01
        svc.handler_backoff_max_s = 0.02
        before = metrics.get("bus.handler_timeout",
                             labels={"service": "oneshot", "subject": "t.x"})
        await svc.start()
        await bus.publish("t.x", b"x")
        await asyncio.wait_for(done.wait(), 5)
        assert len(attempts) == 3  # retried like any transient failure
        after = metrics.get("bus.handler_timeout",
                            labels={"service": "oneshot", "subject": "t.x"})
        assert after == before  # never accounted as a deadline timeout
        await svc.stop()
        await bus.close()

    _run(scenario(0.0))   # timeout disabled
    _run(scenario(5.0))   # timeout armed but not the one that fired


def test_inproc_durable_eviction_settles_for_groups():
    """Retention eviction must settle the evicted seq in every group: an
    unsettled hole below the floor would pin group.acked forever and
    freeze the ack floor (regression test for exactly that)."""
    import symbiont_tpu.bus.inproc as inproc_mod

    async def scenario():
        bus = InprocBus()
        await bus.add_stream("ev", ["e.x"], ack_wait_s=5.0)
        sub = await bus.durable_subscribe("ev", "g", maxsize=4)
        orig = inproc_mod.MAX_RETAINED
        inproc_mod.MAX_RETAINED = 4
        try:
            for i in range(10):  # 6 oldest evicted before any delivery
                await bus.publish("e.x", str(i).encode())
        finally:
            inproc_mod.MAX_RETAINED = orig
        got = []
        for _ in range(4):
            m = await sub.next(2.0)
            assert m is not None
            got.append(int(m.data))
            await bus.ack(m)
        assert got == [6, 7, 8, 9]  # the retained tail, in order
        stats = await bus.stream_stats()
        g = stats["ev"]["groups"]["g"]
        # the floor marched THROUGH the evicted seqs to the end: no
        # permanent hole, no unbounded acked set
        assert g["ack_floor"] == 10
        group = bus._streams["ev"].groups["g"]
        assert not group.acked and not group.state
        await bus.close()

    _run(scenario())


def test_inproc_durable_settled_messages_gc():
    async def scenario():
        bus = InprocBus()
        await bus.add_stream("gc", ["g.x"], ack_wait_s=5.0)
        sub = await bus.durable_subscribe("gc", "g")
        for i in range(10):
            await bus.publish("g.x", str(i).encode())
        for _ in range(10):
            m = await sub.next(2.0)
            await bus.ack(m)
        for _ in range(100):
            stats = await bus.stream_stats()
            if stats["gc"]["messages"] == 0:
                break
            await asyncio.sleep(0.01)
        # fully settled history is GC'd; the seq counter keeps advancing
        assert stats["gc"]["messages"] == 0
        assert stats["gc"]["last_seq"] == 10
        await bus.close()

    _run(scenario())
