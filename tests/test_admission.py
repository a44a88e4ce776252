"""Overload-protection plane (resilience/admission.py + the API edge +
service-base deadline drop): per-tenant quotas with 429/Retry-After,
weighted-fair scheduling under a hot tenant, edge + propagated deadlines,
the SLO shed ladder's hysteresis, capacity-aware generation admission,
SSE-disconnect generation cancellation, and /readyz vs /healthz.

Everything timing-sensitive runs on injectable clocks (TokenBucket,
DegradationLadder) or seeded fault plans — no sleep-and-hope assertions
for the admission arithmetic itself.
"""

import asyncio
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from symbiont_tpu import subjects
from symbiont_tpu.bus.inproc import InprocBus
from symbiont_tpu.config import (
    AdmissionConfig,
    ApiConfig,
    BusConfig,
    GraphStoreConfig,
    SymbiontConfig,
    TextGeneratorConfig,
    VectorStoreConfig,
)
from symbiont_tpu.resilience import admission as adm
from symbiont_tpu.resilience.admission import (
    AdmissionController,
    AdmissionReject,
    DegradationLadder,
    TokenBucket,
    WeightedFairQueue,
)
from symbiont_tpu.runner import SymbiontStack
from symbiont_tpu.services.api import ApiService
from symbiont_tpu.utils.telemetry import (
    DEADLINE_HEADER,
    TENANT_HEADER,
    child_headers,
    metrics,
)

PAGE = ("<html><body><main><p>Admission testing sentence one.</p>"
        "<p>Admission testing sentence two.</p></main></body></html>")


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
        self.stats["embed_calls"] += 1
        rng = np.random.default_rng(len(texts))
        return rng.standard_normal((len(texts), 16)).astype(np.float32)


def _http(port, method, path, body=None, headers=None, timeout=15):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json", **(headers or {})},
        method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read() or b"{}")


async def _wait_for(cond, timeout=20.0, interval=0.02):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if cond():
            return True
        await asyncio.sleep(interval)
    return cond()


# ------------------------------------------------------------- token bucket


def test_token_bucket_burst_and_refill():
    now = [0.0]
    b = TokenBucket(rate=2.0, burst=4.0, clock=lambda: now[0])
    assert [b.try_take() for _ in range(4)] == [True] * 4
    assert b.try_take() is False  # burst exhausted
    assert b.retry_after_s() == pytest.approx(0.5)  # 1 token / 2 per s
    now[0] = 0.5
    assert b.try_take() is True  # refilled exactly one
    assert b.try_take() is False
    now[0] = 100.0
    # refill caps at burst, never beyond
    assert [b.try_take() for _ in range(4)] == [True] * 4
    assert b.try_take() is False


def test_admission_controller_quota_exhaustion_and_recovery():
    """Satellite: quota exhaustion mid-burst → reject, then recovery after
    refill — and tenants are isolated (one tenant's burst never drains
    another's bucket)."""
    now = [0.0]
    ctl = AdmissionController(
        AdmissionConfig(search_rate=1.0, search_burst=2.0),
        clock=lambda: now[0])
    ctl.admit("search", "hot")
    ctl.admit("search", "hot")
    with pytest.raises(AdmissionReject) as ei:
        ctl.admit("search", "hot")
    assert ei.value.reason == "quota"
    assert ei.value.retry_after_s > 0
    ctl.admit("search", "calm")  # other tenant unaffected
    now[0] = 1.0
    ctl.admit("search", "hot")  # recovered after refill
    with pytest.raises(AdmissionReject):
        ctl.admit("search", "hot")


def test_tenant_universe_is_bounded():
    """Review regression: the tenant header is client-supplied — minting a
    fresh tenant per request must not buy a fresh full-burst bucket every
    time (quota bypass) nor grow per-tenant state without bound. Past
    max_tenants, new identities share the overflow tenant; operator-
    configured (weighted) tenants always keep their identity."""
    ctl = AdmissionController(AdmissionConfig(
        max_tenants=3, search_rate=1.0, search_burst=2.0,
        fair_weights="gold=4"))
    assert ctl.resolve_tenant("default") == "default"  # pre-seeded
    assert ctl.resolve_tenant("a") == "a"
    assert ctl.resolve_tenant("b") == "b"
    assert ctl.resolve_tenant("b") == "b"  # known stays known
    assert ctl.resolve_tenant("freshly-minted") == adm.OVERFLOW_TENANT
    assert ctl.resolve_tenant("gold") == "gold"  # operator-configured
    # the shared overflow bucket actually clamps: attacker tenants pool
    ctl.admit("search", ctl.resolve_tenant("atk-1"))
    ctl.admit("search", ctl.resolve_tenant("atk-2"))
    with pytest.raises(AdmissionReject):
        ctl.admit("search", ctl.resolve_tenant("atk-3"))
    assert len(ctl._seen_tenants) == 3  # no growth past the cap


# ------------------------------------------------------ weighted-fair queue


def test_fair_queue_one_hot_tenant_nine_light():
    """Satellite: fairness under one hot tenant + nine light ones. The hot
    tenant floods 30 requests; each light tenant submits one. With the
    stride scheduler every light tenant is served among the FIRST grants
    after the backlog forms — never behind the hot tenant's queue."""

    async def scenario():
        q = WeightedFairQueue(concurrency=1, max_queue=64)
        order = []

        async def worker(tenant):
            await q.acquire(tenant)
            order.append(tenant)
            await asyncio.sleep(0)  # hold the slot across one tick
            q.release(tenant)

        tasks = [asyncio.create_task(worker("hot")) for _ in range(30)]
        await asyncio.sleep(0)  # hot tenant's backlog forms first
        tasks += [asyncio.create_task(worker(f"light{i}"))
                  for i in range(9)]
        await asyncio.gather(*tasks)
        assert len(order) == 39
        # every light tenant served within the first 12 grants: vtimes
        # interleave 1:1, they can never sit behind the hot backlog
        first_12 = order[:12]
        assert all(f"light{i}" in first_12 for i in range(9)), order[:15]
        assert q.queued() == 0

    asyncio.run(scenario())


def test_fair_queue_weights_and_bounded_rejection():
    async def scenario():
        q = WeightedFairQueue(concurrency=1, max_queue=8,
                              weights={"gold": 3.0})
        order = []

        async def worker(tenant):
            await q.acquire(tenant)
            order.append(tenant)
            await asyncio.sleep(0)
            q.release(tenant)

        tasks = [asyncio.create_task(worker(t))
                 for t in ["gold", "free"] * 2 + ["gold", "gold"]]
        await asyncio.sleep(0)
        await asyncio.gather(*tasks)
        # weight 3 tenant gets ~3 grants per 1 of the weight-1 tenant
        assert order[:4].count("gold") >= 3, order

        # bounded: the third queued waiter for one tenant rejects
        q2 = WeightedFairQueue(concurrency=1, max_queue=2)

        async def worker2(tenant):
            await q2.acquire(tenant)
            order.append(tenant)
            await asyncio.sleep(0)
            q2.release(tenant)

        release_x = asyncio.Event()

        async def blocker_fn():
            await q2.acquire("x")
            await release_x.wait()  # pin the only slot deterministically
            q2.release("x")

        blocker = asyncio.create_task(blocker_fn())
        await asyncio.sleep(0)  # x holds the only slot
        held = [asyncio.create_task(worker2("y")) for _ in range(2)]
        await asyncio.sleep(0)  # both y waiters queued (queue full)
        with pytest.raises(AdmissionReject) as ei:
            await q2.acquire("y")
        assert ei.value.reason == "queue_full"
        release_x.set()
        await asyncio.gather(blocker, *held)
        assert q2.queued() == 0

    asyncio.run(scenario())


def test_fair_queue_cancelled_waiter_leaves_queue_usable():
    """Review regression: a queued waiter whose task is cancelled (client
    disconnect) must not leave an empty per-tenant deque mapped — that
    disabled the uncontended fast path forever, with no slot holder left
    to ever grant, deadlocking every later acquire."""

    async def scenario():
        q = WeightedFairQueue(concurrency=1, max_queue=8)
        release_a = asyncio.Event()

        async def holder():
            await q.acquire("a")
            await release_a.wait()
            q.release("a")

        h = asyncio.create_task(holder())
        await asyncio.sleep(0)  # a holds the only slot
        waiter = asyncio.create_task(q.acquire("b"))
        await asyncio.sleep(0)  # b queued
        waiter.cancel()
        with pytest.raises(asyncio.CancelledError):
            await waiter
        assert q.queued() == 0
        release_a.set()
        await h
        # all slots free, nobody waiting: this acquire must return
        # immediately (pre-fix: parked forever behind the stale deque)
        await asyncio.wait_for(q.acquire("c"), timeout=5.0)
        q.release("c")

    asyncio.run(scenario())


def test_fair_queue_uncontended_history_does_not_starve():
    """Review regression: fast-path grants must advance the global virtual
    clock too. A tenant active through a quiet period used to bank virtual
    lateness; once contention started, a fresh tenant (floored at the
    stale clock) monopolized every slot until it caught up — starving the
    previously well-behaved tenant."""

    async def scenario():
        q = WeightedFairQueue(concurrency=1, max_queue=64)
        # tenant a: 100 uncontended fast-path acquires
        for _ in range(100):
            await q.acquire("a")
            q.release("a")
        order = []
        release_x = asyncio.Event()

        async def holder():
            await q.acquire("x")
            await release_x.wait()
            q.release("x")

        async def worker(tenant):
            await q.acquire(tenant)
            order.append(tenant)
            await asyncio.sleep(0)
            q.release(tenant)

        h = asyncio.create_task(holder())
        await asyncio.sleep(0)  # x pins the slot so a backlog forms
        tasks = []
        for _ in range(4):  # interleave arrivals: a, b, a, b, ...
            tasks.append(asyncio.create_task(worker("a")))
            tasks.append(asyncio.create_task(worker("b")))
            await asyncio.sleep(0)
        release_x.set()
        await asyncio.gather(h, *tasks)
        # equal weights from equal footing: grants alternate — b must NOT
        # get all four slots before a's first (the pre-fix order)
        assert order[:4].count("a") == 2, order

    asyncio.run(scenario())


# ----------------------------------------------------------- shed ladder


def test_shed_ladder_hysteresis_no_flapping():
    """Satellite: an oscillating breach (breach, clear, breach, ...) must
    PARK the ladder, not flap it — escalation needs the dwell time, and
    stepping down needs consecutive clean passes AND the dwell."""
    now = [100.0]
    ladder = DegradationLadder(recovery_passes=3, hold_s=5.0,
                               clock=lambda: now[0])
    ladder.observe(True)
    assert ladder.level == 1
    # oscillate fast (1s per pass): WITHIN the dwell window nothing moves
    for i in range(4):
        now[0] += 1.0
        ladder.observe(i % 2 == 0)
        assert ladder.level == 1, (i, ladder.level)
    # a longer oscillation may still ESCALATE (the breach persists every
    # other pass — that is real pressure) but must never step DOWN: the
    # alternating clears can never reach recovery_passes in a row
    levels = []
    for i in range(10):
        now[0] += 1.0
        ladder.observe(i % 2 == 0)
        levels.append(ladder.level)
    assert all(b >= a for a, b in zip(levels, levels[1:])), levels
    assert ladder.level == 2  # parked at the top rung, no bounce
    assert ladder.shed_generation("low") == "degrade_search"
    assert ladder.shed_generation("normal") == "degrade_search"
    assert ladder.shed_generation("high") is None  # never ladder-shed
    assert ladder.search_degraded() and ladder.degrade_top_k(10) == 3
    # zero the clean-pass streak (the oscillation's last pass was clean)
    now[0] += 10.0
    ladder.observe(True)
    assert ladder.level == 2  # already at the top rung: parked
    # recovery: needs recovery_passes CONSECUTIVE clean passes (dwell is
    # amply served by now) — and only ever steps down one rung at a time
    now[0] += 10.0
    ladder.observe(False)
    ladder.observe(False)
    assert ladder.level == 2  # two clean passes < recovery_passes
    ladder.observe(False)
    assert ladder.level == 1  # third clean pass: one rung down
    # a breach RESETS the clean-pass streak (and the dwell blocks its
    # escalation — the level just holds)
    ladder.observe(True)
    assert ladder.level == 1
    now[0] += 10.0
    ladder.observe(False)
    ladder.observe(False)
    assert ladder.level == 1  # streak restarted after the breach
    ladder.observe(False)
    assert ladder.level == 0
    assert ladder.shed_generation("low") is None


def test_watchdog_pass_listener_drives_ladder():
    """The SloWatchdog → ladder wiring: breach passes escalate, clean
    passes (including no-new-samples passes) count toward recovery."""
    from symbiont_tpu.obs.watchdog import SloWatchdog
    from symbiont_tpu.utils.telemetry import Metrics

    reg = Metrics()
    wd = SloWatchdog({"probe.op": 5.0}, registry=reg)
    now = [0.0]
    ladder = DegradationLadder(recovery_passes=1, hold_s=0.0,
                               clock=lambda: now[0])
    wd.add_listener(ladder.on_slo_pass)
    reg.observe("span.probe.op.ms", 100.0)
    assert len(wd.evaluate()) == 1
    assert ladder.level == 1
    wd.thresholds["probe.op"] = 10000.0
    reg.observe("span.probe.op.ms", 1.0)
    wd.evaluate()
    assert ladder.level == 0


# ------------------------------------------------------- deadline helpers


def test_deadline_helpers_and_child_header_threading():
    clock = lambda: 1000.0  # noqa: E731 — seconds
    h = {DEADLINE_HEADER: adm.mint_deadline(500.0, None, clock=clock),
         TENANT_HEADER: "gold"}
    assert adm.parse_deadline_ms(h) == 1000_500.0
    assert not adm.expired(h, clock=clock)
    assert adm.expired(h, clock=lambda: 1001.0)
    assert adm.tenant_of(h) == "gold"
    assert adm.tenant_of({}) == "default"
    # a client deadline can only TIGHTEN the edge budget, never extend it
    tighter = adm.mint_deadline(500.0, {DEADLINE_HEADER: "1000100"},
                                clock=clock)
    assert tighter == "1000100"
    looser = adm.mint_deadline(500.0, {DEADLINE_HEADER: "9999999999"},
                               clock=clock)
    assert looser == str(int(1000.0 * 1000 + 500))
    # garbage is NO deadline (work must not become immortal or insta-dead)
    assert adm.parse_deadline_ms({DEADLINE_HEADER: "soon"}) is None
    # the PR 2 span-header threading carries the admission pair verbatim
    out = child_headers({"X-Trace-Id": "t", "X-Span-Id": "s",
                         DEADLINE_HEADER: "123", TENANT_HEADER: "acme"})
    assert out[DEADLINE_HEADER] == "123" and out[TENANT_HEADER] == "acme"
    assert out["X-Trace-Id"] == "t" and out["X-Span-Id"] == "s"


# --------------------------------------------------------- API edge (HTTP)


def _stack_config(tmp_path, **admission_kw):
    cfg = SymbiontConfig(
        vector_store=VectorStoreConfig(dim=16,
                                       data_dir=str(tmp_path / "vs"),
                                       shard_capacity=64),
        graph_store=GraphStoreConfig(data_dir=str(tmp_path / "gs")),
        text_generator=TextGeneratorConfig(markov_state_path=None),
        api=ApiConfig(host="127.0.0.1", port=0, fused_search=False),
        admission=AdmissionConfig(**admission_kw),
    )
    cfg.runner.services = ("perception,preprocessing,vector_memory,"
                           "knowledge_graph,text_generator,api")
    return cfg


def test_edge_deadline_already_expired_rejects_without_publish(tmp_path):
    """Satellite: a request arriving with an already-expired deadline is
    429'd at the edge — no bus publish, nothing downstream ever sees it."""

    async def scenario():
        bus = InprocBus()
        stack = SymbiontStack(_stack_config(tmp_path), bus=bus,
                              engine=_StubEngine(), fetcher=lambda u: PAGE)
        await stack.start()
        loop = asyncio.get_running_loop()
        port = stack.api.port
        seen = []
        sub = await bus.subscribe(subjects.TASKS_PERCEIVE_URL)

        async def spy():
            async for m in sub:
                seen.append(m)

        spy_task = asyncio.create_task(spy())
        try:
            status, headers, body = await loop.run_in_executor(
                None, lambda: _http(
                    port, "POST", "/api/submit-url",
                    {"url": "http://x/doc"},
                    {DEADLINE_HEADER: "1"}))  # epoch ms 1: long dead
            assert status == 429 and body["reason"] == "deadline"
            assert "Retry-After" in headers
            # generation and search refuse the same way
            for path, payload in (
                    ("/api/generate-text",
                     {"task_id": "t", "max_length": 4}),
                    ("/api/search/semantic",
                     {"query_text": "q", "top_k": 1})):
                status, headers, body = await loop.run_in_executor(
                    None, lambda p=path, b=payload: _http(
                        port, "POST", p, b, {DEADLINE_HEADER: "1"}))
                assert status == 429 and body["reason"] == "deadline"
            await asyncio.sleep(0.2)
            assert seen == []  # nothing was published
        finally:
            spy_task.cancel()
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


def test_quota_429_with_retry_after_then_recovery_over_http(tmp_path):
    """Satellite: quota exhaustion mid-burst answers 429 + Retry-After at
    the HTTP surface, and the SAME tenant recovers after the refill
    (injectable clock on the controller — no sleeps)."""

    async def scenario():
        now = [0.0]
        cfg = _stack_config(tmp_path)
        bus = InprocBus()
        stack = SymbiontStack(cfg, bus=bus, engine=_StubEngine(),
                              fetcher=lambda u: PAGE)
        await stack.start()
        # swap in a clock-injected controller (the runner built a real one)
        stack.api.admission = AdmissionController(
            AdmissionConfig(ingest_rate=1.0, ingest_burst=2.0),
            clock=lambda: now[0])
        loop = asyncio.get_running_loop()
        port = stack.api.port

        def submit(tenant):
            return _http(port, "POST", "/api/submit-url",
                         {"url": "http://x/doc"}, {TENANT_HEADER: tenant})

        try:
            for _ in range(2):
                status, _, _ = await loop.run_in_executor(
                    None, submit, "burst")
                assert status == 200
            status, headers, body = await loop.run_in_executor(
                None, submit, "burst")
            assert status == 429 and body["reason"] == "quota"
            assert int(headers["Retry-After"]) >= 1
            # another tenant is untouched by the hot tenant's exhaustion
            status, _, _ = await loop.run_in_executor(None, submit, "calm")
            assert status == 200
            now[0] = 2.0  # refill
            status, _, _ = await loop.run_in_executor(None, submit, "burst")
            assert status == 200
            assert metrics.get("admission.throttled",
                               labels={"class": "ingest",
                                       "tenant": "burst"}) >= 1
        finally:
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


def test_readyz_gates_on_stack_readiness(tmp_path):
    """Satellite: /healthz is liveness (200 as soon as the socket is up);
    /readyz is readiness — 503 while deferred, 200 after mark_ready. The
    runner wires defer + mark around engine placement."""

    async def scenario():
        api = ApiService(InprocBus(), ApiConfig(host="127.0.0.1", port=0),
                         BusConfig(), defer_ready=True)
        await api.start()
        loop = asyncio.get_running_loop()
        try:
            status, _, body = await loop.run_in_executor(
                None, _http, api.port, "GET", "/healthz")
            assert status == 200 and body["status"] == "ok"
            status, _, body = await loop.run_in_executor(
                None, _http, api.port, "GET", "/readyz")
            assert status == 503 and body["status"] == "starting"
            # review regression: the open-but-cold port must refuse
            # data-path work honestly (503 + Retry-After) — a 200 would
            # publish into a bus with no consumers yet: silent loss
            status, hdrs, body = await loop.run_in_executor(
                None, lambda: _http(api.port, "POST", "/api/submit-url",
                                    {"url": "http://x/warm"}))
            assert status == 503 and "Retry-After" in hdrs
            assert metrics.get("api.not_ready_rejects") >= 1
            api.mark_ready()
            status, _, body = await loop.run_in_executor(
                None, _http, api.port, "GET", "/readyz")
            assert status == 200 and body["status"] == "ready"
            status, _, _ = await loop.run_in_executor(
                None, lambda: _http(api.port, "POST", "/api/submit-url",
                                    {"url": "http://x/warm"}))
            assert status == 200  # same request admitted once ready
        finally:
            await api.stop()

        # the full runner stack arrives ready (placement done in start())
        bus = InprocBus()
        stack = SymbiontStack(_stack_config(tmp_path), bus=bus,
                              engine=_StubEngine(), fetcher=lambda u: PAGE)
        await stack.start()
        try:
            status, _, body = await loop.run_in_executor(
                None, _http, stack.api.port, "GET", "/readyz")
            assert status == 200
        finally:
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


def test_generation_capacity_shed_consults_lm():
    """Capacity-aware generation admission: the edge consults the LM's
    can_admit (KV-row occupancy) BEFORE accepting a stream — at capacity
    the answer is 429/kv_capacity, and admission.shed counts it."""

    async def scenario():
        full = [True]
        api = ApiService(InprocBus(), ApiConfig(host="127.0.0.1", port=0),
                         BusConfig(), gen_capacity=lambda: not full[0])
        await api.start()
        loop = asyncio.get_running_loop()
        try:
            def gen():
                return _http(api.port, "POST", "/api/generate-text",
                             {"task_id": "cap", "max_length": 4},
                             {TENANT_HEADER: "t"})

            status, headers, body = await loop.run_in_executor(None, gen)
            assert status == 429 and body["reason"] == "kv_capacity"
            assert "Retry-After" in headers
            assert metrics.get("admission.shed",
                               labels={"reason": "kv_capacity",
                                       "tenant": "t"}) >= 1
            full[0] = False
            status, _, _ = await loop.run_in_executor(None, gen)
            assert status == 200
        finally:
            await api.stop()

    asyncio.run(scenario())


def test_lm_can_admit_counts_allocated_rows():
    """LmEngine.can_admit against real sessions: allocated KV rows gate
    admission, and a finished session releases its rows."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from symbiont_tpu.config import LmConfig
    from symbiont_tpu.engine.lm import LmEngine

    lm = LmEngine(LmConfig(enabled=True, hidden_size=32, num_layers=1,
                           num_heads=2, intermediate_size=64,
                           max_positions=64, dtype="float32",
                           prompt_buckets=[8], new_token_buckets=[8],
                           stream_chunk=4, session_min_rows=2))
    assert lm.can_admit(1, 0)  # cap 0 = unbounded
    assert lm.kv_rows_allocated() == 0
    sess = lm.start_session(["a", "b"], [8, 8], temperature=0.0)
    assert lm.kv_rows_allocated() == sess.bb
    assert lm.can_admit(1, sess.bb + 1)
    assert not lm.can_admit(1, sess.bb)
    while not sess.done():
        sess.step()
    assert lm.kv_rows_allocated() == 0
    assert lm.can_admit(1, sess.bb)


def test_lm_can_admit_paged_quotes_pages_not_rows():
    """The 429-vs-admit boundary under kv_layout=paged: can_admit answers
    from free-page accounting (pool free + evictable − reserved by live
    rows), not dense row capacity — and a radix-hit prompt, which needs
    only its post-fork fresh pages, is admitted where a cold prompt of
    the same shape is refused."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from symbiont_tpu.config import LmConfig
    from symbiont_tpu.engine.lm import LmEngine

    def mk(**kw):
        return LmEngine(LmConfig(
            enabled=True, hidden_size=32, num_layers=1, num_heads=2,
            intermediate_size=64, max_positions=256, dtype="float32",
            prompt_buckets=[16], new_token_buckets=[32], stream_chunk=8,
            session_min_rows=1, gen_max_batch=1, kv_layout="paged",
            kv_page_tokens=16, temperature=0.0, **kw))

    # pool sized for ONE session (3 blocks/row: 16 prompt + 32 decode
    # tokens at 16/page): a second concurrent session must 429 even
    # though a dense engine would have row capacity for it
    lm = mk(kv_pool_pages=5, kv_radix=False)
    assert lm.can_admit(1, 0)
    sess = lm.start_session(["hold the pool"], [32], temperature=0.0)
    assert not lm.can_admit(1, 0)
    while not sess.done():
        sess.step()
    assert lm.can_admit(1, 0)  # pages returned → admissible again

    # radix deduction: same boundary, but a warm prompt's shared pages
    # don't count against the quote
    lm2 = mk(kv_pool_pages=6)
    sess2 = lm2.start_session(["warm this prompt"], [32], temperature=0.0)
    while not sess2.done():
        sess2.step()
    held = lm2.pool.alloc(3)  # leave 1 free + 1 retained
    assert lm2.can_admit(1, 0, prompts=["warm this prompt"],
                         max_new_tokens=[32])
    assert not lm2.can_admit(1, 0, prompts=["cold prompt here"],
                             max_new_tokens=[32])
    for pid in held:
        lm2.pool.release(pid)


# -------------------------------------------- deadline propagation (chaos)


def test_expired_deadline_dropped_at_every_downstream_service(tmp_path):
    """Acceptance: an expired message is dropped at EVERY downstream
    service — handler never invoked, no retry, no DLQ. The deadline is
    minted at the edge (valid there), and a seeded fault DELAYS the
    perception handler past it, so everything downstream receives
    already-expired work through the real child_headers threading."""
    from symbiont_tpu.resilience.faults import FaultPlan, FaultRule

    plan = FaultPlan(seed=21, rules=[
        FaultRule(seam="handler", kind="delay", delay_s=0.7,
                  match="perception:tasks.perceive.url", times=1)])

    async def scenario():
        cfg = _stack_config(tmp_path,
                            deadline_ingest_ms=300.0)  # expires mid-scrape
        cfg.bus.durable = True
        cfg.bus.durable_ack_wait_s = 0.2
        engine = _StubEngine()
        bus = InprocBus()
        stack = SymbiontStack(cfg, bus=bus, engine=engine,
                              fetcher=lambda u: PAGE)
        await stack.start()
        loop = asyncio.get_running_loop()
        port = stack.api.port
        base_expired = metrics.get("admission.expired",
                                   labels={"service": "preprocessing",
                                           "subject":
                                           "data.raw_text.discovered"})
        try:
            with plan.activate():
                status, _, _ = await loop.run_in_executor(
                    None, lambda: _http(port, "POST", "/api/submit-url",
                                        {"url": "http://x/doc"}))
                assert status == 200  # valid at the edge: accepted
                # perception's delayed handler publishes AFTER the deadline
                ok = await _wait_for(lambda: metrics.get(
                    "admission.expired",
                    labels={"service": "preprocessing",
                            "subject": "data.raw_text.discovered"})
                    > base_expired, timeout=10.0)
            assert ok, "preprocessing never counted the expired drop"
            await asyncio.sleep(0.6)  # would-be redeliveries / retries
            # the handler body NEVER ran: no embed, nothing stored
            assert engine.stats["embed_calls"] == 0
            assert stack.vector_store.count() == 0
            # ACKED, not retried: durable redelivery never fired for it,
            # and it never landed in the DLQ as poison
            assert len(bus.dlq) == 0
            assert metrics.get("bus.failed",
                               labels={"service": "preprocessing",
                                       "subject":
                                       "data.raw_text.discovered"}) == 0
        finally:
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


def test_fresh_deadline_flows_end_to_end(tmp_path):
    """Control for the drop test: the same stack with a roomy deadline
    ingests normally — the deadline machinery is inert for live work."""

    async def scenario():
        cfg = _stack_config(tmp_path, deadline_ingest_ms=30000.0)
        cfg.bus.durable = True
        bus = InprocBus()
        stack = SymbiontStack(cfg, bus=bus, engine=_StubEngine(),
                              fetcher=lambda u: PAGE)
        await stack.start()
        loop = asyncio.get_running_loop()
        try:
            status, _, _ = await loop.run_in_executor(
                None, lambda: _http(stack.api.port, "POST",
                                    "/api/submit-url",
                                    {"url": "http://x/doc"}))
            assert status == 200
            assert await _wait_for(lambda: stack.vector_store.count() >= 2)
        finally:
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


# ------------------------------------------- SSE disconnect cancellation


def test_cancel_tag_frees_rows_and_kv_gauges_return_to_baseline():
    """Satellite (deterministic half): cancelling a session row frees it
    immediately — capacity returns, and the lm.kv_* occupancy gauges read
    baseline once every row is cancelled, without decoding to budget."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from symbiont_tpu.config import LmConfig
    from symbiont_tpu.engine.lm import LmEngine

    lm = LmEngine(LmConfig(enabled=True, hidden_size=32, num_layers=1,
                           num_heads=2, intermediate_size=64,
                           max_positions=64, dtype="float32",
                           prompt_buckets=[8], new_token_buckets=[8],
                           stream_chunk=4, session_min_rows=2))
    labels = {"service": "lm", "kv_dtype": "float32"}
    sess = lm.start_session(["a", "b"], [8, 8], temperature=0.0)
    tags = [r.tag for r in sess.rows if r is not None]
    assert metrics.gauge_get("lm.kv_rows_active", labels=labels) == 2
    assert metrics.gauge_get("lm.kv_rows_allocated",
                             labels=labels) == sess.bb
    assert sess.cancel_tag(tags[0])
    assert metrics.gauge_get("lm.kv_rows_active", labels=labels) == 1
    assert sess.capacity() >= 1  # the slot is admissible again
    assert sess.cancel_tag(tags[1])
    assert sess.done()
    # every gauge back to baseline without a single further decode step
    assert metrics.gauge_get("lm.kv_rows_active", labels=labels) == 0
    assert metrics.gauge_get("lm.kv_rows_allocated", labels=labels) == 0
    assert not sess.cancel_tag(tags[0])  # idempotent on a dead tag


def test_sse_disconnect_cancels_stream_and_skips_final(tmp_path):
    """Satellite (end-to-end half): an SSE client following its task
    disconnects mid-stream → the gateway publishes
    tasks.generation.cancel → the text generator closes the decode stream
    early and publishes NO final event; the kv gauges stay at baseline
    after the abort."""
    pytest.importorskip("jax")
    from symbiont_tpu.config import LmConfig
    from symbiont_tpu.services.text_generator import TextGeneratorService

    cfg = SymbiontConfig(
        vector_store=VectorStoreConfig(dim=16,
                                       data_dir=str(tmp_path / "vs"),
                                       shard_capacity=64),
        graph_store=GraphStoreConfig(data_dir=str(tmp_path / "gs")),
        text_generator=TextGeneratorConfig(markov_state_path=None),
        api=ApiConfig(host="127.0.0.1", port=0, sse_keepalive_s=0.3),
        # heavy enough that a 256-token decode spans many chunk
        # boundaries of real wall time — the cancel must land mid-flight
        lm=LmConfig(enabled=True, hidden_size=256, num_layers=2,
                    num_heads=4, intermediate_size=512, max_positions=512,
                    dtype="float32", prompt_buckets=[16],
                    new_token_buckets=[256], stream_chunk=8,
                    gen_flush_deadline_ms=5.0, temperature=0.0),
    )
    cfg.runner.services = "text_generator,api"

    async def scenario():
        bus = InprocBus()
        stack = SymbiontStack(cfg, bus=bus)
        await stack.start()
        loop = asyncio.get_running_loop()
        port = stack.api.port
        # "mid-flight" made certain: the decode is held after its first
        # delta until the cancel has been counted. Left to itself the
        # 256-token decode has to outlast the gateway's discovery of the
        # closed socket (two writes to it: 0.3-0.6 s of keep-alives), and
        # beside busy test workers it does not always — the stream then
        # completes, and no cancel is due or sent
        tg = next(s for s in stack.services
                  if isinstance(s, TextGeneratorService))
        decode, cancel_landed = tg.lm_stream, threading.Event()

        def held_after_first_delta(*a, **kw):
            for i, delta in enumerate(decode(*a, **kw)):
                yield delta
                if i == 0:
                    cancel_landed.wait(60)

        tg.lm_stream = held_after_first_delta
        finals, closed = [], []
        sub = await bus.subscribe(subjects.EVENTS_TEXT_GENERATED)
        partials = await bus.subscribe(
            subjects.EVENTS_TEXT_GENERATED_PARTIAL)

        async def collect():
            async for m in sub:
                finals.append(json.loads(m.data))

        async def collect_partials():
            async for m in partials:
                chunk = json.loads(m.data)
                if chunk["original_task_id"] == "cancel-me" and chunk["done"]:
                    closed.append(chunk)

        collectors = [asyncio.create_task(collect()),
                      asyncio.create_task(collect_partials())]
        try:
            # SSE client follows ITS task
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            writer.write(b"GET /api/events?task_id=cancel-me HTTP/1.1\r\n"
                         b"Host: x\r\n\r\n")
            await writer.drain()
            status, _, _ = await loop.run_in_executor(
                None, lambda: _http(port, "POST", "/api/generate-text",
                                    {"task_id": "cancel-me",
                                     "prompt": "tensor", "max_length": 256,
                                     "stream": True}))
            assert status == 200
            # wait for the FIRST delta (decode demonstrably in flight)...
            got_delta = False
            deadline = loop.time() + 120
            while loop.time() < deadline and not got_delta:
                line = await asyncio.wait_for(reader.readline(), 120)
                got_delta = line.startswith(b"data: ")
            assert got_delta
            # ...then vanish mid-generation
            writer.close()
            ok = await _wait_for(
                lambda: metrics.get("text_generator.cancelled") >= 1,
                timeout=60.0)
            cancel_landed.set()
            assert ok, "cancel never reached the text generator"
            assert metrics.get("api.sse_gen_cancels") >= 1
            # the generator closes a stream it abandons with a terminal
            # chunk (done=True), after the deltas already in flight: wait
            # for THAT, not for a fixed drain a loaded machine outlasts.
            # 60 s: the idle case takes < 1 s.
            assert await _wait_for(lambda: closed, timeout=60.0), \
                "the cancelled stream was never closed"
            chunks = metrics.get("text_generator.stream_chunks")
            # decode actually STOPPED (no further chunks) and no final
            # message was published for the cancelled task: a wait that
            # load can only lengthen in the passing direction
            assert not await _wait_for(
                lambda: metrics.get("text_generator.stream_chunks") != chunks
                or any(f["original_task_id"] == "cancel-me" for f in finals),
                timeout=1.0)
            assert len(closed) == 1
            # stream path holds no session rows: gauges at baseline
            labels = {"service": "lm", "kv_dtype": "float32"}
            assert metrics.gauge_get("lm.kv_rows_active",
                                     labels=labels) == 0
        finally:
            for c in collectors:
                c.cancel()
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


def test_cancel_arriving_before_generate_is_honored():
    """Review regression: under overload a generate task can sit bus-queued
    while its SSE reader vanishes — the cancel then arrives BEFORE
    _handle_generate registers the task. It must tombstone the id so the
    decode aborts on arrival instead of running its full budget (and no
    final event is published for a reader that is already gone)."""
    from symbiont_tpu.bus.core import Msg
    from symbiont_tpu.schema import GenerateTextTask, to_json_bytes
    from symbiont_tpu.services.text_generator import TextGeneratorService

    async def scenario():
        bus = InprocBus()
        svc = TextGeneratorService(bus, train_on_ingest=False,
                                   state_path=None)
        finals = []
        sub = await bus.subscribe(subjects.EVENTS_TEXT_GENERATED)

        async def collect():
            async for m in sub:
                finals.append(json.loads(m.data))

        collector = asyncio.create_task(collect())
        before = metrics.get("text_generator.cancelled")
        try:
            await svc._handle_cancel(Msg(
                subjects.TASKS_GENERATION_CANCEL,
                json.dumps({"task_id": "race-1"}).encode()))
            task = GenerateTextTask(task_id="race-1", prompt="hello",
                                    max_length=32)
            await svc._handle_generate(Msg(
                subjects.TASKS_GENERATION_TEXT, to_json_bytes(task)))
            assert metrics.get("text_generator.cancelled") == before + 1
            assert "race-1" not in svc._cancelled_early  # consumed
            await asyncio.sleep(0.1)
            assert finals == []  # no final event for the vanished reader
            # an UNcancelled task on the same service still publishes
            task2 = GenerateTextTask(task_id="live-1", prompt="hello",
                                     max_length=16)
            await svc._handle_generate(Msg(
                subjects.TASKS_GENERATION_TEXT, to_json_bytes(task2)))
            assert await _wait_for(
                lambda: any(f["original_task_id"] == "live-1"
                            for f in finals))
            # review regression: a LATE cancel (task already finished —
            # e.g. the SSE reader closed right as the final raced out)
            # must not tombstone the id: a resubmission reusing it would
            # be silently cancelled before decoding
            await svc._handle_cancel(Msg(
                subjects.TASKS_GENERATION_CANCEL,
                json.dumps({"task_id": "live-1"}).encode()))
            assert "live-1" not in svc._cancelled_early
            finals.clear()
            await svc._handle_generate(Msg(
                subjects.TASKS_GENERATION_TEXT, to_json_bytes(task2)))
            assert await _wait_for(
                lambda: any(f["original_task_id"] == "live-1"
                            for f in finals))
        finally:
            collector.cancel()
            await bus.close()

    asyncio.run(scenario())


def test_sse_disconnect_of_unsubmitted_task_publishes_no_cancel():
    """Review regression: a reader that pre-connects /api/events with a
    client-minted task id and drops BEFORE ever POSTing the generation
    must not publish a cancel — the tombstone would silently kill the
    legitimate submission that follows."""

    async def scenario():
        bus = InprocBus()
        api = ApiService(bus, ApiConfig(host="127.0.0.1", port=0,
                                        sse_keepalive_s=0.2), BusConfig())
        await api.start()
        cancels = []

        async def watch():
            sub = await bus.subscribe(subjects.TASKS_GENERATION_CANCEL)
            async for m in sub:
                cancels.append(json.loads(m.data))

        watcher = asyncio.create_task(watch())
        before = metrics.get("api.sse_gen_cancels")
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           api.port)
            writer.write(b"GET /api/events?task_id=never-submitted "
                         b"HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            await reader.readline()  # status line: connection is live
            writer.close()
            await asyncio.sleep(0.5)  # teardown ran (keepalive tick)
            assert cancels == []
            assert metrics.get("api.sse_gen_cancels") == before
        finally:
            watcher.cancel()
            await api.stop()
            await bus.close()

    asyncio.run(scenario())


def test_graph_search_rides_fair_queue_and_degraded_rung():
    """Review regression: /api/search/graph shares the 'search' admission
    class — it must also ride the weighted-fair concurrency queue and the
    ladder's degraded top-k clamp, or a graph-search storm sidesteps both
    protections semantic search enforces."""

    async def scenario():
        bus = InprocBus()
        ctl = AdmissionController(AdmissionConfig(
            search_rate=1000, search_burst=1000, search_concurrency=1))
        ladder = DegradationLadder(clock=lambda: 100.0)
        ladder.level = 2  # degraded search rung
        api = ApiService(bus, ApiConfig(host="127.0.0.1", port=0),
                         BusConfig(), admission=ctl, ladder=ladder)
        await api.start()
        seen = []

        async def answer():
            sub = await bus.subscribe(subjects.TASKS_SEARCH_GRAPH_REQUEST)
            async for m in sub:
                seen.append(json.loads(m.data))
                await bus.publish(m.reply, json.dumps(
                    {"results": [], "error_message": None}).encode())

        answering = asyncio.create_task(answer())
        loop = asyncio.get_running_loop()
        try:
            acquires = []
            real_acquire = ctl.fair_queue.acquire

            async def counting_acquire(tenant):
                acquires.append(tenant)
                await real_acquire(tenant)

            ctl.fair_queue.acquire = counting_acquire
            status, _, body = await loop.run_in_executor(
                None, lambda: _http(api.port, "POST", "/api/search/graph",
                                    {"query_text": "abc", "top_k": 50},
                                    {TENANT_HEADER: "g"}))
            assert status == 200
            assert acquires == ["g"]  # rode the fair queue
            assert ctl.fair_queue.queued() == 0
            assert ctl.fair_queue._free == 1  # and released the slot
            # rung 2 clamped the requested top_k before the bus hop
            assert seen and seen[0]["top_k"] == ladder.degraded_top_k
            assert metrics.get("admission.degraded",
                               labels={"what": "search",
                                       "tenant": "g"}) >= 1
        finally:
            answering.cancel()
            await api.stop()
            await bus.close()

    asyncio.run(scenario())


# ------------------------------------------------- graph-augmented search


def test_graph_search_end_to_end(tmp_path):
    """Satellite: the knowledge-graph limb as a live scenario — ingest
    builds the graph (entity extraction → graph upsert), then
    POST /api/search/graph answers token-overlap hits with snippets."""

    async def scenario():
        bus = InprocBus()
        stack = SymbiontStack(_stack_config(tmp_path), bus=bus,
                              engine=_StubEngine(), fetcher=lambda u: PAGE)
        await stack.start()
        loop = asyncio.get_running_loop()
        port = stack.api.port
        try:
            status, _, _ = await loop.run_in_executor(
                None, lambda: _http(port, "POST", "/api/submit-url",
                                    {"url": "http://x/doc"}))
            assert status == 200
            assert await _wait_for(
                lambda: stack.graph_store.counts()["Document"] >= 1)
            status, _, body = await loop.run_in_executor(
                None, lambda: _http(port, "POST", "/api/search/graph",
                                    {"query_text":
                                     "admission TESTING sentence",
                                     "top_k": 3}))
            assert status == 200 and body["error_message"] is None
            assert len(body["results"]) == 1
            hit = body["results"][0]
            assert hit["match_count"] == 3  # case-insensitive overlap
            assert "admission" in hit["matched_tokens"]
            assert "Admission testing sentence one." in hit["snippet"]
            # no-overlap query: clean empty result, not an error
            status, _, body = await loop.run_in_executor(
                None, lambda: _http(port, "POST", "/api/search/graph",
                                    {"query_text": "zzz qqq", "top_k": 3}))
            assert status == 200 and body["results"] == []
            # empty query: 400 at the edge
            status, _, body = await loop.run_in_executor(
                None, lambda: _http(port, "POST", "/api/search/graph",
                                    {"query_text": " ", "top_k": 3}))
            assert status == 400
        finally:
            await stack.stop()
            await bus.close()

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# Engine-plane tenant fairness (PR 10): the batcher's per-tenant lanes must
# uphold the fairness guarantee WITHOUT any edge admission in front — the
# exact scenario where a replicated/bypassed/restarted gateway would
# otherwise re-create hot-tenant starvation at the device queue.
# ---------------------------------------------------------------------------


def _jain(xs):
    xs = [float(x) for x in xs]
    ssq = sum(x * x for x in xs)
    return 0.0 if not ssq else (sum(xs) ** 2) / (len(xs) * ssq)


class _SlowStubEngine:
    """Duck-typed embed engine whose forward is slow enough that a backlog
    forms — chunk composition (not engine speed) decides who gets served."""

    class _ModelCfg:
        hidden_size = 8

    def __init__(self, delay_s=0.005):
        from symbiont_tpu.config import EngineConfig

        self.config = EngineConfig(embedding_dim=8, max_batch=4,
                                   flush_deadline_ms=1.0)
        self.model_cfg = self._ModelCfg()
        self.delay_s = delay_s
        self.served = []  # flush order, one entry per text

    def embed_texts(self, texts):
        import time as _t

        _t.sleep(self.delay_s)
        self.served.extend(texts)
        return np.zeros((len(texts), 8), np.float32)


def test_batcher_fairness_with_edge_admission_disabled():
    """One ~10x hot tenant floods the micro-batcher DIRECTLY (no edge, no
    quotas, no fair queue): per-tenant admitted throughput across the
    backlog window must still be fair (Jain >= 0.8 over completion of the
    normals' work), because TenantLanes interleaves lanes stride-fair
    instead of FIFO-serving the hot tenant's head start."""
    from symbiont_tpu.engine.batcher import MicroBatcher

    engine = _SlowStubEngine()
    normals = [f"t{i}" for i in range(4)]

    async def scenario():
        b = MicroBatcher(engine)
        await b.start()
        try:
            # the hot tenant gets its whole flood queued FIRST — under the
            # old FIFO every normal tenant would wait out all 60 items
            hot = [asyncio.ensure_future(
                b.embed([f"hot-{i}"], tenant="hot")) for i in range(60)]
            waits = {}
            t0 = asyncio.get_running_loop().time()

            async def timed(tenant, i):
                await b.embed([f"{tenant}-{i}"], tenant=tenant)
                waits.setdefault(tenant, []).append(
                    asyncio.get_running_loop().time() - t0)

            normal_futs = [asyncio.ensure_future(timed(t, i))
                           for t in normals for i in range(6)]
            await asyncio.gather(*normal_futs)
            # every normal tenant finished its 6 items while the hot flood
            # was still draining — the FIFO order would have served all 60
            # hot items first
            remaining_hot = sum(1 for f in hot if not f.done())
            assert remaining_hot > 0, (
                "hot flood fully drained before the normals finished — "
                "the lanes did not interleave")
            admitted = {t: len(waits[t]) for t in normals}
            admitted["hot"] = 60 - remaining_hot
            jain = _jain(admitted.values())
            assert jain >= 0.8, (jain, admitted)
            await asyncio.gather(*hot)
        finally:
            await b.close()

    asyncio.run(scenario())


def test_tenant_lanes_stride_order_and_requeue():
    from symbiont_tpu.engine.batcher import TenantLanes

    class Item:
        def __init__(self, tag, tenant):
            self.tag, self.tenant = tag, tenant
            self.future = None

    lanes = TenantLanes(kind="test")
    for i in range(4):
        lanes.append(Item(f"a{i}", "a"))
    for i in range(2):
        lanes.append(Item(f"b{i}", "b"))
    # stride order with equal weights: strict interleave while both lanes
    # hold items, per-lane FIFO always
    order = [it.tag for it in lanes.fair_order()]
    assert order == ["a0", "b0", "a1", "b1", "a2", "a3"]
    # iteration (the duck-typed deque surface) matches the fair order and
    # consumes nothing
    assert [it.tag for it in lanes] == order
    assert len(lanes) == 6
    # popleft serves exactly that order; peek always previews it
    assert lanes.peek().tag == "a0"
    got = [lanes.popleft().tag for _ in range(3)]
    assert got == ["a0", "b0", "a1"]
    # requeue_front returns items to their OWN lanes, ahead, in order
    back = [it for it in lanes.fair_order()]
    lanes.requeue_front([i for i in back if i.tenant == "a"][:1])
    assert lanes.peek().tenant in ("a", "b")
    assert len(lanes) == 4


def test_tenant_lanes_bounded_reject_and_overflow_fold():
    from symbiont_tpu.engine.batcher import TenantLanes
    from symbiont_tpu.resilience.admission import (
        OVERFLOW_TENANT,
        AdmissionReject,
    )

    class Item:
        def __init__(self, tenant):
            self.tenant = tenant
            self.future = None

    lanes = TenantLanes(kind="test", max_per_tenant=2, max_lanes=3)
    lanes.append(Item("a"))
    lanes.append(Item("a"))
    with pytest.raises(AdmissionReject) as ei:
        lanes.append(Item("a"))  # lane full -> bounded, shed
    assert ei.value.reason == "engine_lane_full"
    # the identity bound is CUMULATIVE (resolve_tenant stance, and the
    # default lane is pre-seeded like the edge's): max_lanes=3 means
    # {default, a, b} — every identity AFTER that shares the overflow
    # lane forever, so cycling fresh tenant names grows no clock state
    # and no gauge label cardinality
    lanes.append(Item("b"))
    assert lanes._lane_key(Item("c")) == OVERFLOW_TENANT
    lanes.append(Item("c"))
    lanes.append(Item("fresh-1"))
    assert lanes._lane_key(Item("fresh-2")) == OVERFLOW_TENANT
    # overflow lane is bounded too
    with pytest.raises(AdmissionReject):
        lanes.append(Item("fresh-2"))
    # ...and DRAINING everything retires the clock debt: a drained lane's
    # entry is forgotten (≤ one grant past the floor), so the vtime book
    # tracks live lanes, not every identity ever seen
    while len(lanes):
        lanes.popleft()
    assert lanes._clock._vtime == {}


def test_tenant_depth_gauge_tracks_lanes():
    from symbiont_tpu.engine.batcher import TenantLanes
    from symbiont_tpu.utils.telemetry import metrics

    class Item:
        def __init__(self, tenant):
            self.tenant = tenant
            self.future = None

    lanes = TenantLanes(kind="gaugetest")
    lanes.append(Item("gold"))
    lanes.append(Item("gold"))
    assert metrics.gauge_get("batcher.tenant_depth",
                             labels={"batcher": "gaugetest",
                                     "tenant": "gold"}) == 2
    lanes.popleft()
    assert metrics.gauge_get("batcher.tenant_depth",
                             labels={"batcher": "gaugetest",
                                     "tenant": "gold"}) == 1


def test_gen_batcher_threads_tenant_and_stays_bounded():
    """GenBatcher lanes: tenant kwarg lands items in their lanes and the
    gen lane bound rejects with the typed AdmissionReject."""
    from types import SimpleNamespace

    from symbiont_tpu.engine.batcher import GenBatcher

    class FakeLm:
        config = SimpleNamespace(gen_max_batch=8, gen_flush_deadline_ms=1.0,
                                 new_token_buckets=[16], temperature=1.0,
                                 top_k=0, gen_tenant_lane_depth=2)

    async def scenario():
        b = GenBatcher(FakeLm())  # _run not started: queue-only test
        futs = [asyncio.ensure_future(
            b.generate("p", 4, tenant="flood")) for _ in range(2)]
        await asyncio.sleep(0)  # let the submits land
        with pytest.raises(AdmissionReject):
            await b.generate("p", 4, tenant="flood")
        assert len(b._queue) == 2
        for f in futs:
            f.cancel()

    asyncio.run(scenario())


def test_stride_clock_shared_between_edge_and_lanes():
    """The edge fair queue and the batcher lanes run the SAME scheduler
    class (StrideClock) — weight semantics cannot drift between planes."""
    from symbiont_tpu.engine.batcher import TenantLanes
    from symbiont_tpu.resilience.admission import StrideClock

    clock = StrideClock({"gold": 2.0})
    # gold (weight 2) gets two grants per free grant
    grants = []
    for _ in range(6):
        t = clock.pick(["gold", "free"])
        grants.append(t)
        clock.charge(t)
    assert grants.count("gold") == 4 and grants.count("free") == 2
    lanes = TenantLanes(kind="wtest", weights={"gold": 2.0})
    assert lanes._clock.weights == {"gold": 2.0}
