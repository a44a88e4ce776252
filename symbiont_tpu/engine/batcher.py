"""Async micro-batching queues in front of the engine.

SURVEY.md §7 hard-part #1/#4: the bus delivers one document/query at a time,
the TPU wants large uniform batches, and the interactive search path (p50
latency) must not wait behind bulk ingest. Two policies over one engine:

- `MicroBatcher` (embedding) — aggregates submissions; flushes when
  `max_batch` items are queued or the oldest item has waited
  `flush_deadline_ms`. Queries ride in the next flush (small batch, low
  latency); bulk ingest fills batches.
- `GenBatcher` (generation) — same loop; concurrent tasks.generation.text
  requests within the flush window decode as ONE batched gpt.generate call
  instead of serializing on the engine lock, sharing every weight read of
  the decode loop. Requests group by new-token bucket.

Both share one flush loop (`_BatcherBase`): wake on submission, wait up to
the deadline for the batch to fill, then flush AT MOST max_batch items —
a backlog drains in max_batch-sized chunks, never as one giant device call.

The reference's model — spawn a task per message, all contending on one model
(reference: services/preprocessing_service/src/main.rs:376,425) — is exactly
what this replaces (SURVEY.md §5.2 hazard).
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from symbiont_tpu.engine.engine import TpuEngine
from symbiont_tpu.obs.engine_timeline import engine_timeline
from symbiont_tpu.obs.usage import usage
from symbiont_tpu.resilience.admission import (
    DEFAULT_TENANT,
    OVERFLOW_TENANT,
    AdmissionReject,
    StrideClock,
)
from symbiont_tpu.utils.telemetry import (
    carry_context,
    current_headers,
    metrics,
    span,
)

log = logging.getLogger(__name__)

# distinct tenant lanes a batcher keeps before folding NEW identities into
# the shared overflow lane — same bounded-universe stance as the edge's
# admission.max_tenants (the tenant header is client-supplied)
MAX_TENANT_LANES = 256

# interactive lane class: a tenant's single query embed must never FIFO
# behind that SAME tenant's hundreds-deep bulk-ingest lane — measured by
# the load_ramp tier (4x ingest ramp: same-tenant query embeds waited out
# the whole backlog, 10s bus timeouts) — so interactive work rides
# "<tenant>#q", which the stride clock interleaves fairly against the
# tenant's bulk lane. At most 2x the lane cardinality, still bounded by
# MAX_TENANT_LANES.
INTERACTIVE_LANE_SUFFIX = "#q"


def interactive_lane(tenant: str) -> str:
    """The fairness-lane identity for one tenant's INTERACTIVE work."""
    return f"{tenant}{INTERACTIVE_LANE_SUFFIX}"


class TenantLanes:
    """Per-tenant FIFO lanes drained in stride-fair order (engine-plane
    fairness, ROADMAP item 5 remainder).

    The PR 9 overload plane enforced tenant fairness only at the API edge;
    the micro-batcher itself was one FIFO deque — so any path that bypasses
    the edge (a replicated gateway without admission, a native shell calling
    engine.* directly, a restarted worker draining a durable backlog)
    re-created hot-tenant starvation at the device queue. These lanes move
    the guarantee into the batcher: each queued item lands in its tenant's
    bounded lane, and the drain order is stride scheduling over the SAME
    `StrideClock` the edge fair queue runs (resilience/admission.py) — a
    tenant with 80 queued embeds interleaves 1:1 with a tenant holding 2,
    instead of serializing ahead of it.

    Single-tenant behavior is exactly the old FIFO deque (one lane), so
    every pre-existing ordering contract holds unchanged. A full lane
    rejects (`AdmissionReject` → typed engine error / handler failure whose
    durable delivery redelivers later) — bounded memory, never unbounded
    queue growth behind the device.

    Duck-typing: supports the deque surface the batcher (and its tests)
    use — `len`, truthiness, iteration in drain order (non-mutating),
    `clear()` — plus the fair `append/peek/popleft/requeue_front/drain`
    cycle. Items without a `.tenant` attribute ride the default lane.
    """

    def __init__(self, kind: str = "batcher", max_per_tenant: int = 0,
                 max_lanes: int = MAX_TENANT_LANES,
                 weights: Optional[dict] = None):
        self.kind = kind
        self.max_per_tenant = int(max_per_tenant)
        self.max_lanes = int(max_lanes)
        self._clock = StrideClock(weights)
        self._lanes: "dict[str, deque]" = {}
        # CUMULATIVE identity bound (the edge's resolve_tenant stance): the
        # tenant header is client-supplied, so bounding only the CONCURRENT
        # lane count would still let a client cycling fresh identities one
        # request at a time grow clock state and the tenant_depth gauge
        # label space without limit — past max_lanes identities ever seen,
        # every NEW name shares the overflow lane.
        self._seen: set = {DEFAULT_TENANT}
        self._n = 0

    # ------------------------------------------------------------- plumbing

    def _lane_key(self, item) -> str:
        tenant = getattr(item, "tenant", None) or DEFAULT_TENANT
        if tenant in self._seen or tenant in self._clock.weights:
            return tenant
        if len(self._seen) >= self.max_lanes:
            return OVERFLOW_TENANT
        self._seen.add(tenant)
        return tenant

    def _gauge(self, tenant: str) -> None:
        metrics.gauge_set("batcher.tenant_depth",
                          len(self._lanes.get(tenant, ())),
                          labels={"batcher": self.kind, "tenant": tenant})

    def _drop_if_empty(self, tenant: str) -> None:
        lane = self._lanes.get(tenant)
        if lane is not None and not lane:
            del self._lanes[tenant]
            # no banked lateness is erased: the clock only forgets a tenant
            # whose virtual time is at/below the floor
            self._clock.forget(tenant)

    # ------------------------------------------------------------------ api

    def __len__(self) -> int:
        return self._n

    def __bool__(self) -> bool:
        return self._n > 0

    def __iter__(self):
        return iter(self.fair_order())

    def fair_order(self) -> List:
        """Every queued item in the order popleft() would serve them —
        computed on snapshots, nothing consumed."""
        clock = self._clock.snapshot()
        lanes = {t: list(q) for t, q in self._lanes.items() if q}
        out: List = []
        while lanes:
            tenant = clock.pick(lanes)
            lane = lanes[tenant]
            out.append(lane.pop(0))
            clock.charge(tenant)
            if not lane:
                del lanes[tenant]
        return out

    def append(self, item) -> None:
        tenant = self._lane_key(item)
        lane = self._lanes.setdefault(tenant, deque())
        if self.max_per_tenant and len(lane) >= self.max_per_tenant:
            self._drop_if_empty(tenant)
            metrics.inc("batcher.lane_rejected",
                        labels={"batcher": self.kind, "tenant": tenant})
            raise AdmissionReject(
                "engine_lane_full", retry_after_s=1.0,
                message=f"tenant {tenant!r} {self.kind} lane is full "
                        f"({self.max_per_tenant} queued at the engine)")
        lane.append(item)
        self._n += 1
        self._gauge(tenant)

    def peek(self):
        """The item the next popleft() will return (deterministic between
        mutations); None when empty."""
        tenant = self._clock.pick(t for t, q in self._lanes.items() if q)
        return None if tenant is None else self._lanes[tenant][0]

    def popleft(self):
        tenant = self._clock.pick(t for t, q in self._lanes.items() if q)
        if tenant is None:
            raise IndexError("pop from empty TenantLanes")
        item = self._lanes[tenant].popleft()
        self._clock.charge(tenant)
        self._n -= 1
        self._gauge(tenant)
        self._drop_if_empty(tenant)
        return item

    def requeue_front(self, items: List) -> None:
        """Stolen-but-unserved items go back to the FRONT of their own
        lanes in original arrival order — the cross-lane drain order is the
        clock's business, per-lane FIFO is preserved."""
        per_lane: "dict[str, List]" = {}
        for item in items:
            per_lane.setdefault(self._lane_key(item), []).append(item)
        for tenant, block in per_lane.items():
            lane = self._lanes.setdefault(tenant, deque())
            # extendleft reverses its argument, so reversed() lands the
            # block at the front IN ORIGINAL ORDER (pinned by tests)
            lane.extendleft(reversed(block))
            self._n += len(block)
            self._gauge(tenant)

    def drain_fair(self) -> List:
        """Pop everything in fair order (the GenBatcher steal)."""
        out: List = []
        while self._n:
            out.append(self.popleft())
        return out

    def clear(self) -> None:
        for tenant, lane in list(self._lanes.items()):
            lane.clear()
            self._gauge(tenant)
            self._drop_if_empty(tenant)
        self._n = 0

    def oldest_submit(self) -> Optional[float]:
        """Earliest _t_submit across lane heads (each lane is FIFO, so its
        head is its oldest) — feeds the queue-age gauge."""
        heads = [q[0] for q in self._lanes.values() if q]
        times = [getattr(h, "_t_submit", None) for h in heads]
        times = [t for t in times if t is not None]
        return min(times) if times else None


class _BatcherBase:
    """Queue + wake + deadline-flush loop shared by the embed and generation
    batchers. Subclasses define `_size(item)` (how much of max_batch an item
    consumes) and `_flush(batch)` (resolve every item's future).

    `max_inflight_flushes` > 1 lets the loop start flush N+1 while flush N's
    results are still materializing (the engine's entry points are
    thread-safe by design; see engine.py's concurrency contract): batch
    N+1 tokenizes/pads/dispatches
    on its own executor thread while batch N's forward runs. Generation
    keeps it at 1: decode sessions admit newcomers at chunk boundaries
    instead, and two sessions would only contend on the LM lock.

    Result-order contract under overlap: each submission's future is bound
    to its exact slice of its OWN flush, so flush N+1 completing before
    flush N (a short batch overtaking a long one) resolves the later
    submitters first but can never mis-route rows — per-submission results
    are positionally exact regardless of flush completion order (pinned by
    tests/test_coalesce.py's slow-forward ordering test).

    Live accounting for the double-buffering (engine-plane gauges):
    `batcher.inflight` is the flush count currently in the air and
    `batcher.overlap_ratio` is the fraction of cumulative flush seconds
    that ran concurrently with another flush — 0.0 means lockstep (no
    overlap won), approaching 1-1/k means the window of k stayed full."""

    # metric label distinguishing the two policies over one registry
    kind = "batcher"

    def __init__(self, max_batch: int, deadline_s: float,
                 max_inflight_flushes: int = 1, lane_depth: int = 0):
        self.max_batch = max_batch
        self.deadline_s = deadline_s
        # per-tenant bounded lanes drained stride-fair (TenantLanes): the
        # single-tenant case degenerates to the old FIFO deque; under a
        # multi-tenant backlog the chunk composition interleaves tenants so
        # an edge-bypassing hot tenant cannot starve the rest at the device
        self._queue: TenantLanes = TenantLanes(kind=self.kind,
                                               max_per_tenant=lane_depth)
        self._queued = 0
        self._wake = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._closed = False
        self._inflight = asyncio.Semaphore(max_inflight_flushes)
        self._flushes: set = set()
        # overlap accounting (all touched on the event-loop thread only):
        # span = Σ individual flush durations; busy = wall seconds with ≥1
        # flush in flight. span - busy is flush time that OVERLAPPED another
        # flush — overlap_ratio = 1 - busy/span.
        self._inflight_n = 0
        self._busy_since = 0.0
        self._flush_busy_s = 0.0
        self._flush_span_s = 0.0

    async def start(self) -> None:
        if self._task is None:
            self._task = asyncio.create_task(
                self._run(), name=type(self).__name__)
            self._register_gauges()

    def _register_gauges(self) -> None:
        """Engine-plane queue gauges, read at scrape time. Weakref-bound
        (register_weakref_gauge): a dead or closed batcher's gauges retire
        themselves — tests churn through batchers and the registry must not
        pin them."""
        labels = {"service": "engine", "batcher": self.kind}

        def depth(b):
            return None if b._closed else b._queued

        def oldest_wait_s(b):
            if b._closed:
                return None
            if not b._queue:
                return 0.0
            # per-lane FIFO (requeues go to the FRONT), so the oldest item
            # is the earliest lane head
            t = b._queue.oldest_submit()
            return 0.0 if t is None else max(0.0, time.monotonic() - t)

        def inflight(b):
            return None if b._closed else b._inflight_n

        def overlap_ratio(b):
            if b._closed:
                return None
            span = b._flush_span_s
            if span <= 0.0:
                return 0.0
            return round(max(0.0, 1.0 - b._flush_busy_s / span), 4)

        metrics.register_weakref_gauge("batcher.queue_depth", self, depth,
                                       labels=labels)
        metrics.register_weakref_gauge("batcher.oldest_wait_s", self,
                                       oldest_wait_s, labels=labels)
        metrics.register_weakref_gauge("batcher.inflight", self, inflight,
                                       labels=labels)
        metrics.register_weakref_gauge("batcher.overlap_ratio", self,
                                       overlap_ratio, labels=labels)

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        if self._task is not None:
            await self._task
            self._task = None
        if self._flushes:
            await asyncio.gather(*self._flushes, return_exceptions=True)
        # a flush can re-queue items (splice rejection, unadmittable keep)
        # after _run has already exited — with no loop left to serve them,
        # their futures would hang forever. All flushes are done now, so the
        # queue is final: fail what's left.
        leftovers = self._queue.drain_fair()
        self._queued = 0
        for item in leftovers:
            if not item.future.done():
                item.future.set_exception(RuntimeError("batcher closed"))

    def _submit(self, item) -> None:
        if self._closed:
            raise RuntimeError("batcher closed")
        item._t_submit = time.monotonic()  # queue-age gauge reads this
        # the submitter's open span: a flush rides its first item's trace
        item._trace_ctx = current_headers()
        self._queue.append(item)
        self._queued += self._size(item)
        self._wake.set()

    def _requeue(self, items: List) -> None:
        """Put stolen-but-unserved items back, ahead of anything submitted
        meanwhile (preserve per-lane arrival order), and wake the run loop —
        it may have parked on a cleared _wake after the steal emptied the
        queue; without a wake the re-queued items sit unserved until an
        unrelated submission arrives (ADVICE r4 medium)."""
        if not items:
            return
        self._queue.requeue_front(items)
        self._queued += sum(self._size(k) for k in items)
        self._wake.set()

    def _take_chunk(self) -> List:
        """Pop up to max_batch's worth of items (always at least one),
        composed across tenant lanes in stride-fair order."""
        taken: List = []
        size = 0
        while self._queue and (not taken
                               or size + self._size(self._queue.peek()) <= self.max_batch):
            item = self._queue.popleft()
            size += self._size(item)
            taken.append(item)
        self._queued -= size
        if taken:
            labels = {"service": "engine", "batcher": self.kind}
            now = time.monotonic()
            for item in taken:
                # time work waited for the batcher, per item taken: its
                # submit -> this chunk (an item a session put back is seen
                # again, still from its submit)
                metrics.observe("batcher.queue_wait_ms",
                                (now - item._t_submit) * 1e3, labels=labels)
            fill = size / self.max_batch if self.max_batch else 0.0
            metrics.observe("batcher.flush_fill_ratio", fill, labels=labels)
            metrics.gauge_set("batcher.last_flush_fill_ratio", round(fill, 4),
                              labels=labels)
            # decode-plane flight recorder: queue depth AFTER the take —
            # the backlog a flush boundary leaves behind, on the same time
            # axis as the step/flush counters (obs/engine_timeline.py)
            engine_timeline.note_queue_depth(self.kind, self._queued)
        return taken

    async def _run(self) -> None:
        while True:
            if not self._queue:
                if self._closed:
                    return
                self._wake.clear()
                await self._wake.wait()
                continue
            if self._queued < self.max_batch and not self._closed:
                # deadline flush: give late arrivals a short window to batch up
                try:
                    await asyncio.wait_for(self._sleep_until_full(),
                                           self.deadline_s)
                except asyncio.TimeoutError:
                    pass
            await self._inflight.acquire()
            chunk = self._take_chunk()
            if not chunk:
                # an in-flight session's chunk-boundary admission can drain
                # the queue while we waited on the semaphore
                self._inflight.release()
                continue
            t = asyncio.create_task(self._flush_release(chunk))
            self._flushes.add(t)
            t.add_done_callback(self._flushes.discard)

    async def _flush_release(self, batch: List) -> None:
        t0 = time.monotonic()
        self._inflight_n += 1
        if self._inflight_n == 1:
            self._busy_since = t0
        try:
            # busy time of the batcher: one flush, whatever the subclass
            # does in it (tokenize, pad, dispatch, fetch; a whole decode
            # session), on the trace of the first item it carries
            with span("batcher.flush", batch[0]._trace_ctx, batcher=self.kind,
                      rows=sum(self._size(item) for item in batch)):
                await self._flush(batch)
        finally:
            self._inflight.release()
            t1 = time.monotonic()
            self._flush_span_s += t1 - t0
            self._inflight_n -= 1
            if self._inflight_n == 0:
                self._flush_busy_s += t1 - self._busy_since

    async def _sleep_until_full(self) -> None:
        while self._queued < self.max_batch and not self._closed:
            self._wake.clear()
            await self._wake.wait()

    # subclass interface -----------------------------------------------------

    def _size(self, item) -> int:
        raise NotImplementedError

    async def _flush(self, batch: List) -> None:
        raise NotImplementedError


@dataclass
class _Pending:
    texts: List[str]
    future: asyncio.Future
    # engine-plane fairness: the lane this item queues in (bus-header tenant
    # threaded down by the calling service; default lane otherwise)
    tenant: str = DEFAULT_TENANT


class MicroBatcher(_BatcherBase):
    kind = "embed"

    def __init__(self, engine: TpuEngine, max_batch: Optional[int] = None,
                 flush_deadline_ms: Optional[float] = None,
                 max_inflight_flushes: Optional[int] = None,
                 lane_depth: Optional[int] = None):
        deadline = (flush_deadline_ms if flush_deadline_ms is not None
                    else engine.config.flush_deadline_ms) / 1000.0
        from symbiont_tpu.config import EngineConfig

        mb = max_batch or engine.config.max_batch
        # mesh-aware flush sizing (docs/SCALING.md): round the flush cap up
        # to a multiple of the mesh 'data' axis so a full flush splits into
        # EVEN replica shards — a cap of, say, 100 over 8 replicas would
        # batch-bucket to 104 and ship 4 permanent pad rows per full flush.
        # Stub engines without DP accounting (tests) default to 1.
        nd = getattr(engine, "_n_data", 1)
        if nd > 1:
            mb = ((mb + nd - 1) // nd) * nd
        super().__init__(mb, deadline,
                         max_inflight_flushes=(
                             max_inflight_flushes
                             if max_inflight_flushes is not None
                             # duck-typed test configs may predate the
                             # field; fall back to the REAL dataclass
                             # default so a future tuning there is never
                             # shadowed by a stale literal here
                             else getattr(
                                 engine.config, "max_inflight_flushes",
                                 EngineConfig.max_inflight_flushes)),
                         lane_depth=(
                             lane_depth if lane_depth is not None
                             else getattr(engine.config, "tenant_lane_depth",
                                          EngineConfig.tenant_lane_depth)))
        self.engine = engine

    async def embed(self, texts: Sequence[str],
                    tenant: Optional[str] = None) -> np.ndarray:
        """Submit texts; resolves with [n, dim] when their batch flushes.
        `tenant` picks the fairness lane (engine-plane fairness survives
        edge bypass — docs/RESILIENCE.md); None rides the default lane."""
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._submit(_Pending(list(texts), fut,
                              tenant=tenant or DEFAULT_TENANT))
        return await fut

    def _size(self, item: _Pending) -> int:
        return len(item.texts)

    @staticmethod
    def _usage_tenant(lane: str) -> str:
        """The BILLING identity behind a fairness lane: interactive lanes
        ('<tenant>#q') charge the tenant itself — the lane split is a
        scheduling detail, not a second customer."""
        if lane.endswith(INTERACTIVE_LANE_SUFFIX):
            return lane[: -len(INTERACTIVE_LANE_SUFFIX)] or DEFAULT_TENANT
        return lane

    async def _flush(self, batch: List) -> None:
        texts: List[str] = []
        for p in batch:
            texts.extend(p.texts)
            # usage ledger (obs/usage.py): embed rows billed per tenant at
            # the flush that carries them
            usage.note(self._usage_tenant(p.tenant), embed_rows=len(p.texts))
        try:
            # off the event loop: the forward is CPU/TPU-bound
            vecs = await asyncio.get_running_loop().run_in_executor(
                None, carry_context(self.engine.embed_texts), texts)
            offset = 0
            for p in batch:
                n = len(p.texts)
                if not p.future.cancelled():
                    p.future.set_result(vecs[offset:offset + n])
                offset += n
        except Exception as e:  # propagate to every waiter
            log.exception("batch embed failed")
            for p in batch:
                if not p.future.cancelled():
                    p.future.set_exception(e)


@dataclass
class _PendingGen:
    prompt: str
    max_new: int
    temperature: float
    top_k: int
    future: asyncio.Future
    # cancellation signal (anything with .is_set(); e.g. asyncio.Event):
    # checked at every chunk boundary — a vanished SSE reader's request
    # frees its decode row mid-session instead of pinning it to budget
    # exhaustion. A cancelled request's future resolves to None.
    cancel: Optional[object] = None
    # fairness lane (see _Pending.tenant)
    tenant: str = DEFAULT_TENANT
    # originating task id: keys the row's durability snapshots in the
    # generation journal (resilience/genlog.py); None = not journaled
    task_id: Optional[str] = None

    def cancelled(self) -> bool:
        return self.cancel is not None and self.cancel.is_set()


class GenBatcher(_BatcherBase):
    """Continuous batching for autoregressive generation.

    Requests that arrive within one flush window start a decode SESSION
    together (LmEngine.start_session); the session decodes in chunks, and at
    every chunk boundary newly-queued requests JOIN the in-flight decode in
    free batch rows (row padding from the power-of-two bucket, or rows whose
    request already finished) — a request that misses the window no longer
    waits behind the whole decode (VERDICT r3 item 3). Per-request
    temperature/top_k ride as per-row traced vectors; requests group by
    new-token bucket; a newcomer is admitted when a slot is free, its budget
    fits the session's remaining steps, and its prompt fits the session's
    prompt bucket (LmEngine.BatchSession.can_admit) — otherwise it waits for
    the next session."""

    kind = "generate"

    def __init__(self, lm, max_batch: Optional[int] = None,
                 flush_deadline_ms: Optional[float] = None,
                 lane_depth: Optional[int] = None):
        from symbiont_tpu.config import LmConfig

        deadline = (flush_deadline_ms if flush_deadline_ms is not None
                    else lm.config.gen_flush_deadline_ms) / 1000.0
        super().__init__(max_batch or lm.config.gen_max_batch, deadline,
                         lane_depth=(
                             lane_depth if lane_depth is not None
                             else getattr(lm.config, "gen_tenant_lane_depth",
                                          LmConfig.gen_tenant_lane_depth)))
        self.lm = lm
        self.stats = {"sessions": 0, "admitted_midflight": 0}

    async def generate(self, prompt: str, max_new_tokens: int,
                       temperature: Optional[float] = None,
                       top_k: Optional[int] = None,
                       cancel: Optional[object] = None,
                       tenant: Optional[str] = None,
                       task_id: Optional[str] = None) -> Optional[str]:
        """Returns the generated text, or None when `cancel` (an object
        with .is_set(), e.g. asyncio.Event) was set mid-decode and the
        request's row was freed at a chunk boundary. `tenant` picks the
        fairness lane (default lane otherwise); `task_id` keys the row's
        crash-resume snapshots in the generation journal."""
        cfg = self.lm.config
        temperature = cfg.temperature if temperature is None else temperature
        top_k = cfg.top_k if top_k is None else top_k
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._submit(_PendingGen(prompt, int(max_new_tokens),
                                 float(temperature), int(top_k), fut,
                                 cancel=cancel,
                                 tenant=tenant or DEFAULT_TENANT,
                                 task_id=task_id))
        return await fut

    def _size(self, item: _PendingGen) -> int:
        return 1

    def _bucket(self, max_new: int) -> int:
        for b in self.lm.config.new_token_buckets:
            if max_new <= b:
                return b
        return self.lm.config.new_token_buckets[-1]

    async def _flush(self, batch: List) -> None:
        loop = asyncio.get_running_loop()
        groups: dict = {}
        for p in batch:
            groups.setdefault(self._bucket(p.max_new), []).append(p)
        for group in groups.values():
            # requests cancelled while they sat in the flush window never
            # enter a session at all — their futures resolve to None here
            still = [p for p in group if not p.cancelled()]
            for p in group:
                if p.cancelled() and not p.future.done():
                    p.future.set_result(None)
            if not still:
                continue
            group = still
            # every request that ever joins this session; on session failure
            # each unresolved future gets the exception (a silently dropped
            # future would hang its caller forever)
            participants: List = list(group)
            by_tag: dict = {}
            prep_fut = None  # in-flight prepare: (future, take-items)
            # requests this session can NEVER admit (prompt over its prompt
            # bucket, or budget over its monotonically-shrinking remaining
            # steps): parked here until the session ends instead of
            # re-queued, or every chunk boundary would re-steal and
            # re-tokenize them (can_admit encodes the full prompt)
            deferred: List = []
            try:
                sess = await loop.run_in_executor(
                    None, lambda g=group: self.lm.start_session(
                        [p.prompt for p in g], [p.max_new for p in g],
                        temperature=[p.temperature for p in g],
                        top_k=[p.top_k for p in g],
                        tenants=[p.tenant for p in g],
                        task_ids=[p.task_id for p in g]))
                self.stats["sessions"] += 1
                for tag, p in zip((r.tag for r in sess.rows if r is not None),
                                  group):
                    by_tag[tag] = p
                while True:
                    # 1) harvest a finished prepare: splice the prefilled
                    #    rows in at this chunk boundary (cheap merge). Block
                    #    on the prepare only when the session has nothing
                    #    left to decode — otherwise keep stepping.
                    if prep_fut is not None and (
                            prep_fut[0].done()
                            or (sess.done() and not by_tag)):
                        fut, take = prep_fut
                        prep_fut = None
                        try:
                            prep = await fut
                        except Exception as e:
                            # a failed prefill kills only the newcomers —
                            # the in-flight session rows keep decoding
                            log.exception("newcomer prefill failed")
                            for p in take:
                                if not p.future.done():
                                    p.future.set_exception(e)
                            prep = None
                        if prep is not None:
                            try:
                                tags = await loop.run_in_executor(
                                    None, sess.splice, prep)
                            except Exception as e:
                                # same stance as a failed prefill: kill the
                                # newcomers, keep the session rows decoding
                                # (their futures are not in participants, so
                                # the outer handler can't reach them)
                                log.exception("newcomer splice failed")
                                for p in take:
                                    if not p.future.done():
                                        p.future.set_exception(e)
                                tags = None
                            if tags is None:
                                continue
                            for tag, p in zip(tags, take):
                                if tag is None:
                                    # splice rejection is permanent for this
                                    # session too (budget vs remaining)
                                    deferred.append(p)
                                else:
                                    by_tag[tag] = p
                                    participants.append(p)
                                    self.stats["admitted_midflight"] += 1
                    # 1b) cancellation sweep at the chunk boundary: a
                    #     vanished client's row frees NOW (admissible to
                    #     newcomers, kv gauges drop it) instead of decoding
                    #     to budget exhaustion (BatchSession.cancel_tag)
                    swept = [(tag, p) for tag, p in by_tag.items()
                             if p.cancelled()]
                    if swept:
                        # cancel_tag takes the ENGINE lock, which an
                        # executor thread can hold through a decode chunk
                        # or a first-call XLA compile — never block the
                        # event loop on it
                        await loop.run_in_executor(
                            None,
                            lambda: [sess.cancel_tag(t) for t, _ in swept])
                    for tag, p in swept:
                        by_tag.pop(tag)
                        if not p.future.done():
                            p.future.set_result(None)
                        self.stats["cancelled"] = (
                            self.stats.get("cancelled", 0) + 1)
                    if sess.done() and not by_tag and prep_fut is None:
                        # prep_fut pending (e.g. the sweep just cancelled
                        # every row) must NOT be abandoned here: the next
                        # iteration's harvest force-awaits it — splicing
                        # its rows in if budget remains, failing/deferring
                        # them otherwise — so no newcomer future ever
                        # dangles off a normal session exit
                        break
                    # 2) steal the queue and start preparing newcomers —
                    #    overlapped with the step below, never awaited here
                    if (prep_fut is None and self._queue
                            and sess.capacity() > 0):
                        # steal in stride-fair order: admission slots fill
                        # across tenants, not first-come within one lane
                        candidates = self._queue.drain_fair()
                        self._queued -= sum(self._size(c) for c in candidates)
                        try:
                            take, retry, defer = await loop.run_in_executor(
                                None, self._filter_candidates, sess,
                                candidates)
                        except Exception as e:
                            # stolen items are in nobody's hands now — fail
                            # them or their callers hang forever
                            log.exception("admission filter failed")
                            for p in candidates:
                                if not p.future.done():
                                    p.future.set_exception(e)
                            take, retry, defer = [], [], []
                        # transiently rejected (batch full) go straight back:
                        # a row may free at the next chunk boundary and they
                        # must not wait out the whole session
                        self._requeue(retry)
                        deferred.extend(defer)
                        if take:
                            prep_fut = (loop.run_in_executor(
                                None, self._do_prepare, sess, take), take)
                    # 3) decode one chunk (the prepare, if any, is prefilling
                    #    on another executor thread meanwhile). Turnaround
                    #    includes the event-loop -> executor hop both ways:
                    #    subtracting the timeline's device wall for the same
                    #    chunk isolates the batcher's share of the host gap
                    #    that obs/xprof.py attributes per chunk.
                    t_hop = time.monotonic()
                    finished = await loop.run_in_executor(None, sess.step)
                    metrics.observe("batcher.step_turnaround_ms",
                                    (time.monotonic() - t_hop) * 1000.0,
                                    labels={"service": "lm"})
                    for tag, text in finished:
                        p = by_tag.pop(tag)
                        if not p.future.cancelled():
                            p.future.set_result(text)
            except Exception as e:
                log.exception("batch generate session failed")
                if prep_fut is not None:
                    prep_fut[0].cancel()
                    participants.extend(prep_fut[1])
                for p in participants:
                    if not p.future.done():
                        p.future.set_exception(e)
            finally:
                # deferred items never joined this session — hand them to
                # the next one (front of queue: preserve arrival order)
                self._requeue(deferred)

    def _filter_candidates(self, sess, candidates: List):
        """Executor-side: split candidates into (take, keep). can_admit
        tokenizes, so it runs off the loop. The budget margin covers the
        chunks that will decode while the prepare runs: one chunk when the
        prefill shape is already compiled; a compile allowance when it's
        cold (a splice rejection throws the whole prefill away, so
        over-reserving beats racing a multi-second XLA compile — and a cold
        shape happens at most once per power-of-two admission batch)."""
        # One pass: pick the margin up front from the warmth of the LIKELY
        # admission shape (can_admit tokenizes the full prompt — splitting
        # twice would double that work). The guess can overshoot the final
        # take count and land on a different power-of-two shape; the cost of
        # a wrong guess is only a slightly off budget margin.
        guess = min(len(candidates), sess.capacity())
        if sess.prefill_warm(guess):
            margin = 1
        else:
            # reserve up to 8 chunks for the compile, but never so much that
            # admission becomes impossible in principle — cap at half the
            # session's remaining chunks
            # round_slots: a speculative round burns spec_k+1 slots, so the
            # compile reserve is counted in the session's ACTUAL round size
            margin = min(8, max(1, sess.remaining_steps()
                                // (2 * sess.round_slots())))
        take: List = []
        retry: List = []   # transient rejection: no free row RIGHT NOW
        defer: List = []   # permanent for this session: budget/prompt
        for item in candidates:
            if len(take) >= sess.capacity():
                # rows free as requests finish — retry next chunk boundary
                retry.append(item)
            elif sess.can_admit(item.prompt, item.max_new,
                                lookahead_chunks=margin):
                take.append(item)
            else:
                defer.append(item)
        return take, retry, defer

    def _do_prepare(self, sess, take: List):
        """Executor-side admission phase 1: prefill the newcomers WITHOUT
        the engine lock (BatchSession.prepare_admit) so a prefill — which
        may compile a fresh shape, seconds of host time — cannot stall the
        in-flight chunk running concurrently (VERDICT r4 weak #4)."""
        return sess.prepare_admit([p.prompt for p in take],
                                  [p.max_new for p in take],
                                  temperature=[p.temperature for p in take],
                                  top_k=[p.top_k for p in take],
                                  tenants=[p.tenant for p in take],
                                  task_ids=[p.task_id for p in take])
