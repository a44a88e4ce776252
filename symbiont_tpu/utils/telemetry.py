"""Tracing + metrics — the observability layer the reference lacks.

Reference state (SURVEY.md §5.1/§5.5): bare env_logger lines with bracket tags,
ids carried only inside payloads, NATS monitoring port exposed but unscraped,
zero counters. Here:

- Trace: every message carries trace/span ids in bus headers
  (X-Trace-Id/X-Span-Id); `child_headers` propagates across hops; `span`
  times a handler, logs a structured line, AND appends a SpanRecord to the
  process-global flight recorder (obs/trace_store.py) so
  `GET /api/traces/<id>` can reassemble the full pipeline tree.
- Metrics: process-global registry of counters, histograms (p50/p95/p99 +
  exact running min/max), and gauges (set/add, plus callback gauges read at
  scrape time). All three kinds take optional `{label: value}` labels —
  rendered as JSON (api /api/metrics) and as Prometheus text exposition
  (api /metrics, obs/prometheus.py).
- Who holds the interpreter: `span(..., cpu=True)` records the thread's CPU
  time beside the wall time, `python_cpu_s` sums the interpreter's threads,
  `start_loop_lag_probe` measures how long a ready continuation waits for the
  event loop (docs/OBSERVABILITY.md "Wall against CPU under a GIL").

Span-id semantics (the contract the trace tree depends on): the X-Span-Id
header names the ACTIVE span — the one under which a message was published.
`span()` mints its own id with the header's id as parent and exposes its own
context at `handle.headers`; `child_headers` PROPAGATES the active context
unchanged (a bus hop is an edge, not a span). The service base loop hands
each handler a message rebound to the handler span's context, so every
downstream publish links to it (services/base.py).
"""

from __future__ import annotations

import asyncio
import bisect
import contextvars
import functools
import json
import logging
import sys
import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, Optional, Tuple

from symbiont_tpu.obs.trace_store import SpanRecord, trace_store
from symbiont_tpu.utils.ids import generate_uuid

log = logging.getLogger("symbiont.trace")

TRACE_HEADER = "X-Trace-Id"
SPAN_HEADER = "X-Span-Id"
# Overload-protection plane (resilience/admission.py): the request deadline
# (absolute unix epoch MILLISECONDS, minted at the API edge) and the tenant
# identity ride the same bus-header channel as the trace context, and
# child_headers threads them across every hop — a downstream service drops
# expired work BEFORE its handler runs (services/base.py).
DEADLINE_HEADER = "X-Symbiont-Deadline"
TENANT_HEADER = "X-Symbiont-Tenant"

# headers child_headers carries verbatim beyond the trace pair
_THREADED_HEADERS = (DEADLINE_HEADER, TENANT_HEADER)


def new_trace_headers() -> Dict[str, str]:
    return {TRACE_HEADER: generate_uuid(), SPAN_HEADER: generate_uuid()}


def child_headers(parent: Optional[Dict[str, str]]) -> Dict[str, str]:
    """Propagate the active trace context; start a new trace without one.

    The span id is carried over VERBATIM (it names the publishing span):
    the receiving handler's span records it as parent_id, which is what
    links hops into one tree. (Pre-obs versions minted a fresh span id per
    hop — an id that no recorded span owned, so trees could never link.)

    Deadline/tenant headers (the admission plane's channel) thread through
    verbatim too: a deadline minted at the API edge must reach the LAST hop
    of the pipeline, or expired work is only droppable at the first."""
    if not parent or TRACE_HEADER not in parent:
        out = new_trace_headers()
    else:
        out = {TRACE_HEADER: parent[TRACE_HEADER]}
        if SPAN_HEADER in parent:
            out[SPAN_HEADER] = parent[SPAN_HEADER]
    if parent:
        for h in _THREADED_HEADERS:
            if h in parent:
                out[h] = parent[h]
    return out


class SpanHandle:
    """Live-span context yielded by `span()`. `headers` is the context to
    publish downstream messages under (same trace, THIS span as the active
    id); `fields` may be extended while the span is open and lands on the
    flight-recorder record."""

    __slots__ = ("trace_id", "span_id", "parent_id", "fields")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str], fields: dict):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.fields = fields

    @property
    def headers(self) -> Dict[str, str]:
        return {TRACE_HEADER: self.trace_id, SPAN_HEADER: self.span_id}


# the open span of the running task or thread: a span() given no headers
# takes it as its parent, so inner spans join the request that caused them
# without every signature threading headers down
_open_span: contextvars.ContextVar = contextvars.ContextVar(
    "symbiont_open_span", default=None)


def current_headers() -> Optional[Dict[str, str]]:
    """The open span's context (what a message published now should carry),
    or None outside any span. Queues capture it at submit so the work they
    later do on another task can ride the submitter's trace."""
    handle = _open_span.get()
    return None if handle is None else handle.headers


def carry_context(fn: Callable) -> Callable:
    """`fn` bound to a copy of the caller's context, for `run_in_executor`
    (which, unlike `asyncio.to_thread`, hands the pool thread an empty
    one): spans opened on the pool thread then parent to the caller's."""
    return functools.partial(contextvars.copy_context().run, fn)


class _ProfilerAnnotations:
    """Every open span as a host annotation `symbiont.<name>` (trace id as
    an event stat) in a running `jax.profiler` trace, so the program's
    spans lie on the host plane of the same `.xplane.pb` as the device ops,
    on one clock. While no profile runs a span costs a flag test and one
    dict entry here.

    The profiler keeps an annotation only if it both began and ended while
    the trace ran, and nothing tells the program when a trace starts or
    stops: a span longer than the traced window (a 5 s store flush in a 4 s
    window — the span that matters most there) would never appear, and
    while everything waits behind it no other span opens or closes either.
    So the open spans are registered here and a ticker thread (started with
    the first span of a process that has jax) looks every `ROLL_S`: while a
    trace runs, an open span with no annotation in it gets one, and an
    annotation older than `ROLL_S` is closed and opened again. A span
    shorter than `ROLL_S` is one event with its own ends; a longer one is a
    chain of segments under one name and trace id that misses at most
    `ROLL_S` after the trace's start and 2 x `ROLL_S` before its stop.

    Only jax can start a profile, so a process that has not imported jax
    has none to annotate, is not made to import it, and gets no ticker."""

    ROLL_S = 0.1

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._open: Dict["SpanHandle", list] = {}  # [event, annotation, t]
        self._cls = None  # jax.profiler.TraceAnnotation, once jax is there
        self._ticker: Optional[threading.Thread] = None

    def _tracing(self):
        """The annotation class while a profile runs, else None."""
        cls = self._cls
        if cls is None:
            profiler = getattr(sys.modules.get("jax"), "profiler", None)
            cls = self._cls = getattr(profiler, "TraceAnnotation", None)
        return cls if cls is not None and cls.is_enabled() else None

    @staticmethod
    def _annotate(cls, entry: list, handle: "SpanHandle") -> None:
        entry[1] = cls(entry[0], trace_id=handle.trace_id)
        entry[1].__enter__()
        entry[2] = time.monotonic()

    def opened(self, handle: "SpanHandle", name: str) -> None:
        cls = self._tracing()
        entry = [f"symbiont.{name}", None, 0.0]
        if cls is not None:
            self._annotate(cls, entry, handle)
        with self._lock:
            self._open[handle] = entry
            if self._ticker is None and self._cls is not None:
                self._ticker = threading.Thread(
                    target=self._run_ticker, name="symbiont-span-ticker",
                    daemon=True)
                self._ticker.start()

    def closed(self, handle: "SpanHandle") -> None:
        with self._lock:
            annotation = self._open.pop(handle)[1]
        if annotation is not None:
            annotation.__exit__(None, None, None)

    def tick(self) -> None:
        """One look of the ticker: bring the open spans' annotations up to
        date with the running trace, if there is one."""
        cls = self._tracing()
        if cls is None:
            return
        with self._lock:
            stale = time.monotonic() - self.ROLL_S
            for handle, entry in self._open.items():
                if entry[1] is None or entry[2] <= stale:
                    if entry[1] is not None:
                        entry[1].__exit__(None, None, None)
                    self._annotate(cls, entry, handle)

    def _run_ticker(self) -> None:
        while True:
            time.sleep(self.ROLL_S)
            self.tick()


_annotations = _ProfilerAnnotations()


@contextmanager
def span(name: str, headers: Optional[Dict[str, str]] = None, *,
         cpu: bool = False, **fields):
    """Timed span: `span.<name>.ms` histogram + a SpanRecord in the flight
    recorder + a structured log line at INFO + a profiler annotation
    (`_ProfilerAnnotations`). The parent is the span `headers` name, else the span
    open in this task or thread, else none (a new trace). Errors are
    accounted, not swallowed: status lands on the record (queryable via
    /api/traces) and `span.<name>.errors` increments before the exception
    propagates.

    `cpu=True` is for a synchronous section (one thread, no `await`
    inside): the thread's CPU time over the body is also added to the
    counter `span.<name>.cpu_ms_total` and put on the record's fields as
    `cpu_ms`. A counter, since the thread clock may tick coarsely (10 ms on
    the v5e's host), so only a sum over many spans means anything. wall -
    cpu is the time the section did not hold the interpreter (waiting for
    the GIL, in `fsync`, on the device). Closed on another thread than the
    one that opened it, a span records no CPU number rather than a wrong
    one."""
    t0 = time.perf_counter()
    start_s = time.time()
    outer = _open_span.get()
    ctx = headers if headers and TRACE_HEADER in headers else (
        outer.headers if outer is not None else {})
    trace_id = ctx.get(TRACE_HEADER) or generate_uuid()
    handle = SpanHandle(trace_id, generate_uuid(), ctx.get(SPAN_HEADER),
                        dict(fields))
    _annotations.opened(handle, name)
    _open_span.set(handle)
    status = "ok"
    if cpu:
        cpu_thread, cpu0 = threading.get_ident(), time.thread_time()
    try:
        yield handle
    except BaseException as e:
        status = "error"
        handle.fields.setdefault("error", type(e).__name__)
        metrics.inc(f"span.{name}.errors")
        raise
    finally:
        if cpu and threading.get_ident() == cpu_thread:
            handle.fields["cpu_ms"] = (time.thread_time() - cpu0) * 1000
            metrics.inc(f"span.{name}.cpu_ms_total", handle.fields["cpu_ms"])
        # set, not reset(token): a span closed from another context than
        # the one that opened it (a generator finalized elsewhere) must
        # not raise out of the finally
        _open_span.set(outer)
        _annotations.closed(handle)
        dur_ms = (time.perf_counter() - t0) * 1000
        # the trace id rides along as an exemplar: a bad histogram bucket
        # on /metrics links straight to a concrete flight-recorder trace
        metrics.observe(f"span.{name}.ms", dur_ms,
                        exemplar={"trace_id": trace_id})
        trace_store.record(SpanRecord(
            trace_id=trace_id, span_id=handle.span_id,
            parent_id=handle.parent_id, name=name, start_s=start_s,
            duration_ms=dur_ms, status=status, fields=handle.fields))
        if log.isEnabledFor(logging.INFO):
            log.info(json.dumps({"span": name, "trace": trace_id,
                                 "status": status,
                                 "duration_ms": round(dur_ms, 3),
                                 **handle.fields}, ensure_ascii=False,
                                default=str))


def _thread_cpu_clock(native_id: int) -> int:
    """The clock id of a thread's CPU clock, from its kernel id: Linux's
    encoding of CPUCLOCK_SCHED for one thread, what `pthread_getcpuclockid`
    returns. That call reads through a `pthread_t`, which is undefined once
    its thread has ended; a kernel id that is gone fails with EINVAL."""
    return (~native_id << 3) | 6


def python_cpu_s() -> Optional[float]:
    """CPU seconds the interpreter's live threads have used (callback gauge
    `host.python_cpu_s`): the threads `threading` knows, each by its own
    CPU clock. The runtime's C++ threads are not among them, so a delta of
    this is what Python threads cost, whichever of them ran the work; a
    thread that has ended takes its seconds with it. None (the gauge
    retires) where the platform has no such clocks."""
    if not sys.platform.startswith("linux"):
        return None
    total = 0.0
    for thread in threading.enumerate():
        if thread.native_id is None:
            continue
        try:
            total += time.clock_gettime(_thread_cpu_clock(thread.native_id))
        except OSError:
            continue  # ended between the enumeration and the read
    return total


# how often the lag probe's timer is due. Each firing wakes the loop and so
# takes the interpreter from whichever thread holds it: every 10 ms cost
# the host-paced ingest cell 0.5% of its rate (PERF.md section 6, PR 36)
LOOP_LAG_PROBE_S = 0.05


def start_loop_lag_probe() -> Callable[[], None]:
    """Histogram `loop.lag_ms`: a timer on the running loop, due every
    `LOOP_LAG_PROBE_S`, observes how late it fired. That is how long a
    continuation that is ready waits for the event loop: what a flush's
    resume, a handler's next step and a search's reply all pay while
    something else holds the loop (or the interpreter). A bare timer, not a
    task asleep: one loop iteration a sample. Returns what stops it."""
    loop = asyncio.get_running_loop()

    def tick(due: float) -> None:
        nonlocal handle
        now = loop.time()
        metrics.observe("loop.lag_ms", max(0.0, now - due) * 1000)
        handle = loop.call_later(LOOP_LAG_PROBE_S, tick,
                                 now + LOOP_LAG_PROBE_S)

    handle = loop.call_later(LOOP_LAG_PROBE_S, tick,
                             loop.time() + LOOP_LAG_PROBE_S)
    return lambda: handle.cancel()


# default cumulative-bucket bounds for span-duration histograms, in ms
# (Prometheus `le` upper bounds; +Inf is implicit). Chosen to straddle the
# measured pipeline: sub-ms bus hops up through multi-second cold compiles.
# Override per process via ObsConfig.histogram_buckets_ms (runner applies
# Metrics.set_bucket_bounds at boot — BEFORE traffic; bounds are fixed per
# histogram at first observation, rebucketing recorded data is impossible).
DEFAULT_BUCKET_BOUNDS_MS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                            500.0, 1000.0, 2500.0, 5000.0, 10000.0)


class _Histogram:
    __slots__ = ("values", "count", "total", "vmin", "vmax",
                 "bounds", "bucket_counts", "exemplars")

    def __init__(self, bounds: tuple = DEFAULT_BUCKET_BOUNDS_MS) -> None:
        self.values: list = []  # sorted reservoir (bounded)
        self.count = 0
        self.total = 0.0
        # exact running extremes: the reservoir decimation below drops
        # alternating samples (including, half the time, the true min) and
        # truncates tails — min/max must not ride the lossy reservoir
        self.vmin: Optional[float] = None
        self.vmax: Optional[float] = None
        # real Prometheus histogram state: exact per-bucket counts (the
        # reservoir's quantiles cannot be aggregated across processes;
        # `_bucket`/`le` series can) + the latest exemplar seen per bucket
        # (value, {label: v}, unix ts) — a bad bucket links to a concrete
        # flight-recorder trace
        self.bounds: Tuple[float, ...] = tuple(bounds)
        self.bucket_counts: list = [0] * (len(self.bounds) + 1)
        self.exemplars: list = [None] * (len(self.bounds) + 1)

    def observe(self, v: float,
                exemplar: Optional[Dict[str, str]] = None) -> None:
        self.count += 1
        self.total += v
        if self.vmin is None or v < self.vmin:
            self.vmin = v
        if self.vmax is None or v > self.vmax:
            self.vmax = v
        # non-cumulative bucket index; bisect_left keeps `le` INCLUSIVE
        # (v == bound counts in that bound's bucket, Prometheus semantics)
        b = bisect.bisect_left(self.bounds, v)
        self.bucket_counts[b] += 1
        if exemplar:
            self.exemplars[b] = (v, dict(exemplar), time.time())
        bisect.insort(self.values, v)
        if len(self.values) > 4096:
            # drop alternating samples to stay bounded but keep the shape
            del self.values[::2]

    def quantile(self, q: float) -> float:
        if not self.values:
            return 0.0
        idx = min(len(self.values) - 1, int(q * len(self.values)))
        return self.values[idx]

    def cumulative_buckets(self) -> list:
        """[(le_bound, cumulative_count), ...] ending with ("+Inf", count)."""
        out, running = [], 0
        for bound, n in zip(self.bounds, self.bucket_counts):
            running += n
            out.append((bound, running))
        out.append(("+Inf", running + self.bucket_counts[-1]))
        return out

    def summary(self) -> dict:
        return {"count": self.count,
                "sum": self.total,  # exact running total (renderers must
                                    # not reconstruct it as mean*count)
                "mean": self.total / self.count if self.count else 0.0,
                "min": self.vmin if self.vmin is not None else 0.0,
                "max": self.vmax if self.vmax is not None else 0.0,
                "p50": self.quantile(0.50), "p95": self.quantile(0.95),
                "p99": self.quantile(0.99),
                "buckets": self.cumulative_buckets(),
                "exemplars": list(self.exemplars)}


# label set normalized to a sorted tuple: one canonical key per
# (name, labels) pair regardless of caller dict ordering
_LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Optional[Dict[str, str]]) -> _LabelKey:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _render_key(name: str, lk: _LabelKey) -> str:
    if not lk:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in lk)
    return f"{name}{{{inner}}}"


class Metrics:
    """Counters + histograms + gauges, each optionally labeled.

    Gauges come in two flavors: value gauges (`gauge_set`/`gauge_add` — e.g.
    live SSE clients) and callback gauges (`register_gauge` — evaluated at
    scrape time, e.g. batcher queue depth). A callback returning None (or
    raising) is dropped from the registry: callbacks close over weakrefs of
    engine/batcher instances, and a dead instance must disappear from the
    scrape instead of pinning the object or poisoning the snapshot."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, _LabelKey], float] = {}
        self._hists: Dict[Tuple[str, _LabelKey], _Histogram] = {}
        self._gauges: Dict[Tuple[str, _LabelKey], float] = {}
        self._gauge_fns: Dict[Tuple[str, _LabelKey], Callable] = {}
        self._bucket_bounds: Tuple[float, ...] = DEFAULT_BUCKET_BOUNDS_MS

    def set_bucket_bounds(self, bounds) -> None:
        """Cumulative-bucket upper bounds (`le`) for histograms created
        AFTER this call — existing histograms keep theirs (recorded samples
        cannot be rebucketed). The runner applies ObsConfig
        .histogram_buckets_ms here at boot, before traffic."""
        bounds = tuple(float(b) for b in bounds)
        if not bounds or any(b <= 0 for b in bounds) \
                or list(bounds) != sorted(set(bounds)):
            raise ValueError(
                "bucket bounds must be positive, strictly increasing and "
                f"non-empty, got {bounds!r}")
        with self._lock:
            self._bucket_bounds = bounds

    # ------------------------------------------------------------- counters

    def inc(self, name: str, n: float = 1,
            labels: Optional[Dict[str, str]] = None) -> None:
        key = (name, _label_key(labels))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def get(self, name: str,
            labels: Optional[Dict[str, str]] = None) -> float:
        with self._lock:
            return self._counters.get((name, _label_key(labels)), 0)

    # ----------------------------------------------------------- histograms

    def observe(self, name: str, value: float,
                labels: Optional[Dict[str, str]] = None,
                exemplar: Optional[Dict[str, str]] = None) -> None:
        """`exemplar` is a tiny label dict (by convention `{"trace_id":
        ...}`) attached to the bucket this sample lands in — rendered as an
        OpenMetrics exemplar so a bad bucket links to a flight-recorder
        trace."""
        key = (name, _label_key(labels))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = _Histogram(self._bucket_bounds)
            h.observe(value, exemplar=exemplar)

    def histogram_summary(self, name: str,
                          labels: Optional[Dict[str, str]] = None
                          ) -> Optional[dict]:
        with self._lock:
            h = self._hists.get((name, _label_key(labels)))
            return h.summary() if h is not None else None

    def histogram_summaries(self, name: str) -> list:
        """Every labeled variant of one histogram family:
        [(labels_dict, summary), ...]. The SLO watchdog judges each variant
        separately — remote-role span durations federated by the fleet
        plane (obs/fleet.py) land as `{role: ...}`-labeled histograms, and
        a breach in ONE role must not hide inside a fleet-wide blend."""
        with self._lock:
            found = [(dict(lk), h.summary())
                     for (n, lk), h in self._hists.items() if n == name]
        return found

    # --------------------------------------------------------------- gauges

    def gauge_set(self, name: str, value: float,
                  labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._gauges[(name, _label_key(labels))] = value

    def gauge_add(self, name: str, delta: float,
                  labels: Optional[Dict[str, str]] = None) -> float:
        key = (name, _label_key(labels))
        with self._lock:
            v = self._gauges.get(key, 0) + delta
            self._gauges[key] = v
            return v

    def gauge_get(self, name: str,
                  labels: Optional[Dict[str, str]] = None) -> float:
        key = (name, _label_key(labels))
        with self._lock:
            if key in self._gauges:
                return self._gauges[key]
            fn = self._gauge_fns.get(key)
        if fn is None:
            return 0
        evaluated = self._eval_gauge_fns({key: fn})
        return evaluated.get(key, 0)

    def register_gauge(self, name: str, fn: Callable,
                       labels: Optional[Dict[str, str]] = None) -> None:
        """Callback gauge, read at scrape time. Re-registering the same
        (name, labels) replaces the callback (a fresh engine instance takes
        over its predecessor's gauge)."""
        with self._lock:
            self._gauge_fns[(name, _label_key(labels))] = fn

    def register_weakref_gauge(self, name: str, obj, reader: Callable,
                               labels: Optional[Dict[str, str]] = None
                               ) -> None:
        """Callback gauge bound to `obj` WITHOUT pinning it: the registry
        holds a weakref, `reader(obj)` produces the value, and when the
        owner dies (or the reader signals retirement by returning None) the
        gauge unregisters itself at the next scrape. The one place the
        owner-lifecycle contract lives — engine/batcher/LM gauges all go
        through here."""
        import weakref

        ref = weakref.ref(obj)

        def fn():
            o = ref()
            return None if o is None else reader(o)

        self.register_gauge(name, fn, labels=labels)

    def unregister_gauge(self, name: str,
                         labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._gauge_fns.pop((name, _label_key(labels)), None)

    def _eval_gauge_fns(self, fns: Dict) -> Dict:
        """Evaluate callback gauges OUTSIDE the registry lock (a callback may
        take an engine/batcher lock; holding ours too invites ordering
        deadlocks). A callback returning None is retired (the weakref-death
        convention); one that RAISES is skipped for this scrape but kept —
        a transient error (e.g. a racing collection mutation) must not
        silently delete a gauge for the life of the process."""
        out, dead = {}, []
        for key, fn in fns.items():
            try:
                v = fn()
            except Exception:
                log.debug("callback gauge %s failed this scrape", key[0],
                          exc_info=True)
                continue
            if v is None:
                dead.append(key)
            else:
                out[key] = v
        if dead:
            with self._lock:
                for key in dead:
                    self._gauge_fns.pop(key, None)
        return out

    # ------------------------------------------------------------ rendering

    def export(self) -> dict:
        """Structured dump for renderers: kind → [(name, labels-dict,
        value-or-summary)]. Callback gauges are evaluated here."""
        with self._lock:
            counters = list(self._counters.items())
            hists = [(k, h.summary()) for k, h in self._hists.items()]
            gauges = list(self._gauges.items())
            fns = dict(self._gauge_fns)
        gauges += list(self._eval_gauge_fns(fns).items())
        return {
            "counters": [(n, dict(lk), v) for (n, lk), v in counters],
            "histograms": [(n, dict(lk), s) for (n, lk), s in hists],
            "gauges": [(n, dict(lk), v) for (n, lk), v in gauges],
        }

    def snapshot(self) -> dict:
        """JSON-shaped view (api /api/metrics; BASELINE.md numbers). Labeled
        series render as `name{k="v"}` keys; unlabeled keep their bare name
        (the shape every pre-obs consumer knows)."""
        ex = self.export()
        return {
            "counters": {_render_key(n, _label_key(lb)): v
                         for n, lb, v in ex["counters"]},
            # exemplars (trace-id samples) are an exposition-format detail;
            # the JSON view keeps stats + buckets only
            "histograms": {_render_key(n, _label_key(lb)):
                           {k: v for k, v in s.items() if k != "exemplars"}
                           for n, lb, s in ex["histograms"]},
            "gauges": {_render_key(n, _label_key(lb)): v
                       for n, lb, v in ex["gauges"]},
        }

    def flat_snapshot(self) -> Dict[str, float]:
        """One flat string→number dict (archived into bench JSON so
        BENCH_*.json carries the internal gauges, not just external
        timings). Histograms contribute count/p50/p99/min/max."""
        snap = self.snapshot()
        flat: Dict[str, float] = {}
        for k, v in snap["counters"].items():
            flat[f"counter.{k}"] = v
        for k, v in snap["gauges"].items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                flat[f"gauge.{k}"] = float(v)
        for k, s in snap["histograms"].items():
            for stat in ("count", "p50", "p99", "min", "max"):
                flat[f"hist.{k}.{stat}"] = float(s[stat])
        return flat

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._hists.clear()
            self._gauges.clear()
            self._gauge_fns.clear()


metrics = Metrics()
