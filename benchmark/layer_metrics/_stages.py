"""Readers of the program's stage spans (`span(..., cpu=True)`: the
thread's CPU time over a synchronous section, summed in the counter
`span.<name>.cpu_ms_total`, beside the wall histogram `span.<name>.ms`) and
of the two process-wide series that say who holds the interpreter
(`host.python_cpu_s`, `loop.lag_ms`). Every function returns None where the
program records no such series, so a program from before the stage spans
reports none of these metrics.

Which part of the window. The harness runs in the program's process, and
its profiler thread is one of the threads `host.python_cpu_s` sums:
`stop_trace` burns tens of seconds on it, inside the measured window, and
the program runs at half its pace meanwhile; and while the profile runs,
the Python tracer's hooks multiply the CPU of a stage made of small Python
calls (the tokenizer of `ingest_pages` read 16.9 ms a page there, 4.0
before). So everything here that is CPU time or the loop's lag is taken
over the QUIET part of the window: from its start (`ctx["snap0"]`) to the
profiler's (`ctx["trace"]["snap0"]`, taken as `start_trace` returns), a
quarter of the window in which the program runs as it does untraced and
that thread sleeps. The wall means of single stages (`store_wal_sync_ms`,
`embed_dispatch_ms`, `embed_hop_ms`, the two `qsearch_*_ms`) keep the whole
window, as the accepted metrics they split do, so that the parts add up.

"Per page" divides by the quiet part's delta of the counter
`preprocessing.embedded_docs`: pages whose embeddings were published. A
passage cell's quiet part holds two pages or three: the per-page metrics
are not listed for it."""
# the ten synchronous sections of the served ingest path (the two of the
# fused query, `engine.qsearch.tokenize` / `.dispatch`, are not a page's)
INGEST_STAGES = (
    "perception.extract", "preprocessing.split", "preprocessing.frame",
    "vector_memory.decode", "engine.embed.tokenize", "engine.embed.pack",
    "engine.embed.dispatch", "store.ingest_rows", "store.wal_encode",
    "store.wal_sync")


def _quiet(ctx):
    """The snapshots at the ends of the window's quiet part."""
    sub = ctx.get("trace")
    return (ctx["snap0"], sub["snap0"]) if sub else (None, None)


def _counter(snap, name):
    return sum(v for k, v in snap["counters"].items()
               if k == name or k.startswith(name + "{"))


def pages(ctx):
    snap0, snap1 = _quiet(ctx)
    if snap1 is None:
        return None
    name = "preprocessing.embedded_docs"
    n = _counter(snap1, name) - _counter(snap0, name)
    return n if n > 0 else None


def stage_cpu_ms(ctx, *stages):
    """CPU milliseconds the named stage spans took over the quiet part,
    summed; None unless the program counts every one of them. (A delta of
    0 is a reading: the thread clock ticks in 10 ms on the v5e's host.)"""
    snap0, snap1 = _quiet(ctx)
    if snap1 is None:
        return None
    total = 0.0
    for stage in stages:
        name = f"span.{stage}.cpu_ms_total"
        if name not in snap1["counters"]:
            return None
        total += snap1["counters"][name] - snap0["counters"].get(name, 0.0)
    return total


def stage_cpu_ms_per_page(ctx, *stages):
    cpu, n = stage_cpu_ms(ctx, *stages), pages(ctx)
    return None if cpu is None or n is None else cpu / n


def python_cpu_ms(ctx):
    """CPU milliseconds the interpreter's threads used over the quiet part
    (delta of the callback gauge `host.python_cpu_s`)."""
    snap0, snap1 = _quiet(ctx)
    if snap1 is None:
        return None
    a = snap0["gauges"].get("host.python_cpu_s")
    b = snap1["gauges"].get("host.python_cpu_s")
    return None if a is None or b is None or b <= a else (b - a) * 1e3


def histogram_mean(ctx, name):
    """Mean of what histogram `name` observed in the quiet part."""
    snap0, snap1 = _quiet(ctx)
    if snap1 is None or name not in snap1["histograms"]:
        return None
    h0 = snap0["histograms"].get(name, {"count": 0, "sum": 0.0})
    h1 = snap1["histograms"][name]
    n = h1["count"] - h0["count"]
    return (h1["sum"] - h0["sum"]) / n if n > 0 else None
