"""Busy time of the store per coalesced flush: mean of the coalescer's
`span.vector_memory.flush.ms` over the window (one store call: append + WAL
+ fsync; no wait)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "span.vector_memory.flush.ms")
