"""Mean of `span.vector_memory.upsert.ms` per upserted message over the
window (host clock inside the program: the store's append + WAL + fsync)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "span.vector_memory.upsert.ms")
