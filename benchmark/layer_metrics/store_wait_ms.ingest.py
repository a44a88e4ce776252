"""Time a message waited for the store: mean of `coalesce.wait_ms` over the
window (`add()` -> the flush that carries it starts its store call: the age
window plus the queue behind earlier flushes)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "coalesce.wait_ms")
