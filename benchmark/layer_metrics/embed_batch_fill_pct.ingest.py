"""Mean fill of the embed micro-batcher's flushes over the window
(`batcher.flush_fill_ratio`: sentences taken per flush / max_batch)."""
from _common import histogram_mean_delta


def read(ctx):
    v = histogram_mean_delta(ctx, "batcher.flush_fill_ratio")
    return None if v is None else 100.0 * v
