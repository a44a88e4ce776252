"""Time an item waited in the embed micro-batcher: mean of
`batcher.queue_wait_ms{batcher="embed"}` over the window (submit -> taken
into a chunk)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "batcher.queue_wait_ms",
                                label='batcher="embed"')
