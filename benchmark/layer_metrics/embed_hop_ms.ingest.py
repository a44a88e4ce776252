"""What a flush of the embed micro-batcher spends outside the engine's
call: mean `span.batcher.flush.ms` (as `embed_flush_ms.ingest` reads it)
less mean `span.engine.embed.ms`: the hop onto a pool thread and the event
loop getting round to the flush's continuation. With `embed_host_ms.ingest`
and `embed_device_wait_ms.ingest` it adds up to `embed_flush_ms.ingest`."""
from _common import histogram_mean_delta


def read(ctx):
    outer = histogram_mean_delta(ctx, "span.batcher.flush.ms")
    inner = histogram_mean_delta(ctx, "span.engine.embed.ms")
    return None if outer is None or inner is None else outer - inner
