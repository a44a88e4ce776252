"""Device-wait stage of one batched encoder call: mean of
`engine.embed.device_wait_ms` over the window (last dispatch returned ->
every result on the host)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "engine.embed.device_wait_ms")
