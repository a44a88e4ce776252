"""Wall milliseconds per device dispatch of the batched encoder
(`engine.embed.dispatch`: the executable looked up, ids and segment lengths
moved to the device, the call returning: h2d + enqueue)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "span.engine.embed.dispatch.ms")
