"""Host stage of one batched encoder call: mean of `engine.embed.host_ms`
over the window (entry -> the last dispatch returned: tokenize, pad, h2d,
dispatch)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "engine.embed.host_ms")
