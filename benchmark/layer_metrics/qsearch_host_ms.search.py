"""Host stage of one fused search: mean of `engine.qsearch.host_ms` over the
window (entry -> the dispatch call returns: tokenize, pad, h2d, dispatch)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "engine.qsearch.host_ms")
