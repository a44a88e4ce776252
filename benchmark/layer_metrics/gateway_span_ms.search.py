"""Mean of the gateway's own `span.api.search.ms` over the window."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "span.api.search.ms")
