"""Wall milliseconds per fused search in `engine.qsearch.dispatch`: the
executable looked up, ids and mask moved to the device, the call returning.
Second part of `qsearch_host_ms.search`."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "span.engine.qsearch.dispatch.ms")
