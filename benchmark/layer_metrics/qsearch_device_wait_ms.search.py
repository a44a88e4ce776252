"""Device-wait stage of one fused search: mean of
`engine.qsearch.device_wait_ms` over the window (dispatch returned -> both
results on the host: queueing behind other queries on the device included)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "engine.qsearch.device_wait_ms")
