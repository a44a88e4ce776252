"""Busy time of the embed micro-batcher per flush: mean of
`span.batcher.flush.ms` over the window (tokenize, pad, dispatch, fetch; in
this role the embed batcher is the only one)."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "span.batcher.flush.ms")
