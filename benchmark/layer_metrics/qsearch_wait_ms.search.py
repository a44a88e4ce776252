"""What a search spends in the engine service around the engine's call: mean
`span.engine.query.search.ms` less mean `span.engine.qsearch.ms` over the
window (executor queue, store lock, hits assembly, reply)."""
from _common import histogram_mean_delta


def read(ctx):
    outer = histogram_mean_delta(ctx, "span.engine.query.search.ms")
    inner = histogram_mean_delta(ctx, "span.engine.qsearch.ms")
    return None if outer is None or inner is None else outer - inner
