"""What a search spends at the gateway and on the bus: mean
`span.api.search.ms` less mean `span.engine.query.search.ms` over the window
(gateway work, both bus hops, the handler's decode)."""
from _common import histogram_mean_delta


def read(ctx):
    outer = histogram_mean_delta(ctx, "span.api.search.ms")
    inner = histogram_mean_delta(ctx, "span.engine.query.search.ms")
    return None if outer is None or inner is None else outer - inner
