"""Share of the traced window in which the device idled while an embed
batcher flush (`symbiont.batcher.flush`) was open: host work of the flush
itself (tokenize, pad, fetch), not a wait for pages."""
from _host_spans import idle_inside_pct


def read(ctx):
    return idle_inside_pct(ctx, "batcher.flush")
