"""Share of the traced window in which the device idled while a coalesced
store flush (`symbiont.vector_memory.flush`) was open."""
from _host_spans import idle_inside_pct


def read(ctx):
    return idle_inside_pct(ctx, "vector_memory.flush")
