"""Share of the traced window in which the device idled while at least one
search was open at the gateway (`symbiont.api.search`): idle time with work
waiting, as against idle time with no query in flight."""
from _host_spans import idle_inside_pct


def read(ctx):
    return idle_inside_pct(ctx, "api.search")
