"""Mean number of requests in flight (due -> done) over the window, on the
client's clock: how many B=1 streams share the chip."""
from _common import gen_done


def read(ctx):
    done = gen_done(ctx)
    if not done:
        return None
    return sum(r["done_ms"] for r in done if "done_ms" in r) / (1e3 * ctx["seconds"])
