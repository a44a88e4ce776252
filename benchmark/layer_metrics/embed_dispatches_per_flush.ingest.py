"""Device dispatches one batched encoder call makes: the window's delta of
the counter `engine.embed.dispatches` over the calls of `embed_texts` in it
(the observations `engine.embed.host_ms` took: one per call, and a flush of
the embed micro-batcher is one call). A program that packs a flush's
sentences into full rows reads 1; None where the program has no such
counter."""
from _common import counter_delta


def read(ctx):
    def calls(snap):
        return sum(h["count"] for k, h in snap["histograms"].items()
                   if k.split("{")[0] == "engine.embed.host_ms")

    dispatches = counter_delta(ctx, "engine.embed.dispatches")
    n = calls(ctx["snap1"]) - calls(ctx["snap0"])
    return dispatches / n if dispatches > 0 and n > 0 else None
