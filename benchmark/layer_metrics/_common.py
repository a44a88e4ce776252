"""Helpers the per-layer readers share. A reader is `read(ctx) -> float or
None`; None means there was nothing to read, and the harness leaves the
metric out of the line (it never reports 0 for a share of a peak).

`ctx` (run.py): cell, config, model, mix, plan, client (the client's
record), window_s, snap0/snap1 (the program's /api/metrics view at the
window's ends), trace (trace_reduce.reduce of the traced sub-window, with
`snap0`/`snap1` taken at ITS ends), peaks, yardstick, arch.
"""


def counter_delta(ctx, name, trace=False):
    a, b = ((ctx["trace"]["snap0"], ctx["trace"]["snap1"]) if trace
            else (ctx["snap0"], ctx["snap1"]))

    def total(snap):
        return sum(v for k, v in snap["counters"].items()
                   if k == name or k.startswith(name + "{"))

    return total(b) - total(a)


def histogram_mean_delta(ctx, name, label=""):
    """Mean of the observations a histogram family took during the window
    (all label sets of `name` whose rendered key contains `label`)."""
    def totals(snap):
        hit = [h for k, h in snap["histograms"].items()
               if (k == name or k.startswith(name + "{")) and label in k]
        return sum(h["count"] for h in hit), sum(h["sum"] for h in hit)

    (n0, s0), (n1, s1) = totals(ctx["snap0"]), totals(ctx["snap1"])
    return (s1 - s0) / (n1 - n0) if n1 > n0 else None


def module_time(ctx, pattern):
    """(count, seconds) on the device of the XLA programs matching
    `pattern` in the traced sub-window; None without a trace or a match."""
    import re

    if not ctx.get("trace"):
        return None
    rx = re.compile(pattern)
    hit = [m for n, m in ctx["trace"]["modules"].items() if rx.search(n)]
    count = sum(m["count"] for m in hit)
    return (count, sum(m["seconds"] for m in hit)) if count else None


def encoder_dims(ctx):
    m = ctx["model"]
    return m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]


def gpt_dims(ctx):
    m = ctx["model"]
    H = m["n_embd"]
    return H, m.get("n_inner") or 4 * H, m["n_layer"], m["vocab_size"]


def stream_chunk(ctx):
    """Decode steps one chunk executable computes (`lm.stream_chunk`)."""
    return int(ctx["config"]["env"].get("SYMBIONT_LM_STREAM_CHUNK", 16))


def page_token_lengths(ctx):
    """Real encoder tokens of each sentence of one page: every page of a
    mix holds the same multiset of lengths, so one page stands for all."""
    from kinds import ingest

    return [ctx["arch"].token_count(s, ctx["config"]["max_tokens"])
            for s in ingest.page_sentences(ctx["mix"], 0, 0)]


def gen_done(ctx):
    return [r for r in ctx["client"].get("records", []) if r["ok"]]
