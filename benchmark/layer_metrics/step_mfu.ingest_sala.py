"""Whole-step share of the chip's peak: useful FLOPs of the real tokens of
every passage that became searchable in the window (per token: the mixers'
projections, the feed-forward, the keys of its selected set and the visible
compressed keys in a sparse layer, the recurrence in a linear layer) /
(window x peak FLOP/s)."""
import yardstick_sala as ys
from _common import page_token_lengths


def read(ctx):
    rows = ctx["rows1"] - ctx["rows0"]
    if rows <= 0 or not ctx["peaks"]:
        return None
    lens = page_token_lengths(ctx)
    flops = ys.forward_flops(lens, ctx["model"]) / len(lens) * rows
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
