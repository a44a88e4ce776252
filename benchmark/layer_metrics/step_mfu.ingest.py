"""Whole-step share of the chip's peak: useful encoder FLOPs of every row
that became searchable in the window / (window x peak FLOP/s)."""
from _common import encoder_dims, page_token_lengths


def read(ctx):
    rows = ctx["rows1"] - ctx["rows0"]
    if rows <= 0 or not ctx["peaks"]:
        return None
    H, I, L = encoder_dims(ctx)
    lens = page_token_lengths(ctx)
    flops = ctx["yardstick"].bert_fwd_flops(lens, H, I, L) / len(lens) * rows
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
