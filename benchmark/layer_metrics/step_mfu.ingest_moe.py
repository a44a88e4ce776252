"""Whole-step share of the chip's peak: useful FLOPs of the real tokens of
every row that became searchable in the window (per token: the MLA
projections and attention over its causal prefix, and the dense FFN or the
router + 6 routed + the shared experts) / (window x peak FLOP/s)."""
import yardstick_mla_moe as ym
from _common import page_token_lengths


def read(ctx):
    rows = ctx["rows1"] - ctx["rows0"]
    if rows <= 0 or not ctx["peaks"]:
        return None
    lens = page_token_lengths(ctx)
    flops = ym.forward_flops(lens, ctx["model"]) / len(lens) * rows
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
