"""Whole-step share of the chip's peak: useful FLOPs of the real tokens of
every chunk that became searchable in the window (per token and block
application: the four projections and the SwiGLU; per chunk its own causal
attention; times steps x layers; never the padding, never keys outside the
chunk) / (window x peak FLOP/s)."""
import yardstick_ouro as yo
from _common import page_token_lengths


def read(ctx):
    rows = ctx["rows1"] - ctx["rows0"]
    if rows <= 0 or not ctx["peaks"]:
        return None
    lens = page_token_lengths(ctx)
    flops = yo.forward_flops(lens, ctx["model"]) / len(lens) * rows
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
