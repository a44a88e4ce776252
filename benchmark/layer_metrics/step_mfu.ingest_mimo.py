"""Whole-step share of the chip's peak: useful FLOPs of the real tokens of
every passage that became searchable in the window (per token: the window
and full layers' projections, each token's window keys or its passage's
causal keys at head_dim, the dense feed-forward and the router) plus the
routed experts' (token, held expert) pairs the program computed in the
window (`engine.moe.assignments`; a choice of an expert another chip holds
is not this chip's work) / (window x peak FLOP/s)."""
import yardstick_mimo as ym
from _common import counter_delta, page_token_lengths


def read(ctx):
    rows = ctx["rows1"] - ctx["rows0"]
    pairs = counter_delta(ctx, "engine.moe.assignments")
    if rows <= 0 or pairs <= 0 or not ctx["peaks"]:
        return None
    lens, m = page_token_lengths(ctx), ctx["model"]
    flops = (ym.forward_flops(lens, m) / len(lens) * rows
             + ym.routed_flops(pairs, m))
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
