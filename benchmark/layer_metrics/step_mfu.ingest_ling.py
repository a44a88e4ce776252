"""Whole-step share of the chip's peak: useful FLOPs of the real tokens of
every passage that became searchable in the window (per token: the KDA and
MLA projections, the token-by-token recurrence, each passage's own causal
attention, the dense feed-forward, the router and the shared expert) plus
the routed experts' (token, held expert) pairs the program computed in the
window (`engine.moe.assignments`; a choice of an expert another chip holds is
not this chip's work) / (window x peak FLOP/s)."""
import yardstick_ling as yl
from _common import counter_delta, page_token_lengths


def read(ctx):
    rows = ctx["rows1"] - ctx["rows0"]
    pairs = counter_delta(ctx, "engine.moe.assignments")
    if rows <= 0 or pairs <= 0 or not ctx["peaks"]:
        return None
    lens, m = page_token_lengths(ctx), ctx["model"]
    flops = (yl.forward_flops(lens, m) / len(lens) * rows
             + yl.routed_flops(pairs, m))
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
