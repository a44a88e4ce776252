"""The MLA blocks against their roofline in the traced sub-window: the least
time for the REAL tokens embedded there (projections, and causal attention
at the page's sentence lengths) over the device time under `symbiont.embed`
> `mla`. Bound: max(FLOPs / peak, each block's four kernels read once per
dispatch at bfloat16 / bandwidth)."""
import yardstick_mla_moe as ym
from _common import page_token_lengths
from _moe import (WEIGHT_BYTES, dispatch_layers, moe_layers, scope_seconds,
                  trace_delta)


def read(ctx):
    pairs, seconds = dispatch_layers(ctx), scope_seconds(ctx, "mla")
    tokens = trace_delta(ctx, "engine.tokens_real")
    if not pairs or not seconds or tokens <= 0:
        return None
    m = ctx["model"]
    lens = page_token_lengths(ctx)
    L = m["num_hidden_layers"]
    flops = L * ym.mla_flops(lens, m) / sum(lens) * tokens
    dispatches = pairs / moe_layers(ctx)
    bytes_ = dispatches * L * ym.mla_params(m) * WEIGHT_BYTES
    least = ctx["yardstick"].roofline_seconds(flops, bytes_, ctx["peaks"])
    return 100.0 * least / seconds
