"""Whole-step share of the chip's peak: useful model FLOPs of every request
the window finished (causal prefill of its real prompt + one step per
delivered token at its cache length) / (window x peak FLOP/s)."""
from _common import gen_done, gpt_dims


def read(ctx):
    done = gen_done(ctx)
    if not done or not ctx["peaks"]:
        return None
    H, I, L, V = gpt_dims(ctx)
    y = ctx["yardstick"]
    flops = 0.0
    for r in done:
        p, n = r["prompt_tokens"], r["asked"]
        flops += y.gpt_prefill_flops([p], H, I, L, V)
        flops += y.gpt_token_flops([p + k for k in range(n)], H, I, L, V)
    return 100.0 * flops / (ctx["seconds"] * ctx["peaks"]["bf16_flops"])
