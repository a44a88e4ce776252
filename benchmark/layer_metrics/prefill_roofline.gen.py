"""The prefill executables' share of their roofline: the least time a
causal prefill of the mean REAL prompt could take (FLOPs of the real
tokens; weights read once in bfloat16) over the mean device time of
`jit_prefill`."""
from _common import gen_done, gpt_dims, module_time


def read(ctx):
    hit = module_time(ctx, r"^jit_prefill$")
    done = gen_done(ctx)
    if not hit or not done:
        return None
    H, I, L, V = gpt_dims(ctx)
    y = ctx["yardstick"]
    mean_prompt = sum(r["prompt_tokens"] for r in done) / len(done)
    flops = y.gpt_prefill_flops([mean_prompt], H, I, L, V)
    bytes_ = (L * (4.0 * H * H + 2.0 * H * I) + H * V) * 2.0
    return 100.0 * y.roofline_seconds(flops, bytes_, ctx["peaks"]) / (hit[1] / hit[0])
