"""The decode executables' share of their roofline: the least time one
decode step of one sequence could take at the mean live cache length
(weights and the tied head read once in bfloat16, the live KV read once;
FLOPs never bound a B=1 step) over the device time per USEFUL step. A step
the chunk computed past a request's budget is not useful work, so the
device time per computed step is scaled by computed / delivered tokens."""
from _common import gen_done, gpt_dims, module_time, stream_chunk


def read(ctx):
    hit = module_time(ctx, r"decode_chunk")
    done = gen_done(ctx)
    if not hit or not done:
        return None
    H, I, L, V = gpt_dims(ctx)
    y = ctx["yardstick"]
    chunk = stream_chunk(ctx)
    delivered = sum(r["asked"] for r in done)
    computed = sum(-(-r["asked"] // chunk) * chunk for r in done)
    mean_len = sum(r["prompt_tokens"] + r["asked"] / 2.0 for r in done) / len(done)
    flops = y.gpt_token_flops([mean_len], H, I, L, V)
    bytes_ = y.gpt_decode_step_bytes([mean_len], H, I, L, V, 2.0, 2.0)
    per_useful_step = hit[1] / (hit[0] * chunk) * computed / delivered
    return 100.0 * y.roofline_seconds(flops, bytes_, ctx["peaks"]) / per_useful_step
