"""The embed executables' share of their roofline in the traced sub-window:
the least time the chip could take for the REAL tokens of the sentences
embedded there (padding is not useful work) over the device time of the
`jit_fn` programs (in this role every one of them is an embed forward).
Bound: max(useful FLOPs / peak, layer weights read once per dispatch at
float32 / bandwidth)."""
from _common import counter_delta, encoder_dims, module_time, page_token_lengths


def read(ctx):
    hit = module_time(ctx, r"^jit_fn$")
    if not hit:
        return None
    rows = counter_delta(ctx, "preprocessing.embedded_sentences", trace=True)
    if rows <= 0:
        return None
    count, seconds = hit
    H, I, L = encoder_dims(ctx)
    y = ctx["yardstick"]
    lens = page_token_lengths(ctx)
    flops = y.bert_fwd_flops(lens, H, I, L) / len(lens) * rows
    bytes_ = y.encoder_param_bytes(H, I, L, 4.0) * count
    return 100.0 * y.roofline_seconds(flops, bytes_, ctx["peaks"]) / seconds
