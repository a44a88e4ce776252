"""The fused embed+top-k executable's share of its roofline in the traced
sub-window: per query the chip has to read the valid corpus rows once at the
precision they rest in (float32) and the encoder's layer weights (float32),
and do the B=1 forward over the query's real tokens plus the scan's FLOPs;
the bound is max(FLOPs / peak, bytes / bandwidth) over the device time of
the `jit_fn` programs (in this cell every one is a fused search)."""
from _common import encoder_dims, module_time


def read(ctx):
    hit = module_time(ctx, r"^jit_fn$")
    if not hit:
        return None
    count, seconds = hit
    H, I, L = encoder_dims(ctx)
    y = ctx["yardstick"]
    rows, dim = ctx["config"]["corpus"]["rows"], ctx["config"]["corpus"]["dim"]
    words = ctx["mix"]["query_words"]["median"]
    flops = y.bert_fwd_flops([words + 2], H, I, L) + y.topk_scan_flops(rows, dim)
    bytes_ = y.topk_scan_bytes(rows, dim, 4.0) + y.encoder_param_bytes(H, I, L, 4.0)
    return 100.0 * count * y.roofline_seconds(flops, bytes_, ctx["peaks"]) / seconds
