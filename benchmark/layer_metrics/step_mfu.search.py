"""Whole-step share of the chip's peak: useful FLOPs (query forward over its
real tokens + the scan over the valid rows) of every search the window
answered / (window x peak FLOP/s)."""
from _common import encoder_dims


def read(ctx):
    ok = [r for r in ctx["client"].get("records", []) if r["ok"]]
    if not ok or not ctx["peaks"]:
        return None
    H, I, L = encoder_dims(ctx)
    y = ctx["yardstick"]
    c = ctx["config"]["corpus"]
    lens = [ctx["arch"].token_count(ctx["plan"]["window"][r["i"]]["query_text"],
                                    ctx["config"]["max_tokens"]) for r in ok]
    flops = y.bert_fwd_flops(lens, H, I, L) + len(ok) * y.topk_scan_flops(
        c["rows"], c["dim"])
    return 100.0 * flops / (ctx["seconds"] * ctx["peaks"]["bf16_flops"])
