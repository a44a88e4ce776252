"""The window attention kernel against its roofline: the least time for a
page's passages through every window layer's attention core (q.k at
head_dim and p.v for each real token's keys in its window inside its
passage; q, k and v read and the context written once at bfloat16, at the
model's widths: `yardstick_mimo`) over the device time of the Mosaic call
`window_attention` under `symbiont.embed` > `swa` per page. Bandwidth-bound:
at 128 keys a query the core is ~94 FLOPs a byte, under the v5e's 240."""
import yardstick_mimo as ym
from _mimo import kernel_seconds, page_roofline


def read(ctx):
    def work(lens, m):
        layers = ym.layer_kinds(m)[0]
        return (layers * ym.window_attn_flops(lens, m),
                layers * ym.attn_core_bytes(lens, m, True))

    return page_roofline(ctx, kernel_seconds(ctx, "swa"), work)
