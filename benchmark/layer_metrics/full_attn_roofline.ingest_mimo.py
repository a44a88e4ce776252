"""The full attention kernel against its roofline: the least time for a
page's passages through every full layer's attention core (q.k at
head_dim and p.v for each real token's causal keys in its own passage,
never another passage's keys or the padding; q, k and v read and the
context written once at bfloat16: `yardstick_mimo`) over the device time
of the Mosaic call `grouped_attention` under `symbiont.embed` >
`full_attn` per page. Compute-bound at a passage of thousands of tokens."""
import yardstick_mimo as ym
from _mimo import kernel_seconds, page_roofline


def read(ctx):
    def work(lens, m):
        layers = ym.layer_kinds(m)[1]
        return (layers * ym.full_attn_flops(lens, m),
                layers * ym.attn_core_bytes(lens, m, False))

    return page_roofline(ctx, kernel_seconds(ctx, "full_attn"), work)
