"""The MLA layers against their roofline: the least time for a page's
passages (the five projections per real token and each passage's own causal
attention over nope + rope and the values: `yardstick_ling.mla_flops`; the
layer's kernels read once per dispatch at bfloat16) over the device time
under `symbiont.embed` > `mla` per page (the projections, the QK-norms,
RoPE, the segment-masked flash kernel, the head gate)."""
import yardstick_ling as yl
from _ling import page_roofline
from _moe import WEIGHT_BYTES


def read(ctx):
    def work(lens, m, programs):
        mla = yl.layer_kinds(m)[1]
        return (mla * yl.mla_flops(lens, m),
                mla * programs * yl.mla_params(m) * WEIGHT_BYTES)

    return page_roofline(ctx, ("mla",), work)
