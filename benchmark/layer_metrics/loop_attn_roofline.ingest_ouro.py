"""The looped block's attention sub-layer against its roofline: the least
time the chip could take for a page's chunks through steps x layers
applications (q, k, v, o per real token and each chunk's own causal
attention against the peak; the four kernels once per application and
dispatch and the float32 stream once against the bandwidth) over the
device time under `symbiont.embed` > `loop_attn` per page, which also holds
both attention norms, RoPE, the mask and the softmax (`_ouro.roofline`).
Compute-bound at a page's 3,565 tokens."""
import yardstick_ouro as yo
from _ouro import roofline


def read(ctx):
    return roofline(ctx, "loop_attn", yo.attn_flops, yo.attn_bytes)
