"""The looped block's feed-forward sub-layer against its roofline: the
least time the chip could take for a page's chunks through steps x layers
applications of the SwiGLU (6 H I FLOPs a real token against the peak; the
three kernels once per application and dispatch and the float32 stream once
against the bandwidth) over the device time under `symbiont.embed` >
`loop_ffn` per page, which also holds both feed-forward norms
(`_ouro.roofline`). Compute-bound at a page's 3,565 tokens."""
import yardstick_ouro as yo
from _ouro import roofline


def read(ctx):
    return roofline(ctx, "loop_ffn", yo.ffn_flops, yo.ffn_bytes)
