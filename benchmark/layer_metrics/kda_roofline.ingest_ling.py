"""The KDA layers' recurrence against its roofline: the least time the chip
could take for a page's passages through every KDA layer (6 d_k d_v FLOPs a
token and head, the token-by-token form; q, k, v and the output moved once
at bfloat16 and the log-decays once at float32: `yardstick_ling`) over the
device time under `symbiont.embed` > `kda` > `delta_rule` per page (the
chunked form: the intra-chunk products, the triangular solve, the scan over
chunks carrying the float32 state). Bandwidth-bound: 6 d^2 FLOPs against
12 d bytes a token and head is 64 FLOPs a byte at d = 128, under the v5e's
240."""
import yardstick_ling as yl
from _ling import page_roofline


def read(ctx):
    def work(lens, m, _programs):
        kda = yl.layer_kinds(m)[0]
        return (kda * yl.kda_rule_flops(lens, m),
                kda * yl.kda_rule_bytes(lens, m))

    return page_roofline(ctx, ("delta_rule",), work)
