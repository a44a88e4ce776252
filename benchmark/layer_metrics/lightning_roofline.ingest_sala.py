"""The linear layers' recurrence against its roofline: the least time the
chip could take for a page's passages (d x d multiply-adds into and out of
the state per token and head; q, k, v and the output moved once) over the
device time under `symbiont.embed` > `lightning` per page
(`_sala.roofline`). Bandwidth-bound: the recurrence is 16 FLOPs a byte."""
import yardstick_sala as ys
from _sala import roofline


def read(ctx):
    return roofline(ctx, ("lightning",), ys.lightning_flops,
                    ys.lightning_bytes, ys.LINEAR)
