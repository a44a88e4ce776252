"""The sparse layers' selection and attention against their roofline: the
least time the chip could take for a page's passages (every query head over
the visible compressed keys and over the keys of its set, never the causal
prefix; q, K, V, the compressed keys and the context moved once) over the
device time under `symbiont.embed` > `sparse_select` and `sparse_attn` per
page (`_sala.roofline`). Compute-bound at these lengths."""
import yardstick_sala as ys
from _sala import roofline


def read(ctx):
    return roofline(ctx, ("sparse_select", "sparse_attn"), ys.sparse_flops,
                    ys.sparse_bytes, ys.SPARSE)
