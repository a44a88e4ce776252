"""Device time of one prefill executable (`jit_prefill`), mean over the
traced sub-window."""
from _common import module_time


def read(ctx):
    hit = module_time(ctx, r"^jit_prefill$")
    return None if not hit else 1e3 * hit[1] / hit[0]
