"""Device time of the decode-chunk executables (XLA programs named `*decode_chunk*`) per
decode step they computed (a chunk is `stream_chunk` steps)."""
from _common import module_time, stream_chunk


def read(ctx):
    hit = module_time(ctx, r"decode_chunk")
    if not hit:
        return None
    chunk = stream_chunk(ctx)
    return 1e3 * hit[1] / (hit[0] * chunk)
