"""Share of the full-attention kernel's grid steps under the diagonal that
computed a key block, over the window: 100 x `engine.attn.block_steps_run`
/ `engine.attn.block_steps_causal` (the program's counters, booked per
dispatch from the bounds the kernel walks: each query block from the first
key block whose passages can meet its own to its diagonal, nothing for a
block of padding; against every block under the diagonal).

Lower is less work the mask throws away; 100 is a kernel that walks every
block under the diagonal. The page's packing fixes it (about 50 at the
`ingest_longdocs` page). None where the program has no such counters."""
from _common import counter_delta


def read(ctx):
    causal = counter_delta(ctx, "engine.attn.block_steps_causal")
    if causal <= 0:
        return None
    return 100.0 * counter_delta(ctx, "engine.attn.block_steps_run") / causal
