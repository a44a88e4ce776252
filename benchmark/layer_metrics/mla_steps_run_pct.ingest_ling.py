"""Share of the MLA kernel's (query block, key block) pairs under the
diagonal that its grid walked, over the window: 100 x
`engine.attn.block_steps_run` / `engine.attn.block_steps_causal` (the
program's counters, booked per dispatch from the list the streaming kernel
is handed: each query block's pairs from the first key block whose
passages can meet its own to its diagonal, nothing for a block of padding;
against every pair under the diagonal).

Lower is less work the mask throws away; 100 is a kernel that walks every
pair under the diagonal. The page's packing fixes it (51.08 at the
`ingest_longdocs` page). None where the program has no such counters."""
from _common import counter_delta


def read(ctx):
    causal = counter_delta(ctx, "engine.attn.block_steps_causal")
    if causal <= 0:
        return None
    return 100.0 * counter_delta(ctx, "engine.attn.block_steps_run") / causal
