"""CPU milliseconds per page planning and packing a flush's rows
(`engine.embed.pack`: `plan_packed` once a call, then per dispatch
`pack_rows`, the padding counters and the index lists)."""
from _stages import stage_cpu_ms_per_page


def read(ctx):
    return stage_cpu_ms_per_page(ctx, "engine.embed.pack")
