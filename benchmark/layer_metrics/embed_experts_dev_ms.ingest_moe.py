"""Device ms per embed program of the routed expert layers (sort, gathers,
three grouped matmuls, weighted sum): ops traced under `experts` inside
`symbiont.embed` and the `ragged-dot-*` kernels (`_moe.scope_seconds`), per
`jit_fn` program of the traced sub-window."""
from _moe import ms_per_program


def read(ctx):
    return ms_per_program(ctx, "experts")
