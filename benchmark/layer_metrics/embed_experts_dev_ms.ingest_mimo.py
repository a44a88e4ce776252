"""Device ms per embed program (one 32,768-token row) of the routed
experts of every expert layer: the sort by expert, the gathers, the grouped
matmuls and the weighted sum, in 8,192-token blocks; ops traced under
`experts` inside `symbiont.embed` and the compiler's `ragged-dot-*`
kernels, the loops' own events left out, per program at the window's rate
(`_mimo.programs_traced`)."""
from _mimo import ms_per_program, scope_seconds


def read(ctx):
    return ms_per_program(ctx, scope_seconds(ctx, ("experts",)))
