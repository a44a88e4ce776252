"""Device ms per embed program of the MLA blocks (norm, projections, RoPE,
attention): ops traced under `mla` inside `symbiont.embed`, per `jit_fn`
program of the traced sub-window."""
from _moe import ms_per_program


def read(ctx):
    return ms_per_program(ctx, "mla")
