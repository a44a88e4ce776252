"""Device ms per query of the fused search's encoder part: ops traced under
`embeddings`, `encoder` or `pool` inside `symbiont.qsearch`, per `jit_fn`
program of the traced sub-window."""
from _scopes import ms_per_program


def read(ctx):
    return ms_per_program(ctx, "symbiont.qsearch",
                          ("embeddings", "encoder", "pool"))
