"""Device ms per query of the fused search's top-k: ops traced under `topk`
inside `symbiont.qsearch`, per `jit_fn` program of the traced sub-window."""
from _scopes import ms_per_program


def read(ctx):
    return ms_per_program(ctx, "symbiont.qsearch", ("topk",))
