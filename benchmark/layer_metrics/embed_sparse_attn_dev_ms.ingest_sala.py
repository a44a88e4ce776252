"""Device ms per embed program (one 32,768-token row) of the sparse layers'
selection and attention: ops traced under `sparse_select` and `sparse_attn`
inside `symbiont.embed`, the loops' own events left out
(`_sala.scope_seconds`), per `jit_fn` program of the traced sub-window."""
from _sala import ms_per_program


def read(ctx):
    return ms_per_program(ctx, ("sparse_select", "sparse_attn"))
