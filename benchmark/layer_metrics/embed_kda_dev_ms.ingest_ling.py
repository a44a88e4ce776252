"""Device ms per embed program (one 32,768-token row) of the KDA mixers,
whole: q, k, v with their short convolutions, the gates, the chunked delta
rule (`kda/delta_rule`), the output norm and projections; ops traced under
`kda` inside `symbiont.embed`, the loops' own events left out
(`_ling.scope_seconds`), per `jit_fn` program of the traced sub-window."""
from _ling import ms_per_program


def read(ctx):
    return ms_per_program(ctx, ("kda",))
