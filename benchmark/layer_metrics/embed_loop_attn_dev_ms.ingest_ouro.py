"""Device ms per embed program (one [8, 512] dispatch: steps x layers block
applications) of the attention sub-layer (both norms, q/k/v/o, RoPE,
scores, softmax, context): ops traced under `loop_attn` inside
`symbiont.embed`, the loops' own events left out (`_sala.scope_seconds`),
per `jit_fn` program of the traced sub-window."""
from _sala import ms_per_program


def read(ctx):
    return ms_per_program(ctx, ("loop_attn",))
