"""Device ms per embed program (one [8, 512] dispatch: steps x layers block
applications) of the feed-forward sub-layer (both norms and the SwiGLU):
ops traced under `loop_ffn` inside `symbiont.embed`, the loops' own events
left out (`_sala.scope_seconds`), per `jit_fn` program of the traced
sub-window."""
from _sala import ms_per_program


def read(ctx):
    return ms_per_program(ctx, ("loop_ffn",))
