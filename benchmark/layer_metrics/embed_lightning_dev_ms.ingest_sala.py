"""Device ms per embed program (one 32,768-token row) of the linear layers'
chunked recurrence (RoPE, the scan, the output norm): ops traced under
`lightning` inside `symbiont.embed`, the loops' own events left out
(`_sala.scope_seconds`), per `jit_fn` program of the traced sub-window."""
from _sala import ms_per_program


def read(ctx):
    return ms_per_program(ctx, ("lightning",))
