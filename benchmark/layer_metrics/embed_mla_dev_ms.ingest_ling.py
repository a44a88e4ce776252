"""Device ms per embed program (one 32,768-token row) of the MLA layer
whole: the projections, the QK-norms, RoPE, the segment-masked flash kernel
(its Mosaic call carries the scope) and the head gate; ops traced under
`mla` inside `symbiont.embed`, per `jit_fn` program of the traced
sub-window (`_ling.scope_seconds`)."""
from _ling import ms_per_program


def read(ctx):
    return ms_per_program(ctx, ("mla",))
