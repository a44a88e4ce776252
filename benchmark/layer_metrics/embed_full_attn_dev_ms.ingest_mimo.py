"""Device ms per embed program (one 32,768-token row) of the full
attention layers whole: q, k, v and o and the attention kernel (RoPE,
scores, causal-and-passage mask, softmax, context); ops traced under
`full_attn` inside `symbiont.embed`, per program at the window's rate
(`_mimo.programs_traced`)."""
from _mimo import ms_per_program, scope_seconds


def read(ctx):
    return ms_per_program(ctx, scope_seconds(ctx, ("full_attn",)))
