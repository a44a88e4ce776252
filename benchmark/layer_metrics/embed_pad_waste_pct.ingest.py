"""Share of the token slots the encoder computed over the window that were
padding: `engine.tokens_padding` / (`engine.tokens_real` + padding)."""
from _common import counter_delta


def read(ctx):
    pad = counter_delta(ctx, "engine.tokens_padding")
    real = counter_delta(ctx, "engine.tokens_real")
    return 100.0 * pad / (pad + real) if pad + real > 0 else None
