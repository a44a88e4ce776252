"""Share of the causal keys the window layers attended, over the window:
100 x `engine.attn.window_keys` / `engine.attn.keys_causal` (the keys each
real token's softmax took, as the attention kernel counts them from its own
mask, against position + 1, summed over the window layers).

A check, not a target: sound, the passages' lengths and the published
window fix it (`yardstick_mimo.window_keys_kept_pct` gives the exact value
for a page, 1.2675 at the mix's lengths); 100 means the kernel attended the
whole causal prefix (the planted `window_off`), which is another model."""
from _common import counter_delta


def read(ctx):
    causal = counter_delta(ctx, "engine.attn.keys_causal")
    if causal <= 0:
        return None
    return 100.0 * counter_delta(ctx, "engine.attn.window_keys") / causal
