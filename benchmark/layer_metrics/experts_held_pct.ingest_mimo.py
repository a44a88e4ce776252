"""Share of the routed choices that name an expert this chip holds, over
the window: 100 x `engine.moe.assignments` / `engine.moe.assignments_routed`
(every real token's 8 choices in every expert layer, held or not).

A check, not a target: under routing close to uniform it reads the held
share of the experts (16 of 256: ~6.25); 100 means every choice was made
among the held experts alone, i.e. the router or its weights were cut to
the share, which is another model. Whether the held choices got the
router's own weights is `correct`'s to see (PERF.md, section 2: the
planted renormalisation)."""
from _common import counter_delta


def read(ctx):
    routed = counter_delta(ctx, "engine.moe.assignments_routed")
    if routed <= 0:
        return None
    return 100.0 * counter_delta(ctx, "engine.moe.assignments") / routed
