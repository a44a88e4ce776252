"""The routed experts against their roofline, per embed program: the least
time for the (real token, held expert) pairs a program computed
(`engine.moe.assignments` over the window's dispatches; padding and a
choice of an expert another chip holds are not this chip's work) over the
device time under `symbiont.embed` > `experts` (the grouped matmuls, the
sort, the gathers and the weighted sum) and the compiler's `ragged-dot-*`
kernels per program. Bound: max(FLOPs / peak, the kernels of the held
experts that got at least one real token, read once per dispatch and layer
at bfloat16 / bandwidth)."""
import yardstick_mimo as ym
from _common import counter_delta
from _mimo import ms_per_program, scope_seconds
from _moe import WEIGHT_BYTES


def _layer_fetches(ctx):
    """(dispatch, expert layer) pairs over the window: the load histogram
    takes one observation for each that got a real token."""
    def count(snap):
        return sum(h["count"] for k, h in snap["histograms"].items()
                   if k.startswith("engine.moe.expert_load_max_over_mean"))

    return count(ctx["snap1"]) - count(ctx["snap0"])


def read(ctx):
    ms = ms_per_program(ctx, scope_seconds(ctx, ("experts",)))
    programs = counter_delta(ctx, "engine.embed.dispatches")
    pairs = counter_delta(ctx, "engine.moe.assignments")
    if not ms or programs <= 0 or pairs <= 0 or not ctx["peaks"]:
        return None
    m = ctx["model"]
    active = (_layer_fetches(ctx) * ym.held(m)
              - counter_delta(ctx, "engine.moe.experts_idle"))
    least = ctx["yardstick"].roofline_seconds(
        ym.routed_flops(pairs / programs, m),
        active / programs * ym.expert_params(m) * WEIGHT_BYTES, ctx["peaks"])
    return 100.0 * least / (1e-3 * ms)
