"""The routed experts' three grouped matmuls against their roofline in the
traced sub-window: the least time the chip could take for the (real token,
expert) pairs the program computed there (`engine.moe.assignments`; padding
is not useful work) over the device time of the `ragged-dot-*` kernels the
compiler makes of the grouped matmuls and of the ops under `symbiont.embed`
> `experts` (the sort, the gathers and the weighted sum: the layer's cost,
not its useful work; `_moe.scope_seconds`). Bound: max(FLOPs / peak, the kernels of
the experts that got at least one real token, read once per dispatch and
layer at bfloat16 / bandwidth): an idle expert's bytes are not counted."""
import yardstick_mla_moe as ym
from _moe import WEIGHT_BYTES, dispatch_layers, scope_seconds, trace_delta


def read(ctx):
    pairs, seconds = dispatch_layers(ctx), scope_seconds(ctx, "experts")
    assignments = trace_delta(ctx, "engine.moe.assignments")
    if not pairs or not seconds or assignments <= 0:
        return None
    m = ctx["model"]
    active = (pairs * m["n_routed_experts"]
              - trace_delta(ctx, "engine.moe.experts_idle"))
    least = ctx["yardstick"].roofline_seconds(
        ym.routed_flops(assignments, m),
        active * ym.expert_params(m) * WEIGHT_BYTES, ctx["peaks"])
    return 100.0 * least / seconds
