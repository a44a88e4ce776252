"""The routed experts against their roofline in the traced sub-window, as
`experts_roofline.ingest_moe` counts them: the least time for the (real
token, held expert) pairs the program computed there
(`engine.moe.assignments`; padding and a choice of an expert another chip
holds are not this chip's work) over the device time under
`symbiont.embed` > `experts` (the grouped matmuls, the sort, the gathers and
the weighted sum) and the compiler's `ragged-dot-*` kernels. Bound: max(FLOPs
/ peak, the kernels of the held experts that got at least one real token,
read once per dispatch and layer at bfloat16 / bandwidth)."""
import yardstick_ling as yl
from _ling import scope_seconds
from _moe import WEIGHT_BYTES, dispatch_layers, trace_delta


def read(ctx):
    pairs, seconds = dispatch_layers(ctx), scope_seconds(ctx, ("experts",))
    assignments = trace_delta(ctx, "engine.moe.assignments")
    if not pairs or not seconds or assignments <= 0:
        return None
    m = ctx["model"]
    active = pairs * yl.held(m) - trace_delta(ctx, "engine.moe.experts_idle")
    least = ctx["yardstick"].roofline_seconds(
        yl.routed_flops(assignments, m),
        active * yl.expert_params(m) * WEIGHT_BYTES, ctx["peaks"])
    return 100.0 * least / seconds
