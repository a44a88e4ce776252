"""How unevenly the router loads the experts: mean over the window of
`engine.moe.expert_load_max_over_mean` (one observation per dispatch and
expert layer: the busiest expert's real tokens over the mean expert's; 1 is
even). The grouped matmul's tiles follow the busiest experts."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "engine.moe.expert_load_max_over_mean")
