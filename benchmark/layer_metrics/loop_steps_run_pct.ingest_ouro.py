"""Share of the published loop the program ran over the window: 100 x
`engine.loop.token_steps_run` / `engine.loop.token_steps_published` (the
program's counters, booked per dispatch from what the device counted: real
tokens once per step the loop ran, against real tokens x the checkpoint's
`total_ut_steps`).

A check, not a target: 100 as published (`early_exit_threshold` 1: every
token runs every step); under 100 a step was skipped for some token, which
is another model until the configuration states a threshold under 1.
`better: higher` in BENCHMARK.json says only which side is sound. Whether
the steps that ran computed the published block is `correct`'s to see
(PERF.md, section 2: the planted loop faults)."""
from _common import counter_delta


def read(ctx):
    published = counter_delta(ctx, "engine.loop.token_steps_published")
    if published <= 0:
        return None
    return 100.0 * counter_delta(ctx, "engine.loop.token_steps_run") / published
