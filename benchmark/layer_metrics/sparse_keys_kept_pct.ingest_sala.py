"""Share of the causal keys the sparse layers attended to over the window:
100 x `engine.sparse.keys_attended` / `engine.sparse.keys_causal` (the
program's counters, booked per dispatch from what the device counted).

A check that selection is on, not a target: the value is fixed by the
passages' lengths and the published topk and window
(`yardstick_sala.keys_attended` gives it exactly: 35.66 for the cell's page),
100 means every passage took the dense path, and a value under the
yardstick's means fewer keys than published were read, which is another
model. `better: lower` in BENCHMARK.json says only which side of 100 is
sound. It holds the sets' SIZES; which blocks are in them is `correct`'s
to see (PERF.md, section 2: the planted selection faults)."""
from _common import counter_delta


def read(ctx):
    causal = counter_delta(ctx, "engine.sparse.keys_causal")
    if causal <= 0:
        return None
    return 100.0 * counter_delta(ctx, "engine.sparse.keys_attended") / causal
