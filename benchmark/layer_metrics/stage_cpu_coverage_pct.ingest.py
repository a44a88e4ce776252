"""Share of the interpreter's CPU time that stands in a named stage: the
CPU milliseconds of the ten ingest stage spans (`_stages.py`) over the
delta of `host.python_cpu_s`, both over the quiet part of the window,
before the profiler starts. What is left is Python outside every stage: the
bus, the handlers between their sections, the batcher and the coalescer,
the gateway, the spans' own bookkeeping, the fetches."""
from _stages import INGEST_STAGES, python_cpu_ms, stage_cpu_ms


def read(ctx):
    staged = stage_cpu_ms(ctx, *INGEST_STAGES)
    cpu = python_cpu_ms(ctx)
    return None if staged is None or cpu is None else 100.0 * staged / cpu
