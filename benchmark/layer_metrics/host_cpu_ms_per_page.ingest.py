"""CPU milliseconds of the interpreter's threads per page: the delta of
`host.python_cpu_s` (the CPU clocks of the threads `threading` knows,
summed; the runtime's own threads are not in it) over the pages embedded,
both over the quiet part of the window, before the profiler starts
(`_stages.py`: the harness's profiler thread is one of those threads, and
asleep there). To hold against the page period (200,000 /
`ingest_emb_per_s` ms at 200 sentences a page): where the two are close,
one interpreter is the pace."""
from _stages import pages, python_cpu_ms


def read(ctx):
    cpu, n = python_cpu_ms(ctx), pages(ctx)
    return None if cpu is None or n is None else cpu / n
