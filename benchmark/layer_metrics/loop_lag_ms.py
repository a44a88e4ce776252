"""How long a continuation that is ready waits for the event loop: mean of
`loop.lag_ms` (a timer on the loop that observes how late it fired) over
the quiet part of the window, before the profiler starts (`_stages.py`:
under the Python tracer the loop is slower, and one firing that waits out
`stop_trace` reads ten seconds). Every family of cells:
`loop_lag_ms.ingest`, `.search`."""
from _stages import histogram_mean


def read(ctx):
    return histogram_mean(ctx, "loop.lag_ms")
