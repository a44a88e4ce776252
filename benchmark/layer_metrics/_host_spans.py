"""The program's spans on the profiler's clock, beside the device's busy
time. `utils/telemetry.span()` opens a `symbiont.<name>` host annotation
for every span, so a traced run's `.xplane.pb` holds them on the clock of
the device ops. This reads the run's OWN trace file (where `run.py`'s
`Tracer` wrote it; `ctx["trace"]` holds only what `trace_reduce.reduce`
kept), clipped to the harness's `benchmark.window` annotation:

    read(path) -> {"window": (w0_ps, w1_ps),
                   "spans": {name without the prefix: [(a_ps, b_ps), ...]},
                   "idle": [[(a_ps, b_ps), ...] per device plane]
                            device-idle stretches: the gaps of the union
                            of the plane's "XLA Ops" intervals, in order}
                  or None without the window annotation.

A program that opens no such annotation (the parent of the PR that added
them) gives no spans, and every reader here then returns None.
"""

from functools import lru_cache

import artefacts
import trace_reduce

PREFIX = "symbiont."
WINDOW = "benchmark.window"


def trace_file(ctx):
    """The `.xplane.pb` of this run's traced sub-window, or None."""
    if not ctx.get("trace"):
        return None
    try:
        return trace_reduce.find_xplane(
            artefacts.CACHE / "state" / ctx["cell"]["name"] / "trace")
    except FileNotFoundError:
        return None


@lru_cache(maxsize=1)  # several readers of one run share one parse
def read(path):
    space = trace_reduce.load(path)
    window, found = None, []
    for plane in space.planes:
        if trace_reduce._is_device(plane.name):
            continue
        names = {m.key: m.value.name for m in plane.event_metadata
                 if m.value.name == WINDOW
                 or m.value.name.startswith(PREFIX)}
        if not names:
            continue
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for ev in line.events:
                name = names.get(ev.metadata_id)
                if name is None:
                    continue
                a = base + ev.offset_ps
                if name == WINDOW:
                    window = (a, a + ev.duration_ps)
                else:
                    found.append((name[len(PREFIX):], a, a + ev.duration_ps))
    if window is None:
        return None
    w0, w1 = window
    spans: dict = {}
    for name, a, b in found:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            spans.setdefault(name, []).append((a, b))
    idle = []
    for plane in space.planes:
        if not trace_reduce._is_device(plane.name):
            continue
        for line in plane.lines:
            if line.name == trace_reduce.OP_LINE:
                start, end, _ = trace_reduce._clip(
                    *trace_reduce._arrays(line), w0, w1)
                gaps = trace_reduce._busy_and_gaps(start, end, w0, w1)[1]
                idle.append([(a, b) for _, a, b in gaps])
    return {"window": window, "spans": spans, "idle": idle}


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def overlap_ps(xs, ys):
    """Picoseconds in both of two lists of sorted, disjoint intervals."""
    total, i, j = 0, 0, 0
    while i < len(xs) and j < len(ys):
        total += max(0, min(xs[i][1], ys[j][1]) - max(xs[i][0], ys[j][0]))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_inside_share(got, name):
    """Of `read`'s result: the share of the window in which the device was
    idle while at least one `symbiont.<name>` span was open (the mean over
    the device planes, as `busy_s` is in trace_reduce). None without such
    a span or a device plane."""
    if not got or name not in got["spans"] or not got["idle"]:
        return None
    w0, w1 = got["window"]
    open_ = union(got["spans"][name])
    idle_ps = sum(overlap_ps(open_, gaps) for gaps in got["idle"])
    return idle_ps / len(got["idle"]) / (w1 - w0)


def idle_inside_pct(ctx, name):
    """`idle_inside_share` of this run's trace, in %: device time that work
    of that layer was there to fill. None without a trace."""
    path = trace_file(ctx)
    share = idle_inside_share(read(path), name) if path else None
    return None if share is None else 100.0 * share
