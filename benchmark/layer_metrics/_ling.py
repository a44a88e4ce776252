"""What the `.ingest_ling` readers share: device seconds under the scopes
of the KDA mixer, its recurrence, MLA and the routed experts, per page, and
the program's expert series over the traced sub-window. Each returns None
where the program has no such scope or series (a parent without the
family): the harness then leaves the metric out.

The recurrence runs inside a scan and a row longer than 8,192 tokens takes
the expert layer in a loop, and the device's op line holds an event for the
loop itself around the events of the ops in its body: the loops' own events
(`while`, `conditional`, `call`) are left out, or a second inside a loop
would count twice (as `_sala.py` does). `experts` also takes the compiler's
`ragged-dot-*` kernels, which keep no scope (`_moe.py` says why).

A traced sub-window holds a handful of programs (one 32,768-token row
each), cut where it falls; every page holds the same passages and packs into
the same rows, so the window's seconds per program times a page's programs
(`engine.embed.dispatches` over pages landed, whole window) is set against
the least time for a page's passages."""
import _host_spans
import _scopes
from _common import module_time, page_token_lengths
from _sala import WRAPPERS, programs_per_page


def scope_seconds(ctx, scopes):
    path = _host_spans.trace_file(ctx)
    if not path:
        return None
    table = _scopes.by_path(path)
    s = _scopes.seconds_under(
        {(k, op): v for (k, op), v in table.items()
         if not op.startswith(WRAPPERS)}, "symbiont.embed", scopes)
    if s and "experts" in scopes:
        s += sum(v for (k, op), v in table.items()
                 if not k and op.startswith("ragged-dot"))
    return s or None


def ms_per_program(ctx, scopes):
    seconds, programs = scope_seconds(ctx, scopes), module_time(ctx, r"^jit_fn$")
    return 1e3 * seconds / programs[0] if seconds and programs else None


def page_roofline(ctx, scopes, work):
    """100 x (least time for one page's work) / (device seconds under
    `scopes` per page); `work(lengths, model, programs_per_page)` -> the
    page's (FLOPs, bytes)."""
    ms = ms_per_program(ctx, scopes)
    per_page = programs_per_page(ctx) if ms else None
    if not per_page or not ctx["peaks"]:
        return None
    flops, bytes_ = work(page_token_lengths(ctx), ctx["model"], per_page)
    least = ctx["yardstick"].roofline_seconds(flops, bytes_, ctx["peaks"])
    return 100.0 * least / (1e-3 * ms * per_page)
