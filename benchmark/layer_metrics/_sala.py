"""What the `.ingest_sala` readers share: device seconds under the scopes
of the sparse and linear mixers, per page. Each returns None where the
program has no such scope or series (a parent without the family): the
harness then leaves the metric out.

Both mixers run inside loops (`lax.scan`, `fori_loop`, `lax.map`), and the
device's op line holds an event for the loop itself around the events of
the ops in its body: the loops' own events (`while`, `conditional`) are
left out, or a second inside a loop would count twice.

A traced sub-window holds a handful of programs (one 32,768-token row
each), cut where it falls; every page holds the same passages and packs
into the same rows, so the window's seconds per program times a page's
programs (`engine.embed.dispatches` over pages landed, whole window) is
set against the least time for a page's passages."""
import _host_spans
import _scopes
from _common import counter_delta, module_time, page_token_lengths

WRAPPERS = ("while", "conditional", "call")


def scope_seconds(ctx, scopes):
    path = _host_spans.trace_file(ctx)
    if not path:
        return None
    table = {(k, op): v for (k, op), v in _scopes.by_path(path).items()
             if not op.startswith(WRAPPERS)}
    return _scopes.seconds_under(table, "symbiont.embed", scopes) or None


def ms_per_program(ctx, scopes):
    seconds, programs = scope_seconds(ctx, scopes), module_time(ctx, r"^jit_fn$")
    return 1e3 * seconds / programs[0] if seconds and programs else None


def programs_per_page(ctx):
    pages = ((ctx["rows1"] - ctx["rows0"])
             / float(ctx["mix"]["sentences_per_page"]))
    dispatches = counter_delta(ctx, "engine.embed.dispatches")
    return dispatches / pages if pages > 0 and dispatches > 0 else None


def roofline(ctx, scopes, flops_fn, bytes_fn, kind):
    """100 x (least time for one page's passages through every layer of
    `kind`) / (device seconds under `scopes` per page)."""
    ms = ms_per_program(ctx, scopes)
    per_page = programs_per_page(ctx) if ms else None
    if not per_page or not ctx["peaks"]:
        return None
    m, lens = ctx["model"], page_token_lengths(ctx)
    layers = sum(1 for k in m["mixer_types"] if k == kind)
    least = layers * ctx["yardstick"].roofline_seconds(
        flops_fn(lens, m), bytes_fn(lens, m), ctx["peaks"])
    return 100.0 * least / (1e-3 * ms * per_page)
