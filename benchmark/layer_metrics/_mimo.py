"""What the `.ingest_mimo` readers share: device seconds under the scopes
of the window and full attention mixers and the routed experts, and of the
two attention kernels alone, per embed program; the program's series over
the window. Each returns None where the program has no such scope, kernel
or series (a parent without the family): the harness then leaves the
metric out.

A row longer than 8,192 tokens takes the expert layer in a loop, and the
device's op line holds an event for the loop itself around the events of
the ops in its body: the loops' own events (`while`, `conditional`, `call`)
are left out, or a second inside a loop would count twice (as `_sala.py`
does). `experts` also takes the compiler's `ragged-dot-*` kernels, which
keep no scope (`_moe.py` says why).

Per program: a traced sub-window of 8 s holds a few programs of one
32,768-token row each and cuts one or two of them, so its op seconds are
divided by the programs it holds at the window's own rate (embed
dispatches over the whole window's seconds, times the traced seconds), not
by the whole `jit_fn` events counted in it, which leave the cut ones out
and read high by a share that moves with the program's length. Every page
holds the same passages and packs into the same rows, so a program's
seconds times a page's programs (`engine.embed.dispatches` over pages
landed) is set against the least time for a page's passages."""
import _host_spans
import _scopes
from _common import counter_delta, page_token_lengths
from _sala import WRAPPERS, programs_per_page

# the attention kernels' op names (ops/flash_attention.py `_grouped_call`)
KERNELS = {"swa": "window_attention", "full_attn": "grouped_attention"}


def _table(ctx):
    path = _host_spans.trace_file(ctx)
    if not path:
        return None
    return {(k, op): v for (k, op), v in _scopes.by_path(path).items()
            if not op.startswith(WRAPPERS)}


def scope_seconds(ctx, scopes):
    table = _table(ctx)
    if not table:
        return None
    s = _scopes.seconds_under(table, "symbiont.embed", scopes)
    if s and "experts" in scopes:
        s += sum(v for (k, op), v in table.items()
                 if not k and op.startswith("ragged-dot"))
    return s or None


def kernel_seconds(ctx, scope):
    """Device seconds of the attention kernel of the `scope` layers alone
    (its Mosaic call carries the scope in `tf_op`)."""
    table = _table(ctx)
    if not table:
        return None
    s = sum(v for (k, op), v in table.items()
            if "symbiont.embed" in k and scope in k
            and op.startswith(KERNELS[scope]))
    return s or None


def programs_traced(ctx):
    """Embed programs the traced sub-window holds, at the window's rate."""
    if not ctx.get("trace"):
        return None
    rate = counter_delta(ctx, "engine.embed.dispatches") / ctx["window_s"]
    return rate * ctx["trace"]["window_s"] if rate > 0 else None


def ms_per_program(ctx, seconds):
    programs = programs_traced(ctx)
    return 1e3 * seconds / programs if seconds and programs else None


def page_roofline(ctx, seconds, work):
    """100 x (least time for one page's work) / (`seconds` of the traced
    sub-window per program x a page's programs); `work(lengths, model)` ->
    the page's (FLOPs, bytes)."""
    ms = ms_per_program(ctx, seconds)
    per_page = programs_per_page(ctx) if ms else None
    if not per_page or not ctx["peaks"]:
        return None
    flops, bytes_ = work(page_token_lengths(ctx), ctx["model"])
    least = ctx["yardstick"].roofline_seconds(flops, bytes_, ctx["peaks"])
    return 100.0 * least / (1e-3 * ms * per_page)
