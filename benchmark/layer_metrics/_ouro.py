"""What the `.ingest_ouro` readers share: device seconds under the scopes
of the looped block's two sub-layers, per page. Each returns None where the
program has no such scope or series (a parent without the family): the
harness then leaves the metric out.

Both sub-layers run inside the scan over layers inside the scan over steps,
and the device's op line holds an event for each loop itself around the
events of the ops in its body: the loops' own events (`while`,
`conditional`, `call`) are left out, or a second inside a loop would count
twice (`_sala.py`'s reduction, imported).

A traced sub-window holds a dozen programs, cut where it falls; every page
holds the same chunks and packs into the same rows, so the window's seconds
per program times a page's programs (`engine.embed.dispatches` over pages
landed, whole window) is set against the least time for a page's chunks:
every block application's FLOPs against the peak, or its bytes (the
sub-layer's kernels once per application and dispatch, the float32 stream
once) against the bandwidth, whichever is longer."""
from _common import page_token_lengths
from _sala import ms_per_program, programs_per_page  # loops' own events left out


def roofline(ctx, scope, flops_fn, bytes_fn):
    """100 x (least time for one page's chunks through every application of
    the sub-layer) / (device seconds under `scope` per page)."""
    import yardstick_ouro as yo

    ms = ms_per_program(ctx, (scope,))
    per_page = programs_per_page(ctx) if ms else None
    if not per_page or not ctx["peaks"]:
        return None
    m, lens = ctx["model"], page_token_lengths(ctx)
    least = yo.applications(m) * ctx["yardstick"].roofline_seconds(
        flops_fn(lens, m), bytes_fn(lens, m, per_page), ctx["peaks"])
    return 100.0 * least / (1e-3 * ms * per_page)
