"""Rows that became searchable in the store over the window / its seconds
(all rows over all the time between the two flush landings that bound it)."""


def read(ctx):
    rows = ctx.get("rows1", 0) - ctx.get("rows0", 0)
    return rows / ctx["window_s"] if rows > 0 and ctx["window_s"] > 0 else None
