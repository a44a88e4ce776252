"""85th percentile over ALL generation requests that finished of due time ->
first non-empty streamed delta, on the client's clock."""


def read(ctx):
    v = [r["ttft_ms"] for r in ctx["client"].get("records", [])
         if r["ok"] and "ttft_ms" in r]
    return ctx["yardstick"].percentile(v, 85) if v else None
