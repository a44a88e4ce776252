"""85th percentile over requests of (last delta - first delta) / tokens
delivered after the first delta, on the client's clock."""


def read(ctx):
    v = [r["tpot_ms"] for r in ctx["client"].get("records", [])
         if r["ok"] and "tpot_ms" in r]
    return ctx["yardstick"].percentile(v, 85) if v else None
