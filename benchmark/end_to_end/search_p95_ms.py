"""95th percentile over ALL searches the window answered, each timed on the
client's clock from when it was due to the last byte of its reply (a failed
or refused request counts as `failed`)."""


def read(ctx):
    v = [r["latency_ms"] for r in ctx["client"].get("records", []) if r["ok"]]
    return ctx["yardstick"].percentile(v, 95) if v else None
