"""How late the generator sent (sent - due), 95th percentile, on its own
clock (every open-loop family: `loadgen_late_p95_ms.search`, ...)."""


def read(ctx):
    late = [r["late_ms"] for r in ctx["client"].get("records", [])
            if r.get("late_ms") is not None]
    return ctx["yardstick"].percentile(late, 95) if late else None
