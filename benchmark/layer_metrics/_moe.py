"""What the `.ingest_moe` readers share: the program's expert-load series
(`engine.moe.*`, docs/OBSERVABILITY.md) over the traced sub-window, and the
device seconds under a scope of the embed program. Each returns None where
the program has no such series or scope (a parent without the family): the
harness then leaves the metric out."""
import _host_spans
import _scopes
from _common import counter_delta

WEIGHT_BYTES = 2.0  # bfloat16 at rest (the configuration's `precision`)


def dispatch_layers(ctx):
    """(dispatch, expert layer) pairs fetched in the traced sub-window: the
    load histogram takes one observation for each."""
    def count(snap):
        return sum(h["count"] for k, h in snap["histograms"].items()
                   if k.startswith("engine.moe.expert_load_max_over_mean"))

    if not ctx.get("trace"):
        return None
    n = count(ctx["trace"]["snap1"]) - count(ctx["trace"]["snap0"])
    return n if n > 0 else None


def moe_layers(ctx):
    m = ctx["model"]
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def scope_seconds(ctx, scope):
    """Device seconds of the ops under `symbiont.embed` > `scope` in the
    traced sub-window. `experts` also takes the grouped matmuls themselves:
    the TPU compiler rewrites `ragged_dot` into kernels named
    `ragged-dot-*` that keep no `tf_op`, so no scope (seen on the v5e: 63%
    of the device's busy time stood under no scope); in this program only
    the routed experts issue them."""
    path = _host_spans.trace_file(ctx)
    if not path:
        return None
    table = _scopes.by_path(path)
    s = _scopes.seconds_under(table, "symbiont.embed", (scope,))
    if s and scope == "experts":
        s += sum(v for (k, op), v in table.items()
                 if not k and op.startswith("ragged-dot"))
    return s if s else None


def ms_per_program(ctx, scope):
    """`scope_seconds` per run of a `jit_fn` program, in ms."""
    from _common import module_time

    seconds, programs = scope_seconds(ctx, scope), module_time(ctx, r"^jit_fn$")
    return 1e3 * seconds / programs[0] if seconds and programs else None


def trace_delta(ctx, name):
    return counter_delta(ctx, name, trace=True) if ctx.get("trace") else 0
