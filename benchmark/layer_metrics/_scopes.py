"""Device time by `jax.named_scope`. A scope lands in each device op's
metadata as the `tf_op` stat (`jit(fn)/symbiont.qsearch/scan/dot_general:`),
which `trace_reduce.py`'s message class skips; so this file declares the
few fields more that it needs (`XEventMetadata.stats`, `XPlane.
stat_metadata`; numbers as in tsl/profiler/protobuf/xplane.proto) and sums
the "XLA Ops" time inside the harness's window per scope path.

    by_path(path) -> {((scope, ...), op): seconds}   the op's `tf_op`
        split at "/", less the `jit(..)` wrappers and the primitive's own
        name, () for an op with no `tf_op` (copies the compiler added,
        say); and the instruction's name as `trace_reduce.op_name` has it

A fusion belongs to the scope its own `tf_op` holds (its root's). A
program traced without scopes (the parent of the PR that added them) holds
none, and `seconds_under` then returns None.

    python _scopes.py <file.xplane.pb | dir>    the table, for a look by hand
"""

import json
import sys
from functools import lru_cache
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import _host_spans
import trace_reduce

_XSPACE = None


def xspace_class():
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane_scopes.proto",
        package="benchmark_xplane_scopes", syntax="proto3")

    def message(name: str, fields: list) -> None:
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=T.LABEL_REPEATED if repeated
                            else T.LABEL_OPTIONAL)
            if type_name:
                f.type_name = f".benchmark_xplane_scopes.{type_name}"

    I64, U64, STR, MSG = (T.TYPE_INT64, T.TYPE_UINT64, T.TYPE_STRING,
                          T.TYPE_MESSAGE)
    message("XStat", [("metadata_id", 1, I64, False, None),
                      ("str_value", 5, STR, False, None),
                      ("ref_value", 7, U64, False, None)])
    message("XEvent", [("metadata_id", 1, I64, False, None),
                       ("offset_ps", 2, I64, False, None),
                       ("duration_ps", 3, I64, False, None)])
    message("XLine", [("name", 2, STR, False, None),
                      ("timestamp_ns", 3, I64, False, None),
                      ("events", 4, MSG, True, "XEvent")])
    message("XEventMetadata", [("id", 1, I64, False, None),
                               ("name", 2, STR, False, None),
                               ("stats", 5, MSG, True, "XStat")])
    message("XStatMetadata", [("id", 1, I64, False, None),
                              ("name", 2, STR, False, None)])
    message("EventEntry", [("key", 1, I64, False, None),
                           ("value", 2, MSG, False, "XEventMetadata")])
    message("StatEntry", [("key", 1, I64, False, None),
                          ("value", 2, MSG, False, "XStatMetadata")])
    message("XPlane", [("name", 2, STR, False, None),
                       ("lines", 3, MSG, True, "XLine"),
                       ("event_metadata", 4, MSG, True, "EventEntry"),
                       ("stat_metadata", 5, MSG, True, "StatEntry")])
    message("XSpace", [("planes", 1, MSG, True, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmark_xplane_scopes.XSpace"))
    return _XSPACE


def scope_path(tf_op: str) -> tuple:
    """`jit(fn)/symbiont.qsearch/scan/dot_general:` -> ("symbiont.qsearch",
    "scan"): the named scopes an op was traced under, outermost first."""
    parts = [p for p in tf_op.rstrip(":").split("/")[:-1]
             if p and not (p.endswith(")") and "(" in p)]
    return tuple(parts)


@lru_cache(maxsize=1)  # several readers of one run share one parse
def by_path(path):
    space = xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    spans = _host_spans.read(path)
    out: dict = {}
    for plane in space.planes:
        if not trace_reduce._is_device(plane.name):
            continue
        stat_names = {m.key: m.value.name for m in plane.stat_metadata}
        paths = {}
        for m in plane.event_metadata:
            tf_op = next((s.str_value or stat_names.get(s.ref_value, "")
                          for s in m.value.stats
                          if stat_names.get(s.metadata_id) == "tf_op"), "")
            paths[m.key] = (scope_path(tf_op),
                            trace_reduce.op_name(m.value.name))
        for line in plane.lines:
            if line.name != trace_reduce.OP_LINE:
                continue
            start, end, ids = trace_reduce._arrays(line)
            if spans:
                start, end, ids = trace_reduce._clip(start, end, ids,
                                                     *spans["window"])
            for mid, _count, ps in trace_reduce._by_id(start, end, ids):
                key = paths.get(mid, ((), "?"))
                out[key] = out.get(key, 0.0) + ps / 1e12
    return out


def seconds_under(table: dict, top: str, inner: tuple):
    """Of `by_path`'s table: the seconds of the ops traced under the
    program scope `top` and any of the scopes `inner`; None where no op
    holds `top` (a program without scopes)."""
    under = [(k, v) for (k, _op), v in table.items() if top in k]
    if not under:
        return None
    return sum(v for k, v in under if set(inner) & set(k))


def ms_per_program(ctx, top: str, inner: tuple):
    """`seconds_under` in this run's traced sub-window per run of a
    `jit_fn` program, in ms; None without a trace."""
    from _common import module_time

    path, programs = _host_spans.trace_file(ctx), module_time(ctx, r"^jit_fn$")
    seconds = seconds_under(by_path(path), top, inner) if path else None
    if seconds is None or not programs:
        return None
    return 1e3 * seconds / programs[0]


if __name__ == "__main__":
    target = Path(sys.argv[1])
    if target.is_dir():
        target = trace_reduce.find_xplane(target)
    rows = sorted(by_path(target).items(), key=lambda kv: -kv[1])
    print(json.dumps([["/".join(k) or "(no scope)", op, v]
                      for (k, op), v in rows]))
