"""From a profiler trace (`.xplane.pb`) to numbers: the one reduction every
PR uses, checked on the recorded trace under `tests/`.

    reduce(path, window_name) -> {
      "window_s":  seconds of the traced window (the harness's own
                   `TraceAnnotation(window_name)` on the host plane; the
                   whole span of the device events where it is absent),
      "busy_s":    seconds in which an operation ran on a device: the union
                   of the "XLA Ops" intervals clipped to the window,
                   averaged over the device planes,
      "modules":   {name: {"count", "seconds"}}  XLA programs by jitted
                   function name (the trailing "(id)" dropped),
      "ops":       {name: {"count", "seconds"}}  device ops by instruction
                   name (numeric suffix dropped, so the layers' copies of
                   one fusion add up),
      "device_ops": top ops [[name, seconds], ...],
      "idle_gaps": longest device-idle gaps [[host activity, seconds], ...],
      "planes":    plane names seen}

    python trace_reduce.py <file.xplane.pb | dir> [--inventory]

The file is an `XSpace` protobuf (tsl/profiler/protobuf/xplane.proto). A
four-second window of a decode loop holds millions of op events, which
`jax.profiler.ProfileData` takes minutes to walk in Python; so the few
fields needed are declared here and the file is parsed by `protobuf`'s own
C parser, then reduced with numpy. No device, no jax, no program.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import numpy as np

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
_ID = re.compile(r"\(\d+\)$")
_OP_SUFFIX = re.compile(r"\.\d+$")
_XSPACE = None


def op_name(event_name: str) -> str:
    """An op event's name is its whole HLO line: keep the instruction's
    name, and drop the numeric suffix (`%convolution_add_fusion.11 = ...`
    -> `convolution_add_fusion`)."""
    return _OP_SUFFIX.sub("", event_name.split(" = ", 1)[0].lstrip("%"))


def xspace_class():
    """The message class for the part of xplane.proto this file reads
    (field numbers as in tsl/profiler/protobuf/xplane.proto; every field
    left out, the per-event stats above all, is skipped by the parser)."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane.proto", package="benchmark_xplane",
        syntax="proto3")

    def message(name: str, fields: list) -> None:
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=T.LABEL_REPEATED if repeated
                            else T.LABEL_OPTIONAL)
            if type_name:
                f.type_name = f".benchmark_xplane.{type_name}"

    I64, STR, MSG = T.TYPE_INT64, T.TYPE_STRING, T.TYPE_MESSAGE
    message("XEvent", [("metadata_id", 1, I64, False, None),
                       ("offset_ps", 2, I64, False, None),
                       ("duration_ps", 3, I64, False, None)])
    message("XLine", [("name", 2, STR, False, None),
                      ("timestamp_ns", 3, I64, False, None),
                      ("events", 4, MSG, True, "XEvent")])
    message("XEventMetadata", [("id", 1, I64, False, None),
                               ("name", 2, STR, False, None)])
    message("MetadataEntry", [("key", 1, I64, False, None),
                              ("value", 2, MSG, False, "XEventMetadata")])
    message("XPlane", [("name", 2, STR, False, None),
                       ("lines", 3, MSG, True, "XLine"),
                       ("event_metadata", 4, MSG, True, "MetadataEntry")])
    message("XSpace", [("planes", 1, MSG, True, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmark_xplane.XSpace"))
    return _XSPACE


def load(path):
    space = xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    return space


def find_xplane(artifact_dir) -> Path:
    files = sorted(Path(artifact_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {artifact_dir}")
    return files[-1]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CUSTOM" not in plane_name


def _arrays(line) -> tuple:
    """(start_ps, end_ps, metadata_id) of a line's events, as arrays."""
    n = len(line.events)
    flat = np.fromiter(
        (v for e in line.events
         for v in (e.offset_ps, e.duration_ps, e.metadata_id)),
        dtype=np.int64, count=3 * n).reshape(n, 3)
    start = flat[:, 0] + line.timestamp_ns * 1000
    return start, start + flat[:, 1], flat[:, 2]


def _clip(start, end, ids, w0: int, w1: int) -> tuple:
    start, end = np.maximum(start, w0), np.minimum(end, w1)
    keep = end > start
    return start[keep], end[keep], ids[keep]


def _by_id(start, end, ids) -> list:
    """[(metadata_id, events, picoseconds)] summed per metadata id."""
    uniq, inverse = np.unique(ids, return_inverse=True)
    counts = np.bincount(inverse, minlength=len(uniq))
    ps = np.bincount(inverse, weights=(end - start).astype(np.float64),
                     minlength=len(uniq))
    return list(zip(uniq.tolist(), counts.tolist(), ps.tolist()))


def _busy_and_gaps(start, end, w0: int, w1: int) -> tuple:
    """Seconds covered by the union of [start, end) inside [w0, w1), and
    the uncovered stretches as (length_ps, from_ps, to_ps)."""
    if len(start) == 0:
        return 0.0, [(w1 - w0, w0, w1)] if w1 > w0 else []
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    gap_from = np.concatenate(([w0], e))
    gap_to = np.concatenate((s, [w1]))
    length = gap_to - gap_from
    open_ = length > 0
    busy_ps = (w1 - w0) - int(length[open_].sum())
    gaps = list(zip(length[open_].tolist(), gap_from[open_].tolist(),
                    gap_to[open_].tolist()))
    return busy_ps / 1e12, gaps


# host events that say nothing about the program: the harness's own tracer
# thread sleeps through the whole window
NOT_BLAMED = ("$time sleep",)


def reduce(path, window_name: str = "benchmark.window", top: int = 10) -> dict:
    space = load(path)
    window = None
    host_events = []  # (start_ps, end_ps, name) on host threads: gap blame
    for plane in space.planes:
        if _is_device(plane.name):
            continue
        names = {m.key: m.value.name for m in plane.event_metadata}
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for ev in line.events:
                a = base + ev.offset_ps
                b = a + ev.duration_ps
                name = names.get(ev.metadata_id, "")
                if name == window_name:
                    window = (a, b)
                elif (plane.name.startswith("/host:") and b - a >= 20_000_000
                      and name not in NOT_BLAMED):
                    host_events.append((a, b, name))
    dev = [p for p in space.planes if _is_device(p.name)]
    if window is None:
        spans = [(start.min(), end.max())
                 for start, end, _ in (_arrays(ln) for p in dev
                                       for ln in p.lines if len(ln.events))]
        window = ((min(s for s, _ in spans), max(e for _, e in spans))
                  if spans else (0, 0))
    w0, w1 = int(window[0]), int(window[1])
    modules: dict = {}
    ops: dict = {}
    busy, gaps = [], []
    for plane in dev:
        names = {m.key: m.value.name for m in plane.event_metadata}
        lines = {line.name: line for line in plane.lines}
        for line_name, table, label in ((OP_LINE, ops, op_name),
                                        (MODULE_LINE, modules,
                                         lambda n: _ID.sub("", n))):
            if line_name not in lines:
                continue
            start, end, ids = _clip(*_arrays(lines[line_name]), w0, w1)
            for mid, count, ps in _by_id(start, end, ids):
                row = table.setdefault(label(names.get(mid, "?")),
                                       {"count": 0, "seconds": 0.0})
                row["count"] += count
                row["seconds"] += ps / 1e12
            if line_name == OP_LINE:
                b, g = _busy_and_gaps(start, end, w0, w1)
                busy.append(b)
                gaps += g
    idle = []
    for length, a, b in sorted(gaps, reverse=True)[:top]:
        best, best_len = "no host event", 0
        for ha, hb, name in host_events:
            overlap = min(b, hb) - max(a, ha)
            # the innermost (shortest) event covering most of the gap says
            # most about what the host was doing
            if overlap > 0.5 * length and (best_len == 0 or hb - ha < best_len):
                best, best_len = name, hb - ha
        idle.append([best, length / 1e12])
    return {
        "window_s": (w1 - w0) / 1e12,
        "busy_s": (sum(busy) / len(busy)) if busy else 0.0,
        "modules": modules, "ops": ops,
        "device_ops": [[n, o["seconds"]] for n, o in sorted(
            ops.items(), key=lambda kv: -kv[1]["seconds"])[:top]],
        "idle_gaps": idle,
        "planes": [p.name for p in space.planes],
    }


def module_seconds(reduced: dict, pattern: str) -> tuple:
    """(count, seconds) of the XLA programs whose name matches `pattern`."""
    rx = re.compile(pattern)
    hit = [m for n, m in reduced["modules"].items() if rx.search(n)]
    return sum(m["count"] for m in hit), sum(m["seconds"] for m in hit)


def inventory(path) -> dict:
    """What a trace holds, for a first look by hand: planes, lines, event
    counts and the names that took most time on each line."""
    out = {}
    for plane in load(path).planes:
        names = {m.key: m.value.name for m in plane.event_metadata}
        lines = {}
        for line in plane.lines:
            if not len(line.events):
                continue
            start, end, ids = _arrays(line)
            rows = sorted(((ps / 1e12, count, names.get(mid, "?")[:90])
                           for mid, count, ps in _by_id(start, end, ids)),
                          reverse=True)[:25]
            lines[line.name] = {"events": len(line.events),
                                "top_by_seconds": rows}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    target = Path(sys.argv[1])
    if target.is_dir():
        target = find_xplane(target)
    if "--inventory" in sys.argv:
        print(json.dumps(inventory(target), indent=1))
    else:
        red = reduce(target)
        red["ops"] = dict(sorted(red["ops"].items(),
                                 key=lambda kv: -kv[1]["seconds"])[:40])
        print(json.dumps(red, indent=1))
