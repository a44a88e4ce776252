"""`layer_metrics/_host_spans.py` and `_scopes.py` on a small trace recorded
on the v5e (`tests/record_spans_trace.py`, PR 25): inside the harness's
window, the program span `busy` around four calls of a jitted `fn` whose
work sits under `symbiont.qsearch` > `scan` / `topk`, then the span `idle`
around a sleep with nothing on the device. The numbers the readers made of
it on the day are kept beside it; the structural facts are asserted
outright. The toy runs at the end show the readers in the harness."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "layer_metrics"), str(HERE.parent),
                str(HERE)]

import _host_spans  # noqa: E402
import _scopes  # noqa: E402
import record_spans_trace  # noqa: E402
import trace_reduce  # noqa: E402

TRACE = HERE / "recorded" / "spans.xplane.pb"
EXPECTED = json.loads((HERE / "recorded" / "spans.expected.json").read_text())


@pytest.fixture(scope="module")
def spans():
    return _host_spans.read(TRACE)


def test_program_spans_are_found_once_each_inside_the_window(spans):
    w0, w1 = spans["window"]
    assert set(spans["spans"]) == {"busy", "idle"}
    for name in ("busy", "idle"):
        ((a, b),) = spans["spans"][name]
        assert w0 <= a < b <= w1
    assert spans["spans"]["busy"][0][1] <= spans["spans"]["idle"][0][0]
    # one device plane; its idle stretches are in order and disjoint
    (gaps,) = spans["idle"]
    assert all(a < b for a, b in gaps)
    assert all(x[1] <= y[0] for x, y in zip(gaps, gaps[1:]))


def test_idle_inside_a_span_is_the_span_less_the_device_work_in_it(spans):
    w0, w1 = spans["window"]
    busy_s = trace_reduce.reduce(TRACE)["busy_s"]
    length = {n: (s[0][1] - s[0][0]) / 1e12 for n, s in spans["spans"].items()}
    inside = {n: _host_spans.idle_inside_share(spans, n) * (w1 - w0) / 1e12
              for n in length}
    # nothing ran on the device during `idle`: all of it is idle time
    assert inside["idle"] == pytest.approx(length["idle"], rel=1e-9)
    # all the device work of the window was dispatched inside `busy`
    assert inside["busy"] == pytest.approx(length["busy"] - busy_s, rel=1e-6)
    assert 0.0 < busy_s < length["busy"]
    assert _host_spans.idle_inside_share(spans, "absent") is None


def test_device_time_goes_to_the_scope_each_op_was_traced_under():
    table = _scopes.by_path(TRACE)
    by_scope: dict = {}
    for (path, op), seconds in table.items():
        by_scope.setdefault(path, {})[op] = seconds
    assert set(by_scope) == {("symbiont.qsearch", "scan"),
                             ("symbiont.qsearch", "topk")}
    assert "sort" in by_scope[("symbiont.qsearch", "topk")]
    scan = _scopes.seconds_under(table, "symbiont.qsearch", ("scan",))
    topk = _scopes.seconds_under(table, "symbiont.qsearch", ("topk",))
    assert scan > 0 and topk > 0
    # every op of the window sits under one of the two: they add up to the
    # device's busy time (the ops of one program do not overlap)
    assert scan + topk == pytest.approx(
        trace_reduce.reduce(TRACE)["busy_s"], rel=1e-6)
    assert _scopes.seconds_under(table, "symbiont.qsearch",
                                 ("encoder",)) == 0.0
    assert _scopes.seconds_under(table, "symbiont.embed", ("scan",)) is None


def test_scope_path_keeps_scopes_and_drops_wrappers_and_the_primitive():
    assert _scopes.scope_path(
        "jit(fn)/symbiont.qsearch/scan/dot_general:") == (
            "symbiont.qsearch", "scan")
    assert _scopes.scope_path("jit(fn)/jit(main)/jvp(encoder)/mul") == ()
    assert _scopes.scope_path("jit(fn)/dot_general:") == ()
    assert _scopes.scope_path("") == ()


def test_a_trace_without_scopes_or_spans_reads_nothing():
    """The trace PR 24 recorded: no `symbiont.*` annotation, no scope."""
    old = HERE / "recorded" / "small.xplane.pb"
    got = _host_spans.read(old)
    assert got["spans"] == {} and len(got["idle"]) == 1
    assert _host_spans.idle_inside_share(got, "api.search") is None
    assert _scopes.seconds_under(_scopes.by_path(old), "symbiont.qsearch",
                                 ("scan",)) is None


def test_numbers_repeat():
    got = json.loads(json.dumps(record_spans_trace.expected(TRACE)))
    assert got["scopes"] == EXPECTED["scopes"]
    assert got["span_s"] == EXPECTED["span_s"]
    assert got["idle_inside"] == pytest.approx(EXPECTED["idle_inside"],
                                               rel=1e-9)


def test_interval_helpers():
    assert _host_spans.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        [0, 4], [5, 10]]
    assert _host_spans.overlap_ps([[0, 4], [5, 10]], [(3, 6), (8, 20)]) == 4


NEW_PROGRAM_SPAN = {
    "ingest_pages": ("store_flush_ms.ingest", "store_wait_ms.ingest",
                     "embed_queue_wait_ms.ingest", "embed_flush_ms.ingest",
                     "embed_host_ms.ingest", "embed_device_wait_ms.ingest"),
    "search_fused": ("gateway_self_ms.search", "qsearch_wait_ms.search",
                     "qsearch_host_ms.search",
                     "qsearch_device_wait_ms.search"),
}


@pytest.mark.parametrize("cell", sorted(NEW_PROGRAM_SPAN))
def test_toy_runs_report_the_new_program_span_metrics(cell):
    """On the CPU at toy size the traced run reads every new `program_span`
    metric, and none of the device ones (no device plane to read)."""
    p = subprocess.run(
        [sys.executable, str(HERE.parent / "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--rehearse-cpu"], capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    for name in NEW_PROGRAM_SPAN[cell]:
        assert out["metrics"][name]["value"] >= 0.0, name
    assert not [m for m in out["metrics"]
                if "idle" in m or "_dev_ms" in m], out["metrics"]
    if cell == "search_fused":
        m = {k: v["value"] for k, v in out["metrics"].items()}
        parts = sum(m[k] for k in NEW_PROGRAM_SPAN[cell])
        # the four stages partition the gateway's span but for the few
        # lines between a span's ends and its stamps
        assert parts == pytest.approx(m["gateway_span_ms.search"], rel=0.05)
