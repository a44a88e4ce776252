"""The stage-span readers (PR 36; `layer_metrics/_stages.py` and the
thirteen reader files of the fourteen metrics below): toy CPU runs of
`ingest_pages` and `search_fused` report every one of them, the splits lie
inside the stages they split, and on snapshots of a program that records
none of the new series every reader that needs one returns None.

Run without xdist, as `test_span_readers.py`: each toy run is a process of
its own that holds every core it can get."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "layer_metrics"), str(HERE.parent)]

import run  # noqa: E402

STAGE_METRICS = {
    "ingest_pages": (
        "host_cpu_ms_per_page.ingest", "stage_cpu_coverage_pct.ingest",
        "handlers_cpu_ms_per_page.ingest",
        "store_rows_cpu_ms_per_page.ingest",
        "store_wal_encode_cpu_ms_per_page.ingest",
        "store_wal_sync_ms.ingest", "embed_hop_ms.ingest",
        "embed_tokenize_cpu_ms_per_page.ingest",
        "embed_pack_cpu_ms_per_page.ingest", "embed_dispatch_ms.ingest",
        "loop_lag_ms.ingest"),
    "search_fused": ("loop_lag_ms.search", "qsearch_tokenize_ms.search",
                     "qsearch_dispatch_ms.search"),
}
ALL = [m for ms in STAGE_METRICS.values() for m in ms]
# CPU time is read over the window's quiet quarter, before the profiler
# starts, and a passage cell's quarter holds two pages or three: the
# per-page metrics are not listed there
PER_PAGE = [m for m in ALL if "_per_page" in m or "coverage" in m]


def test_benchmark_json_lists_the_fourteen_with_a_reader_each():
    bench = run.load_benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert len(ALL) == 14 and len(PER_PAGE) == 7
    ingest = [w["name"] for w in bench["workloads"]
              if w["name"].startswith("ingest_")]
    for name in ALL:
        m = listed[name]
        search = name.endswith(".search")
        cells = (["search_fused"] if search else
                 [c for c in ingest if name not in PER_PAGE
                  or c != "ingest_longdocs_sala"])
        assert m["workloads"] == cells
        assert m["moves"] == ("search_p95_ms" if search
                              else "ingest_emb_per_s")
        assert callable(run.load_reader("layer_metrics", name))
    files = {run.load_reader("layer_metrics", n).__code__.co_filename
             for n in ALL}
    assert len(files) == 13  # `loop_lag_ms.py` reads both families


@pytest.mark.parametrize("cell", sorted(STAGE_METRICS))
def test_toy_runs_report_every_stage_metric(cell):
    p = subprocess.run(
        [sys.executable, str(HERE.parent / "run.py"), "--workload", cell,
         "--seed", "2147483693", "--seconds", "3", "--trace", "1",
         "--rehearse-cpu"], capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in STAGE_METRICS[cell]:
        assert m[name] >= 0.0, name
    if cell == "search_fused":
        parts = m["qsearch_tokenize_ms.search"] + m["qsearch_dispatch_ms.search"]
        # the two sections lie inside the host stage they split
        assert 0.0 < parts <= m["qsearch_host_ms.search"]
    else:
        assert 0.0 < m["stage_cpu_coverage_pct.ingest"] <= 100.0
        assert m["host_cpu_ms_per_page.ingest"] > 0.0
        staged = sum(m[k] for k in (
            "handlers_cpu_ms_per_page.ingest",
            "store_rows_cpu_ms_per_page.ingest",
            "store_wal_encode_cpu_ms_per_page.ingest",
            "embed_tokenize_cpu_ms_per_page.ingest",
            "embed_pack_cpu_ms_per_page.ingest"))
        # the per-page stage metrics are part of what the coverage counts
        assert 0.0 < staged <= (m["stage_cpu_coverage_pct.ingest"] / 100.0
                                * m["host_cpu_ms_per_page.ingest"]) * 1.0001
        # flush = hop + host + device wait, but for the lines between a
        # span's ends and its stamps
        assert (m["embed_hop_ms.ingest"] + m["embed_host_ms.ingest"]
                + m["embed_device_wait_ms.ingest"]) == pytest.approx(
                    m["embed_flush_ms.ingest"], rel=0.05)
        assert m["store_wal_sync_ms.ingest"] <= m["store_flush_ms.ingest"]


def _hist(count, total):
    return {"count": count, "sum": total}


def _ctx(snap0, snap1, sub0=None):
    """`sub0`: the snapshot as the profiler starts, where the window's
    quiet part ends (the window's end where not given)."""
    return {"snap0": snap0, "snap1": snap1,
            "trace": {"snap0": sub0 or snap1, "snap1": snap1}}


def _empty():
    return {"counters": {}, "histograms": {}, "gauges": {}}


@pytest.mark.parametrize("name", ALL)
def test_a_program_without_the_series_reads_none(name):
    """Snapshots as a program from before the stage spans gives them: the
    old spans and counters, none of the new series. `embed_hop_ms.ingest`
    alone is made of two spans that program has (PR 25's), so it reads
    there too; the other thirteen leave their metric out."""
    snap0, snap1 = _empty(), _empty()
    snap1["counters"]["preprocessing.embedded_docs"] = 40
    for old, total in (("engine.qsearch", 30.0), ("batcher.flush", 400.0),
                       ("vector_memory.flush", 300.0),
                       ("engine.embed", 250.0)):
        snap1["histograms"][f"span.{old}.ms"] = _hist(10, total)
    got = run.load_reader("layer_metrics", name)(_ctx(snap0, snap1))
    if name == "embed_hop_ms.ingest":
        assert got == pytest.approx(15.0)
    else:
        assert got is None


def test_the_readers_arithmetic_on_snapshots_made_by_hand():
    import _stages

    def ends(pages0, pages1, cpu0, cpu1, scale):
        snap0, snap1 = _empty(), _empty()
        snap0["counters"]["preprocessing.embedded_docs"] = pages0
        snap1["counters"]["preprocessing.embedded_docs"] = pages1
        snap0["gauges"]["host.python_cpu_s"] = cpu0
        snap1["gauges"]["host.python_cpu_s"] = cpu1
        for i, stage in enumerate(_stages.INGEST_STAGES):
            name = f"span.{stage}.cpu_ms_total"
            snap0["counters"][name] = 10.0
            snap1["counters"][name] = 10.0 + scale * 10.0 * (i + 1)
        return snap0, snap1

    # the window: 50 pages, stages 50..500 ms, and 3 s of the interpreter's
    # threads, of which the harness's profiler thread burnt most
    snap0, snap1 = ends(10, 60, 100.0, 103.0, 5.0)
    # its quiet part, to the profiler's start: 20 pages, stages 10..100 ms,
    # 0.5 s of CPU
    sub0 = ends(10, 30, 100.0, 100.5, 1.0)[1]
    snap0["histograms"]["span.batcher.flush.ms"] = _hist(2, 80.0)
    snap1["histograms"]["span.batcher.flush.ms"] = _hist(12, 480.0)  # 40
    snap0["histograms"]["span.engine.embed.ms"] = _hist(2, 50.0)
    snap1["histograms"]["span.engine.embed.ms"] = _hist(12, 350.0)   # 30
    # one firing of the probe waited out `stop_trace`: after the quiet part
    snap0["histograms"]["loop.lag_ms"] = _hist(200, 40.0)
    sub0["histograms"]["loop.lag_ms"] = _hist(300, 65.0)
    snap1["histograms"]["loop.lag_ms"] = _hist(900, 12000.0)
    ctx = _ctx(snap0, snap1, sub0)

    def value(name):
        return run.load_reader("layer_metrics", name)(ctx)

    # all CPU over the quiet part: 500 ms over 20 pages, 550 ms staged
    assert value("host_cpu_ms_per_page.ingest") == pytest.approx(25.0)
    assert value("stage_cpu_coverage_pct.ingest") == pytest.approx(110.0)
    assert value("loop_lag_ms.ingest") == pytest.approx(0.25)
    assert value("loop_lag_ms.search") == pytest.approx(0.25)
    # extract 10 + split 20 + frame 30 + decode 40, over 20 pages
    assert value("handlers_cpu_ms_per_page.ingest") == pytest.approx(5.0)
    assert value("embed_tokenize_cpu_ms_per_page.ingest") == pytest.approx(2.5)
    assert value("embed_pack_cpu_ms_per_page.ingest") == pytest.approx(3.0)
    assert value("store_rows_cpu_ms_per_page.ingest") == pytest.approx(4.0)
    assert value("store_wal_encode_cpu_ms_per_page.ingest") == pytest.approx(4.5)
    # a wall mean keeps the whole window, as the metric it splits
    assert value("embed_hop_ms.ingest") == pytest.approx(10.0)
    # a stage whose clock did not tick in the quiet part still reads (0)
    sub0["counters"]["span.store.ingest_rows.cpu_ms_total"] = 10.0
    assert value("store_rows_cpu_ms_per_page.ingest") == 0.0
    # a stage the program does not count: no number for what needs it
    del sub0["counters"]["span.store.wal_sync.cpu_ms_total"]
    assert value("stage_cpu_coverage_pct.ingest") is None
    assert value("host_cpu_ms_per_page.ingest") == pytest.approx(25.0)
    # no profile, so no quiet part: no reading of CPU or lag
    ctx["trace"] = None
    assert value("host_cpu_ms_per_page.ingest") is None
    assert value("loop_lag_ms.ingest") is None
    assert value("handlers_cpu_ms_per_page.ingest") is None
    assert value("embed_hop_ms.ingest") == pytest.approx(10.0)
