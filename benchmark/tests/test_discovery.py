"""The harness is driven by data: a new configuration, traffic mix, kind of
traffic, end-to-end metric and per-layer metric are ADDED as files and
entries, and nothing that exists is edited. Shown on a copy of the benchmark
in a temporary directory."""

from __future__ import annotations

import importlib.util
import json
import re
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


OWN_MODULES = ("traffic", "artefacts", "check", "yardstick", "client",
               "trace_reduce", "refs", "kinds", "_common")


def load_run(bench_dir: Path):
    """Import `run.py` of the benchmark at `bench_dir` with ITS modules."""
    import sys

    for name in list(sys.modules):
        if name.split(".")[0] in OWN_MODULES:
            del sys.modules[name]
    sys.path[:] = [p for p in sys.path if "/layer_metrics" not in p]
    spec = importlib.util.spec_from_file_location(
        f"run_{abs(hash(str(bench_dir)))}", bench_dir / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_benchmark_json_names_files_that_exist():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith("benchmark/")
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    run = load_run(BENCH)
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert callable(run.load_reader("layer_metrics", m["name"]))
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in b["end_to_end"]:
        assert m["bound"] <= 0.1 and m["source"] in ("host_clock",
                                                     "device_trace")
        assert m["name"] == "setup_s" or callable(
            run.load_reader("end_to_end", m["name"]))
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}


def test_one_reader_serves_the_families_that_share_a_quantity():
    run = load_run(BENCH)
    idle = {"trace": {"window_s": 4.0, "busy_s": 1.0}}
    for name in ("device_idle_pct.ingest", "device_idle_pct.search",
                 "device_idle_pct.a_later_family"):
        assert run.load_reader("layer_metrics", name)(idle) == 75.0
    try:
        run.load_reader("layer_metrics", "no_such_metric.search")
    except FileNotFoundError:
        pass
    else:
        raise AssertionError("a metric without a reader must be an error")


KIND = '''
"""A later PR's kind: pings `/healthz` (files only, nothing edited)."""
LATENCY_FIELD = "latency_ms"


def requests(mix, seed, n, model):
    return {"warmup": [], "window": [{"n": i} for i in range(n)]}


async def drive(plan, port, io):
    raise NotImplementedError


def attempted_failed(client):
    return client["attempted"], 0


def check(ctx):
    return {"pings_lost": ctx["number"](0, 0)}
'''


def test_adding_files_and_entries_is_enough(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    # a later PR's additions: a configuration, a kind of traffic, a mix of
    # that kind, an end-to-end metric and a per-layer metric
    new = tmp_path / "benchmark"
    cfg = json.loads((BENCH / "configs/xlmr-base-retrieval.json").read_text())
    cfg["name"] = "xlmr-other"
    (new / "configs/xlmr-other.json").write_text(json.dumps(cfg))
    (new / "kinds/ping.py").write_text(KIND)
    (new / "traffic/ping_slow.json").write_text(json.dumps(
        {"kind": "ping", "loop": "open", "rate_per_s": 2}))
    (new / "end_to_end/ping_p50_ms.py").write_text(
        "def read(ctx):\n    v = [r['latency_ms'] for r in "
        "ctx['client']['records']]\n"
        "    return ctx['yardstick'].percentile(v, 50) if v else None\n")
    (new / "layer_metrics/pings_done.py").write_text(
        "def read(ctx):\n    return float(len(ctx['client']['records'])) "
        "or None\n")
    b["configs"].append({"name": "xlmr-other", "source": "x", "file":
                         "benchmark/configs/xlmr-other.json",
                         "reduced": [], "why": "y"})
    b["workloads"].append({"name": "ping_slow", "config": "xlmr-other",
                           "traffic": "ping_slow", "chips": 1, "why": "z"})
    b["end_to_end"].append({"name": "ping_p50_ms", "unit": "ms",
                            "better": "lower", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["ping_slow"]})
    b["per_layer"].append({"name": "pings_done.ping", "unit": "requests",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator", "moves": "ping_p50_ms",
                           "workloads": ["ping_slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    run = load_run(new)
    bench = run.load_benchmark()
    cell, config, mix = run.find_cell(bench, "ping_slow")
    assert config["name"] == "xlmr-other" and mix["rate_per_s"] == 2
    plan = run.traffic.build_plan(mix, 7, 10, config["model"])
    assert len(plan["window"]) == 20 == len(plan["due"])
    client = {"attempted": 20,
              "records": [{"latency_ms": float(i)} for i in range(20)]}
    ctx = {"cell": cell, "config": config, "mix": mix, "plan": plan,
           "client": client, "seed": 7, "setup_s": 1.0,
           "yardstick": run.yardstick}
    assert run.end_to_end(ctx, bench) == {"setup_s": 1.0, "ping_p50_ms": 9.5}
    per_layer = run.metrics_of(bench, "per_layer", "ping_slow",
                               {"ping_p50_ms", "setup_s"})
    assert [m["name"] for m in per_layer] == ["pings_done.ping"]
    assert run.load_reader("layer_metrics", "pings_done.ping")(ctx) == 20.0
    cfg_limits = {**config, "limits": {"ping": {}}}
    numbers = run.check.compare({**ctx, "config": cfg_limits})
    assert numbers["pings_lost"]["ok"]
    assert run.traffic.load_kind("ping").attempted_failed(client) == (20, 0)
    # nothing that existed was touched
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_reader_that_finds_nothing_returns_nothing():
    run = load_run(BENCH)
    import _gen_cell

    b = _gen_cell.merged()  # the generation readers too
    empty = {"client": {"records": []}, "trace": None, "peaks": None,
             "snap0": {"counters": {}, "histograms": {}, "gauges": {}},
             "snap1": {"counters": {}, "histograms": {}, "gauges": {}},
             "rows0": 0, "rows1": 0, "window_s": 1.0, "seconds": 1.0}
    for m in b["per_layer"]:
        assert run.load_reader("layer_metrics", m["name"])(empty) is None, m["name"]
    for m in b["end_to_end"]:
        if m["name"] != "setup_s":
            assert run.load_reader("end_to_end", m["name"])(empty) is None
