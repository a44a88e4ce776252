"""Each cell end to end on the CPU at its configuration's toy sizes: the
reference and the program agree (`correct` true), the line names platform
`cpu` and carries no device metric; the control (the program's own int8 or
fp8 path, as the configuration names it for the cell) and one planted fault
per cell come out NOT correct.

`gen_chat` is not a cell of BENCHMARK.json (PERF.md, Open questions); its
entries are merged in from `gen_cell.json`, so the `generate` kind, its
readers and the GPT-2 reference stay proven for the cell that comes next.

Each run is a process of its own (one stack, one metrics registry, one jax
per process), about 20-40 s each.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import _gen_cell  # noqa: E402

RUN = HERE.parent / "run.py"
CELLS = ["ingest_pages", "gen_chat", "search_fused"]
FAULTS = {"ingest_pages": "ingest_row", "gen_chat": "gen_token",
          "search_fused": "search_hit"}
DEVICE_ONLY = ("roofline", "mfu", "idle", "_dev_ms")


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    return str(_gen_cell.write(
        tmp_path_factory.mktemp("bench") / "BENCHMARK.json"))


def last_line(cmd: list) -> dict:
    p = subprocess.run([sys.executable] + cmd, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_and_names_the_cpu(cell, trace, bench_json):
    out, err = last_line([str(RUN), "--workload", cell, "--seed",
                          "2147483653", "--seconds", "3", "--trace",
                          str(trace), "--rehearse-cpu", "--benchmark-json",
                          bench_json])
    assert out["correct"] is True, out["compared"]
    assert out["device"]["platform"] == "cpu"
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "compared"
    assert "correct = True" in err.strip().splitlines()[-1]
    assert not [m for m in out["metrics"]
                if any(tag in m for tag in DEVICE_ONLY)], out["metrics"]
    if trace == 0:
        assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    else:
        assert out["metrics"] and "window_s" in out["device"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, bench_json):
    out, _ = last_line([str(RUN), "--workload", cell, "--seed",
                        "2147483655", "--seconds", "3", "--trace", "0",
                        "--rehearse-cpu", "--control", "cell",
                        "--benchmark-json", bench_json])
    assert out["control"] in ("int8", "fp8")
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, bench_json):
    out, _ = last_line([str(HERE / "fault_run.py"), cell, FAULTS[cell],
                        "--benchmark-json", bench_json])
    assert out["correct"] is False, out["compared"]


def test_without_a_chip_there_is_no_result_line():
    import os

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "cpu"  # a CPU is all there is, and nobody
    # passed --rehearse-cpu: the harness must refuse, not fall back
    p = subprocess.run([sys.executable, str(RUN), "--workload",
                        "search_fused", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       timeout=300, env=env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
