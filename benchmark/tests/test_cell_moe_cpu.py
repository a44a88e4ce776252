"""`ingest_pages_moe` end to end on the CPU at its configuration's toy sizes
(8 experts, top-2, a shared expert, 1 dense + 2 expert layers), as
test_cells_cpu.py does for the other cells: the plain reference and the
program agree (`correct` true), the line names platform `cpu` and carries
no device metric; the configuration's control (int8 weights) and a planted
fault come out NOT correct. Each run is a process of its own."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
RUN = HERE.parent / "run.py"
CELL = "ingest_pages_moe"
DEVICE_ONLY = ("roofline", "mfu", "idle", "_dev_ms")


def last_line(cmd: list) -> tuple:
    p = subprocess.run([sys.executable] + cmd, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_and_names_the_cpu(trace):
    out, err = last_line([str(RUN), "--workload", CELL, "--seed",
                          "2147483653", "--seconds", "3", "--trace",
                          str(trace), "--rehearse-cpu"])
    assert out["correct"] is True, out["compared"]
    assert out["device"]["platform"] == "cpu"
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "correct = True" in err.strip().splitlines()[-1]
    assert "_router_gap_under_0.001_share" in err
    assert not [m for m in out["metrics"]
                if any(tag in m for tag in DEVICE_ONLY)], out["metrics"]
    if trace == 0:
        assert {"setup_s", "ingest_emb_per_s"} <= set(out["metrics"])
    else:
        # the program's own expert-load series reach the line
        assert out["metrics"]["expert_load_max_over_mean.ingest_moe"][
            "value"] >= 1.0
        assert "embed_pad_waste_pct.ingest" in out["metrics"]


def test_control_is_not_correct():
    out, _ = last_line([str(RUN), "--workload", CELL, "--seed",
                        "2147483655", "--seconds", "3", "--trace", "0",
                        "--rehearse-cpu", "--control", "cell"])
    assert out["control"] == "int8"
    assert out["correct"] is False, out["compared"]


def test_planted_fault_is_not_correct():
    out, _ = last_line([str(HERE / "fault_run.py"), CELL, "ingest_row"])
    assert out["correct"] is False, out["compared"]


def test_the_configuration_states_the_published_model_once_and_the_same():
    """The file's top level holds the catalog row's `config` (what the
    driver compares with the catalog); `model` is what is run: the same
    numbers, plus the assumed `model_type` and the `weights_seed`. Only
    `num_hidden_layers` differs from the source, and it is listed."""
    config = json.loads((HERE.parent / "configs"
                         / "kimi-vl-a3b-embed.json").read_text())
    model = config["model"]
    extra = {"model_type", "weights_seed"}
    assert {k: v for k, v in model.items() if k not in extra} == {
        k: config[k] for k in model if k not in extra}
    assert config["reduced"] == ["num_hidden_layers"] == list(
        config["reduced_from"])
    assert config["reduced_from"]["num_hidden_layers"]["published"] == 27
    assert (model["n_routed_experts"], model["num_experts_per_tok"],
            model["n_shared_experts"], model["vocab_size"]) == (
        64, 6, 2, 163840)
    assert config["env"]["SYMBIONT_ENGINE_QUANTIZE"] == "f16"
