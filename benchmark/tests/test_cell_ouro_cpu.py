"""`ingest_chunks_ouro` end to end on the CPU at its configuration's toy
sizes (hidden 64, 4 heads of 16, 4 layers, 3 steps; chunks of 20-120 tokens
in 128-token rows), as test_cell_moe_cpu.py does for `ingest_pages_moe`:
the plain reference and the program agree (`correct` true), the line names
platform `cpu` and carries no device metric; the configuration's control
(int8 weights) and every planted fault come out NOT correct. Each run is a
process of its own."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
RUN = HERE.parent / "run.py"
CELL = "ingest_chunks_ouro"
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
    assert "3 steps x 4 layers" in err
    assert not [m for m in out["metrics"]
                if any(tag in m for tag in DEVICE_ONLY)], out["metrics"]
    if trace == 0:
        assert {"setup_s", "ingest_emb_per_s"} <= set(out["metrics"])
    else:
        # the program's own loop counters reach the line: every step ran
        assert out["metrics"]["loop_steps_run_pct.ingest_ouro"][
            "value"] == 100.0
        assert "embed_pad_waste_pct.ingest" in out["metrics"]
        assert out["metrics"]["embed_dispatches_per_flush.ingest"][
            "value"] >= 1.0


def test_control_is_not_correct():
    out, _ = last_line([str(RUN), "--workload", CELL, "--seed",
                        "2147483655", "--seconds", "3", "--trace", "0",
                        "--rehearse-cpu", "--control", "cell"])
    assert out["control"] == "int8"
    assert out["correct"] is False, out["compared"]


def test_planted_fault_is_not_correct():
    out, _ = last_line([str(HERE / "fault_run.py"), CELL, "ingest_row"])
    assert out["correct"] is False, out["compared"]
    assert out["compared"]["embed_rel_err_max"]["value"] > 1.5  # negated


@pytest.mark.parametrize("fault", ["loop_one_step_short",
                                   "loop_no_norm_between",
                                   "block_no_second_norm"])
def test_planted_loop_faults_are_not_correct(fault):
    """fault_run_ouro.py breaks the loop or its block underneath a run. The
    fault is in the program (nothing compiles in the window) and `correct`
    sees it by both limits, at toy sizes as at the cell's own on the chip
    (`--chip`; PERF.md, section 2)."""
    out, _ = last_line([str(HERE / "fault_run_ouro.py"), CELL, fault])
    compared = out["compared"]
    assert compared["compiles_in_window"]["value"] == 0
    assert out["correct"] is False, compared
    for name in ("embed_rel_err_mean", "embed_rel_err_max"):
        assert compared[name]["value"] > 10 * compared[name]["limit"]


def test_the_configuration_states_the_published_model_whole():
    """The file's top level holds every key of the catalog row's `config`;
    `model` is what is run: the same numbers plus the `weights_seed`.
    Nothing is reduced: all 48 layers, 4 steps, every width and the whole
    vocabulary."""
    config = json.loads((HERE.parent / "configs"
                         / "ouro-2.6b-embed.json").read_text())
    model = config["model"]
    assert {k: v for k, v in model.items() if k != "weights_seed"} == {
        k: config[k] for k in model if k != "weights_seed"}
    assert config["reduced"] == [] and config["reduced_from"] == {}
    assert (model["hidden_size"], model["intermediate_size"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], model["num_hidden_layers"],
            model["total_ut_steps"], model["early_exit_threshold"],
            model["vocab_size"], model["rope_theta"]) == (
        2048, 5632, 16, 16, 128, 48, 4, 1, 49152, 1000000)
    assert model["layer_types"] == ["full_attention"] * 48
    assert config["source"].endswith("ByteDance/Ouro-2.6B/blob/main/"
                                     "config.json")
    assert config["env"]["SYMBIONT_ENGINE_QUANTIZE"] == "f16"
    assert config["env"]["SYMBIONT_ENGINE_LENGTH_BUCKETS"] == [512]
    for point in ("sandwich_norm", "norm_every_step", "exit_gate",
                  "exit_distribution", "attention", "tensor_names",
                  "encoder_head", "not_instantiated"):
        assert point in config["assumed"], point
    toy = config["toy"]["model"]
    assert (toy["num_hidden_layers"], toy["total_ut_steps"],
            toy["hidden_size"], toy["head_dim"]) == (4, 3, 64, 16)


def test_a_page_of_the_mix_is_one_dispatch_and_none_is_truncated():
    sys.path.insert(0, str(HERE.parent))
    sys.path.insert(1, str(HERE.parent.parent))
    import traffic
    from kinds import ingest
    from refs.xlmr import token_count

    from symbiont_tpu.engine.bucketing import plan_packed

    mix = traffic.load_mix("ingest_chunks")
    lens = [token_count(s, 1 << 30)
            for s in ingest.page_sentences(mix, 12345, 3)]
    assert len(lens) == 18 and min(lens) >= 51 and max(lens) <= 483
    assert sum(lens) == 3565
    L, dispatches = plan_packed(lens, [512], 8)
    assert L == 512 and [len(rows) for rows in dispatches] == [8]
