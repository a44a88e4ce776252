"""`ingest_longdocs_mimo` end to end on the CPU at its configuration's toy
sizes (7 layers in the published pattern, 4 query heads over 1 or 2 KV
heads, a 16-token window with a sink, 32 experts of which 2 are held,
top-4; passages of 67-253 tokens in 256-token rows, so both kernels run
under the interpreter), as test_cell_ling_cpu.py does for
`ingest_longdocs_ling`: the plain reference and the program agree
(`correct` true), the line names platform `cpu` and carries no device
metric; the configuration's control (int8 weights) and the four planted
faults come out NOT correct. Each run is a process of its own."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
RUN = HERE.parent / "run.py"
CELL = "ingest_longdocs_mimo"
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
        # the program's own counters reach the line: a share of the choices
        # is held (2 of 32 experts), and the window keeps a share of the
        # causal keys that only the passages' lengths fix
        metrics = out["metrics"]
        held = metrics["experts_held_pct.ingest_mimo"]["value"]
        assert 2.0 < held < 15.0
        kept = metrics["window_keys_kept_pct.ingest_mimo"]["value"]
        assert 5.0 < kept < 40.0


def test_control_is_not_correct():
    out, _ = last_line([str(RUN), "--workload", CELL, "--seed",
                        "2147483655", "--seconds", "3", "--trace", "0",
                        "--rehearse-cpu", "--control", "cell"])
    assert out["control"] == "int8"
    assert out["correct"] is False, out["compared"]


FAULT_SEED = "2147483659"


@pytest.fixture(scope="module")
def sound_mean():
    out, _ = last_line([str(RUN), "--workload", CELL, "--seed", FAULT_SEED,
                        "--seconds", "3", "--trace", "0", "--rehearse-cpu"])
    assert out["correct"] is True, out["compared"]
    return out["compared"]["embed_rel_err_mean"]["value"]


@pytest.mark.parametrize("fault", ["window_off", "sink_dropped",
                                   "full_as_window", "held_renormalised"])
def test_planted_faults_are_not_correct(sound_mean, fault):
    """fault_run_mimo.py breaks the program underneath a run: the rows move
    away from the reference, nothing compiles in the window, and `correct`
    comes out false (at the cell's own size on the chip too, `--chip`:
    PERF.md, section 2)."""
    out, _ = last_line([str(HERE / "fault_run_mimo.py"), CELL, fault,
                        "--seed", FAULT_SEED])
    compared = out["compared"]
    assert compared["compiles_in_window"]["value"] == 0
    assert compared["embed_rel_err_mean"]["value"] > 2 * sound_mean
    assert out["correct"] is False, compared


def test_the_configuration_states_the_published_model_and_its_cut():
    """The file's top level holds the catalog row's `config` but for the
    two keys listed in `reduced` (depth, experts held); `model` is what is
    run: the published widths, the router over all 256 experts, the held
    share as `experts_held`, the whole vocabulary and the `weights_seed`."""
    config = json.loads((HERE.parent / "configs"
                         / "mimo-v2-flash-embed.json").read_text())
    model = config["model"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts"] \
        == list(config["reduced_from"])
    assert (config["num_hidden_layers"], config["n_routed_experts"]) == (7, 16)
    assert {k: v["published"] for k, v in config["reduced_from"].items()} == {
        "num_hidden_layers": 48, "n_routed_experts": 256}
    assert (model["n_routed_experts"], model["experts_held"]) == (256, 16)
    assert model["vocab_size"] == config["vocab_size"] == 152576
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads",
              "swa_num_key_value_heads", "head_dim", "v_head_dim",
              "sliding_window", "partial_rotary_factor",
              "num_experts_per_tok", "hybrid_layer_pattern",
              "moe_layer_freq", "attention_value_scale")
    assert {k: model[k] for k in widths} == {k: config[k] for k in widths}
    # the cut holds one whole period after the leading dense layer
    kinds = ["window" if model["hybrid_layer_pattern"][i] else "full"
             for i in range(model["num_hidden_layers"])]
    assert kinds == ["full"] + ["window"] * 4 + ["full", "window"]
    assert model["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    assert config["env"]["SYMBIONT_ENGINE_QUANTIZE"] == "f16"
    toy = config["toy"]["model"]
    assert toy["n_routed_experts"] // toy["experts_held"] == 16
