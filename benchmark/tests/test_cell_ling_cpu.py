"""`ingest_longdocs_ling` end to end on the CPU at its configuration's toy
sizes (8 layers in the published pattern, 32 experts in 8 groups of 4 with
8 held, top-4; passages of 67-253 tokens), as test_cell_sala_cpu.py does for
`ingest_longdocs_sala`: the plain reference and the program agree (`correct`
true), the line names platform `cpu` and carries no device metric; the
configuration's control (int8 weights) and the planted faults come out NOT
correct where the toy sizes can see them. Each run is a process of its own."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
RUN = HERE.parent / "run.py"
CELL = "ingest_longdocs_ling"
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
        # the program's own expert counters reach the line: a share of the
        # choices is held, neither none nor all (8 of 32 experts here)
        held = out["metrics"]["experts_held_pct.ingest_ling"]["value"]
        assert 10.0 < held < 60.0
        assert out["metrics"]["embed_dispatches_per_flush.ingest"][
            "value"] >= 1.0


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


@pytest.mark.parametrize("fault,seen", [("kda_no_reset", True),
                                        ("held_renormalised", False)])
def test_planted_faults_reach_the_program(sound_mean, fault, seen):
    """fault_run_ling.py breaks the program underneath a run: the rows move
    away from the reference and nothing compiles in the window. What
    `correct` makes of it at toy sizes is recorded, not wished for: a state
    carried into the next passage is seen; at 64 dimensions an expert's
    SwiGLU adds ~1e-3 to a residual of ~1, so weights four times too large
    on the held choices hardly move a row. At the cell's own size on the
    chip (`--chip`) both read not correct (PERF.md, section 2)."""
    out, _ = last_line([str(HERE / "fault_run_ling.py"), CELL, fault,
                        "--seed", FAULT_SEED])
    compared = out["compared"]
    assert compared["compiles_in_window"]["value"] == 0
    assert compared["embed_rel_err_mean"]["value"] > 1.01 * sound_mean
    assert out["correct"] is not seen, compared


def test_the_configuration_states_the_published_model_and_its_cut():
    """The file's top level holds the catalog row's `config` but for the
    three keys listed in `reduced` (depth, experts held, vocabulary); `model`
    is what is run: the published widths, the router over all 512 experts,
    the held share as `experts_held`, the keys the VL row omits that the
    text-only sibling gives, and the `weights_seed`."""
    config = json.loads((HERE.parent / "configs"
                         / "ling-3.0-flash-embed.json").read_text())
    model = config["model"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"] == list(config["reduced_from"])
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 128, 39296)
    assert {k: v["published"] for k, v in config["reduced_from"].items()} == {
        "num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184}
    assert (model["num_experts"], model["experts_held"]) == (512, 128)
    assert 157184 // model["vocab_size"] == 4
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "moe_shared_expert_intermediate_size", "num_attention_heads",
              "head_dim", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
              "n_group", "topk_group", "layer_group_size",
              "short_conv_kernel_size", "first_k_dense_replace")
    assert {k: model[k] for k in widths} == {k: config[k] for k in widths}
    assert (model["hidden_size"], model["num_experts_per_tok"],
            model["n_group"], model["topk_group"]) == (2560, 8, 8, 4)
    # the cut holds one whole period: KDA x 5 and MLA x 1 after the dense two
    kinds = ["mla" if (i + 1) % model["layer_group_size"] == 0 else "kda"
             for i in range(model["num_hidden_layers"])]
    assert kinds == ["kda"] * 5 + ["mla"] + ["kda"] * 2
    assert not any(model["expert_swiglu_limit_list"][:8])
    assert config["env"]["SYMBIONT_ENGINE_QUANTIZE"] == "f16"
    toy = config["toy"]["model"]
    assert toy["num_experts"] // toy["experts_held"] == 4


def test_every_passage_of_the_mix_fits_a_row_and_spans_chunks():
    sys.path.insert(0, str(HERE.parent))
    import traffic
    from kinds import ingest
    from refs.xlmr import token_count

    mix = traffic.load_mix("ingest_longdocs")
    lens = [token_count(s, 1 << 30)
            for s in ingest.page_sentences(mix, 12345, 3)]
    assert len(lens) == 6 and min(lens) > 8192 and max(lens) <= 32768
