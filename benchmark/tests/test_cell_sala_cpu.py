"""`ingest_longdocs_sala` end to end on the CPU at its configuration's toy
sizes (two periods of [sparse, linear x 3], 2 kv heads, a shrunk
`sparse_config`; passages of 67-253 tokens, over the toy `dense_len`), as
test_cell_moe_cpu.py does for `ingest_pages_moe`: the plain reference and
the program agree (`correct` true), the line names platform `cpu` and
carries no device metric; the configuration's control (int8 weights) and a
planted fault come out NOT correct. Each run is a process of its own."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
RUN = HERE.parent / "run.py"
CELL = "ingest_longdocs_sala"
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
    assert "_selection_gap_under_0.001_share" in err
    assert not [m for m in out["metrics"]
                if any(tag in m for tag in DEVICE_ONLY)], out["metrics"]
    if trace == 0:
        assert {"setup_s", "ingest_emb_per_s"} <= set(out["metrics"])
    else:
        # the program's own selection counters reach the line: selection is
        # on (under 100) and keeps the forced blocks at least
        kept = out["metrics"]["sparse_keys_kept_pct.ingest_sala"]["value"]
        assert 10.0 < kept < 100.0
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


FAULT_SEED = "2147483659"


@pytest.fixture(scope="module")
def sound_mean():
    out, _ = last_line([str(RUN), "--workload", CELL, "--seed", FAULT_SEED,
                        "--seconds", "3", "--trace", "0", "--rehearse-cpu"])
    assert out["correct"] is True, out["compared"]
    return out["compared"]["embed_rel_err_mean"]["value"]


@pytest.mark.parametrize("fault,seen", [
    ("select_worst", False), ("select_no_window", False),
    ("select_no_others", True)])
def test_planted_selection_faults_reach_the_program(sound_mean, fault, seen):
    """fault_run_sala.py breaks the sets underneath a run. The fault is in
    the program (the rows move away from the reference, nothing compiles in
    the window), and what `correct` makes of it at toy sizes is recorded,
    not wished for: top-6 of ~30 blocks at 64 dimensions hardly moves a
    row, so only the fault that drops keys is seen here, by the mean. At
    the cell's own size on the chip (`--chip`) each fault reads not correct
    by both limits (PERF.md, section 2)."""
    out, _ = last_line([str(HERE / "fault_run_sala.py"), CELL, fault,
                        "--seed", FAULT_SEED])
    compared = out["compared"]
    assert compared["compiles_in_window"]["value"] == 0
    assert compared["embed_rel_err_mean"]["value"] > 1.03 * sound_mean
    assert out["correct"] is not seen, compared


def test_the_configuration_states_the_published_model_and_its_cut():
    """The file's top level holds the catalog row's `config` but for the two
    keys listed in `reduced`; `model` is what is run: the same numbers plus
    this program's keys (`sparse_config`, `depth_layers`) and the
    `weights_seed`. No width, head count, vocabulary or sparse size is
    cut."""
    config = json.loads((HERE.parent / "configs"
                         / "minicpm-sala-embed.json").read_text())
    model = config["model"]
    extra = {"sparse_config", "depth_layers", "weights_seed"}
    assert {k: v for k, v in model.items() if k not in extra} == {
        k: config[k] for k in model if k not in extra}
    assert config["reduced"] == ["num_hidden_layers", "mixer_types"] == list(
        config["reduced_from"])
    published = config["reduced_from"]["mixer_types"]["published"]
    assert len(published) == 32 == config["reduced_from"][
        "num_hidden_layers"]["published"]
    assert model["mixer_types"] == published[9:17] == (
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"])
    assert published.count("minicpm4") * 3 == published.count(
        "lightning-attn")  # the published ratio, and the slice's
    assert (model["hidden_size"], model["intermediate_size"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], model["lightning_nh"], model["vocab_size"],
            model["depth_layers"]) == (4096, 16384, 32, 2, 128, 32, 73448, 32)
    assert model["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64, "dense_len": 8192}
    assert config["env"]["SYMBIONT_ENGINE_QUANTIZE"] == "f16"
    toy = config["toy"]["model"]
    assert toy["mixer_types"] == (["minicpm4"] + ["lightning-attn"] * 3) * 2
    assert toy["num_key_value_heads"] == 2


def test_every_passage_of_the_mix_is_sparse_and_none_is_truncated():
    sys.path.insert(0, str(HERE.parent))
    import traffic
    from kinds import ingest
    from refs.xlmr import token_count

    mix = traffic.load_mix("ingest_longdocs")
    lens = [token_count(s, 1 << 30)
            for s in ingest.page_sentences(mix, 12345, 3)]
    assert len(lens) == 6 and min(lens) > 8192 and max(lens) <= 32768
