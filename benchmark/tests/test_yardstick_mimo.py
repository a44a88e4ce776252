"""`yardstick_mimo.py` against brute-force counts at toy shapes (every
token's window and causal keys and every layer enumerated), values worked
by hand at MiMo-V2-Flash's published widths, and the `.ingest_mimo`
readers' arithmetic on a made-up window."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent / "layer_metrics"))

import yardstick_mimo as ym  # noqa: E402

SMALL = {"hidden_size": 8, "intermediate_size": 12, "num_attention_heads": 4,
         "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
         "head_dim": 6, "v_head_dim": 4, "moe_intermediate_size": 5,
         "n_routed_experts": 16, "experts_held": 2, "num_experts_per_tok": 2,
         "sliding_window": 3, "num_hidden_layers": 7,
         "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1],
         "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1, 1]}


def mimo() -> dict:
    path = HERE.parent / "configs" / "mimo-v2-flash-embed.json"
    return json.loads(path.read_text())["model"]


def test_layer_kinds_follow_the_pattern():
    assert ym.layer_kinds(SMALL) == (5, 2, 1, 6)
    assert ym.layer_kinds(mimo()) == (5, 2, 1, 6)
    assert ym.layer_kinds({**mimo(), "num_hidden_layers": 48}) == (39, 9, 1,
                                                                   47)


def test_keys_by_brute_force():
    lens = [5, 1, 2, 9]
    W = SMALL["sliding_window"]
    window = sum(1 for n in lens for i in range(n) for j in range(n)
                 if i - W < j <= i)
    causal = sum(1 for n in lens for i in range(n) for j in range(n)
                 if j <= i)
    assert ym.window_keys(lens, SMALL) == window
    assert ym.causal_keys(lens) == causal
    assert ym.window_keys_kept_pct(lens, SMALL) == pytest.approx(
        100 * window / causal)
    per_key = 2 * 4 * (6 + 4)
    assert ym.window_attn_flops(lens, SMALL) == per_key * window
    assert ym.full_attn_flops(lens, SMALL) == per_key * causal


def test_forward_adds_every_layer_by_brute_force():
    lens = [7, 4]
    n = sum(lens)
    H = 8
    total = 0.0
    for i in range(7):
        window = SMALL["hybrid_layer_pattern"][i]
        kv = 2 if window else 1
        params = H * 4 * 6 + H * kv * (6 + 4) + 4 * 4 * H
        total += 2 * params * n + (ym.window_attn_flops(lens, SMALL) if window
                                   else ym.full_attn_flops(lens, SMALL))
        total += n * (6 * H * 12 if i == 0 else 2 * H * 16)
    assert ym.forward_flops(lens, SMALL) == pytest.approx(total)


def test_published_widths_by_hand():
    """By hand (the issue's table): a window mixer 94.37 M matmul
    parameters, a full one 89.13 M, an expert 25.17 M; q, k and v read and
    the context written once at bfloat16 and the model's own widths."""
    m = mimo()
    assert ym.attn_params(m, True) == (4096 * 64 * 192 + 4096 * 8 * 320
                                       + 64 * 128 * 4096)
    assert round(ym.attn_params(m, True) / 1e6, 2) == 94.37
    assert round(ym.attn_params(m, False) / 1e6, 2) == 89.13
    assert round(ym.expert_params(m) / 1e6, 2) == 25.17
    assert ym.attn_core_bytes([1], m, True) == 2 * (64 * 192 + 8 * 320
                                                    + 64 * 128)
    assert ym.attn_core_flops(1, m) == 2 * 64 * (192 + 128)


def test_a_page_of_the_mix():
    """One page (the multiset every page of `ingest_longdocs` holds): the
    two full layers' causal attention 85.8 TFLOP, the five window layers'
    2.7 TFLOP, 1.2675% of the causal keys kept by the window, a sixteenth
    of the routed choices held, and ~283 TFLOP in all with them."""
    import traffic
    from kinds import ingest
    from refs.xlmr import token_count

    m = mimo()
    lens = [token_count(s, 32768)
            for s in ingest.page_sentences(traffic.load_mix(
                "ingest_longdocs"), 0, 0)]
    assert sum(lens) == 104046
    assert round(2 * ym.full_attn_flops(lens, m) / 1e12, 1) == 85.8
    assert round(5 * ym.window_attn_flops(lens, m) / 1e12, 1) == 2.7
    assert round(ym.window_keys_kept_pct(lens, m), 4) == 1.2675
    held_pairs = sum(lens) * 6 * 8 * ym.held(m) / m["n_routed_experts"]
    assert held_pairs == pytest.approx(104046 * 6 * 8 / 16)
    whole = ym.forward_flops(lens, m) + ym.routed_flops(held_pairs, m)
    assert np.isclose(ym.forward_flops(lens, m) / 1e12, 267.0, atol=0.1)
    assert np.isclose(whole / 1e12, 282.7, atol=0.1)


# ------------------------------------------------------------- the readers

def _ctx(counters0, counters1, hist0=0, hist1=0):
    def snap(counters, hist):
        return {"counters": counters, "histograms": {
            'engine.moe.expert_load_max_over_mean{service="engine"}':
                {"count": hist, "sum": 0.0}}}

    import yardstick

    return {"snap0": snap(counters0, hist0), "snap1": snap(counters1, hist1),
            "window_s": 40.0, "trace": {"window_s": 8.0},
            "peaks": yardstick.chip_peaks("TPU v5 lite"), "model": mimo(),
            "yardstick": yardstick, "cell": {"name": "ingest_longdocs_mimo"}}


def test_programs_are_counted_at_the_windows_rate():
    """20 dispatches in a 40 s window: an 8 s traced sub-window holds 4
    programs whether or not it cuts one, so 2 s of op time is 500 ms a
    program."""
    import _mimo

    ctx = _ctx({"engine.embed.dispatches": 100},
               {"engine.embed.dispatches": 120})
    assert _mimo.programs_traced(ctx) == pytest.approx(4.0)
    assert _mimo.ms_per_program(ctx, 2.0) == pytest.approx(500.0)
    assert _mimo.ms_per_program(ctx, None) is None
    assert _mimo.programs_traced({**ctx, "trace": None}) is None


def test_count_readers_read_the_programs_series():
    import importlib.util

    def reader(name):
        path = HERE.parent / "layer_metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    ctx = _ctx({"engine.attn.window_keys": 0, "engine.attn.keys_causal": 0,
                "engine.moe.assignments": 0,
                "engine.moe.assignments_routed": 0},
               {"engine.attn.window_keys": 12675,
                "engine.attn.keys_causal": 1000000,
                "engine.moe.assignments": 625,
                "engine.moe.assignments_routed": 10000})
    assert reader("window_keys_kept_pct.ingest_mimo")(ctx) == pytest.approx(
        1.2675)
    assert reader("experts_held_pct.ingest_mimo")(ctx) == pytest.approx(6.25)
    empty = _ctx({}, {})
    assert reader("window_keys_kept_pct.ingest_mimo")(empty) is None
    assert reader("experts_held_pct.ingest_mimo")(empty) is None
    # no trace file: every device reader leaves its metric out
    for name in ("embed_swa_dev_ms", "embed_full_attn_dev_ms",
                 "embed_experts_dev_ms", "swa_roofline", "full_attn_roofline",
                 "experts_roofline"):
        assert reader(f"{name}.ingest_mimo")({**empty, "trace": None}) is None
