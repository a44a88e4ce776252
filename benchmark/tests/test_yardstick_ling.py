"""`yardstick_ling.py` against brute-force counts at toy shapes (every
token's causal keys and every layer enumerated) and values worked by
hand at Ling-3.0-flash's published widths."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import yardstick_ling as yl  # noqa: E402

SMALL = {"hidden_size": 8, "intermediate_size": 12, "num_attention_heads": 2,
         "head_dim": 3, "moe_intermediate_size": 5,
         "moe_shared_expert_intermediate_size": 5, "num_shared_experts": 1,
         "num_experts": 16, "experts_held": 4, "num_experts_per_tok": 2,
         "first_k_dense_replace": 2, "layer_group_size": 6,
         "num_hidden_layers": 8, "kv_lora_rank": 4, "qk_nope_head_dim": 3,
         "qk_rope_head_dim": 2, "v_head_dim": 3}


def ling() -> dict:
    path = HERE.parent / "configs" / "ling-3.0-flash-embed.json"
    return json.loads(path.read_text())["model"]


def test_layer_kinds_follow_the_group_size():
    assert yl.layer_kinds(SMALL) == (7, 1, 2, 6)
    assert yl.layer_kinds(ling()) == (7, 1, 2, 6)
    assert yl.layer_kinds({**ling(), "num_hidden_layers": 42}) == (35, 7, 2,
                                                                   40)


def test_mla_attention_counts_each_passages_causal_keys():
    lens = [5, 1, 3]
    keys = sum(sum(p + 1 for p in range(n)) for n in lens)
    per_key = 2 * SMALL["num_attention_heads"] * (3 + 2 + 3)
    assert yl.mla_attn_flops(lens, SMALL) == per_key * keys


def test_forward_adds_every_layer_by_brute_force():
    lens = [7, 4]
    n = sum(lens)
    H, nh, d = 8, 2, 3
    total = 0.0
    for i in range(8):
        if (i + 1) % 6 == 0:
            params = (H * nh * 5 + H * (4 + 2) + 4 * nh * 6 + nh * 3 * H
                      + H * nh)
            total += 2 * params * n + yl.mla_attn_flops(lens, SMALL)
        else:
            total += 2 * (6 * H * nh * d + H * nh) * n + 6 * d * d * nh * n
        total += n * (6 * H * 12 if i < 2 else 2 * H * 16 + 6 * H * 5)
    assert yl.forward_flops(lens, SMALL) == pytest.approx(total)


def test_published_widths_by_hand():
    """By hand: a KDA mixer 62.9 M matmul parameters (the 0.05 M
    of convolution taps are no matmul), MLA 32.0 M, an expert 5.898 M; per
    real token ~1,133 MFLOP of projections and dense feed-forward; the
    recurrence 6 d^2 a token and head moving 12 d bytes."""
    m = ling()
    assert yl.kda_params(m) == 6 * 2560 * 4096 + 2560 * 32
    assert round(yl.kda_params(m) / 1e6, 1) == 63.0
    assert yl.mla_params(m) / 1e6 == pytest.approx(31.965, abs=1e-3)
    assert yl.expert_params(m) == 3 * 2560 * 768
    per_token = (7 * 2 * yl.kda_params(m) + 2 * yl.mla_params(m)
                 + 2 * 6 * 2560 * 6144)
    assert round(per_token / 1e6) == 1135  # 1,133 with each part rounded first
    assert yl.kda_rule_flops([1], m) == 6 * 128 * 128 * 32
    assert yl.kda_rule_bytes([1], m) == (4 * 128 * 2 + 128 * 4) * 32


def test_a_page_of_the_mix():
    """One page (the multiset every page of `ingest_longdocs` holds): MLA's
    causal attention 21.4 TFLOP, the KDA recurrence bandwidth-bound at
    ~35.8 GB over 7 layers, about a quarter of the routed choices held,
    and 165 TFLOP in all with them."""
    import traffic
    from kinds import ingest
    from refs.xlmr import token_count

    m = ling()
    lens = [token_count(s, 32768)
            for s in ingest.page_sentences(traffic.load_mix(
                "ingest_longdocs"), 0, 0)]
    assert sum(lens) == 104046
    assert round(yl.mla_attn_flops(lens, m) / 1e12, 1) == 21.4
    assert round(7 * yl.kda_rule_bytes(lens, m) / 1e9, 1) == 35.8
    # (token, held expert) pairs under routing close to uniform: a quarter
    # of every real token's 8 choices in the 6 expert layers
    held_pairs = sum(lens) * 6 * 8 * yl.held(m) / m["num_experts"]
    assert held_pairs == pytest.approx(104046 * 6 * 8 / 4)
    whole = yl.forward_flops(lens, m) + yl.routed_flops(held_pairs, m)
    assert np.isclose(yl.forward_flops(lens, m) / 1e12, 150.8, atol=0.1)
    assert np.isclose(whole / 1e12, 165.5, atol=0.1)
