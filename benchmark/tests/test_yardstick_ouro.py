"""`yardstick_ouro.py` against counts worked by hand at toy shapes and at
Ouro-2.6B's published widths."""

from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import yardstick_ouro as yo  # noqa: E402

SMALL = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "num_key_value_heads": 2, "head_dim": 4, "num_hidden_layers": 3,
         "total_ut_steps": 2}


def ouro() -> dict:
    path = HERE.parent / "configs" / "ouro-2.6b-embed.json"
    return json.loads(path.read_text())["model"]


def test_one_application_by_hand():
    # q, k, v, o: 4 x 8 x 8; per key 4 x heads x head_dim
    assert yo.attn_params(SMALL) == 256 and yo.ffn_params(SMALL) == 384
    # a 3-token chunk: 3 x 2 x 256 for the projections; a token at position
    # p scores p + 1 keys and sums p + 1 values: (1 + 2 + 3) x 4 x 2 x 4
    assert yo.attn_flops([3], SMALL) == 3 * 512 + 6 * 32
    # chunks add up, and two chunks never see each other's keys
    assert yo.attn_flops([3, 2], SMALL) == (5 * 512 + (6 + 3) * 32)
    assert yo.attn_flops([5], SMALL) > yo.attn_flops([3, 2], SMALL)
    assert yo.ffn_flops([3, 2], SMALL) == 5 * 2 * 384


def test_the_loop_multiplies_applications_not_weights():
    assert yo.applications(SMALL) == 6
    lens = [3, 2]
    assert yo.forward_flops(lens, SMALL) == 6 * (
        yo.attn_flops(lens, SMALL) + yo.ffn_flops(lens, SMALL))
    # bytes of ONE application: the sub-layer's kernels once per dispatch
    # at bfloat16, the float32 stream read and written once
    assert yo.stream_bytes(lens, SMALL) == 2 * 5 * 8 * 4
    assert yo.attn_bytes(lens, SMALL) == 256 * 2 + 320
    assert yo.ffn_bytes(lens, SMALL, dispatches=2.0) == 2 * 384 * 2 + 320


def test_published_widths_by_hand():
    m = ouro()
    assert yo.applications(m) == 192
    assert yo.attn_params(m) == 4 * 2048 * 2048 == 16777216
    assert yo.ffn_params(m) == 3 * 2048 * 5632 == 34603008
    # a token far from its chunk's start is ~19.7 GFLOP of projections and
    # SwiGLU (the issue's count) plus its causal keys
    per_token = (yo.forward_flops([400], m) - yo.forward_flops([399], m))
    dense = 192 * 2 * (16777216 + 34603008)
    assert dense == 19730006016
    assert per_token == dense + 192 * 400 * 4 * 16 * 128
    # the cell's page (3,565 real tokens in chunks of 62-483) against the
    # [8, 512] dispatch that holds it: padding is never counted
    page = [62, 82, 97, 109, 120, 132, 143, 154, 167, 180, 194, 210, 228,
            249, 276, 312, 367, 483]
    useful = yo.forward_flops(page, m)
    assert 70.3e12 < useful < 71.5e12
    assert useful < 4096 * dense
    # both sub-layers are compute-bound at a page: FLOPs / 197e12 over
    # bytes / 819e9
    for flops, bytes_ in ((yo.attn_flops(page, m), yo.attn_bytes(page, m)),
                          (yo.ffn_flops(page, m), yo.ffn_bytes(page, m))):
        assert flops / 197e12 > 3 * bytes_ / 819e9
