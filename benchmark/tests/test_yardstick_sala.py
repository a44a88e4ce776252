"""`yardstick_sala.py` against brute-force counts at toy shapes (every
token's set enumerated block by block) and values worked by hand at
MiniCPM-SALA's published widths."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import yardstick_sala as ys  # noqa: E402

SMALL = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 3, "lightning_nh": 2,
         "lightning_head_dim": 5,
         "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn"],
         "sparse_config": {"kernel_size": 4, "kernel_stride": 2,
                           "block_size": 8, "init_blocks": 1,
                           "window_size": 16, "topk": 6, "dense_len": 32}}


def sala() -> dict:
    path = HERE.parent / "configs" / "minicpm-sala-embed.json"
    return json.loads(path.read_text())["model"]


def brute_keys(n: int, sp: dict) -> list:
    """Per token: the size of a set of min(topk, causal blocks) blocks that
    holds the token's own block (always taken: the window), every other
    block whole."""
    out = []
    for p in range(n):
        if n <= sp["dense_len"]:
            out.append(p + 1)
            continue
        blocks = p // sp["block_size"] + 1
        taken = min(blocks, sp["topk"])
        out.append((taken - 1) * sp["block_size"] + p % sp["block_size"] + 1)
    return out


def brute_kernels(n: int, sp: dict) -> list:
    if n <= sp["dense_len"]:
        return [0] * n
    starts = range(0, n - sp["kernel_size"] + 1, sp["kernel_stride"])
    return [sum(1 for s in starts if s + sp["kernel_size"] - 1 <= p)
            for p in range(n)]


@pytest.mark.parametrize("n", [1, 20, 32, 33, 47, 48, 49, 100, 253])
def test_keys_and_kernels_equal_a_brute_force_count(n):
    sp = SMALL["sparse_config"]
    assert ys.keys_attended(n, SMALL).tolist() == brute_keys(n, sp)
    assert ys.kernels_visible(n, SMALL).tolist() == brute_kernels(n, sp)


def test_sparse_flops_count_the_set_never_the_causal_prefix():
    n, nh, d = 100, 4, 3
    sp = SMALL["sparse_config"]
    want = (2 * nh * d * sum(brute_kernels(n, sp))
            + 4 * nh * d * sum(brute_keys(n, sp)))
    assert ys.sparse_flops([n], SMALL) == want
    dense = 4 * nh * d * n * (n + 1) // 2
    assert ys.sparse_flops([n], SMALL) < dense
    # a passage under dense_len IS the causal count, and makes no selection
    assert ys.sparse_flops([20], SMALL) == 4 * nh * d * 20 * 21 // 2
    # passages add up
    assert ys.sparse_flops([100, 20], SMALL) == want + 4 * nh * d * 210


def test_lightning_counts_the_recurrence():
    # per token and head: 5 x 5 multiply-adds in, 5 x 5 out
    assert ys.lightning_flops([7, 3], SMALL) == 10 * 2 * (2 * 25 + 2 * 25)
    assert ys.lightning_bytes([7, 3], SMALL) == 4 * 10 * 2 * 5 * 2


def test_bytes_of_the_sparse_layer_by_hand():
    # 100 tokens: q and ctx 2 x 100 x 12 x 2 B, K and V 2 x 100 x 6 x 2 B,
    # 49 kernels x 6 x 4 B written and read
    assert ys.sparse_bytes([100], SMALL) == 4800 + 2400 + 2 * 49 * 6 * 4
    assert ys.sparse_bytes([20], SMALL) == 20 * (48 + 24)


def test_forward_flops_add_up():
    lens = [100, 20]
    tokens = 120
    sparse_mixer = 3 * 8 * 12 + 2 * 8 * 6
    linear_mixer = 5 * 8 * 10
    ffn = 6 * 8 * 16
    want = (tokens * (2 * sparse_mixer + ffn) + ys.sparse_flops(lens, SMALL)
            + 2 * (tokens * (2 * linear_mixer + ffn)
                   + ys.lightning_flops(lens, SMALL)))
    assert ys.forward_flops(lens, SMALL) == want


def test_published_widths_by_hand():
    m = sala()
    assert ys.mixer_params(m, ys.SPARSE) == 3 * 4096 * 4096 + 2 * 4096 * 256
    assert ys.mixer_params(m, ys.LINEAR) == 5 * 4096 * 4096
    # a 17,595-token passage keeps about two fifths of its causal keys, a
    # 32,003-token one a quarter
    for n, lo, hi in ((17595, 0.40, 0.42), (32003, 0.23, 0.25)):
        kept = ys.keys_attended(n, m).sum() / (n * (n + 1) / 2)
        assert lo < kept < hi, kept
    # per token, far from a passage's start: 4.65 GFLOP over 8 layers
    n = 20000
    per_token = (ys.forward_flops([n], m) - ys.forward_flops([n - 1], m))
    assert 4.5e9 < per_token < 4.8e9
