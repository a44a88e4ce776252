"""`yardstick_mla_moe.py` against values worked by hand, at Kimi-VL-A3B's
published widths and at a size small enough to count on paper."""

from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import yardstick_mla_moe as ym  # noqa: E402

SMALL = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 3,
         "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 5,
         "intermediate_size": 16, "moe_intermediate_size": 6,
         "n_routed_experts": 4, "n_shared_experts": 2,
         "num_experts_per_tok": 3, "first_k_dense_replace": 1,
         "num_hidden_layers": 3}


def kimi() -> dict:
    path = HERE.parent / "configs" / "kimi-vl-a3b-embed.json"
    return json.loads(path.read_text())["model"]


def test_mla_params_by_hand():
    # q 8x(2x5) + kv_a 8x(5+2) + kv_b 5x(2x7) + o (2x4)x8
    assert ym.mla_params(SMALL) == 80 + 56 + 70 + 64
    # published widths: 2048x3072 + 2048x576 + 512x4096 + 2048x2048
    assert ym.mla_params(kimi()) == 6291456 + 1179648 + 2097152 + 4194304


def test_mla_flops_is_projections_plus_a_causal_prefix():
    # one sentence of 3 tokens: projections 2 x 270 x 3; attention: token p
    # meets p + 1 keys, 1 + 2 + 3 = 6 (query, key) pairs, each 2 x 2 heads
    # x (3 + 2 + 4) multiply-adds
    assert ym.mla_flops([3], SMALL) == 2 * 270 * 3 + 6 * 2 * 2 * 9
    # two sentences add up
    assert ym.mla_flops([3, 1], SMALL) == (ym.mla_flops([3], SMALL)
                                           + ym.mla_flops([1], SMALL))


def test_routed_flops_count_pairs_not_experts_held():
    assert ym.expert_params(SMALL) == 3 * 8 * 6
    assert ym.routed_flops(10, SMALL) == 2 * 144 * 10
    # the issue's 138 of 166 MFLOP per token and layer: 6 routed experts
    # 103.8 + the two shared 34.6 (+ the router's 0.26)
    m = kimi()
    assert ym.routed_flops(6, m) == 6 * 2 * 3 * 2048 * 1408
    per = ym.ffn_flops_per_token(m, 1)
    assert per == 6 * 6 * 2048 * 1408 + 6 * 2048 * 2816 + 2 * 2048 * 64
    assert abs(per / 1e6 - 138.7) < 0.1


def test_dense_layer_and_whole_stack():
    assert ym.ffn_flops_per_token(SMALL, 0) == 6 * 8 * 16
    moe = 2 * 8 * 4 + 3 * 2 * 144 + 6 * 8 * 6 * 2
    assert ym.ffn_flops_per_token(SMALL, 2) == moe
    assert ym.forward_flops([3, 1], SMALL) == (
        3 * ym.mla_flops([3, 1], SMALL) + 4 * (6 * 8 * 16 + 2 * moe))
    # the issue's 0.83 GFLOP a token, 5 layers, attention aside
    m = kimi()
    one = ym.forward_flops([1], m)
    assert abs(one / 1e9 - 0.831) < 0.002


def test_experts_seconds_take_the_scopeless_grouped_matmul_kernels(
        monkeypatch):
    """On the v5e the compiler's `ragged-dot-*` kernels keep no `tf_op`: the
    `experts` readers count them in, `mla` does not."""
    sys.path.insert(0, str(HERE.parent / "layer_metrics"))
    import _moe

    table = {(("symbiont.embed", "experts"), "fusion"): 0.25,
             (("symbiont.embed", "experts", "tkh,tk->th"), "fusion"): 0.05,
             (("symbiont.embed", "mla"), "fusion"): 0.2,
             ((), "ragged-dot-none"): 1.5, ((), "ragged-dot-metadata"): 0.01,
             ((), "copy"): 0.3}
    monkeypatch.setattr(_moe._host_spans, "trace_file", lambda ctx: "t")
    monkeypatch.setattr(_moe._scopes, "by_path", lambda path: table)
    assert abs(_moe.scope_seconds({}, "experts") - 1.81) < 1e-9
    assert _moe.scope_seconds({}, "mla") == 0.2
    assert _moe.scope_seconds({}, "router") is None
    ctx = {"trace": {"modules": {"jit_fn": {"count": 10, "seconds": 2.4}}}}
    assert abs(_moe.ms_per_program(ctx, "experts") - 181.0) < 1e-6
    # a program without the family (the parent): no scope, no metric
    monkeypatch.setattr(_moe._scopes, "by_path",
                        lambda path: {((), "ragged-dot-none"): 1.0})
    assert _moe.scope_seconds({}, "experts") is None
