"""The yardstick's arithmetic, checked against values worked by hand."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import traffic  # noqa: E402
import yardstick as y  # noqa: E402


def test_peaks_are_exact_keyed():
    assert y.chip_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    try:
        y.chip_peaks("TPU v5")
    except ValueError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


def test_bert_flops_by_hand():
    # one 10-token sentence, H=4, I=8, L=2:
    # per token 2*(8*16 + 4*4*8) = 512; attention 2*4*4*10*10 = 3200
    assert y.bert_fwd_flops([10], 4, 8, 2) == 10 * 512 + 3200


def test_gpt2_large_parameter_count():
    n = y.gpt_param_count(1280, 5120, 36, 50257, 1024)
    assert abs(n - 774.03e6) < 0.5e6


def test_gpt_decode_step_bytes_is_weights_plus_live_kv():
    H, I, L, V = 1280, 5120, 36, 50257
    b = y.gpt_decode_step_bytes([100], H, I, L, V, 2.0, 2.0)
    weights = (L * (4 * H * H + 2 * H * I) + H * V) * 2
    assert b == weights + L * 2 * H * 100 * 2


def test_prefill_flops_sum_of_token_flops_without_extra_heads():
    H, I, L, V = 8, 16, 2, 32
    by_token = sum(y.gpt_token_flops([s], H, I, L, V) - 2 * H * V
                   for s in range(1, 6)) + 2 * H * V
    assert abs(y.gpt_prefill_flops([5], H, I, L, V) - by_token) < 1e-6


def test_roofline_seconds_takes_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert y.roofline_seconds(200.0, 10.0, peaks) == 2.0
    assert y.roofline_seconds(100.0, 50.0, peaks) == 5.0


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = y.stratified_lognormal(200, 96, 0.6, 16, 256, np.random.default_rng(1))
    b = y.stratified_lognormal(200, 96, 0.6, 16, 256, np.random.default_rng(2))
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.min() >= 16 and a.max() <= 256


def test_arrivals_offer_the_same_load_for_every_seed():
    for seed in (1, 2**31 + 7):
        due = y.stratified_poisson_arrivals(120, 4.0,
                                            np.random.default_rng(seed))
        assert due[0] == 0.0 and np.all(np.diff(due) > 0)
        assert abs(due[-1] + (30.0 - due[-1]) - 30.0) < 1e-9
        assert due[-1] < 30.0


def test_spread_is_the_contracts_quartile_distance():
    assert abs(y.spread([1, 2, 3, 4, 5, 6]) - (5.25 - 1.75) / 3.5) < 1e-12


def test_every_seed_gets_the_same_work_in_another_order():
    """`--seed` draws content and order; the multiset of sizes and of
    arrival gaps is the mix's. A mix with `schedule_seed` fixes the order
    too."""
    mix = traffic.load_mix("search_fused")
    free = {k: v for k, v in mix.items()
            if k not in ("schedule_seed", "order_block")}
    model = {}
    p1 = traffic.build_plan(free, 5, 20, model)
    p2 = traffic.build_plan(free, 2**31 + 11, 20, model)
    size = lambda p: [len(r["query_text"].split()) for r in p["window"]]
    gaps = lambda p: np.round(np.diff(p["due"]), 9).tolist()
    assert sorted(size(p1)) == sorted(size(p2)) and size(p1) != size(p2)
    # the first request is due at 0, so each plan shows all its gaps but one
    assert len(set(gaps(p1)) ^ set(gaps(p2))) <= 2 and gaps(p1) != gaps(p2)
    assert traffic.build_plan(free, 5, 20, model) == p1
    fixed = {**free, "schedule_seed": 24}
    f1 = traffic.build_plan(fixed, 5, 20, model)
    f2 = traffic.build_plan(fixed, 2**31 + 11, 20, model)
    assert size(f1) == size(f2) and f1["due"] == f2["due"]
    assert f1["window"][0]["query_text"] != f2["window"][0]["query_text"]
    assert sorted(size(f1)) == sorted(size(p1))


def test_a_block_order_moves_whole_blocks_and_nothing_inside_them():
    """`order_block`: the mix's schedule inside blocks of that many requests,
    the blocks in the seed's order, sizes and gaps moved together."""
    mix = traffic.load_mix("search_fused")
    block = mix["order_block"]
    assert "schedule_seed" in mix and block == 60
    size = lambda p: [len(r["query_text"].split()) for r in p["window"]]

    def blocks(p):
        # a block's first gap belongs to it: the wait since the request before
        gap = np.round(np.diff(p["due"], prepend=p["due"][0]), 9)
        gap[0] = -1.0  # the window's first request is due at 0 whatever its gap
        pairs = list(zip(size(p), gap.tolist()))
        return [tuple(pairs[a:a + block]) for a in range(0, len(pairs), block)]

    seeds = (5, 2**31 + 11, 77)
    plans = [traffic.build_plan(mix, s, 45, {}) for s in seeds]
    assert all(len(p["window"]) == 1800 for p in plans)
    cut = [blocks(p) for p in plans]
    assert all(len(c) == 30 for c in cut)
    inner = [sorted(b[1:] for b in c) for c in cut]  # less each block's first gap
    assert inner[0] == inner[1] == inner[2]
    order = [[b[1:] for b in c] for c in cut]
    assert order[0] != order[1] != order[2]
    for p in plans:  # the same load over the same span, whatever the order
        assert 44.5 < p["due"][-1] < 45


def test_every_ingest_page_holds_the_same_lengths():
    mix = traffic.load_mix("ingest_pages")
    from kinds import ingest

    words = lambda i, s: [len(x.split())
                          for x in ingest.page_sentences(mix, s, i)]
    assert words(0, 1) == words(7, 1) == words(-1, 99)
    assert len(set(ingest.page_sentences(mix, 1, 0))) == 200
