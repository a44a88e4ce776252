"""models/mimo.py (128-token sliding-window GQA with a learned sink beside
full GQA, 5 : 1, routed experts over the share a chip holds) and the
grouped form of ops/flash_attention.py `packed_attention` against the
benchmark's plain reference (`benchmark/refs/mimo_v2_flash.py`, imported by
path: float32 jax.numpy at matmul precision "highest", attention over
explicit masks, one passage a call, the published head layout, nothing of
the program in it), on seeded weights at toy widths that keep the
mechanisms: 7 layers in the published pattern (full with a dense SwiGLU,
window x 4, full, window, each after the first with experts), 4 query heads
over 1 KV head (full) or 2 (window), a 24-wide q.k head of which 8 dims
turn, a 16-token window with a sink a head, 32 experts of which 2 are held
(16 chips a layer, as published), top-4.

Tolerances, each with its reason:
- float32 program against the reference, and packed rows against each
  passage alone: 2e-5 relative on rows (read: 2e-7 to 3e-7). The same maths
  in the same precision; what differs is summation order (the kernel's
  streaming softmax, the lanes a head is laid in). A dropped sink, a window
  left off or put on a full layer move rows by 10-90% (asserted below).
- the kernel against a dense float32 softmax: 1e-5 relative to the largest
  output (read: 5e-7 to 8e-7), the same reason.
- the shares of a layer against the uncut layer: 1e-4 relative (the
  experts' sum in another order and grouping).
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))
from refs import mimo_v2_flash as ref  # noqa: E402

from symbiont_tpu.config import EngineConfig  # noqa: E402
from symbiont_tpu.engine.engine import TpuEngine  # noqa: E402
from symbiont_tpu.models import convert, families, ling, mimo, mla_moe  # noqa: E402
from symbiont_tpu.models.bert import Segments  # noqa: E402
from symbiont_tpu.models.layers import rmsnorm  # noqa: E402
from symbiont_tpu.ops.flash_attention import packed_attention  # noqa: E402
from symbiont_tpu.utils.telemetry import metrics  # noqa: E402

# the module (the package exports a function of its name)
fa = importlib.import_module("symbiont_tpu.ops.flash_attention")

PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
MODEL = {
    "model_type": "mimo_v2_flash", "vocab_size": 500, "hidden_size": 64,
    "num_hidden_layers": 7, "num_attention_heads": 4,
    "num_key_value_heads": 1, "swa_num_attention_heads": 4,
    "swa_num_key_value_heads": 2, "head_dim": 24, "swa_head_dim": 24,
    "v_head_dim": 16, "swa_v_head_dim": 16, "partial_rotary_factor": 0.334,
    "rope_theta": 5000000, "swa_rope_theta": 10000, "sliding_window": 16,
    "sliding_window_size": 16, "attention_chunk_size": 16,
    "attention_value_scale": 0.707, "hybrid_layer_pattern": PATTERN,
    "moe_layer_freq": [0] + [1] * 47, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "intermediate_size": 96,
    "moe_intermediate_size": 16, "n_routed_experts": 32, "experts_held": 2,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": None, "n_shared_experts": None,
    "layernorm_epsilon": 1e-5, "attention_bias": False, "hidden_act": "silu",
    "max_position_embeddings": 4096,
}
SEED = 7
TOL = 2e-5
LENS = (100, 37, 150)


def _load(model, out):
    ref.write_checkpoint(model, SEED, out)
    params, cfg = convert.load_mimo_model(out)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    return params32, cfg32


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """The reference's checkpoint (assumed names, bfloat16) loaded through
    the program's own converter, upcast for float32 comparisons."""
    out = tmp_path_factory.mktemp("mimo_toy")
    return (out, *_load(MODEL, out))


@pytest.fixture(scope="module")
def passages():
    rng = np.random.default_rng(0)
    return [rng.integers(3, MODEL["vocab_size"], n).astype(np.int32)
            for n in LENS]


@pytest.fixture(scope="module")
def want(passages):
    return np.stack(ref.Reference(MODEL, SEED, 4096).forward(
        [list(p) for p in passages]))


def _segments(lens, L, S=8):
    seg = np.zeros((1, S), np.int32)
    seg[0, :len(lens)] = lens
    return Segments.of_lengths(jnp.asarray(seg), L)


def _rel(got, want):
    return (np.linalg.norm(np.asarray(got, np.float64) - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-12))


def _embed(params, cfg, seqs, L=512):
    ids = np.zeros((1, L), np.int32)
    ids[0, :sum(map(len, seqs))] = np.concatenate(seqs)
    seg = _segments([len(s) for s in seqs], L)
    with jax.default_matmul_precision("highest"):
        rows, aux = mimo.embed_sentences(params, jnp.asarray(ids), seg.real,
                                         cfg, segments=seg)
    return np.asarray(rows)[0, :len(seqs)], np.asarray(aux)


# ------------------------------------------------------------ the kernel

def _dense(q, k, v, ids, nh, nkv, window, sinks, turn, scale):
    """Float32 softmax over explicit masks: q [L, nh, D], k / v [L, nkv,
    D / Dv] already turned by `turn`."""
    L = q.shape[0]
    q, k = turn(q), turn(k)
    g = nh // nkv
    k, v = np.repeat(k, g, axis=1), np.repeat(v, g, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k) * scale
    i, j = np.arange(L)[:, None], np.arange(L)[None]
    keep = (ids[:, None] == ids[None, :]) & (j <= i)
    if window:
        keep &= j > i - window
    s = np.where(keep[None], s, -np.inf)
    if sinks is not None:
        s = np.concatenate([s, np.broadcast_to(sinks[:, None, None],
                                               (nh, L, 1))], -1)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = (p / p.sum(-1, keepdims=True))[..., :L]
    return np.einsum("hqk,khd->qhd", p, v)


KERNEL_CASES = [
    # heads, KV heads, window, sink, row length, passages
    (8, 4, 0, False, 512, (200, 137, 170)),  # full GQA: passages mid-block
    (8, 8, 16, True, 384, (100, 37, 240)),  # window + sink, 8 KV heads
    (8, 4, 128, True, 512, (300, 190)),  # the published window, 4 KV heads
    (8, 2, 0, True, 256, (256,)),  # one passage filling the row, a sink
    # whole query blocks of padding: the passages meet mid-block, padding
    # starts on a block's edge (so every padding row is in such a block)
    (8, 4, 0, False, 1024, (300, 212)),  # full: two blocks of 256 padding
    (8, 4, 128, True, 640, (200, 184)),  # window + sink: two of 128
    # ... or mid-block, whole padding blocks after it
    (8, 2, 0, True, 1024, (100, 333)),  # full with a sink
    (8, 8, 16, False, 512, (50, 70)),  # window, no sink: three of 128
]
PAD = 8  # the padding's id `_segments` gives (S)


def _padding_blocks(ids, L, window):
    """bool [L]: the tokens of query blocks (the grouped kernel's) that are
    all padding."""
    bq = fa._grouped_tiling(L, window)[0]
    return np.repeat((ids.reshape(-1, bq) == PAD).all(1), bq)


@pytest.mark.parametrize("nh, nkv, window, sink, L, lens", KERNEL_CASES)
def test_the_grouped_kernel_is_a_dense_softmax(nh, nkv, window, sink, L,
                                               lens):
    """The kernel under the Pallas interpreter, on a 192-wide q.k head in
    the program's 256 lanes with 64 dims turned (`mimo.lane_of`,
    `mimo.rope_lanes`), against a dense float32 softmax over the published
    head layout with HF's partial rotary: GQA, the window, the sink and
    packed passages that start mid-block. A query block that is all
    padding writes exactly 0."""
    rng = np.random.default_rng(L + nkv + window)
    D, Dv, rot = 192, 128, 64
    cfg = mimo.MimoConfig(head_dim=D, partial_rotary_factor=rot / D)
    where, lanes = mimo.lane_of(D, rot), cfg.lanes
    assert lanes == 256
    q = rng.standard_normal((L, nh, D)).astype(np.float32)
    k = rng.standard_normal((L, nkv, D)).astype(np.float32)
    v = rng.standard_normal((L, nkv, Dv)).astype(np.float32)
    seg = _segments(lens, L)
    ids = np.asarray(seg.index)[0]
    pos = np.asarray(seg.position)[0]
    theta = 10000.0
    tables = mimo.rope_lanes(seg.position, cfg, theta)
    sinks = (rng.standard_normal(nh) * 2).astype(np.float32) if sink else None

    def turn(x):  # HF's partial rotary on the published layout
        return _turn(x, pos, rot, theta)

    def lanes_of(x, heads):
        return mimo.to_lanes(x.reshape(L, heads * D), heads, D, lanes,
                             where)[None]

    got = packed_attention(
        jnp.asarray(lanes_of(q, nh)), jnp.asarray(lanes_of(k, nkv)),
        jnp.asarray(v.reshape(1, L, nkv * Dv)), seg.index, nh,
        rope=tables, kv_heads=nkv, window=window,
        sinks=None if sinks is None else jnp.asarray(sinks),
        scale=1 / np.sqrt(D), padding_id=PAD)
    want = _dense(q, k, v, ids, nh, nkv, window, sinks, turn, 1 / np.sqrt(D))
    got = np.asarray(got)[0].reshape(L, nh, Dv)
    real = ids < len(lens)
    err = np.abs(got[real] - want[real]).max() / np.abs(want[real]).max()
    assert err < 1e-5, err
    padding = _padding_blocks(ids, L, window)
    assert padding.any() == (sum(lens) <= L - 128)
    np.testing.assert_array_equal(got[padding], 0.0)


@pytest.mark.parametrize("nh, nkv, window, sink, L, lens", KERNEL_CASES)
def test_the_kernel_counts_the_keys_its_mask_keeps(nh, nkv, window, sink, L,
                                                  lens):
    """`count_keys`: each query's keys as the kernel's own mask keeps them
    (passage, causality, window), the output as it is without the count;
    the window a call did not get is seen in the count (the full causal
    prefix, which `window_keys_kept_pct` reads as 100). A query block that
    is all padding counts 0."""
    rng = np.random.default_rng(L + nh)
    D = 128
    q = jnp.asarray(rng.standard_normal((1, L, nh * D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, L, nkv * D)), jnp.float32)
    seg = _segments(lens, L)
    ids = np.asarray(seg.index)[0]
    sinks = jnp.zeros((nh,), jnp.float32) if sink else None
    i, j = np.arange(L)[:, None], np.arange(L)[None]
    causal = (ids[:, None] == ids[None, :]) & (j <= i)
    for w in sorted({window, 0}):
        kw = dict(kv_heads=nkv, window=w, sinks=sinks, padding_id=PAD)
        plain = packed_attention(q, k, k, seg.index, nh, **kw)
        out, keys = packed_attention(q, k, k, seg.index, nh, count_keys=True,
                                     **kw)
        keep = causal & (j > i - w) if w else causal
        padding = _padding_blocks(ids, L, w)
        keys = np.asarray(keys)[0]
        real = ~padding
        np.testing.assert_array_equal(keys[real], keep.sum(1)[real])
        np.testing.assert_array_equal(keys[padding], 0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))


def _turn(x, pos, rot, theta):
    """HF's partial rotary at each token's place in its passage."""
    half = rot // 2
    inv = 1.0 / (theta ** (np.arange(0, rot, 2) / rot))
    ang = pos[:, None, None] * inv
    a, b = x[..., :half], x[..., half:rot]
    return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           b * np.cos(ang) + a * np.sin(ang), x[..., rot:]],
                          -1)


def test_the_lanes_hold_each_dim_once_and_pair_the_rotary_halves():
    for D, rot in ((192, 64), (24, 8), (128, 42)):
        where, lanes = mimo.lane_of(D, rot), mimo.qk_lanes(D, rot)
        assert lanes % 128 == 0 and len(set(where)) == D
        assert where.max() < lanes
        half = rot // 2
        np.testing.assert_array_equal(where[half:rot] - where[:half],
                                      lanes // 2)
    assert mimo.qk_lanes(192, 64) == 256


def test_a_window_grid_holds_only_the_blocks_it_reaches(monkeypatch):
    """At a 128-key window over a 4,096-token row the kernel's grid has 2
    key steps a query block of 128 (the block and the one before it), not
    the row's 32."""
    from jax.experimental import pallas as pl

    seen = []
    real = pl.pallas_call

    def spy(kernel, *a, **kw):
        seen.append((kw.get("name"), kw["grid_spec"].grid))
        return real(kernel, *a, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)
    L, D = 4096, 128
    x = jax.ShapeDtypeStruct((1, L, 2 * D), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((1, L), jnp.int32)
    for window in (128, 0):
        jax.eval_shape(lambda q, k, i, w=window: packed_attention(
            q, k, k, i, 2, kv_heads=1, window=w, interpret=True),
            x, jax.ShapeDtypeStruct((1, L, D), jnp.bfloat16), ids)
    assert seen == [("window_attention", (1, 1, 32, 2)),
                    ("grouped_attention", (1, 1, 16, 8))]


def _steps_by_hand(ids, L, window):
    """(key steps that compute, steps in reach) of the grouped kernel over
    one row, counted pair by pair: a step computes where its query block
    is not all padding and some query and key of the two blocks share a
    passage; it is in reach where some key of the block is one a query of
    the query block can see (causal, and inside the window)."""
    bq, bk, _ = fa._grouped_tiling(L, window)
    run = reach = 0
    for qi in range(L // bq):
        rows = np.arange(qi * bq, (qi + 1) * bq)[:, None]
        for kb in range(L // bk):
            cols = np.arange(kb * bk, (kb + 1) * bk)[None]
            seen = cols <= rows
            if window:
                seen &= cols > rows - window
            if not seen.any():
                continue
            reach += 1
            qids, kids = ids[rows[:, 0]], ids[cols[0]]
            run += bool((qids != PAD).any()
                        and (qids[:, None] == kids[None]).any())
    return run, reach


@pytest.mark.parametrize("window", [0, 128])
def test_the_bounds_walk_the_blocks_that_share_a_passage(window):
    """`grouped_steps` (the bounds the kernel is handed) against a count
    of the block pairs, at 4,096 tokens of two passages that meet
    mid-block and 1,596 of padding: full attention walks 24 of the 72
    steps under its diagonal."""
    L = 4096
    seg = _segments((1000, 1500), L)
    ids = np.asarray(seg.index)[0]
    got = np.asarray(fa.grouped_steps(seg.index, window, padding_id=PAD))
    want = _steps_by_hand(ids, L, window)
    assert tuple(got[0]) == want
    if not window:
        assert want == (24, 72)
    # without the padding's id, a block of padding walks its own keys
    assert fa.grouped_steps(seg.index, window)[0, 0] > want[0]


def test_the_family_books_the_full_kernels_steps(checkpoint):
    """`mimo.full_steps` over the layout above, through `_note_mimo`: the
    steps of the two full layers' one KV head, as counted by hand."""
    _, _, cfg = checkpoint
    seg = _segments((1000, 1500), 4096)
    run, reach = _steps_by_hand(np.asarray(seg.index)[0], 4096, 0)
    full = sum(not cfg.is_window(i) for i in range(cfg.num_layers))
    assert (full, cfg.num_kv_heads) == (2, 1)
    width = max(cfg.held, 4)
    aux = np.zeros((2, width), np.int32)  # no expert layer, one batch row
    aux[1, 2:4] = np.asarray(mimo.full_steps(seg, cfg))[0]
    snap = metrics.flat_snapshot()
    families._note_mimo(aux)
    after = metrics.flat_snapshot()

    def grew(name):
        key = "counter." + name + '{service="engine"}'
        return after.get(key, 0) - snap.get(key, 0)

    assert grew("engine.attn.block_steps_run") == full * run
    assert grew("engine.attn.block_steps_causal") == full * reach
    assert grew("engine.attn.window_keys") == 0


# ------------------------------------------------------------- the stack

def test_full_forward_matches_reference(checkpoint, passages, want):
    """Through the kernel (a 512-token row) and the einsum form (a row that
    is not whole 128-token blocks) alike."""
    _, params, cfg = checkpoint
    steps = {}
    for L in (512, 300):
        got, aux = _embed(params, cfg, passages, L)
        assert _rel(got, want).max() < TOL, L
        steps[L] = tuple(aux[1 + aux[0, 3], 2:4])
    # the full kernel's steps: two full layers of one KV head, two query
    # blocks of 256 against one key block, both holding real tokens; the
    # einsum form takes none
    assert steps == {512: (4, 4), 300: (0, 0)}
    routed, windows, held, layers = aux[0, :4]
    assert (windows, held, layers) == (5, 2, 6)
    assert routed == sum(LENS) * 4 * 6  # every real token's 4 choices
    counts = aux[1:1 + layers, :held]
    assert 0 < counts.sum() < routed
    W = MODEL["sliding_window"]
    assert aux[1 + layers, 0] == windows * sum(
        sum(min(p + 1, W) for p in range(n)) for n in LENS)
    assert aux[1 + layers, 1] == sum(n * (n + 1) // 2 for n in LENS)


def test_packed_rows_equal_each_passage_alone(checkpoint, passages):
    _, params, cfg = checkpoint
    packed, _ = _embed(params, cfg, passages)
    alone = np.stack([_embed(params, cfg, [p], L=256)[0][0]
                      for p in passages])
    assert _rel(packed, alone).max() < TOL


@pytest.mark.parametrize("fault", ["window_off", "sink_dropped",
                                   "full_as_window"])
def test_the_tolerance_sees_a_wrong_attention(checkpoint, passages, want,
                                              monkeypatch, fault):
    """The three attention faults of `benchmark/tests/fault_run_mimo.py`,
    planted in the kernel's entry: every passage moves far outside the
    tolerance, and with the window off the window layers' keys are the
    causal keys (`window_keys_kept_pct` 100)."""
    real = fa.packed_attention

    def broken(*a, window=0, sinks=None, **kw):
        if fault == "window_off":
            window = 0
        elif fault == "sink_dropped":
            sinks = None
        elif not window:
            window = MODEL["sliding_window"]
        return real(*a, window=window, sinks=sinks, **kw)

    monkeypatch.setattr(fa, "packed_attention", broken)
    _, params, cfg = checkpoint
    got, aux = _embed(params, cfg, passages)
    assert _rel(got, want).min() > 1000 * TOL
    windows, layers = aux[0, 1], aux[0, 3]
    attended, causal = aux[1 + layers, :2]
    assert (attended == windows * causal) == (fault == "window_off")


# --------------------------------------------------------------- experts

def test_sixteen_shares_add_up_to_the_uncut_layer(tmp_path):
    """Sixteen chips of a layer, each holding 2 of the 32 experts: their
    layers (attention and the residual, which every chip computes alike,
    counted once) add up to the reference's layer with every expert held;
    and one chip's part alone is far from it: the held weights are the
    router's, not renormalised over the share."""
    uncut = {**MODEL, "num_hidden_layers": 2, "experts_held": 32}
    params, cfg = _load(uncut, tmp_path)
    layer, E, share = params["layers"][1], 32, 2
    w = jax.tree.map(jnp.asarray, ref.layer_weights(uncut, 1, SEED))
    rng = np.random.default_rng(3)
    L = 96
    x = jnp.asarray(rng.standard_normal((1, L, cfg.hidden_size)),
                    jnp.float32)
    seg = _segments((60, 36), L)
    tables = mimo.rope_lanes(seg.position, cfg, cfg.swa_rope_theta)
    eps = cfg.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        h = x + mimo.attention(layer["attn"], rmsnorm(x, layer["ln1"], eps),
                               seg, tables, cfg, True)[0]
        parts = []
        for c in range(E // share):
            mine = {"router": {
                        "kernel": jnp.roll(layer["moe"]["router"]["kernel"],
                                           -c * share, axis=1),
                        "bias": jnp.roll(layer["moe"]["router"]["bias"],
                                         -c * share)},
                    "experts": jax.tree.map(
                        lambda a: a[c * share:(c + 1) * share],
                        layer["moe"]["experts"])}
            part_cfg = dataclasses.replace(cfg, experts_held=share)
            parts.append(h + mla_moe.moe_ffn(mine, h, seg.real, layer["ln2"],
                                             part_cfg.moe)[0])
        summed = np.asarray(sum(parts) - (E // share - 1) * h)[0]
        want = []
        a = 0
        for n in (60, 36):
            xs = x[0, a:a + n]
            hr = xs + ref.attention(w, ref.rms_norm(
                xs, w["input_layernorm"], eps), uncut, True)
            nr = ref.rms_norm(hr, w["post_attention_layernorm"], eps)
            idx, wts, _ = ref.router(w, nr, uncut)
            want.append(np.asarray(hr + ref.experts(w, nr, idx, wts, uncut,
                                                    n)))
            a += n
    want = np.concatenate(want)
    np.testing.assert_allclose(summed, want, rtol=1e-4, atol=1e-4)
    alone = np.asarray(parts[0])[0]
    assert (np.abs(alone - want).max()
            > 10 * np.abs(summed - want).max() + 1e-4)


def test_the_held_share_is_not_renormalised(checkpoint, passages, want,
                                            monkeypatch):
    """The fourth planted fault: the held choices given the weights of the
    choices another chip holds. At toy widths an expert adds ~1e-3 to a
    residual of ~1, so the rows move by less than the cell's limits (the
    cell sees it at its own size: PERF.md, section 2), but far outside this
    test's tolerance."""
    real = mla_moe.routed_experts

    def broken(p, x, idx, w, real_tok, cfg):
        here = idx < cfg.held
        kept = jnp.where(here, w, 0.0).sum(-1, keepdims=True)
        w = jnp.where(here, w * w.sum(-1, keepdims=True)
                      / jnp.maximum(kept, 1e-20), w)
        return real(p, x, idx, w, real_tok, cfg)

    monkeypatch.setattr(mla_moe, "routed_experts", broken)
    _, params, cfg = checkpoint
    got, _ = _embed(params, cfg, passages)
    assert _rel(got, want).min() > 10 * TOL


# ------------------------------------------------------- config and seams

@pytest.mark.parametrize("key, value", [
    ("n_group", 8), ("hidden_act", "gelu"), ("attention_bias", True),
    ("n_shared_experts", 1), ("swa_head_dim", 128),
    ("attention_chunk_size", 64), ("topk_method", "greedy"),
])
def test_unsupported_settings_are_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        mimo.MimoConfig.from_hf({**MODEL, key: value})


def test_the_published_config_reads_as_published():
    cfg = mimo.MimoConfig.from_hf({**MODEL, "num_hidden_layers": 7,
                                   "head_dim": 192, "swa_head_dim": 192})
    assert [cfg.is_window(i) for i in range(7)] == [
        False, True, True, True, True, False, True]
    assert (cfg.rotary_dim, cfg.lanes, cfg.routed_scaling_factor) == (
        64, 256, 1.0)
    assert [cfg.kv_heads(i) for i in (0, 1)] == [1, 2]
    assert [cfg.sink(i) for i in (0, 1)] == [False, True]


def test_engine_takes_the_family_from_the_checkpoint(checkpoint):
    out, _, _ = checkpoint
    assert families.family_of_checkpoint(out) is families.MIMO
    eng = TpuEngine(EngineConfig(model_dir=str(out), length_buckets=(256,),
                                 batch_buckets=(1,), dtype="float32"))
    assert eng.family is families.MIMO
    snap = metrics.flat_snapshot()
    texts = ["one two three four five six seven eight nine ten eleven "
             "twelve thirteen fourteen fifteen sixteen seventeen.",
             "six seven."]
    rows = eng.embed_texts(texts)
    after = metrics.flat_snapshot()

    def grew(name):
        key = "counter." + name + '{service="engine"}'
        return after.get(key, 0) - snap.get(key, 0)

    assert rows.shape == (2, MODEL["hidden_size"])
    assert np.isfinite(rows).all()
    assert grew("engine.moe.assignments_routed") > grew(
        "engine.moe.assignments") > 0
    assert 0 < grew("engine.attn.window_keys") < grew(
        "engine.attn.keys_causal")
    assert 0 < grew("engine.attn.block_steps_run") <= grew(
        "engine.attn.block_steps_causal")


# -------------------------------------- the programs other cells compile

def _ling_mla_kernel():
    q = jax.ShapeDtypeStruct((1, 1024, 4 * 256), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 1024, 4 * 128), jnp.bfloat16)
    return jax.jit(lambda q, k, v, i: packed_attention(
        q, k, v, i, 4, interpret=True, scale=0.125)).lower(
            q, q, v, jax.ShapeDtypeStruct((1, 1024), jnp.int32))


def _experts(cfg, moe, B, S):
    ln = {"scale": jax.ShapeDtypeStruct((cfg.hidden_size,), jnp.float32)}
    return jax.jit(lambda p, h, m, ln: mla_moe.moe_ffn(p, h, m, ln, cfg)
                   ).lower(moe, jax.ShapeDtypeStruct(
                       (B, S, cfg.hidden_size), jnp.float32),
                       jax.ShapeDtypeStruct((B, S), jnp.int32), ln)


def _ling_experts():
    cfg = ling.LingConfig(
        vocab_size=500, hidden_size=64, num_layers=8, num_heads=4,
        head_dim=16, intermediate_size=96, moe_intermediate_size=16,
        shared_intermediate_size=16, num_experts=32, experts_held=8,
        num_experts_per_tok=4, n_group=8, topk_group=4, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        dtype="float32")
    params = jax.eval_shape(lambda: ling.init_params(jax.random.key(0), cfg))
    return _experts(cfg.mla, params["layers"][2]["moe"], 1, 16384)


def _kimi_experts():
    cfg = mla_moe.MlaMoeConfig(
        vocab_size=1000, hidden_size=64, num_layers=3, num_heads=4,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, dtype="float32")
    params = jax.eval_shape(lambda: mla_moe.init_params(jax.random.key(0),
                                                        cfg))
    return _experts(cfg, params["layers"][1]["moe"], 32, 128)


# sha256 (first 12 hex digits) of each program's lowered text on the tree
# before the `mimo` family (Ling's MLA call of the packed kernel, which the
# grouped form leaves alone; Ling's and Kimi-VL's expert layers, whose
# helpers it calls), read with this repository's jax 0.9.0: another jax
# lowers other text, and the digests are then taken again from that tree
# with the same calls. Ouro's kernel calls are held in tests/test_ling.py.
PARENT = {
    "ling_mla_kernel_1x1024": ("0.9.0", "045efc5e38ed"),
    "ling_experts_1x16384": ("0.9.0", "9282dffdef12"),
    "kimi_experts_32x128": ("0.9.0", "3478431e2832"),
}
LOWER = {
    "ling_mla_kernel_1x1024": _ling_mla_kernel,
    "ling_experts_1x16384": _ling_experts,
    "kimi_experts_32x128": _kimi_experts,
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_ling_and_kimi_lower_to_the_parent_text(name):
    version, digest = PARENT[name]
    assert jax.__version__ == version, "take the digests again (see PARENT)"
    text = LOWER[name]().as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:12] == digest
