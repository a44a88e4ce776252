"""models/mla_moe.py (latent attention + routed/shared experts) against the
benchmark's plain reference (`benchmark/refs/kimi_mla_moe.py`, imported by
path: float32 jax.numpy at matmul precision "highest", nothing of the
program in it), on seeded weights at toy widths that keep every ratio of
the published model: 8 experts, top-2, a shared expert, one dense + two
expert layers, rope (8) and nope (16) parts of different size, value heads
(16) narrower than query heads (24).

Tolerances, each with its reason:
- float32 program against the reference: 2e-5 relative. Same maths in the
  same precision; what differs is summation order (grouped matmul against a
  masked sum over experts).
- bfloat16 at rest and in the matmuls (`f16`): 0.012 on the mean relative
  error of 16 pooled rows. bfloat16 keeps 8 bits; over 3 layers of toy width
  three seeds read 0.0048-0.0061 (most of it two or three tokens whose 2nd
  and 3rd router scores lie within rounding and pick another expert).
- int8 and fp8 weights must read ABOVE that tolerance on the same rows
  (three seeds: int8 0.017-0.019, fp8 0.060-0.066): int8 is the benchmark's
  control, the step below the stated precision, and a tolerance it passed
  would prove nothing.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark"))
from refs import kimi_mla_moe as ref  # noqa: E402

from symbiont_tpu.config import EngineConfig  # noqa: E402
from symbiont_tpu.engine.engine import TpuEngine  # noqa: E402
from symbiont_tpu.engine.tokenizer import HashTokenizer  # noqa: E402
from symbiont_tpu.models import convert, families, mla_moe, quant  # noqa: E402
from symbiont_tpu.models.layers import rope  # noqa: E402
from symbiont_tpu.utils.telemetry import metrics  # noqa: E402

MODEL = {
    "model_type": "deepseek_v3", "vocab_size": 500, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "n_shared_experts": 1, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.446,
    "norm_topk_prob": True, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 800000, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
}
SEED = 11
F32_TOL = 2e-5
BF16_TOL = 0.012


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """The reference's checkpoint (HF names, bfloat16) loaded through the
    program's own converter, upcast for the float32 comparisons."""
    out = tmp_path_factory.mktemp("kimi_toy")
    ref.write_checkpoint(MODEL, SEED, out)
    params, cfg = convert.load_mla_moe_model(out)
    cfg32 = mla_moe.MlaMoeConfig(**{**cfg.__dict__, "dtype": "float32"})
    params32 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    return out, params, params32, cfg32


@pytest.fixture(scope="module")
def tensors():
    return ref.common.f32(ref.common.seeded_tensors(ref.tensor_specs(MODEL),
                                                    SEED))


def _batch(rng, B=4, S=12):
    ids = rng.integers(3, MODEL["vocab_size"], (B, S)).astype(np.int32)
    lens = rng.integers(2, S + 1, B)
    lens[0] = S
    mask = (np.arange(S) < lens[:, None]).astype(np.int32)
    return ids * mask, mask


def _rel(got, want):
    return (np.linalg.norm(np.asarray(got, np.float32) - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-12))


def _ref_rows(ids, mask):
    r = ref.Reference(MODEL, SEED, 512)
    return r.forward([(ids, mask)])[0], r


# ------------------------------------------------------------ the forward

def test_full_forward_matches_reference(checkpoint):
    _, _, params32, cfg = checkpoint
    ids, mask = _batch(np.random.default_rng(0))
    want, r = _ref_rows(ids, mask)
    got, counts = mla_moe.embed_sentences(params32, ids, mask, cfg)
    assert _rel(got, want).max() < F32_TOL
    # the load counters count real tokens only, each k times, per layer
    assert counts.shape == (2, 8)
    assert (np.asarray(counts).sum(1) == mask.sum() * 2).all()
    assert 0.0 <= r.gap_share <= 1.0


def test_padding_never_reaches_a_row(checkpoint):
    _, _, params32, cfg = checkpoint
    ids, mask = _batch(np.random.default_rng(1))
    got, _ = mla_moe.embed_sentences(params32, ids, mask, cfg)
    junk = np.where(mask > 0, ids, 77)
    wide = np.pad(junk, ((0, 0), (0, 5)), constant_values=9)
    got2, _ = mla_moe.embed_sentences(params32, wide,
                                      np.pad(mask, ((0, 0), (0, 5))), cfg)
    assert _rel(got2, np.asarray(got)).max() < F32_TOL


def test_mla_block_matches_reference(checkpoint, tensors):
    _, _, params32, cfg = checkpoint
    rng = np.random.default_rng(2)
    _, mask = _batch(rng)
    x = rng.standard_normal((*mask.shape, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.mla(ref.layer_weights(tensors, MODEL, 1), jnp.asarray(x),
                       jnp.asarray(mask), MODEL)
    got = mla_moe.mla_attention(params32["layers"][1]["attn"], jnp.asarray(x),
                                jnp.asarray(mask), cfg)
    real = mask > 0  # a padded QUERY position is nobody's input
    assert _rel(np.asarray(got)[real], np.asarray(want)[real]).max() < F32_TOL


def test_expert_ffn_matches_reference(checkpoint, tensors):
    _, _, params32, cfg = checkpoint
    rng = np.random.default_rng(3)
    x = rng.standard_normal((40, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(ref.layer_weights(tensors, MODEL, 2),
                          jnp.asarray(x), MODEL)
    p = params32["layers"][2]["moe"]
    idx, w = mla_moe.route(p["router"], jnp.asarray(x), cfg)
    y, counts = mla_moe.routed_experts(p, jnp.asarray(x), idx, w,
                                       jnp.ones((40,), bool), cfg)
    from symbiont_tpu.models.layers import swiglu

    got = y + swiglu(jnp.asarray(x), p["shared"])
    assert _rel(got, np.asarray(want)).max() < F32_TOL
    assert int(counts.sum()) == 40 * 2


def _expert_loop(p, x, idx, w, real):
    """Every expert over exactly the tokens that chose it, one after
    another, in numpy: what the grouped path must equal."""
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    ex = {k: np.asarray(v["kernel"], np.float64)
          for k, v in p["experts"].items()}
    for e in range(ex["gate"].shape[0]):
        for t, j in zip(*np.nonzero(np.asarray(idx) == e)):
            if not real[t]:
                continue
            g = x[t] @ ex["gate"][e]
            h = g / (1.0 + np.exp(-g)) * (x[t] @ ex["up"][e])
            out[t] += float(w[t, j]) * (h @ ex["down"][e])
    return out


def test_grouped_path_equals_per_expert_loop(checkpoint):
    _, _, params32, cfg = checkpoint
    rng = np.random.default_rng(4)
    x = rng.standard_normal((33, 64)).astype(np.float32)
    real = rng.random(33) < 0.7
    p = params32["layers"][1]["moe"]
    idx, w = mla_moe.route(p["router"], jnp.asarray(x), cfg)
    y, counts = mla_moe.routed_experts(p, jnp.asarray(x), idx, w,
                                       jnp.asarray(real), cfg)
    want = _expert_loop(p, x, idx, np.asarray(w), real)
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    assert (np.asarray(y)[~real] == 0).all()  # padding goes to no expert
    assert int(counts.sum()) == int(real.sum()) * 2


def test_skewed_routing_drops_no_token(checkpoint):
    """One expert takes (nearly) every token and some take none: a capacity
    buffer would drop here; the sorted grouped matmul must not."""
    _, _, params32, cfg = checkpoint
    rng = np.random.default_rng(5)
    x = rng.standard_normal((64, 64)).astype(np.float32)
    p = dict(params32["layers"][1]["moe"])
    bias = np.full((8,), -5.0, np.float32)
    bias[3], bias[6] = 5.0, 0.5  # everyone picks 3, most pick 6 second
    p["router"] = {**p["router"], "bias": bias}
    idx, w = mla_moe.route(p["router"], jnp.asarray(x), cfg)
    real = np.ones((64,), bool)
    y, counts = mla_moe.routed_experts(p, jnp.asarray(x), idx, w,
                                       jnp.asarray(real), cfg)
    counts = np.asarray(counts)
    assert counts[3] == 64 and counts.sum() == 128 and (counts == 0).any()
    want = _expert_loop(p, x, idx, np.asarray(w), real)
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    # weights come from the scores, not from score + bias
    assert np.allclose(np.asarray(w).sum(1), 2.446, atol=1e-5)


def test_rope_pairing_by_hand():
    """d = 4, theta = 100, position 3: pairs are (x0, x1) at angle 3 and
    (x2, x3) at angle 3 * 100^(-1/2) = 0.3; the program de-interleaves and
    rotates halves, so its layout is [x0', x2', x1', x3']."""
    x = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    c0, s0, c1, s1 = np.cos(3.0), np.sin(3.0), np.cos(0.3), np.sin(0.3)
    want = np.array([1 * c0 - 2 * s0, 3 * c1 - 4 * s1,
                     2 * c0 + 1 * s0, 4 * c1 + 3 * s1], np.float32)
    pos = jnp.full((1, 1), 3, jnp.int32)
    got = rope(mla_moe._deinterleave(jnp.asarray(x))[None, None, None, :],
               pos, 100.0)[0, 0, 0]
    assert np.allclose(got, want, atol=1e-6)
    assert np.allclose(ref.rope_pairs(jnp.asarray(x)[None, :],
                                      jnp.asarray([3]), 100.0)[0], want,
                       atol=1e-6)


# ------------------------------------------------- names, families, quant

def test_hf_names_round_trip(checkpoint):
    """The reference writes the HF DeepSeek-V3 names; the program's converter
    reads every one of them (the full-forward test proves the values land in
    the right places), stacks the experts and keeps the file's dtype."""
    out, params, _, cfg = checkpoint
    moe = params["layers"][1]["moe"]
    assert moe["experts"]["gate"]["kernel"].shape == (8, 64, 32)
    assert moe["experts"]["down"]["kernel"].shape == (8, 32, 64)
    assert moe["router"]["kernel"].shape == (64, 8)
    assert moe["router"]["bias"].dtype == np.float32
    assert params["layers"][0]["mlp"]["gate"]["kernel"].shape == (64, 128)
    # leaf by leaf in the checkpoint's dtype: no float32 copy of a kernel
    assert moe["experts"]["gate"]["kernel"].dtype == ref.common.BF16
    assert params["wte"].dtype == ref.common.BF16
    written = sum(int(np.prod(shape)) for _, shape, _ in
                  ref.tensor_specs(MODEL))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(params)) == written
    assert families.family_of_checkpoint(out) is families.MLA_MOE
    # a vision-language config.json nests the same settings one level down
    assert (mla_moe.MlaMoeConfig.from_hf({"model_type": "kimi_vl",
                                          "text_config": MODEL})
            == mla_moe.MlaMoeConfig.from_hf(MODEL))
    assert cfg.hidden_size == 64 and cfg.n_routed_experts == 8


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_stacked_kernels_are_transposed_once_and_contiguous(dtype):
    """Torch [out, in] kernels become ONE C-contiguous [n, in, out] array
    (the upload then copies nothing), whatever the width: 700 columns are
    one block of 512 and a rest."""
    import ml_dtypes

    dt = np.dtype(getattr(ml_dtypes, dtype, dtype))
    rng = np.random.default_rng(4)
    mats = [rng.standard_normal((37, 700)).astype(dt) for _ in range(5)]
    got = convert._transposed(mats)
    assert got.shape == (5, 700, 37) and got.dtype == dt
    assert got.flags["C_CONTIGUOUS"]
    want = np.stack([m.T for m in mats])
    assert (got.astype(np.float32) == want.astype(np.float32)).all()


def test_a_vision_language_checkpoint_nests_its_text_tower(checkpoint):
    out, params, _, _ = checkpoint
    from safetensors.numpy import load_file

    sd = {"language_model." + k: v
          for k, v in load_file(str(out / "model.safetensors")).items()}
    sd["vision_tower.patch_embed.weight"] = np.zeros((2, 2), np.float32)
    cfg = mla_moe.MlaMoeConfig.from_hf(
        {"model_type": "kimi_vl", "text_config": MODEL})
    nested = convert.convert_mla_moe(sd, cfg)
    assert np.array_equal(np.asarray(nested["wte"], np.float32),
                          np.asarray(params["wte"], np.float32))


def test_unsupported_settings_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        mla_moe.MlaMoeConfig.from_hf({**MODEL, "q_lora_rank": 1536})
    with pytest.raises(NotImplementedError, match="n_group"):
        mla_moe.MlaMoeConfig.from_hf({**MODEL, "n_group": 8})


def test_stacked_kernels_get_a_scale_per_expert_and_channel():
    rng = np.random.default_rng(6)
    w = rng.standard_normal((4, 16, 8)).astype(np.float32)
    w[2] *= 50.0  # one loud expert must not flatten the others
    qt = quant.channel_quantize(w, 127.0, jnp.int8)
    assert qt.q.shape == (4, 16, 8) and qt.scale.shape == (4, 8)
    err = np.abs(np.asarray(qt.dequantize()) - w)
    assert (err <= np.asarray(qt.scale)[:, None, :] * 0.5 + 1e-7).all()
    quiet = np.abs(w[0]).max(0) / 127.0
    assert np.allclose(np.asarray(qt.scale)[0], quiet, rtol=1e-6)
    # the grouped matmul dequantizes each row with its own expert's scales
    x = rng.standard_normal((10, 16)).astype(np.float32)
    sizes = jnp.asarray([3, 0, 5, 2], jnp.int32)
    group = jnp.asarray([0] * 3 + [2] * 5 + [3] * 2, jnp.int32)
    got = quant.ragged_mm(jnp.asarray(x), qt, sizes, group)
    want = np.concatenate([x[:3] @ w[0], x[3:8] @ w[2], x[8:] @ w[3]])
    assert _rel(got, want).max() < 0.02


# ------------------------------------------- the Pallas grouped matmul

@pytest.fixture
def as_on_the_chip(monkeypatch):
    """What `grouped_matmul` sees on the chip (a `tpu` backend), with the
    kernel run by the Pallas TPU interpreter: uninitialised buffers hold
    NaN there, so a row the kernel must not write shows."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


GROUPED_CASES = {
    # 8 groups over 256 rows of 128 -> 256: two row tiles of 128
    "an_empty_group": ([40, 0, 88, 30, 0, 50, 48, 0], 128, 256, False),
    "a_group_straddles_two_row_tiles": ([100, 80, 76, 0, 0, 0, 0, 0],
                                        128, 256, False),
    "two_groups_inside_one_tile": ([128, 50, 78, 0, 0, 0, 0, 0],
                                   128, 256, False),
    "rows_past_the_last_group": ([30, 0, 20, 0, 0, 0, 10, 0], 128, 256,
                                 False),
    "no_row_in_any_group": ([0] * 8, 128, 256, False),
    # the first kernel fetched is the last group's, and none after it
    "only_the_last_group_has_rows": ([0] * 7 + [5], 128, 256, False),
    "a_quantized_stack_with_scales_per_expert": (
        [40, 0, 88, 30, 0, 50, 20, 0], 128, 256, True),
    # toy widths (the CPU tests', a lane is 128): the compiler's kernel
    "a_toy_width_keeps_ragged_dot": ([40, 0, 88, 30, 0, 50, 48, 0], 16, 8,
                                     False),
}


@pytest.mark.parametrize("case", list(GROUPED_CASES))
def test_grouped_matmul_kernel_equals_ragged_dot_and_the_loop(
        as_on_the_chip, case):
    sizes, k, n, quantized = GROUPED_CASES[case]
    m, total = 256, sum(sizes)
    rng = np.random.default_rng(8)
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((len(sizes), k, n)).astype(np.float32) * 0.1
    if quantized:
        w[2] *= 50.0
        stack = quant.channel_quantize(w, 127.0, jnp.int8)
        w = np.asarray(stack.dequantize())
    else:
        stack = jnp.asarray(w)
    group = np.minimum(np.searchsorted(np.cumsum(sizes), np.arange(m),
                                       side="right"), len(sizes) - 1)
    path = "pallas" if k % 128 == 0 else "ragged_dot"
    before = {p: _counter(f'moe.grouped_mm{{path="{p}"}}')
              for p in ("pallas", "ragged_dot")}

    # a fresh trace each case: the counter bumps when a call is traced
    got = jax.jit(lambda *a: quant.ragged_mm(*a))(
        jnp.asarray(x), stack, jnp.asarray(sizes, jnp.int32),
        jnp.asarray(group, jnp.int32))

    after = {p: _counter(f'moe.grouped_mm{{path="{p}"}}') for p in before}
    assert {p: after[p] - before[p] for p in before} == {
        "pallas": float(path == "pallas"),
        "ragged_dot": float(path == "ragged_dot")}
    # the caller's `where`: rows of no group are dropped, whatever they hold
    got = np.asarray(jnp.where((jnp.arange(m) < total)[:, None], got, 0))
    assert np.isfinite(got).all()
    loop = np.zeros((m, n), np.float32)
    at = 0
    for g, size in enumerate(sizes):
        loop[at:at + size] = x[at:at + size] @ w[g]
        at += size
    assert np.abs(got - loop).max() < 2e-5 * np.sqrt(k)
    compiler = np.asarray(jax.lax.ragged_dot(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(sizes, jnp.int32)))
    assert np.abs(got[:total] - compiler[:total]).max(initial=0) < (
        2e-5 * np.sqrt(k))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_visits_are_the_group_row_tile_pairs_that_hold_rows(seed):
    """The kernel's grid, against a plain loop: every (group, 128-row tile)
    pair with a row in it, in row order; which of the two kernel buffers a
    group uses and which group's kernel to fetch next."""
    import importlib

    visits = importlib.import_module(
        "symbiont_tpu.ops.grouped_matmul").visits
    rng = np.random.default_rng(seed)
    for _ in range(40):
        G, m = int(rng.integers(1, 12)), 128 * int(rng.integers(1, 8))
        sizes = (rng.integers(0, 300, G) * (rng.random(G) < 0.6)).astype(
            np.int32)
        while sizes.sum() > m:
            sizes //= 2
        offsets, group_ids, tile_ids, buffer, after, count = (
            np.asarray(a) for a in visits(jnp.asarray(sizes), m))
        ends = np.cumsum(sizes)
        pairs = [(g, t) for g in range(G) if sizes[g]
                 for t in range((ends[g] - sizes[g]) // 128,
                                (ends[g] + 127) // 128)]
        assert list(zip(group_ids[:count], tile_ids[:count])) == pairs
        assert (offsets == np.concatenate([[0], ends])).all()
        with_rows = [g for g in range(G) if sizes[g]]
        assert [buffer[g] for g in with_rows] == [
            i % 2 for i in range(len(with_rows))]
        assert [after[g] for g in with_rows] == with_rows[1:] + [-1] * bool(
            with_rows)


def test_expert_layer_through_the_kernel_equals_the_loop(as_on_the_chip):
    """The routed layer at lane-aligned widths (64 tokens x top-2 = one row
    tile of 128; a third of the tokens padding, so rows of no group pass
    through all three projections): what the kernel leaves in them must not
    reach a token."""
    cfg = mla_moe.MlaMoeConfig(
        vocab_size=50, hidden_size=128, num_layers=2, num_heads=2,
        intermediate_size=128, moe_intermediate_size=128,
        n_routed_experts=8, n_shared_experts=0, num_experts_per_tok=2,
        dtype="float32")
    p = mla_moe.init_params(jax.random.PRNGKey(3), cfg)["layers"][1]["moe"]
    rng = np.random.default_rng(9)
    x = rng.standard_normal((64, 128)).astype(np.float32)
    real = rng.random(64) < 0.67
    idx, w = mla_moe.route(p["router"], jnp.asarray(x), cfg)
    before = _counter('moe.grouped_mm{path="pallas"}')
    y, counts = jax.jit(lambda *a: mla_moe.routed_experts(*a, cfg))(
        p, jnp.asarray(x), idx, w, jnp.asarray(real))
    assert _counter('moe.grouped_mm{path="pallas"}') - before == 3
    assert np.isfinite(np.asarray(y)).all()
    assert (np.asarray(y)[~real] == 0).all()
    want = _expert_loop(p, x, idx, np.asarray(w), real)
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    assert int(counts.sum()) == int(real.sum()) * 2


def test_a_programs_expert_layers_share_one_trace(checkpoint):
    """Two expert layers, one shape: `encode` traces (and lowers) the layer
    once and calls it twice, so a program bumps `moe.grouped_mm` 3 times,
    not 3 a layer. Every warmed bucket is traced at every boot."""
    _, _, params32, cfg = checkpoint
    ids, mask = _batch(np.random.default_rng(5), B=2, S=7)
    before = _counter('moe.grouped_mm{path="ragged_dot"}')
    got, counts = jax.jit(lambda p, i, m: mla_moe.embed_sentences(
        p, i, m, cfg))(params32, ids, mask)
    assert _counter('moe.grouped_mm{path="ragged_dot"}') - before == 3
    assert counts.shape == (2, 8)
    want, _ = _ref_rows(ids, mask)
    assert _rel(got, want).max() < F32_TOL


@pytest.mark.parametrize("mode", ["f16", "int8", "fp8"])
def test_f16_is_inside_the_tolerance_and_the_steps_below_outside(checkpoint,
                                                                  mode):
    _, params, _, cfg32 = checkpoint
    cfg = mla_moe.MlaMoeConfig(**{**cfg32.__dict__, "dtype": "bfloat16"})
    rng = np.random.default_rng(7)
    ids, mask = _batch(rng, B=16, S=24)
    want, _ = _ref_rows(ids, mask)

    got, _ = mla_moe.embed_sentences(quant.quantize_params(params, mode),
                                     ids, mask, cfg)
    err = _rel(got, want).mean()
    assert np.isfinite(err)
    assert (err < BF16_TOL) == (mode == "f16"), err


# ------------------------------------------------------------- the engine

def _engine(model_dir, **kw):
    cfg = EngineConfig(model_dir=str(model_dir), length_buckets=[16, 32],
                       batch_buckets=[1, 8], max_batch=8, **kw)
    return TpuEngine(cfg)


def _counter(name: str) -> float:
    return sum(v for k, v in metrics.snapshot()["counters"].items()
               if k.startswith(name))


def test_engine_takes_the_family_from_the_checkpoint(checkpoint):
    out, _, _, _ = checkpoint
    engine = _engine(out, dtype="float32")
    assert engine.family is families.MLA_MOE
    assert engine.model_cfg.hidden_size == 64
    assert isinstance(engine.tokenizer, HashTokenizer)
    texts = ["tensor processing unit", "the memory bandwidth of embeddings "
             "semantic search pipeline", "graph"]
    before = {n: _counter(n) for n in ("engine.moe.assignments",
                                       "engine.moe.experts_idle")}
    got = engine.embed_texts(texts)
    want = ref.Reference(MODEL, SEED, 32).embed(texts)
    assert _rel(got, want).max() < F32_TOL
    # every real token, k times, in each expert layer: nothing dropped
    real = sum(len(engine.tokenizer.encode(t, 32)) for t in texts)
    assert (_counter("engine.moe.assignments")
            - before["engine.moe.assignments"]) == real * 2 * 2
    hist = metrics.snapshot()["histograms"]
    assert any(k.startswith("engine.moe.expert_load_max_over_mean")
               and h["count"] > 0 for k, h in hist.items())


def test_engine_fused_search_runs_on_the_family(checkpoint):
    out, _, _, _ = checkpoint
    engine = _engine(out, quantize="f16")
    texts = ["vector graph tokens", "model attention masked pooling batch"]
    rows = engine.embed_texts(texts)
    corpus = rows / np.linalg.norm(rows, axis=1, keepdims=True)
    corpus = jnp.asarray(np.pad(corpus, ((0, 6), (0, 0))))
    scores, idx = engine.embed_and_search(texts[1], corpus, 2, 2)
    assert idx[0] == 1 and scores[0] > 0.98
    labels = [k for k in metrics.snapshot()["gauges"]
              if k.startswith("engine.param_bytes") and 'dtype="f16"' in k]
    assert labels


def test_engine_takes_the_family_from_a_config_handed_in():
    """The seam's other entry: params and a config given directly (tests,
    a trainer), no checkpoint to name the family."""
    cfg = mla_moe.MlaMoeConfig.from_hf(MODEL)
    params = families.MLA_MOE.init_params(jax.random.key(0), cfg)
    engine = TpuEngine(EngineConfig(length_buckets=[16], batch_buckets=[8],
                                    max_batch=8, dtype="float32"),
                       params=params, model_cfg=cfg,
                       tokenizer=HashTokenizer(cfg.vocab_size))
    assert engine.family is families.MLA_MOE
    rows = engine.embed_texts(["graph tokens model", "attention"])
    assert rows.shape == (2, 64) and np.isfinite(rows).all()


def test_bert_checkpoints_still_load_as_bert(tmp_path):
    from symbiont_tpu.models import bert

    cfg = bert.BertConfig(vocab_size=100, hidden_size=32, num_layers=1,
                          num_heads=2, intermediate_size=64,
                          max_position_embeddings=64)
    convert.export_hf_bert(bert.init_params(jax.random.key(0), cfg), cfg,
                           tmp_path)
    assert families.family_of_checkpoint(tmp_path) is families.BERT
    engine = _engine(tmp_path, dtype="float32")
    assert engine.family is families.BERT
    assert engine.embed_texts(["one two"]).shape == (1, 32)
