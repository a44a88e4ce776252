"""Flash-attention kernel vs dense reference (pallas interpret mode on CPU —
same kernel code path that compiles on TPU)."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symbiont_tpu.models.bert import Segments
from symbiont_tpu.models.layers import rope, rope_tables
from symbiont_tpu.ops.flash_attention import (
    _dense_reference,
    flash_attention,
    packed_attention,
)


def _rand_qkv(key, B, NH, NKV, Sq, Sk, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, NH, Sq, D), dtype)
    k = jax.random.normal(kk, (B, NKV, Sk, D), dtype)
    v = jax.random.normal(kv, (B, NKV, Sk, D), dtype)
    return q, k, v


def _pad_bias(key, B, Sk):
    lengths = jax.random.randint(key, (B,), 1, Sk + 1)
    mask = jnp.arange(Sk)[None, :] < lengths[:, None]
    return jnp.where(mask, 0.0, -1e9).astype(jnp.float32), mask


@pytest.mark.parametrize("Sq,Sk,blocks", [(64, 64, 32), (128, 128, 32),
                                          (96, 160, 32)])
def test_matches_dense_padding_mask(Sq, Sk, blocks):
    key = jax.random.key(0)
    q, k, v = _rand_qkv(key, 2, 4, 4, Sq, Sk, 64)
    bias, _ = _pad_bias(jax.random.key(1), 2, Sk)
    got = flash_attention(q, k, v, kv_bias=bias, block_q=blocks, block_k=blocks)
    want, _ = _dense_reference(q, k, v, bias, False, 1 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_matches_dense_causal():
    key = jax.random.key(2)
    q, k, v = _rand_qkv(key, 2, 4, 4, 128, 128, 64)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    want, _ = _dense_reference(q, k, v, jnp.zeros((2, 128)), True,
                               1 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_matches_dense_gqa_causal_padded():
    key = jax.random.key(3)
    q, k, v = _rand_qkv(key, 2, 8, 2, 64, 64, 32)
    bias, _ = _pad_bias(jax.random.key(4), 2, 64)
    got = flash_attention(q, k, v, kv_bias=bias, causal=True,
                          block_q=32, block_k=32)
    want, _ = _dense_reference(q, k, v, bias, True, 1 / np.sqrt(32))
    # rows whose kv positions are all masked (pad rows) are garbage in both
    # implementations; compare only rows with at least one visible key.
    visible = np.asarray(bias[:, None, :, None] == 0) | np.zeros_like(got, bool)
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got[visible[:, :, : got.shape[2]]],
                               want[visible[:, :, : got.shape[2]]],
                               rtol=2e-5, atol=2e-5)


def test_odd_shapes_fall_back_to_dense():
    q, k, v = _rand_qkv(jax.random.key(5), 1, 2, 2, 7, 7, 16)
    got = flash_attention(q, k, v)
    want, _ = _dense_reference(q, k, v, jnp.zeros((1, 7)), False,
                               1 / np.sqrt(16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bfloat16_output_dtype():
    q, k, v = _rand_qkv(jax.random.key(6), 1, 2, 2, 64, 64, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    want, _ = _dense_reference(q, k, v, jnp.zeros((1, 64)), False,
                               1 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_gradients_match_dense():
    key = jax.random.key(7)
    q, k, v = _rand_qkv(key, 1, 2, 2, 64, 64, 32)
    bias, _ = _pad_bias(jax.random.key(8), 1, 64)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, kv_bias=bias, block_q=32,
                               block_k=32).sum()

    def loss_dense(q, k, v):
        out, _ = _dense_reference(q, k, v, bias, False, 1 / np.sqrt(32))
        return out.sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_bert_flash_equals_xla():
    from symbiont_tpu.models import bert

    cfg = bert.BertConfig(vocab_size=128, hidden_size=64, num_layers=2,
                          num_heads=4, intermediate_size=128,
                          max_position_embeddings=64, dtype="float32")
    params = bert.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 128, (3, 64)), jnp.int32)
    lengths = [64, 10, 33]
    mask = jnp.asarray([[1] * n + [0] * (64 - n) for n in lengths], jnp.int32)

    out_xla = bert.embed_sentences(params, ids, mask, cfg)
    out_flash = bert.embed_sentences(
        params, ids, mask, dataclasses.replace(cfg, attn_impl="flash"))
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_xla),
                               rtol=2e-4, atol=2e-4)


def test_gpt_flash_prefill_equals_xla():
    from symbiont_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, intermediate_size=128,
                        max_position_embeddings=64, arch="llama",
                        dtype="float32")
    params = gpt.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(1)
    B, S = 2, 32
    ids = jnp.asarray(rng.integers(0, 128, (B, S)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    kv_valid = jnp.ones((B, S), bool)

    cache = gpt.init_cache(cfg, B, S, jnp.float32)
    logits_xla, _ = gpt.forward(params, ids, cache, positions, cfg, kv_valid)
    cache = gpt.init_cache(cfg, B, S, jnp.float32)
    logits_flash, _ = gpt.forward(
        params, ids, cache, positions,
        dataclasses.replace(cfg, attn_impl="flash"), kv_valid)
    np.testing.assert_allclose(np.asarray(logits_flash),
                               np.asarray(logits_xla), rtol=2e-4, atol=2e-4)


def test_fused_backward_causal_multiblock_asymmetric():
    """The fused pallas backward (dK/dV + dQ kernels) vs the dense gradient:
    causal, multiple blocks per axis, and bq != bk so any transposed
    contraction shows up as a shape-or-value error instead of passing by
    coincidence."""
    key = jax.random.key(21)
    q, k, v = _rand_qkv(key, 2, 2, 2, 128, 128, 32)
    bias, _ = _pad_bias(jax.random.key(22), 2, 128)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, kv_bias=bias, causal=True,
                                block_q=64, block_k=32) ** 2).sum()

    def loss_dense(q, k, v):
        out, _ = _dense_reference(q, k, v, bias, True, 1 / np.sqrt(32))
        return (out ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_fused_backward_bias_gradient():
    """dbias from the fused backward (accumulated in-kernel per head, summed
    outside) matches the dense softmax-gradient column sums."""
    key = jax.random.key(23)
    q, k, v = _rand_qkv(key, 2, 2, 2, 64, 64, 32)

    def loss_flash(bias):
        return (flash_attention(q, k, v, kv_bias=bias, block_q=32,
                                block_k=32) ** 2).sum()

    def loss_dense(bias):
        out, _ = _dense_reference(q, k, v, bias, False, 1 / np.sqrt(32))
        return (out ** 2).sum()

    bias = jnp.zeros((2, 64), jnp.float32)
    g1 = jax.grad(loss_flash)(bias)
    g2 = jax.grad(loss_dense)(bias)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-4, atol=2e-4)


def test_gqa_backward_matches_dense():
    """GQA (kv heads < q heads) routes to the dense-recompute backward and
    must still produce correct grouped-sum gradients."""
    key = jax.random.key(25)
    q, k, v = _rand_qkv(key, 1, 4, 2, 64, 64, 32)
    bias = jnp.zeros((1, 64), jnp.float32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, kv_bias=bias, causal=True,
                               block_q=32, block_k=32).sum()

    def loss_dense(q, k, v):
        out, _ = _dense_reference(q, k, v, bias, True, 1 / np.sqrt(32))
        return out.sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------------------ segments of packed rows

SEGMENT_CASES = {  # chunk lengths of the two rows' first 256 tokens
    "a_boundary_inside_a_block": [(100, 156), (201, 55)],
    "a_boundary_on_a_block_boundary": [(128, 128), (128, 100)],
    "one_chunk_a_row": [(256,), (256,)],
    "a_padded_tail": [(90, 70), (130,)],
    "eight_short_chunks": [(24,) * 8, (31, 9, 40, 17, 33, 8, 25, 29)],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rotary, block, L", [
    (False, 128, 256), (True, 128, 256), (True, 512, 512), (True, 512, 1024)],
    ids=["plain-2x2_blocks", "rope-2x2_blocks", "rope-one_block",
         "rope-2x2_blocks_of_512"])
@pytest.mark.parametrize("case", list(SEGMENT_CASES))
def test_packed_rows_match_dense_with_the_same_mask(case, rotary, block, L,
                                                    dtype):
    """`packed_attention` (q, k, v `[B, L, heads * D]`, a head a column
    block; the mask from per-token segment ids, causal inside a segment;
    RoPE from the segment's start inside the kernel) against the dense
    reference handed the same mask on `[B, heads, L, D]` operands that
    `layers.rope` turned: float32 to rounding, bfloat16 to its own step.
    A row of 2 x 2 blocks streams (the block above the diagonal skipped,
    the one below it whole, two on it); a row of one block takes the direct
    form, two heads a step. A 512-token block goes 256 query rows at a time
    and its first tile reads the first 256 keys only. Tokens past the
    chunks are padding."""
    B, H, D = 2, 2, 128
    lengths = np.zeros((B, 8), np.int32)
    for r, row in enumerate(SEGMENT_CASES[case]):
        lengths[r, :len(row)] = row
    seg = Segments.of_lengths(jnp.asarray(lengths), L)
    q, k, v = (jax.random.normal(key, (B, L, H * D), dtype)
               for key in jax.random.split(jax.random.key(9), 3))
    got = packed_attention(
        q, k, v, seg.index, H, block=block,
        rope=rope_tables(seg.position, D, 1e4) if rotary else None)
    assert got.shape == (B, L, H * D) and got.dtype == dtype

    def heads(t, turn):
        t = t.reshape(B, L, H, D)
        if turn and rotary:
            t = rope(t, seg.position, 1e4)
        return t.transpose(0, 2, 1, 3)

    want, _ = _dense_reference(heads(q, True), heads(k, True),
                               heads(v, False), jnp.zeros((B, L)), True,
                               1 / np.sqrt(D), segment_ids=seg.index)
    want = np.asarray(want.transpose(0, 2, 1, 3).reshape(B, L, H * D))
    tol = 2e-5 if dtype == jnp.float32 else 0.03
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=tol,
                               atol=tol)


def test_packed_attention_refuses_what_does_not_tile():
    q = jnp.zeros((1, 256, 128), jnp.float32)
    ids = jnp.zeros((1, 256), jnp.int32)
    with pytest.raises(ValueError, match="does not tile"):
        packed_attention(q, q, q, ids, 2)  # head_dim 64
    with pytest.raises(ValueError, match="does not tile"):
        packed_attention(q[:, :200], q[:, :200], q[:, :200], ids[:, :200], 1)
    with pytest.raises(ValueError, match="shapes"):
        packed_attention(q, q, q, ids[:, :128], 1)


@pytest.mark.parametrize("causal, text_sha256", [
    (False, "e35e666da375c1ed"), (True, "f1527b70b21f98b0")])
def test_without_segments_the_call_lowers_to_the_text_it_had(causal,
                                                             text_sha256):
    """The segment mask lives in a second kernel (`packed_attention`); a
    `flash_attention` call traces what it traced before it: the lowered
    text at a toy shape (GQA, a per-key bias, unequal blocks) hashes to
    what commit e2bc2c8 lowers to under jax 0.9.0. Another jax writes another text: take the hashes again from that
    commit (`jax.jit(f).lower(...).as_text()`, as below) before trusting a
    difference."""
    if jax.__version__ != "0.9.0":
        pytest.skip(f"the pinned text is jax 0.9.0's, not {jax.__version__}'s")
    q = jax.ShapeDtypeStruct((2, 4, 128, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 2, 128, 64), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((2, 128), jnp.float32)
    text = jax.jit(lambda q, k, v, b: flash_attention(
        q, k, v, kv_bias=b, causal=causal, block_q=64, block_k=32)).lower(
            q, k, k, bias).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == text_sha256
