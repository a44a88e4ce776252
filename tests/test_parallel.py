"""Multi-chip behavior on the 8-virtual-device CPU mesh (SURVEY.md §4 item 4).

Verifies: DP batch sharding reproduces single-device embeddings; TP-sharded
decoder forward matches unsharded logits; ring attention matches full
attention (incl. causal); mesh construction errors.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from symbiont_tpu.models import bert as bert_mod
from symbiont_tpu.models import gpt as gpt_mod
from symbiont_tpu.parallel import (
    batch_sharding,
    build_mesh,
    gpt_param_sharding,
    replicate,
    shard_params,
)
from symbiont_tpu.parallel.ring_attention import ring_attention_sharded

requires_8 = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")


def _full_attention(q, k, v, causal=False):
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@requires_8
def test_mesh_build_and_shape_error():
    mesh = build_mesh()
    assert mesh.shape == {"data": 8, "tensor": 1}
    mesh2 = build_mesh([2, 4])
    assert mesh2.shape == {"data": 2, "tensor": 4}
    # a shape smaller than the host takes the first devices ...
    mesh3 = build_mesh([3, 2])
    assert list(mesh3.devices.flat) == jax.devices()[:6]
    with pytest.raises(ValueError):  # ... a larger one cannot be built
        build_mesh([3, 3])


@requires_8
def test_dp_embedding_matches_single_device():
    cfg = bert_mod.BertConfig(vocab_size=64, hidden_size=16, num_layers=2,
                              num_heads=2, intermediate_size=32,
                              max_position_embeddings=32, dtype="float32")
    params = bert_mod.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    B = 16  # divisible by 8
    ids = rng.integers(3, 64, size=(B, 12)).astype(np.int32)
    mask = np.ones((B, 12), np.int32)
    mask[:, 9:] = 0

    ref = np.asarray(bert_mod.embed_sentences(params, jnp.asarray(ids),
                                              jnp.asarray(mask), cfg))

    mesh = build_mesh()
    params_r = replicate(mesh, params)
    bs = batch_sharding(mesh)
    ids_s = jax.device_put(jnp.asarray(ids), bs)
    mask_s = jax.device_put(jnp.asarray(mask), bs)
    fn = jax.jit(lambda p, i, m: bert_mod.embed_sentences(p, i, m, cfg),
                 out_shardings=bs)
    out = np.asarray(fn(params_r, ids_s, mask_s))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@requires_8
def test_tp_gpt_logits_match_unsharded():
    cfg = gpt_mod.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=8, intermediate_size=64,
                            max_position_embeddings=32, dtype="float32")
    params = gpt_mod.init_params(jax.random.key(1), cfg)
    ids = np.random.default_rng(1).integers(0, 64, size=(2, 10)).astype(np.int32)
    pos = jnp.broadcast_to(jnp.arange(10, dtype=jnp.int32), (2, 10))
    cache = gpt_mod.init_cache(cfg, 2, 10, jnp.float32)
    ref, _ = gpt_mod.forward(params, jnp.asarray(ids), cache, pos, cfg)

    mesh = build_mesh([1, 8])  # pure TP
    spec = gpt_param_sharding(mesh, params, arch="gpt2")
    params_tp = shard_params(mesh, params, spec)
    fn = jax.jit(lambda p, i: gpt_mod.forward(p, i, cache, pos, cfg)[0])
    out = fn(params_tp, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4,
                               rtol=1e-3)


@requires_8
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    rng = np.random.default_rng(2)
    B, S, NH, D = 2, 64, 4, 16  # S = 8 devices × 8 local
    q = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    ref = _full_attention(q, k, v, causal=causal)
    mesh = build_mesh([8, 1])
    out = ring_attention_sharded(q, k, v, mesh, axis_name="data", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-4)


@requires_8
@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    from symbiont_tpu.parallel.ulysses import ulysses_attention_sharded

    rng = np.random.default_rng(4)
    B, S, NH, D = 2, 64, 8, 16  # NH = 8 devices × 1 head each
    q = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    ref = _full_attention(q, k, v, causal=causal)
    mesh = build_mesh([8, 1])
    out = ulysses_attention_sharded(q, k, v, mesh, axis_name="data",
                                    causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-4)
    # and it agrees with the ring scheme on the same shards
    ring = ring_attention_sharded(q, k, v, mesh, axis_name="data",
                                  causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ring), atol=1e-5,
                               rtol=1e-4)


@requires_8
def test_ulysses_rejects_indivisible_heads():
    from symbiont_tpu.parallel.ulysses import ulysses_attention_sharded

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 16, 6, 8)), jnp.float32)  # 6 % 8 != 0
    with pytest.raises(ValueError, match="not divisible"):
        ulysses_attention_sharded(q, q, q, build_mesh([8, 1]))


@requires_8
def test_ring_attention_long_sequence_memory_shape():
    """Sequence 8× a device's local block works (the long-context claim)."""
    rng = np.random.default_rng(3)
    B, S, NH, D = 1, 256, 2, 8
    q = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    out = ring_attention_sharded(q, k, v, build_mesh([8, 1]), causal=True)
    assert out.shape == (B, S, NH, D)
    ref = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-4)


@requires_8
@pytest.mark.parametrize("arch,num_kv", [("gpt2", None), ("llama", 2)])
def test_sp_forward_matches_cache_forward(arch, num_kv):
    """Context-parallel training forward (sequence sharded over 8 devices,
    ring attention) reproduces the KV-cache forward's logits exactly —
    incl. GQA head expansion and RoPE with global positions."""
    from symbiont_tpu.parallel.context import gpt_forward_sp

    cfg = gpt_mod.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, num_kv_heads=num_kv,
                            intermediate_size=64, max_position_embeddings=64,
                            arch=arch, dtype="float32")
    params = gpt_mod.init_params(jax.random.key(2), cfg)
    B, S = 2, 32  # 8 devices × 4 local tokens
    ids = np.random.default_rng(6).integers(0, 64, size=(B, S)).astype(np.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = gpt_mod.init_cache(cfg, B, S, jnp.float32)
    ref, _ = gpt_mod.forward(params, jnp.asarray(ids), cache, pos, cfg)

    mesh = build_mesh([8, 1])
    out = gpt_forward_sp(params, jnp.asarray(ids), mesh, cfg, axis="data")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4,
                               rtol=1e-3)


@requires_8
def test_sp_forward_rejects_indivisible_sequence():
    from symbiont_tpu.parallel.context import gpt_forward_sp

    cfg = gpt_mod.GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                            num_heads=4, intermediate_size=64,
                            max_position_embeddings=64, dtype="float32")
    params = gpt_mod.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="not divisible"):
        gpt_forward_sp(params, jnp.zeros((1, 30), jnp.int32),
                       build_mesh([8, 1]), cfg)


@requires_8
def test_sp_train_step_matches_unsharded():
    """One sequence-parallel train step == one plain train step: same loss,
    same updated params (long-context training is exact, not approximate)."""
    from symbiont_tpu.parallel.context import make_lm_train_step_sp
    from symbiont_tpu.train.trainer import lm_train_step, make_lm_train_state

    cfg = gpt_mod.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, num_kv_heads=2, intermediate_size=64,
                            max_position_embeddings=64, arch="llama",
                            dtype="float32")
    rng = np.random.default_rng(7)
    B, S = 2, 32
    batch = {"ids": jnp.asarray(rng.integers(1, 64, (B, S)), jnp.int32),
             "mask": jnp.asarray((rng.random((B, S)) < 0.9).astype(np.int32))}

    params = gpt_mod.init_params(jax.random.key(3), cfg)
    state_ref, tx = make_lm_train_state(params, learning_rate=1e-3)
    state_ref, m_ref = lm_train_step(state_ref, batch, cfg, tx)

    params2 = gpt_mod.init_params(jax.random.key(3), cfg)
    state_sp, tx2 = make_lm_train_state(params2, learning_rate=1e-3)
    mesh = build_mesh([8, 1])
    step_sp = make_lm_train_step_sp(mesh, cfg, tx2, axis="data")
    state_sp, m_sp = step_sp(state_sp, batch)

    np.testing.assert_allclose(float(m_sp["loss"]), float(m_ref["loss"]),
                               atol=1e-5, rtol=1e-5)
    ref_leaves = jax.tree.leaves(state_ref.params)
    sp_leaves = jax.tree.leaves(state_sp.params)
    for a, b in zip(ref_leaves, sp_leaves):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4,
                                   rtol=1e-3)


@requires_8
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gqa_matches_full(causal):
    """GQA ring: K/V rotate at kv_heads width, expand only locally — result
    must equal full attention over pre-expanded K/V."""
    rng = np.random.default_rng(8)
    B, S, NH, KVH, D = 2, 64, 8, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, NH, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    ref = _full_attention(q, jnp.repeat(k, NH // KVH, axis=2),
                          jnp.repeat(v, NH // KVH, axis=2), causal=causal)
    mesh = build_mesh([8, 1])
    out = ring_attention_sharded(q, k, v, mesh, axis_name="data",
                                 causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-4)


@requires_8
def test_sp_forward_ulysses_matches_cache_forward():
    """The Ulysses (all-to-all) scheme as the SP attention backend must also
    reproduce the KV-cache forward — both schemes are exact, pick per
    workload (heads divisible by axis → Ulysses; else ring)."""
    from symbiont_tpu.parallel.context import gpt_forward_sp

    cfg = gpt_mod.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=8, intermediate_size=64,
                            max_position_embeddings=64, arch="gpt2",
                            dtype="float32")
    params = gpt_mod.init_params(jax.random.key(4), cfg)
    B, S = 2, 32
    ids = np.random.default_rng(9).integers(0, 64, size=(B, S)).astype(np.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = gpt_mod.init_cache(cfg, B, S, jnp.float32)
    ref, _ = gpt_mod.forward(params, jnp.asarray(ids), cache, pos, cfg)

    mesh = build_mesh([8, 1])
    out = gpt_forward_sp(params, jnp.asarray(ids), mesh, cfg, axis="data",
                         attn_impl="ulysses")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4,
                               rtol=1e-3)


@requires_8
def test_sp_forward_ulysses_gqa_matches_cache_forward():
    """Ulysses SP with GQA (nkv < nh): the pre-all-to-all K/V head expansion
    must map query heads to the right KV groups."""
    from symbiont_tpu.parallel.context import gpt_forward_sp

    cfg = gpt_mod.GPTConfig(vocab_size=64, hidden_size=64, num_layers=2,
                            num_heads=8, num_kv_heads=2, intermediate_size=64,
                            max_position_embeddings=64, arch="llama",
                            dtype="float32")
    params = gpt_mod.init_params(jax.random.key(5), cfg)
    B, S = 2, 32
    ids = np.random.default_rng(10).integers(0, 64, size=(B, S)).astype(np.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = gpt_mod.init_cache(cfg, B, S, jnp.float32)
    ref, _ = gpt_mod.forward(params, jnp.asarray(ids), cache, pos, cfg)

    mesh = build_mesh([8, 1])
    out = gpt_forward_sp(params, jnp.asarray(ids), mesh, cfg, axis="data",
                         attn_impl="ulysses")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4,
                               rtol=1e-3)


# ----------------------------------------------------------------- pipeline


@requires_8
@pytest.mark.parametrize("arch,num_kv", [("llama", 2), ("gpt2", None)])
def test_pp_loss_matches_unsharded(arch, num_kv):
    """Pipeline-parallel loss == plain loss on the same params/batch: the
    GPipe schedule changes execution order, not math."""
    from symbiont_tpu.parallel.pipeline import (lm_loss_pp, shard_pp_params,
                                                stack_layers)
    from symbiont_tpu.train.trainer import lm_loss

    cfg = gpt_mod.GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
        num_kv_heads=num_kv, intermediate_size=64,
        max_position_embeddings=32, arch=arch, dtype="float32",
        tie_word_embeddings=True)
    rng = np.random.default_rng(11)
    B, S = 8, 16
    batch = {"ids": jnp.asarray(rng.integers(1, 64, (B, S)), jnp.int32),
             "mask": jnp.asarray((rng.random((B, S)) < 0.9).astype(np.int32))}
    params = gpt_mod.init_params(jax.random.key(5), cfg)
    ref = float(lm_loss(params, batch, cfg))

    mesh = build_mesh([4], axis_names=("pipe",),
                      devices=jax.devices()[:4])  # 4 stages x 1 layer each
    placed = shard_pp_params(mesh, stack_layers(params))
    got = float(lm_loss_pp(placed, batch, cfg, mesh, num_microbatches=4))
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


@requires_8
def test_pp_train_step_matches_unsharded():
    """One pipeline-parallel train step == one plain train step: same loss,
    same updated params (backward is jax.grad's transpose of the pipelined
    forward — reverse ppermutes included)."""
    from symbiont_tpu.parallel.pipeline import (make_lm_train_step_pp,
                                                make_pp_train_state,
                                                stack_layers)
    from symbiont_tpu.train.trainer import lm_train_step, make_lm_train_state

    cfg = gpt_mod.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, num_kv_heads=2, intermediate_size=64,
                            max_position_embeddings=32, arch="llama",
                            dtype="float32")
    rng = np.random.default_rng(13)
    B, S = 4, 16
    batch = {"ids": jnp.asarray(rng.integers(1, 64, (B, S)), jnp.int32),
             "mask": jnp.asarray((rng.random((B, S)) < 0.9).astype(np.int32))}

    params = gpt_mod.init_params(jax.random.key(9), cfg)
    state_ref, tx = make_lm_train_state(params, learning_rate=1e-3)
    state_ref, m_ref = lm_train_step(state_ref, batch, cfg, tx)

    mesh = build_mesh([2], axis_names=("pipe",), devices=jax.devices()[:2])
    params2 = gpt_mod.init_params(jax.random.key(9), cfg)
    state_pp, tx2 = make_pp_train_state(mesh, params2, learning_rate=1e-3)
    step_pp = make_lm_train_step_pp(mesh, cfg, tx2, num_microbatches=2)
    state_pp, m_pp = step_pp(state_pp, batch)

    np.testing.assert_allclose(float(m_pp["loss"]), float(m_ref["loss"]),
                               atol=1e-5, rtol=1e-5)
    # updated params agree leaf-for-leaf (ref's layer list stacked to match)
    ref_stacked = stack_layers(state_ref.params)
    for a, b in zip(jax.tree.leaves(ref_stacked),
                    jax.tree.leaves(state_pp.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4,
                                   rtol=1e-3)
    # params kept their pipe sharding through the optimizer update
    spec = str(jax.tree.leaves(state_pp.params["layers"])[0].sharding.spec)
    assert "pipe" in spec, spec


@requires_8
def test_pp_rejects_indivisible_shapes():
    from symbiont_tpu.parallel.pipeline import (lm_loss_pp, shard_pp_params,
                                                stack_layers)

    cfg = gpt_mod.GPTConfig(vocab_size=64, hidden_size=32, num_layers=3,
                            num_heads=4, num_kv_heads=2, intermediate_size=64,
                            max_position_embeddings=32, arch="llama",
                            dtype="float32")
    params = gpt_mod.init_params(jax.random.key(0), cfg)
    mesh = build_mesh([2], axis_names=("pipe",), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="not divisible by pipe"):
        shard_pp_params(mesh, stack_layers(params))  # 3 layers, 2 stages
    cfg4 = dataclasses.replace(cfg, num_layers=4)
    params4 = gpt_mod.init_params(jax.random.key(0), cfg4)
    placed = shard_pp_params(mesh, stack_layers(params4))
    batch = {"ids": jnp.ones((3, 16), jnp.int32),
             "mask": jnp.ones((3, 16), jnp.int32)}
    with pytest.raises(ValueError, match="not divisible by microbatches"):
        lm_loss_pp(placed, batch, cfg4, mesh, num_microbatches=2)
