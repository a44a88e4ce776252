"""Compile for a described (not attached) TPU v5e, at published widths: what
interpret-free CPU tests cannot show. No chip is needed and nothing runs; a
compile that passes is not a chip run. All such tests live in THIS file (one
process may hold the TPU's library; the topology is described inside a
fixture, never at import), as the on-chip-measurement guide sets out.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from symbiont_tpu.models import mla_moe


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without the chip: keep it out, and the next run quiet
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("tokens, kernel", [(4096, "pallas"),
                                            (32, "ragged_dot")])
def test_routed_experts_compile_to_grouped_matmuls_at_published_widths(
        one_chip, monkeypatch, tokens, kernel):
    """Kimi-VL-A3B's expert layer (64 experts of 2,048 x 1,408, top-6) over
    a 128 x 32-token batch: each of the three projections is ONE Pallas
    grouped matmul (ops/grouped_matmul.py, a Mosaic custom call whose two
    whole-kernel buffers the chip's compiler takes into VMEM) and the
    compiler's own `ragged_dot` kernel is gone; over the 1 x 32-token bucket
    (192 rows: no multiple of the 128-row tile) the compiler's kernel stays.
    Either way the FLOPs counted are of each token's OWN six experts, not of
    all 64 on every token (10.7 x as many)."""
    # `grouped_matmul` asks the backend; the described chip is not attached
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = mla_moe.MlaMoeConfig()
    T, H, I, E, k = (tokens, cfg.hidden_size, cfg.moe_intermediate_size,
                     cfg.n_routed_experts, cfg.num_experts_per_tok)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    p = {"experts": {"gate": {"kernel": shape(E, H, I)},
                     "up": {"kernel": shape(E, H, I)},
                     "down": {"kernel": shape(E, I, H)}}}
    compiled = jax.jit(
        lambda p, x, idx, w, real: mla_moe.routed_experts(p, x, idx, w, real,
                                                          cfg)
    ).lower(p, shape(T, H), shape(T, k, dtype=jnp.int32),
            shape(T, k, dtype=jnp.float32), shape(T, dtype=jnp.bool_)
            ).compile()
    text = compiled.as_text()
    # the compiler's own kernel is a Mosaic custom call too: tell by name
    ours = len(re.findall(r"^\s*%grouped_matmul[.\d]* = .*custom-call\(", text,
                          re.M))
    assert (ours, text.count("ragged_dot_tiling")) == (
        (3, 0) if kernel == "pallas" else (0, 3)), "three grouped matmuls"
    own = 2.0 * 3 * H * I * T * k
    flops = compiled.cost_analysis()["flops"]
    assert own <= flops < 1.2 * own, (flops, own)


@pytest.mark.parametrize("kind", ["minicpm4", "lightning-attn"])
def test_long_row_mixers_compile_within_the_chip_at_published_widths(
        one_chip, kind):
    """MiniCPM-SALA's two mixers over ONE packed 32,768-token row at
    published widths (32 heads of 128, 2 kv heads, 64 slots a row): the
    chip's compiler takes the blocked sparse attention (scan over query
    blocks, a traced-bound loop over key chunks) and the chunked linear
    scan, and neither holds anything [L, L]: float32 scores of 32 heads at
    this length would be 137 GB, the whole temp here is under 2 GB."""
    from symbiont_tpu.engine.bucketing import segments_per_row
    from symbiont_tpu.models import sala
    from symbiont_tpu.models.bert import Segments

    L = 32768
    cfg = sala.SalaConfig(num_layers=1, mixer_types=(kind,))
    layer = jax.eval_shape(lambda: sala.init_params(
        jax.random.key(0), cfg))["layers"][0]["mixer"]
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.bfloat16 if a.ndim > 1 else jnp.float32,
        sharding=one_chip), layer)
    x = jax.ShapeDtypeStruct((1, L, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, segments_per_row(L)), jnp.int32,
                               sharding=one_chip)

    def mixer(p, x, seg_lengths):
        segments = Segments.of_lengths(seg_lengths, L)
        if kind == sala.SPARSE:
            return sala.sparse_mixer(p, x, segments, cfg)
        return sala.lightning_mixer(p, x, segments, cfg)

    compiled = jax.jit(mixer).lower(p, x, seg).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    assert "while" in compiled.as_text()


@pytest.mark.parametrize("k", [8, 16])
def test_the_search_program_sorts_blocks_not_the_score_vector(one_chip, k):
    """`search_fused`'s scan + top-k (1,450,000 rows of 768 in 23 capacity
    blocks, k buckets 8 and 16): the chip's compiler is handed sorts of the
    1,472 block maxes and of the k chosen blocks' k x 1,024 scores, and none
    of the 1,507,328 scores themselves (2.1 ms a query on the v5e)."""
    from symbiont_tpu.memory import device_corpus

    cap = device_corpus.capacity(1_450_000, 65_536)
    text = jax.jit(
        lambda c, q, n: device_corpus.scan_topk(c, q, n, k)
    ).lower(jax.ShapeDtypeStruct((cap, 768), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((768,), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
            ).compile().as_text()
    sorted_lengths = sorted(
        int(n) for n in re.findall(r"= \(\w+\[(\d+)\][^=]*\) sort\(", text))
    assert sorted_lengths == [k, cap // device_corpus.TOPK_BLOCK,
                              k * device_corpus.TOPK_BLOCK]


@pytest.mark.parametrize("rows, length, packed", [(1, 32, False),
                                                  (32, 128, True)])
def test_the_encoder_program_leaves_its_embedding_table_at_rest(
        one_chip, rows, length, packed):
    """`xlmr-base-retrieval` (float32 at rest, bfloat16 compute), a query's
    B = 1 forward and a page's packed dispatch: the chip's compiler is handed
    no op that writes a second [250002, 768] table (the bfloat16 copy cost
    1.15 GB of HBM traffic, 1.8 ms, per program on the v5e) and the program
    needs no scratch the size of one."""
    from symbiont_tpu.engine import bucketing
    from symbiont_tpu.models import bert

    cfg = bert.BertConfig(
        vocab_size=250002, hidden_size=768, num_layers=12, num_heads=12,
        intermediate_size=3072, max_position_embeddings=514,
        type_vocab_size=1, position_offset=2, layer_norm_eps=1e-5,
        dtype="bfloat16")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: bert.init_params(jax.random.key(0), cfg)))
    assert params["embeddings"]["word_embeddings"].dtype == jnp.float32
    if packed:
        def fn(p, ids, seg_lengths):
            seg = bert.Segments.of_lengths(seg_lengths, length)
            return bert.embed_sentences(p, ids, seg.real, cfg, segments=seg)
        second = shape((rows, bucketing.segments_per_row(length)), jnp.int32)
    else:
        def fn(p, ids, mask):
            return bert.embed_sentences(p, ids, mask, cfg)
        second = shape((rows, length), jnp.int32)
    compiled = jax.jit(fn).lower(
        params, shape((rows, length), jnp.int32), second).compile()
    writers = re.findall(r"= \w+\[250002,768\]\S* ([\w-]+)\(",
                         compiled.as_text())
    assert writers and set(writers) == {"parameter"}, writers
    table_bf16 = 250002 * 768 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < table_bf16 // 4


@pytest.mark.parametrize("rows", [8, 1])
def test_the_looped_block_keeps_its_scores_on_the_chip(one_chip, monkeypatch,
                                                       rows):
    """`ouro-2.6b-embed`'s two packed embed programs (`[8, 512]` and
    `[1, 512]`, bfloat16 at rest, 48 layers x 4 steps): the scanned block
    holds ONE Mosaic kernel, traced under `loop_attn` (the scope the
    benchmark reads its device time by), and the chip's compiler is handed
    nothing `[rows, 16, 512, 512]`: 134 MB of float32 scores an application
    at 8 rows, 192 applications a dispatch, before the kernel."""
    from symbiont_tpu.engine.bucketing import segments_per_row
    from symbiont_tpu.models import ouro
    from symbiont_tpu.models.bert import Segments

    # the route asks nothing of the backend, the kernel's `interpret` does
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ouro.OuroConfig()

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: shape(a.shape, jnp.bfloat16 if a.ndim > 2 else a.dtype),
        jax.eval_shape(lambda: ouro.init_params(jax.random.key(0), cfg)))

    def fn(p, ids, seg_lengths):
        seg = Segments.of_lengths(seg_lengths, 512)
        return ouro.embed_sentences(p, ids, seg.real, cfg, "mean", True, seg)

    compiled = jax.jit(fn).lower(
        params, shape((rows, 512), jnp.int32),
        shape((rows, segments_per_row(512)), jnp.int32)).compile()
    text = compiled.as_text()
    kernels = re.findall(r'custom-call\(.*custom_call_target="tpu_custom_call"'
                         r'.*op_name="([^"]*)"', text)
    assert len(kernels) == 1 and "/loop_attn/" in kernels[0], kernels
    assert f"[{rows},16,512,512]" not in text
    # the parent's program needed 1.38 GB of scratch at 8 rows
    assert compiled.memory_analysis().temp_size_in_bytes < 2e8


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_ling_mixers_compile_within_the_chip_at_published_widths(
        one_chip, monkeypatch, kind):
    """Ling-3.0-flash's two mixers over ONE packed 32,768-token row at
    published widths (32 heads; KDA 128-wide, MLA q.k 192 and v 128): the
    chip's compiler takes the delta rule as ONE Mosaic kernel traced under
    `delta_rule` (the float32 state in VMEM; nothing left of the XLA form's
    scan, whose operands were [64, 1, 8, 64, 32, 128]) and MLA through ONE
    Mosaic kernel traced under `mla`, and neither holds anything [L, L]:
    float32 scores of 32 heads at this length would be 137 GB."""
    from symbiont_tpu.engine.bucketing import segments_per_row
    from symbiont_tpu.models import ling
    from symbiont_tpu.models.bert import Segments

    # the route asks nothing of the backend, the kernel's `interpret` does
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L = 32768
    cfg = ling.LingConfig(num_layers=6, first_k_dense_replace=6)
    layer = jax.eval_shape(lambda: ling.init_params(
        jax.random.key(0), cfg))["layers"][5 if kind == "mla" else 0]
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.bfloat16 if a.ndim > 1 else jnp.float32,
        sharding=one_chip), layer["attn" if kind == "mla" else "kda"])
    x = jax.ShapeDtypeStruct((1, L, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, segments_per_row(L)), jnp.int32,
                               sharding=one_chip)

    def mixer(p, x, seg_lengths):
        segments = Segments.of_lengths(seg_lengths, L)
        with jax.named_scope(kind):
            if kind == "mla":
                return mla_moe.mla_attention(p, x, segments.real, cfg.mla,
                                             segments)
            return ling.kda_mixer(p, x, segments, cfg)

    compiled = jax.jit(mixer).lower(p, x, seg).compile()
    text = compiled.as_text()
    assert f"{L},{L}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9
    kernels = re.findall(r'custom-call\(.*custom_call_target="tpu_custom_call"'
                         r'.*op_name="([^"]*)"', text)
    scope = "/mla/" if kind == "mla" else "/kda/delta_rule/"
    assert len(kernels) == 1 and scope in kernels[0], kernels
    assert not re.search(r"\[\d+,1,8,64,32,", text)


@pytest.mark.parametrize("window", [True, False])
def test_mimo_mixers_compile_within_the_chip_at_published_widths(
        one_chip, monkeypatch, window):
    """MiMo-V2-Flash's two attention mixers over ONE packed 32,768-token row
    at published widths (64 query heads of 192 laid in 256 lanes, v 128; 8
    KV heads and a sink in a window layer, 4 KV heads in a full one): the
    chip's compiler takes each as ONE Mosaic kernel (`window_attention` /
    `grouped_attention`) traced under the mixer's scope, and neither program
    holds anything [L, L] or a KV head repeated to the query heads."""
    from symbiont_tpu.engine.bucketing import segments_per_row
    from symbiont_tpu.models import mimo
    from symbiont_tpu.models.bert import Segments

    L = 32768
    cfg = mimo.MimoConfig(num_layers=2, layer_pattern=(0, 1),
                          moe_layers=(0, 0))
    layer = jax.eval_shape(lambda: mimo.init_params(
        jax.random.key(0), cfg))["layers"][int(window)]
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.bfloat16 if a.ndim > 1 else jnp.float32,
        sharding=one_chip), layer["attn"])
    x = jax.ShapeDtypeStruct((1, L, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, segments_per_row(L)), jnp.int32,
                               sharding=one_chip)
    scope = "swa" if window else "full_attn"

    def mixer(p, x, seg_lengths):
        segments = Segments.of_lengths(seg_lengths, L)
        theta = cfg.swa_rope_theta if window else cfg.rope_theta
        tables = mimo.rope_lanes(segments.position, cfg, theta)
        with jax.named_scope(scope):
            return mimo.attention(p, x, segments, tables, cfg, window)

    # the route asks nothing of the backend, the kernel's `interpret` does
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(mixer).lower(p, x, seg).compile()
    text = compiled.as_text()
    assert f"{L},{L}]" not in text
    assert not re.search(rf"\[1,{L},64,256\]", text)  # no KV head repeated
    kernels = re.findall(r'custom-call\(.*custom_call_target="tpu_custom_call"'
                         r'.*op_name="([^"]*)"', text)
    assert len(kernels) == 1 and f"/{scope}/" in kernels[0], kernels
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
