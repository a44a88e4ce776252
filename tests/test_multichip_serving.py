"""Multi-chip SERVING plane gates (ROADMAP item 1) on the 8-virtual-device
CPU mesh.

test_parallel.py proves the parallel/ primitives (DP batch sharding, TP
forward, ring/Ulysses attention) in isolation; this module gates the LIVE
stack shapes the runner now builds from config:

- the runner constructs the mesh purely from `ParallelConfig` and threads
  it through TpuEngine, LmEngine, and the vector store — no caller-supplied
  mesh;
- DP embed through the mesh engine matches single-device (cosine parity on
  a fixed corpus) and the per-replica padding/shard-balance gauges account;
- corpus-sharded fused search (per-shard top-k + global merge,
  memory/device_corpus.scan_topk) returns IDENTICAL hits (ids, scores,
  order) to the single-device store, on both the store path and the fused
  engine path;
- TP greedy decode is token-identical to single-device through
  generate_batch AND a continuous-batching session with a mid-decode
  admit — including with int8-quantized weights (the PR 7 gap: QuantTensor
  leaves now shard with their scales instead of falling back).

Small geometries keep this in the fast tier; every test is seeded and
CPU-deterministic.
"""

import dataclasses

import numpy as np
import pytest

import jax

from symbiont_tpu.config import (
    EngineConfig,
    LmConfig,
    ParallelConfig,
    VectorStoreConfig,
)
from symbiont_tpu.engine.engine import TpuEngine
from symbiont_tpu.engine.lm import LmEngine
from symbiont_tpu.memory.vector_store import VectorStore
from symbiont_tpu.parallel import build_mesh, mesh_from_config, parse_mesh_spec
from symbiont_tpu.utils.telemetry import metrics

requires_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 devices")

ENG_KW = dict(embedding_dim=32, length_buckets=[8, 16], batch_buckets=[8, 16],
              max_batch=16, dtype="float32")
TEXTS = [f"sentence number {i} with a few words" for i in range(12)]


def _row_cos(a, b):
    num = np.sum(a * b, axis=1)
    den = np.maximum(np.linalg.norm(a, axis=1) * np.linalg.norm(b, axis=1),
                     1e-12)
    return num / den


# ------------------------------------------------------------ config → mesh

def test_parse_mesh_spec():
    assert parse_mesh_spec("dp4xtp2") == [4, 2]
    assert parse_mesh_spec("dp8") == [8, 1]
    assert parse_mesh_spec("tp2") == [1, 2]
    assert parse_mesh_spec("4x2") == [4, 2]
    assert parse_mesh_spec("8") == [8, 1]
    with pytest.raises(ValueError):
        parse_mesh_spec("banana")


def test_parallel_config_validation():
    ParallelConfig(mesh_shape=[4, 2])
    with pytest.raises(ValueError):
        ParallelConfig(mesh_shape=[])
    with pytest.raises(ValueError):
        ParallelConfig(mesh_shape=[0, 8])
    with pytest.raises(ValueError):
        ParallelConfig(mesh_shape=[8])  # one size per axis name


@requires_8
def test_mesh_from_config_shapes():
    assert dict(mesh_from_config(ParallelConfig()).shape) == {
        "data": 8, "tensor": 1}
    assert dict(mesh_from_config(
        ParallelConfig(mesh_shape=[4, 2])).shape) == {"data": 4, "tensor": 2}


@requires_8
def test_runner_builds_mesh_purely_from_config(tmp_path):
    """The tentpole contract: a stack configured with mesh_shape=[4, 2]
    serves DP embed, a sharded corpus, AND TP decode with no code changes
    and no caller-supplied mesh — and registers the mesh.devices{axis}
    topology gauges."""
    import asyncio

    from symbiont_tpu.config import SymbiontConfig
    from symbiont_tpu.runner import SymbiontStack

    cfg = SymbiontConfig()
    cfg.parallel.mesh_shape = [4, 2]
    cfg.engine = EngineConfig(**ENG_KW)
    cfg.lm = LmConfig(enabled=True, arch="llama", hidden_size=32,
                      num_layers=1, num_heads=2, intermediate_size=64,
                      max_positions=64, dtype="float32", prompt_buckets=[8],
                      new_token_buckets=[8], stream_chunk=4)
    cfg.vector_store = VectorStoreConfig(dim=32,
                                         data_dir=str(tmp_path / "vs"),
                                         shard_capacity=64)
    cfg.graph_store.data_dir = str(tmp_path / "gs")
    cfg.text_generator.markov_state_path = None
    cfg.runner.services = "preprocessing,vector_memory,text_generator"

    async def scenario():
        stack = SymbiontStack(cfg)
        await stack.start()
        try:
            assert dict(stack.engine.mesh.shape) == {"data": 4, "tensor": 2}
            assert stack.engine._n_data == 4
            assert stack.vector_store.mesh is stack.engine.mesh
            assert stack.lm.mesh is stack.engine.mesh  # TP sharded decode
            assert metrics.gauge_get("mesh.devices",
                                     labels={"axis": "data"}) == 4
            assert metrics.gauge_get("mesh.devices",
                                     labels={"axis": "tensor"}) == 2
        finally:
            await stack.stop()

    asyncio.run(scenario())


@requires_8
def test_runner_standalone_vector_memory_worker_gets_mesh(tmp_path):
    """A store-only worker (engine in another process) still owns a
    device-resident corpus — the runner must build the mesh for it too, or
    corpus-sharded search silently degrades to one chip (review finding)."""
    import asyncio

    from symbiont_tpu.config import SymbiontConfig
    from symbiont_tpu.runner import SymbiontStack

    cfg = SymbiontConfig()
    cfg.vector_store = VectorStoreConfig(dim=32,
                                         data_dir=str(tmp_path / "vs"),
                                         shard_capacity=64)
    cfg.runner.services = "vector_memory"

    async def scenario():
        stack = SymbiontStack(cfg)
        await stack.start()
        try:
            assert stack.engine is None
            assert stack.vector_store.mesh is not None
            assert dict(stack.vector_store.mesh.shape)["data"] == 8
        finally:
            await stack.stop()

    asyncio.run(scenario())


@requires_8
def test_runner_parallel_disabled_keeps_meshless_engines():
    import asyncio

    from symbiont_tpu.config import SymbiontConfig
    from symbiont_tpu.runner import SymbiontStack

    cfg = SymbiontConfig()
    cfg.parallel.enabled = False
    cfg.engine = EngineConfig(**ENG_KW)
    cfg.runner.services = "preprocessing"

    async def scenario():
        stack = SymbiontStack(cfg)
        await stack.start()
        try:
            assert stack.engine.mesh is None
        finally:
            await stack.stop()

    asyncio.run(scenario())


# ------------------------------------------------------------------ DP embed

@requires_8
def test_dp_embed_parity_and_replica_gauges():
    """DP embed over the full 8-way data axis matches single-device row for
    row, and the per-replica padding-waste + shard-balance gauges account
    for the dispatched batch (ISSUE 8 satellite: engine.dp_* / per-replica
    batcher.padding_waste observability)."""
    mesh = build_mesh()
    dp = TpuEngine(EngineConfig(**ENG_KW), mesh=mesh)
    single = TpuEngine(EngineConfig(**ENG_KW, data_parallel=False))
    out_dp = dp.embed_texts(TEXTS)
    out_1 = single.embed_texts(TEXTS)
    np.testing.assert_allclose(out_dp, out_1, atol=1e-4, rtol=1e-3)
    assert _row_cos(out_dp, out_1).min() >= 0.999
    # the per-replica accounting itself, at a pinned shape: 13 real rows in
    # a 16-row batch over 8 replicas (2 rows each) — replicas 0-5 fully
    # real, replica 6 half padding, replica 7 all padding
    dp._note_padding([8] * 13, 8, 16, 13)
    waste = [metrics.gauge_get("batcher.padding_waste",
                               labels={"service": "engine",
                                       "replica": str(r)})
             for r in range(8)]
    assert waste[:6] == [0.0] * 6
    assert waste[6] == pytest.approx(0.5)
    assert waste[7] == pytest.approx(1.0)
    assert metrics.gauge_get("engine.dp_shard_balance",
                             labels={"service": "engine"}) == 0.0
    assert metrics.gauge_get("engine.dp_replicas",
                             labels={"service": "engine"}) == 8


@requires_8
def test_micro_batcher_rounds_flush_cap_to_data_axis():
    import asyncio

    from symbiont_tpu.engine.batcher import MicroBatcher

    mesh = build_mesh()
    eng = TpuEngine(EngineConfig(**ENG_KW), mesh=mesh)

    async def scenario():
        # a 13-item cap would bucket every full flush to 16 rows with 3
        # permanent pad rows; mesh-aware sizing rounds it to 16
        b = MicroBatcher(eng, max_batch=13)
        assert b.max_batch == 16
        await b.start()
        out = await b.embed(TEXTS[:4])
        assert out.shape == (4, 32)
        await b.close()

    asyncio.run(scenario())


# -------------------------------------------------------- sharded search

@requires_8
def test_sharded_search_identical_to_single_device():
    """Corpus-sharded fused search (per-shard top-k + global merge) returns
    IDENTICAL hits — ids, scores, order — to the single-device store, with
    the corpus actually sharded over the 'data' axis."""
    mesh = build_mesh()
    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((300, 32)).astype(np.float32)
    ids = [f"p{i}" for i in range(300)]
    payloads = [{"i": i} for i in range(300)]

    def mk(m):
        s = VectorStore(VectorStoreConfig(dim=32, data_dir="",
                                          shard_capacity=64), mesh=m)
        s.upsert_rows(ids, vecs, payloads)
        return s

    plain, sharded = mk(None), mk(mesh)
    for qi in range(16):
        q = rng.standard_normal(32).astype(np.float32)
        a = plain.search(q, 7)
        b = sharded.search(q, 7)
        assert [(h.id, h.score) for h in a] == [(h.id, h.score) for h in b]
    # the device corpus really lives sharded
    spec = str(sharded._device_corpus.sharding.spec)
    assert "data" in spec, spec
    # 300 rows → capacity rounds to a multiple of both the block and the
    # data axis
    assert sharded._device_corpus.shape[0] % 8 == 0


@requires_8
def test_sharded_search_ties_preserve_index_order():
    """Score ties must resolve identically on both paths (lax.top_k breaks
    ties by position; shards concatenate in global row order)."""
    mesh = build_mesh()
    base = np.zeros((96, 32), np.float32)
    base[:, 0] = 1.0  # every row identical → every score ties
    ids = [f"t{i:03d}" for i in range(96)]

    def mk(m):
        s = VectorStore(VectorStoreConfig(dim=32, data_dir="",
                                          shard_capacity=32), mesh=m)
        s.upsert_rows(ids, base, [{} for _ in ids])
        return s

    q = np.zeros(32, np.float32)
    q[0] = 1.0
    a = mk(None).search(q, 10)
    b = mk(mesh).search(q, 10)
    assert [h.id for h in a] == [h.id for h in b] == ids[:10]


@requires_8
def test_fused_search_sharded_matches_split_and_single():
    """search_fused over a sharded corpus (engine qsearch executable with
    the per-shard top-k) returns the same hits as the single-device fused
    path AND as split search(embed_query)."""
    mesh = build_mesh()
    eng_dp = TpuEngine(EngineConfig(**ENG_KW), mesh=mesh)
    eng_1 = TpuEngine(EngineConfig(**ENG_KW, data_parallel=False))

    corpus_texts = [f"document about topic {i} and detail {i % 7}"
                    for i in range(40)]
    vecs = eng_1.embed_texts(corpus_texts)

    def mk(m):
        s = VectorStore(VectorStoreConfig(dim=32, data_dir="",
                                          shard_capacity=64), mesh=m)
        s.upsert_rows([f"d{i}" for i in range(40)], vecs,
                      [{"t": t} for t in corpus_texts])
        return s

    plain, sharded = mk(None), mk(mesh)
    for q in ("topic detail", "document about seven"):
        fused_sharded = sharded.search_fused(eng_dp, q, 5)
        fused_single = plain.search_fused(eng_1, q, 5)
        # hit sets and order identical; scores to float tolerance (the
        # query embed compiles under GSPMD on the mesh engine, so its f32
        # last bits may differ from the single-device executable)
        assert ([h.id for h in fused_sharded]
                == [h.id for h in fused_single])
        np.testing.assert_allclose([h.score for h in fused_sharded],
                                   [h.score for h in fused_single],
                                   atol=1e-4, rtol=1e-4)
        split = plain.search(eng_1.embed_query(q), 5)
        assert [h.id for h in fused_sharded] == [h.id for h in split]


# ------------------------------------------------------------------ TP decode

LM_KW = dict(enabled=True, arch="gpt2", hidden_size=32, num_layers=2,
             num_heads=2, intermediate_size=64, max_positions=128,
             dtype="float32", prompt_buckets=[8, 16], new_token_buckets=[16],
             stream_chunk=4, session_min_rows=4, seed=3)


def _session_outputs(lm):
    sess = lm.start_session(["the quick brown fox"], [12], temperature=0.0)
    out = dict(sess.step())
    tags = sess.admit(["hello world"], [8], temperature=0.0)
    assert tags and tags[0] is not None
    while not sess.done():
        out.update(sess.step())
    return sorted(out.items())


@requires_8
@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_tp_decode_token_identical_through_serving_paths(quantize):
    """TP greedy decode == single-device, through generate_batch AND a
    session with a mid-decode admit. quantize='int8' runs the SAME bar
    with QuantTensor-sharded weights — the PR 7 'falls back unquantized'
    gap, closed (codes and per-channel scales shard together)."""
    mesh = build_mesh([4, 2])
    single = LmEngine(LmConfig(quantize=quantize, **LM_KW))
    tp = LmEngine(LmConfig(quantize=quantize, **LM_KW), mesh=mesh)
    assert tp.mesh is not None, "TP mesh must shard, not fall back"
    prompts = ["the quick brown fox", "mesh native decode"]
    base = single.generate_batch(prompts, [12, 12], temperature=0.0)
    out = tp.generate_batch(prompts, [12, 12], temperature=0.0)
    assert out == base
    assert _session_outputs(tp) == _session_outputs(single)


@requires_8
def test_tp_int8_params_shard_with_scales():
    """The sharded layout itself: int8 codes take the kernel's spec, the
    per-output-channel scales ride the kernel's LAST axis entry (col-
    sharded q/k/v scales shard on 'tensor', row-sharded o-proj scales
    replicate)."""
    from symbiont_tpu.models.quant import QuantTensor

    mesh = build_mesh([4, 2])
    tp = LmEngine(LmConfig(quantize="int8", **LM_KW), mesh=mesh)
    layer = tp.params["layers"][0]
    q_kernel = layer["q"]["kernel"]
    assert isinstance(q_kernel, QuantTensor)
    assert "tensor" in str(q_kernel.q.sharding.spec)
    assert "tensor" in str(q_kernel.scale.sharding.spec)
    o_kernel = layer["o"]["kernel"]
    assert "tensor" in str(o_kernel.q.sharding.spec)
    # row-sharded kernel: output channels unsharded → scales replicate
    assert "tensor" not in str(o_kernel.scale.sharding.spec)
    # the param-bytes gauge reports the narrow storage on the TP path too
    assert metrics.gauge_get("lm.param_bytes",
                             labels={"service": "lm", "dtype": "int8"}) > 0


@requires_8
def test_tp_on_with_quantize_no_longer_raises_or_warns(caplog):
    """tensor_parallel='on' + quantize=int8 must boot sharded-and-quantized
    silently (previously: unquantized fallback with a warning)."""
    import logging

    mesh = build_mesh([4, 2])
    with caplog.at_level(logging.WARNING, logger="symbiont_tpu.engine.lm"):
        lm = LmEngine(LmConfig(tensor_parallel="on", quantize="int8",
                               **LM_KW), mesh=mesh)
    assert lm.mesh is not None
    assert not [r for r in caplog.records
                if "unquantized" in r.getMessage()]
    assert lm.generate("hello", 8, temperature=0.0)
