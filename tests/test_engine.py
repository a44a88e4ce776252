"""Engine tests: text parity, bucketing, executable cache, DP mesh, batcher."""

import asyncio

import numpy as np
import pytest

import jax

from symbiont_tpu.config import EngineConfig
from symbiont_tpu.engine.bucketing import choose_bucket, pad_to_bucket, plan_batches
from symbiont_tpu.engine.engine import TpuEngine
from symbiont_tpu.engine.text import clean_text, split_sentences, tokenize_words
from symbiont_tpu.engine.tokenizer import HashTokenizer


# ------------------------------------------------------------------- text

def test_clean_text_whitespace_parity():
    # reference: preprocessing_service/src/main.rs:28-33
    assert clean_text("  a\t b\n\nc  ") == "a b c"
    assert clean_text("\n \t ") == ""


def test_split_sentences_parity():
    # reference: preprocessing_service/src/main.rs:41-62
    assert split_sentences("One. Two? Three!") == ["One.", "Two?", "Three!"]
    assert split_sentences("No delimiter here") == ["No delimiter here"]
    assert split_sentences("Trailing remainder. extra") == ["Trailing remainder.", "extra"]
    assert split_sentences("Привет мир. Как дела?") == ["Привет мир.", "Как дела?"]
    # consecutive delimiters produce empty-trimmed slices like the reference
    assert split_sentences("Hi!! Done.") == ["Hi!", "!", "Done."]


def test_tokenize_words():
    assert tokenize_words("a b  c") == ["a", "b", "c"]


# -------------------------------------------------------------- bucketing

def test_choose_bucket():
    assert choose_bucket(5, [32, 64]) == 32
    assert choose_bucket(33, [32, 64]) == 64
    assert choose_bucket(100, [32, 64]) == 64  # clamp to max


def test_pad_to_bucket():
    ids, mask = pad_to_bucket([[1, 2], [3]], 4, pad_id=9)
    np.testing.assert_array_equal(ids, [[1, 2, 9, 9], [3, 9, 9, 9]])
    np.testing.assert_array_equal(mask, [[1, 1, 0, 0], [1, 0, 0, 0]])


def test_plan_batches_groups_by_bucket_and_limits_size():
    lengths = [5, 60, 6, 61, 7, 8]
    plans = plan_batches(lengths, [32, 64], max_batch=2)
    # all short ones in 32-bucket batches of ≤2, long ones in 64
    got = {}
    for bucket, idxs in plans:
        got.setdefault(bucket, []).extend(idxs)
        assert len(idxs) <= 2
    assert sorted(got[32]) == [0, 2, 4, 5]
    assert sorted(got[64]) == [1, 3]


# ----------------------------------------------------------------- engine

def _small_engine(**kw):
    cfg = EngineConfig(embedding_dim=32, length_buckets=[8, 16], batch_buckets=[2, 4],
                       max_batch=4, dtype="float32", data_parallel=False)
    return TpuEngine(cfg, **kw)


def test_embed_texts_order_and_shape():
    eng = _small_engine()
    texts = ["short one", "a much longer sentence with many words repeated " * 3,
             "mid size text here", "tiny"]
    out = eng.embed_texts(texts)
    assert out.shape == (4, 32)
    assert np.isfinite(out).all()
    # order must be restored after sort-by-length batching
    solo = np.stack([eng.embed_texts([t])[0] for t in texts])
    np.testing.assert_allclose(out, solo, atol=1e-4, rtol=1e-3)


def test_embed_empty_and_query():
    eng = _small_engine()
    assert eng.embed_texts([]).shape == (0, 32)
    q = eng.embed_query("hello world")
    assert q.shape == (32,)


def test_executable_cache_bounded_and_reused():
    eng = _small_engine()
    eng.embed_texts(["one two"])
    c0 = eng.stats["compiles"]
    eng.embed_texts(["three four"])  # same (bucket, batch) → no new compile
    assert eng.stats["compiles"] == c0
    eng.embed_texts(["w " * 14])  # longer → next bucket → one new compile
    assert eng.stats["compiles"] == c0 + 1


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_engine_data_parallel_matches_single():
    from symbiont_tpu.parallel import build_mesh

    cfg = EngineConfig(embedding_dim=32, length_buckets=[8, 16],
                       batch_buckets=[8, 16], max_batch=16, dtype="float32")
    mesh = build_mesh()
    eng_dp = TpuEngine(cfg, mesh=mesh)
    eng_1 = TpuEngine(
        EngineConfig(embedding_dim=32, length_buckets=[8, 16], batch_buckets=[8, 16],
                     max_batch=16, dtype="float32", data_parallel=False))
    texts = [f"sentence number {i} with words" for i in range(12)]
    np.testing.assert_allclose(eng_dp.embed_texts(texts), eng_1.embed_texts(texts),
                               atol=1e-4, rtol=1e-3)


def test_rerank_with_synthetic_cross_encoder():
    import jax as _jax

    from symbiont_tpu.models import bert as bert_mod

    ccfg = bert_mod.BertConfig(vocab_size=30000, hidden_size=32, num_layers=2,
                               num_heads=2, intermediate_size=64,
                               max_position_embeddings=64, dtype="float32")
    cparams = bert_mod.init_params(_jax.random.key(7), ccfg, with_pooler=True)
    cfg = EngineConfig(embedding_dim=32, length_buckets=[16, 32], batch_buckets=[2, 4],
                       max_batch=4, dtype="float32", data_parallel=False)
    eng = TpuEngine(cfg, cross_params=cparams, cross_cfg=ccfg)
    scores = eng.rerank("what is tpu", ["tpu is an accelerator", "bananas are yellow",
                                        "tensor processing unit"])
    assert scores.shape == (3,)
    assert np.isfinite(scores).all()


def test_rerank_without_model_raises():
    eng = _small_engine()
    with pytest.raises(RuntimeError, match="no cross-encoder"):
        eng.rerank("q", ["p"])


# ---------------------------------------------------------------- batcher

def test_micro_batcher_batches_and_returns_in_order():
    from symbiont_tpu.engine.batcher import MicroBatcher

    eng = _small_engine()

    async def main():
        b = MicroBatcher(eng, max_batch=8, flush_deadline_ms=10)
        await b.start()
        r1, r2 = await asyncio.gather(
            b.embed(["alpha beta", "gamma"]),
            b.embed(["delta epsilon zeta"]),
        )
        await b.close()
        return r1, r2

    r1, r2 = asyncio.run(main())
    assert r1.shape == (2, 32) and r2.shape == (1, 32)
    ref = eng.embed_texts(["alpha beta", "gamma", "delta epsilon zeta"])
    np.testing.assert_allclose(np.vstack([r1, r2]), ref, atol=1e-4, rtol=1e-3)


def test_micro_batcher_propagates_errors():
    from symbiont_tpu.engine.batcher import MicroBatcher

    eng = _small_engine()

    def boom(texts):
        raise ValueError("device on fire")

    eng.embed_texts = boom  # type: ignore

    async def main():
        b = MicroBatcher(eng, max_batch=2, flush_deadline_ms=5)
        await b.start()
        with pytest.raises(ValueError, match="device on fire"):
            await b.embed(["x"])
        await b.close()

    asyncio.run(main())


def test_hash_tokenizer_deterministic():
    t = HashTokenizer(1000)
    a = t.encode("Hello, World", 16)
    b = t.encode("hello world", 16)
    assert a[0] == t.cls_id and a[-1] == t.sep_id
    # case-insensitive, punctuation tokenized separately
    assert a[1] == b[1]
    ids, types = t.encode_pair("a b", "c d e", 32)
    assert len(ids) == len(types)
    assert types[0] == 0 and types[-1] == 1


def test_fused_query_search_matches_split_path(tmp_path):
    """embed_and_search (one device program) must rank exactly like the
    split embed_query → store.search path."""
    from symbiont_tpu.config import VectorStoreConfig
    from symbiont_tpu.memory.vector_store import VectorStore

    eng = _small_engine()
    store = VectorStore(VectorStoreConfig(dim=32, data_dir=str(tmp_path),
                                          shard_capacity=64))
    corpus = [f"sentence number {i} about topic {i % 5}" for i in range(20)]
    vecs = eng.embed_texts(corpus)
    store.upsert([(f"p{i}", vecs[i], {"sentence_text": corpus[i], "i": i})
                  for i in range(len(corpus))])

    split = store.search(eng.embed_query("topic 3"), 5)
    fused = store.search_fused(eng, "topic 3", 5)
    assert [h.id for h in fused] == [h.id for h in split]
    for a, b in zip(fused, split):
        assert abs(a.score - b.score) < 1e-2  # bf16 matmul rounding
        assert a.payload == b.payload


def test_fused_query_search_empty_store(tmp_path):
    from symbiont_tpu.config import VectorStoreConfig
    from symbiont_tpu.memory.vector_store import VectorStore

    eng = _small_engine()
    store = VectorStore(VectorStoreConfig(dim=32, data_dir=str(tmp_path)))
    assert store.search_fused(eng, "anything", 5) == []


def test_warm_fused_tracks_capacity_blocks(tmp_path):
    """warm_fused records the capacity it compiled for (k=8 AND k=16
    buckets); crossing a capacity block via upserts flags the warm as stale
    so the owner re-warms before the next query pays a fresh compile."""
    from symbiont_tpu.config import VectorStoreConfig
    from symbiont_tpu.memory.vector_store import VectorStore

    eng = _small_engine()
    store = VectorStore(VectorStoreConfig(dim=32, data_dir=str(tmp_path),
                                          shard_capacity=64))
    assert not store.fused_warm_stale()  # never warmed → nothing to re-warm
    store.warm_fused(eng, word_counts=(3,))
    assert store._warmed_capacity == 64
    assert not store.fused_warm_stale()

    rng = np.random.default_rng(0)
    store.upsert([(f"p{i}", rng.standard_normal(32), {})
                  for i in range(65)])  # 65 rows cross the 64-row block
    assert store.fused_warm_stale()
    store.warm_fused(eng, word_counts=(3,))
    assert store._warmed_capacity == 128
    assert not store.fused_warm_stale()


def test_concurrent_entry_points_stress(tmp_path):
    """The engine's concurrency contract (module docstring): embed / rerank /
    fused-search may run concurrently from multiple threads — results must
    equal the serial baselines and the stats counters must be exact (bare
    `+=` would lose increments under this contention)."""
    from concurrent.futures import ThreadPoolExecutor

    from symbiont_tpu.config import VectorStoreConfig
    from symbiont_tpu.memory.vector_store import VectorStore

    cfg = EngineConfig(embedding_dim=32, length_buckets=[8, 16],
                       batch_buckets=[2, 4], max_batch=4, dtype="float32",
                       data_parallel=False, rerank_enabled=True)
    eng = TpuEngine(cfg)
    store = VectorStore(VectorStoreConfig(dim=32, data_dir=str(tmp_path),
                                          shard_capacity=64))
    corpus = [f"doc {i} about topic {i % 3}" for i in range(12)]
    vecs = eng.embed_texts(corpus)
    store.upsert([(f"p{i}", vecs[i], {"i": i}) for i in range(len(corpus))])

    texts = [f"query text number {i}" for i in range(6)]
    base_embed = eng.embed_texts(texts)
    base_rerank = eng.rerank("topic", corpus[:5])
    base_fused = [h.id for h in store.search_fused(eng, "topic 1", 4)]
    s0 = dict(eng.stats)

    N = 8
    with ThreadPoolExecutor(max_workers=12) as pool:
        emb_f = [pool.submit(eng.embed_texts, texts) for _ in range(N)]
        rr_f = [pool.submit(eng.rerank, "topic", corpus[:5]) for _ in range(N)]
        fu_f = [pool.submit(store.search_fused, eng, "topic 1", 4)
                for _ in range(N)]
        for f in emb_f:
            np.testing.assert_allclose(f.result(), base_embed, rtol=1e-5)
        for f in rr_f:
            np.testing.assert_allclose(f.result(), base_rerank, rtol=1e-5)
        for f in fu_f:
            assert [h.id for h in f.result()] == base_fused

    # counters exact under contention
    assert eng.stats["embed_calls"] == s0["embed_calls"] + N
    assert eng.stats["rerank_calls"] == s0["rerank_calls"] + N
    assert eng.stats["qsearch_calls"] == s0["qsearch_calls"] + N
    assert eng.stats["sentences_embedded"] == s0["sentences_embedded"] + N * len(texts)


def test_cold_executable_race_compiles_once():
    """Two threads racing a COLD executable key must converge on one cached
    executable and count one compile (the loser discards its wrapper)."""
    from concurrent.futures import ThreadPoolExecutor

    eng = _small_engine()
    texts = ["same shape text"] * 2
    with ThreadPoolExecutor(max_workers=2) as pool:
        a = pool.submit(eng.embed_texts, texts)
        b = pool.submit(eng.embed_texts, texts)
        np.testing.assert_allclose(a.result(), b.result(), rtol=1e-6)
    # both calls hit one (bucket, batch-bucket) shape → exactly one compile
    assert eng.stats["compiles"] == 1
    assert len(eng._exec_cache) == 1


# ------------------------------------------------- ingest host pipeline (r4)

def test_embed_texts_chunked_pipeline_matches_unchunked():
    """host_prep_chunk splits tokenization into prefetched chunks; results
    (and their row order) must be identical to the single-pass path."""
    texts = [f"sentence {i} " + "pad " * (i % 13) for i in range(30)]
    base = _small_engine().embed_texts(texts)
    cfg = EngineConfig(embedding_dim=32, length_buckets=[8, 16],
                       batch_buckets=[2, 4], max_batch=4, dtype="float32",
                       data_parallel=False, host_prep_chunk=7)
    np.testing.assert_allclose(TpuEngine(cfg).embed_texts(texts), base,
                               atol=1e-4, rtol=1e-3)


def test_embed_texts_prefetch_overlaps_dispatch():
    """Tokenize of chunk N+1 must run CONCURRENTLY with dispatch of chunk N:
    the gated tokenizer blocks chunk 2's encode until chunk 1 has dispatched,
    so a serial implementation (encode everything, then dispatch) times out."""
    import threading

    from symbiont_tpu.engine.tokenizer import HashTokenizer

    dispatched = threading.Event()

    class GatedTok(HashTokenizer):
        def __init__(self):
            super().__init__(30000)
            self.calls = 0

        def encode_batch(self, texts, max_len):
            self.calls += 1
            if self.calls == 2:  # chunk 2 rides the prefetch thread
                assert dispatched.wait(10), \
                    "chunk-2 tokenize did not overlap chunk-1 dispatch"
            return super().encode_batch(texts, max_len)

    tok = GatedTok()
    cfg = EngineConfig(embedding_dim=32, length_buckets=[8, 16],
                       batch_buckets=[2, 4], max_batch=4, dtype="float32",
                       data_parallel=False, host_prep_chunk=4)
    eng = TpuEngine(cfg, tokenizer=tok)
    orig = eng._dispatch_embed

    def wrapped(encoded, offset, buckets, pending):
        orig(encoded, offset, buckets, pending)
        dispatched.set()

    eng._dispatch_embed = wrapped
    out = eng.embed_texts([f"t {i} " + "w " * (i % 10) for i in range(10)])
    assert out.shape == (10, 32)
    assert tok.calls == 3  # 10 texts / chunk 4


def test_ids_ship_narrow_dtype_same_result():
    """Vocab ≤ 65535 ships uint16 ids over the wire (half the h2d bytes);
    embeddings must match the int32 wire bit-for-bit in float32."""
    eng = _small_engine()
    assert eng._ids_dtype == np.uint16  # synthetic vocab 30000 fits
    texts = ["alpha beta gamma", "delta " * 5, "x"]
    narrow = eng.embed_texts(texts)
    eng32 = _small_engine()
    eng32._ids_dtype = np.int32
    np.testing.assert_allclose(eng32.embed_texts(texts), narrow,
                               atol=1e-6, rtol=1e-6)


def test_concat_fetch_groups_match(monkeypatch):
    """Grouped single-copy fetch (CONCAT_FETCH_MAX) must scatter rows
    identically to the per-batch path across group boundaries."""
    texts = [f"g {i} " + "w " * (i % 11) for i in range(26)]
    base = _small_engine().embed_texts(texts)
    monkeypatch.setattr(TpuEngine, "CONCAT_FETCH_MAX", 2)
    np.testing.assert_allclose(_small_engine().embed_texts(texts), base,
                               atol=1e-4, rtol=1e-3)


def test_micro_batcher_overlapping_flushes():
    """max_inflight_flushes=2: a flush stuck materializing must not block
    the next flush from dispatching — and the stuck flush still resolves
    correctly."""
    import asyncio
    import threading

    from symbiont_tpu.engine.batcher import MicroBatcher

    gate = threading.Event()

    class StubEngine:
        class config:
            max_batch = 2
            flush_deadline_ms = 1.0

        def embed_texts(self, texts):
            if texts[0] == "slow":
                assert gate.wait(10), "slow flush never released"
            return np.full((len(texts), 4), float(len(texts)), np.float32)

    async def scenario():
        b = MicroBatcher(StubEngine())
        await b.start()
        slow = asyncio.ensure_future(b.embed(["slow"]))
        await asyncio.sleep(0.1)  # slow flush is in its executor, gated
        fast = await asyncio.wait_for(b.embed(["fast", "fast2"]), 5)
        assert fast.shape == (2, 4) and fast[0, 0] == 2.0
        assert not slow.done()  # proves the second flush overlapped it
        gate.set()
        out = await asyncio.wait_for(slow, 5)
        assert out.shape == (1, 4)
        await b.close()

    asyncio.run(scenario())


def test_max_batch_beyond_largest_batch_bucket():
    """max_batch larger than the top batch bucket must clamp the batch
    PLAN at the top bucket (no executable exists for a bigger shape; an
    unclamped plan underflowed row padding) — regression found by the
    engine-restart chaos test, where a redelivery surge flushed a
    max_batch-sized chunk through buckets smaller than it. Clamping keeps
    the executable set exactly |length_buckets|×|batch_buckets|."""
    cfg = EngineConfig(embedding_dim=32, length_buckets=[8, 16],
                       batch_buckets=[2, 4], max_batch=8, dtype="float32",
                       data_parallel=False)
    eng = TpuEngine(cfg)
    assert eng._plan_cap == 4
    texts = [f"surge doc {i} with words" for i in range(8)]
    out = eng.embed_texts(texts)
    assert out.shape == (8, 32)
    # no shape outside the configured bucket grid was compiled
    assert all(B in (2, 4) for (_, _, B) in eng._exec_cache)
    solo = np.stack([eng.embed_texts([t])[0] for t in texts])
    np.testing.assert_allclose(out, solo, atol=1e-4, rtol=1e-3)
