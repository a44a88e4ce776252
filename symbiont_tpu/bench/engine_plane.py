"""Engine-plane tiers: semantic search, rerank, and the HBM stream
reference kernel (the roofline accountant's independent ceiling).
"""

from __future__ import annotations

import time

import numpy as np

from symbiont_tpu.bench import stats
from symbiont_tpu.bench.tiers import register
from symbiont_tpu.bench.workload import log, make_sentences


@register("search_latency")
def tier_search_latency(results: dict, ctx) -> None:
    """BASELINE.md north-star metric #2: p50 semantic-search latency — query
    embed (MiniLM-L6 geometry) + exact cosine top-k over a 10k-row
    device-resident corpus. This is the compute path of the 2-hop
    request-reply orchestration (SURVEY.md §3.2); bus + HTTP add ~1ms."""
    import tempfile

    from symbiont_tpu.config import EngineConfig, VectorStoreConfig
    from symbiont_tpu.engine.engine import TpuEngine
    from symbiont_tpu.memory.vector_store import VectorStore

    eng = TpuEngine(EngineConfig(
        embedding_dim=384, length_buckets=[32, 64], batch_buckets=[1, 8, 512],
        max_batch=512, dtype="bfloat16", data_parallel=False))
    rng = np.random.default_rng(3)
    corpus = make_sentences(10_000, rng)
    with tempfile.TemporaryDirectory() as td:
        store = VectorStore(VectorStoreConfig(dim=384, data_dir=td,
                                              shard_capacity=16384))
        # warm run over the FULL corpus: the batch plan (and therefore the
        # grouped-concat fetch signatures) must match the timed run, or the
        # timed region pays their compiles
        eng.embed_texts(corpus)
        t_embed = float("inf")
        for _ in range(2):
            t0 = time.time()
            vecs = eng.embed_texts(corpus)
            t_embed = min(t_embed, time.time() - t0)
        t0 = time.time()
        store.upsert([(f"p{i}", vecs[i], {"sentence_text": corpus[i]})
                      for i in range(len(corpus))])
        t_upsert = time.time() - t0
        results["ingest_10k_emb_per_s"] = round(10_000 / t_embed, 1)
        results["upsert_10k_points_per_s"] = round(10_000 / t_upsert, 1)
        results["upsert_10k_s"] = round(t_upsert, 2)
        log(f"bulk ingest: 10k sentences embedded in {t_embed:.2f}s "
            f"({10_000 / t_embed:.0f} emb/s), upserted in {t_upsert:.2f}s")

        def measure(fn):
            """5 repeats of a 32-query sweep → (median, min, max) of the
            per-repeat p50s + median of the p95s (VERDICT r3: search p50s as
            median-of-5, not one sample on a ±20% link)."""
            fn(make_sentences(4, rng)[0])  # warm
            p50s, p95s = [], []
            for _ in range(5):
                lat = []
                for q in make_sentences(32, rng):
                    t0 = time.time()
                    fn(q)
                    lat.append(time.time() - t0)
                ms = sorted(1000 * x for x in lat)
                p50s.append(ms[len(ms) // 2])
                p95s.append(ms[int(len(ms) * 0.95)])
            return p50s, stats.med_min_max(p95s)[0]

        def split(q):
            assert len(store.search(eng.embed_query(q), 5)) == 5

        def fused(q):
            assert len(store.search_fused(eng, q, 5)) == 5

        # warm every query-length bucket for both paths
        for ql in ["a b c", " ".join(["word"] * 40)]:
            split(ql), fused(ql)
        p50s, p95 = measure(split)
        p50 = stats.record(results, "search_split_p50_ms", p50s)
        results["search_split_p95_ms"] = round(p95, 1)
        log(f"semantic search, split path (10k corpus, top-5): "
            f"p50 {p50:.1f}ms [{results['search_split_p50_ms_min']:.1f}–"
            f"{results['search_split_p50_ms_max']:.1f}], p95 {p95:.1f}ms "
            f"(embed call + top-k call; median of 5 sweeps)")
        p50fs, p95f = measure(fused)
        p50f = stats.record(results, "search_fused_p50_ms", p50fs)
        results["search_fused_p95_ms"] = round(p95f, 1)
        log(f"semantic search, FUSED path (10k corpus, top-5): "
            f"p50 {p50f:.1f}ms [{results['search_fused_p50_ms_min']:.1f}–"
            f"{results['search_fused_p50_ms_max']:.1f}], p95 {p95f:.1f}ms "
            f"(one compiled embed+top-k program, one device round-trip)")


@register("rerank")
def tier_rerank(results: dict, ctx) -> None:
    """BASELINE.md config #4: ms-marco-MiniLM-L-6 geometry cross-encoder,
    pairs/sec over a top-k-sized candidate set."""
    from symbiont_tpu.config import EngineConfig
    from symbiont_tpu.engine.engine import TpuEngine

    eng = TpuEngine(EngineConfig(
        embedding_dim=384, length_buckets=[128], batch_buckets=[64, 256],
        max_batch=256, dtype="bfloat16", data_parallel=False,
        rerank_enabled=True))
    rng = np.random.default_rng(1)
    passages = make_sentences(256, rng)
    query = "tensor processing unit matrix products"
    eng.rerank(query, passages)  # warmup: compiles the (128, 256) executable
    dt = float("inf")
    for _ in range(3):
        t0 = time.time()
        eng.rerank(query, passages)
        dt = min(dt, time.time() - t0)
    results["rerank_pairs_per_s"] = round(256 / dt, 1)
    results["rerank_hop_ms"] = round(dt * 1000, 1)
    log(f"rerank (MiniLM-L6 CE geometry, 256 pairs, pad-128, bf16): "
        f"{256 / dt:.0f} pairs/s (256-pair hop {dt * 1000:.1f}ms)")


@register("stream_ceiling")
def tier_stream_ceiling(results: dict, ctx):
    """Measure THIS RUN's achievable HBM stream bandwidth (reduce-sum over a
    3.2 GB bf16 array, 16 in-graph passes, best-of-3). This is the roofline
    accountant's REFERENCE-KERNEL ceiling: an independent kernel the decode
    path has no hand in, measured fresh each run because the same kernel
    measured 581 GB/s and 715 GB/s on this chip hours apart — a fixed
    denominator would make utilization drift meaningless across rounds."""
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu":
        return "explicit CPU run (JAX_PLATFORMS=cpu): no HBM to stream"
    big = jax.random.normal(jax.random.key(0), (24, 8192, 8192), jnp.bfloat16)

    @jax.jit
    def reduce(x):
        def body(acc, _):
            return acc + x.sum(), None
        return jax.lax.scan(body, jnp.zeros((), jnp.float32), None,
                            length=16)[0]

    np.asarray(reduce(big))
    best = float("inf")
    for _ in range(3):
        t0 = time.time()
        np.asarray(reduce(big))
        best = min(best, time.time() - t0)
    gbps = big.size * 2 / (best / 16) / 1e9
    results["hbm_stream_gbps_measured"] = round(gbps, 1)
    del big
    log(f"HBM stream ceiling (reduce-sum, 3.2 GB bf16, this run): "
        f"{gbps:.0f} GB/s (v5e paper: 819)")
